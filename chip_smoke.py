"""Chip smoke test: the served path at full Qwen1.5-4B width on a TPU.

One process, on the chip only: with no TPU it exits non-zero and prints no
result. Phases (each must pass; a caught error still fails the run):

  kernels  -- every served-path Pallas kernel (fused attention over mixed
              int8/int4/host-sentinel rows, quant 8/4, transcode 8<->4,
              dequant 8/4) compiled at full width, against its
              ``kernels/ref.py`` oracle on the same operands;
  engine   -- ``TieredEngine`` on full ``qwen1_5_4b`` (random weights from
              --seed): more requests than slots, two fixed prompt lengths,
              window boundaries that move pages warm->cold. The decode step
              must hold the compiled fused kernel (``tpu_custom_call``), and
              its logits must match the jnp-oracle step on the same state;
  frontend -- the same engine behind ``ContinuousScheduler`` on a burst
              trace; no request may be dropped.

With ``--chips 4`` only the replica phase runs: the burst trace through
``ContinuousScheduler`` over four replicas (one per chip), against the same
trace on one replica. Every replica's arrays must live on its own device.

The persistent compile cache goes to ``JAX_COMPILATION_CACHE_DIR`` when set,
else to ``<repo>/.jax_cache``. The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py [--seed 0] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1_5_4b"
SLOTS = 4
PAGE_TOKENS = 16
MAX_SEQ = 1024
RECENT = 32
PROMPT_LENS = (256, 320)  # two fixed lengths: prefill compiles twice
NEW_TOKENS = 48
N_REQUESTS = 8  # > SLOTS: slots are released and reused
# Analytical placement at a TCO-leaning alpha with short windows: several
# boundaries fall inside the run and demote the coldest warm pages.
ALPHA = 0.1
WINDOW_STEPS = 8
# Logits of the kernel step vs the oracle step: the two attention paths
# agree to f32 rounding, but each layer's output is rounded to bf16 before
# the projection, so a flipped bf16 rounding (relative 2**-8) can propagate
# through 40 layers. Bound the max difference relative to max |logit|.
LOGITS_RTOL = 3e-2


def _now() -> float:
    return time.perf_counter()


def _log(msg: str) -> None:
    print(msg, flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _lowered_has_kernel(fn, *args) -> bool:
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


# --------------------------------------------------------------- (a) kernels
def check_kernels(cfg, seed: int, pages: int = 64, batch: int = SLOTS,
                  recent: int = RECENT) -> None:
    """Each served-path kernel, through ``kernels.ops`` (compiled on TPU),
    against its ref.py oracle at the config's KV widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    kv, hd, h, t = cfg.n_kv_heads, cfg.head_dim_(), cfg.n_heads, PAGE_TOKENS
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (pages, t, kv, hd)), jnp.bfloat16)

    def report(name, err, tol, fn, *args):
        kernel = _lowered_has_kernel(fn, *args)
        _log(f"  {name:<16s} max_err={err:.3e} tol={tol:.3e} compiled_kernel={kernel}")
        _check(kernel, f"{name}: no tpu_custom_call in the lowered program")
        _check(err <= tol, f"{name}: max error {err:.3e} > tolerance {tol:.3e}")

    def deq(pay, sc, bits):
        return np.asarray(ref.dequant_kv_page(pay, sc, bits))

    # quant: dequantized values agree within one quantization step (a
    # round-to-nearest tie may flip between reciprocal-multiply and divide).
    for bits in (8, 4):
        pay, sc = ops.quant_pages(x, bits)
        rp, rs = ref.quant_kv_page(x, bits)
        err = float(np.abs(deq(pay, sc, bits) - deq(rp, rs, bits)).max())
        sc_err = float(np.abs(np.asarray(sc) - np.asarray(rs)).max())
        _check(sc_err <= 1e-6 * float(np.asarray(rs).max()), f"quant{bits}: scales differ")
        report(f"quant{bits}", err, float(np.asarray(rs).max()),
               lambda a, b=bits: ops.quant_pages(a, b), x)

    for bits in (8, 4):
        pay, sc = ref.quant_kv_page(x, bits)
        out = np.asarray(ops.dequant_pages(pay, sc, bits, jnp.float32))
        want = deq(pay, sc, bits)
        report(f"dequant{bits}", float(np.abs(out - want).max()),
               1e-6 * float(np.abs(want).max()),
               lambda a, s, b=bits: ops.dequant_pages(a, s, b, jnp.float32), pay, sc)

    for src, dst in ((8, 4), (4, 8)):
        pay, sc = ref.quant_kv_page(x, src)
        kp, ks = ops.transcode_pages(pay, sc, src, dst)
        rp, rs = ref.transcode_kv_page(pay, sc, src, dst)
        err = float(np.abs(deq(kp, ks, dst) - deq(rp, rs, dst)).max())
        report(f"transcode{src}->{dst}", err, float(np.asarray(rs).max()),
               lambda a, s, a_=src, b_=dst: ops.transcode_pages(a, s, a_, b_), pay, sc)

    # Fused attention over an int8 warm pool, an int4 cold pool and host
    # sentinel rows, plus the dense recent window; per-slot fills differ.
    mp = pages // 2
    k8, s8 = ref.quant_kv_page(x, 8)
    v8, sv8 = ref.quant_kv_page(x * 0.5, 8)
    k4, s4 = ref.quant_kv_page(x, 4)
    v4, sv4 = ref.quant_kv_page(x * 0.5, 4)

    def table():
        return jnp.asarray(rng.integers(0, pages, (batch, mp)), jnp.int32)

    fills = jnp.asarray([mp, mp // 2, 1, 0][:batch], jnp.int32)
    operands = dict(
        k8=k8, s8=s8, v8=v8, sv8=sv8, k4=k4, s4=s4, v4=v4, sv4=sv4,
        warm_table=table(), cold_table=table(), host_table=table(),
        warm_n=fills, cold_n=fills[::-1],
        host_n=jnp.asarray([3, 0, mp, 7][:batch], jnp.int32),
        summary=jnp.asarray(rng.normal(0, 0.3, (pages, kv, hd)), jnp.float32),
    )
    q = jnp.asarray(rng.normal(0, 1, (batch, h, hd)), jnp.bfloat16)
    rk = jnp.asarray(rng.normal(0, 1, (batch, recent, kv, hd)), jnp.bfloat16)
    rv = jnp.asarray(rng.normal(0, 1, (batch, recent, kv, hd)), jnp.bfloat16)
    rlen = jnp.asarray([recent, recent // 2, 1, 5][:batch], jnp.int32)

    def fused(q_, rk_, rv_, rlen_, a):
        pools = {
            "warm": dict(k_pages=a["k8"], k_scales=a["s8"], v_pages=a["v8"],
                         v_scales=a["sv8"], page_table=a["warm_table"],
                         n_pages=a["warm_n"], bits=8),
            "cold": dict(k_pages=a["k4"], k_scales=a["s4"], v_pages=a["v4"],
                         v_scales=a["sv4"], page_table=a["cold_table"],
                         n_pages=a["cold_n"], bits=4),
        }
        host = dict(summary=a["summary"], table=a["host_table"], n=a["host_n"],
                    page_tokens=t)
        return ops.tiered_decode_attention(q_, pools, rk_, rv_, rlen_,
                                           with_telemetry=True, host=host)

    out, hot = fused(q, rk, rv, rlen, operands)
    ops.use_pallas(False)
    try:
        with jax.default_matmul_precision("highest"):
            out_r, hot_r = fused(q, rk, rv, rlen, operands)
    finally:
        ops.use_pallas(True)
    want = np.asarray(out_r)
    err = float(np.abs(np.asarray(out) - want).max())
    hot_err = max(float(np.abs(np.asarray(hot[k]) - np.asarray(hot_r[k])).max()) for k in hot_r)
    _log(f"  {'hotness':<16s} max_err={hot_err:.3e} tol={1e-4:.3e}")
    _check(hot_err <= 1e-4, f"fused hotness: max error {hot_err:.3e} > 1e-4")
    report("fused_attention", err, 1e-3 * max(1.0, float(np.abs(want).max())),
           lambda *a: fused(*a)[0], q, rk, rv, rlen, operands)


# ---------------------------------------------------------------- (b) engine
def build_engine(cfg, seed: int, device=None, params=None):
    import jax

    from repro.configs.base import TierScapeRunConfig
    from repro.models import Model
    from repro.serving import TieredEngine

    model = Model(cfg)
    if params is None:
        params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    ts = TierScapeRunConfig(enabled=True, policy="analytical", alpha=ALPHA,
                            window_steps=WINDOW_STEPS)
    return TieredEngine(model, params, batch_slots=SLOTS, page_tokens=PAGE_TOKENS,
                        max_seq_len=MAX_SEQ, recent_window=RECENT, ts=ts, device=device)


def _pctl(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def check_oracle_step(eng, compiled) -> None:
    """The kernel decode step and the jnp-oracle decode step on the same
    live state: same logits up to bf16 propagation."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ParallelConfig
    from repro.runtime import serve as serve_rt

    with jax.default_device(eng.device), jax.default_matmul_precision("highest"):
        oracle = jax.jit(serve_rt.make_tiered_decode_step(
            eng.model, eng.mesh, ParallelConfig(), eng.ts, use_kernels=False))
        tokens = jnp.asarray(
            [[r.out_tokens[-1] if r is not None else 0] for r in eng.slots], jnp.int32)
        args = (eng.params, tokens, eng.cache.state, eng.ssm_state)
        t0 = _now()
        want = np.asarray(oracle(*args)[0], np.float32)
        t_oracle = _now() - t0
        got = np.asarray(compiled(*args)[0], np.float32)
    live = [i for i, r in enumerate(eng.slots) if r is not None]
    err = float(np.abs(got[live] - want[live]).max())
    scale = float(np.abs(want[live]).max())
    agree = int((got[live, 0].argmax(-1) == want[live, 0].argmax(-1)).sum())
    _log(f"  decode step vs oracle step: max|dlogits|={err:.3e} "
         f"tol={LOGITS_RTOL * scale:.3e} (max|logit|={scale:.3e}) "
         f"argmax agree {agree}/{len(live)} slots; oracle compile+run {t_oracle:.1f}s")
    _check(np.isfinite(got).all(), "non-finite logits from the kernel step")
    _check(err <= LOGITS_RTOL * scale, "kernel step logits differ from the oracle step")


def serve_requests(cfg, seed: int):
    """Phase (b): returns the engine for the frontend phase."""
    import jax

    from repro.serving.kv_cache import COLD, INFLIGHT, WARM

    t0 = _now()
    eng = build_engine(cfg, seed)
    _log(f"  weights + engine set-up: {_now() - t0:.2f}s on {eng.device}")
    t0 = _now()
    compiled = eng.compile_step()
    t_compile = _now() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    _log(f"  decode step compile (set-up): {t_compile:.2f}s; "
         f"compiled text holds tpu_custom_call: {has_kernel}")
    _check(has_kernel, "decode step has no compiled fused kernel")

    rng = np.random.default_rng(seed)
    pending = [rng.integers(1, cfg.vocab_size, PROMPT_LENS[i % len(PROMPT_LENS)])
               for i in range(N_REQUESTS)]
    reqs, prefill_s, step_s, window_of_step = [], [], [], []
    settled = eng.cache.physical.copy()
    warm_to_cold, moved_windows = 0, set()
    oracle_checked = False

    def settle():
        nonlocal settled
        phys = eng.cache.physical
        now = np.where(phys != INFLIGHT, phys, settled)
        moved = int(((settled == WARM) & (now == COLD)).sum())
        settled = now
        return moved

    while pending or any(s is not None for s in eng.slots):
        for slot in eng.free_slots():
            if not pending:
                break
            req = eng.make_request(pending.pop(0), NEW_TOKENS)
            t0 = _now()
            eng.start_request(slot, req)
            jax.block_until_ready(eng.cache.state)
            prefill_s.append((len(req.prompt), _now() - t0))
            reqs.append(req)
            settle()
        if not oracle_checked and eng.stats.windows >= 2 and all(eng.slots):
            check_oracle_step(eng, compiled)
            oracle_checked = True
        t0 = _now()
        eng.step()
        jax.block_until_ready(eng.cache.state)
        step_s.append(_now() - t0)
        window_of_step.append(eng.stats.windows)
        moved = settle()
        if moved:
            warm_to_cold += moved
            moved_windows.add(eng.stats.windows)
    stats = eng.finish()

    for n in PROMPT_LENS:
        times = [s for length, s in prefill_s if length == n]
        _log(f"  prefill {n} tokens: first (compile) {times[0]:.2f}s, "
             f"then median {_pctl(times[1:], 50):.3f}s over {len(times) - 1}")
    steady = step_s[1:]
    _log(f"  decode steps: {len(step_s)}; first {step_s[0]:.3f}s; wall per step "
         f"median {_pctl(steady, 50) * 1e3:.1f}ms p90 {_pctl(steady, 90) * 1e3:.1f}ms "
         f"max {max(steady) * 1e3:.1f}ms mean {np.mean(steady) * 1e3:.1f}ms")
    _log(f"  windows={stats.windows} migrations={stats.migrations} "
         f"warm->cold pages={warm_to_cold} in {len(moved_windows)} windows; "
         f"peak TCO savings {stats.tco_savings_pct:.2f}%")
    done = [r for r in reqs if r.done and len(r.out_tokens) == NEW_TOKENS]
    _log(f"  completed {len(done)}/{N_REQUESTS} requests x {NEW_TOKENS} tokens; "
         f"req0 tokens {reqs[0].out_tokens[:8]}")
    _check(oracle_checked, "never reached a full batch after two windows")
    _check(len(done) == N_REQUESTS, "a request did not complete its full token count")
    _check(all(0 <= tok < cfg.vocab_size for r in reqs for tok in r.out_tokens),
           "token id out of vocabulary")
    _check(len(moved_windows) >= 2, "fewer than two windows moved pages warm->cold")
    return eng


# -------------------------------------------------------------- (c) frontend
def burst_trace(seed: int, steps: int = 24):
    from repro.frontend.traces import TraceConfig, generate

    return generate(TraceConfig(
        kind="burst", steps=steps, rate=0.2, seed=seed, n_sessions=8,
        prompt_len=(PROMPT_LENS[0], PROMPT_LENS[0]), new_tokens=(16, 24),
        burst_every=12, burst_len=2, burst_mult=4.0,
    ))


def run_frontend(engines, events, label: str):
    import jax

    from repro.frontend.scheduler import ContinuousScheduler

    sched = ContinuousScheduler(engines, events, engines[0].cfg.vocab_size,
                                prefill_chunk_tokens=PROMPT_LENS[0])
    t0 = _now()
    stats = sched.run(max_steps=2_000)
    for eng in engines:
        jax.block_until_ready(eng.cache.state)
    wall = _now() - t0
    summary = stats.summary()
    per_replica = [sum(1 for r in stats.records if r.replica == i and r.state == "done")
                   for i in range(len(engines))]
    _log(f"  {label}: {summary['completed']}/{len(events)} completed, "
         f"refused={stats.refused} preemptions={stats.preemptions} "
         f"resumes={stats.resumes} virtual steps={stats.steps} "
         f"decoded tokens={stats.decoded_tokens} wall={wall:.2f}s "
         f"per replica={per_replica}")
    _check(stats.refused == 0 and summary["completed"] == len(events),
           f"{label}: a request was dropped")
    return stats


def frontend(eng, seed: int) -> None:
    run_frontend([eng], burst_trace(seed), "1 replica")


# ------------------------------------------------------------ (d) replicas
def _devices_of(eng):
    import jax

    leaves = jax.tree.leaves((eng.params, eng.cache.state, eng.ssm_state))
    return {d for x in leaves for d in x.devices()}


def replicas(cfg, seed: int, n: int) -> None:
    from concurrent.futures import ThreadPoolExecutor

    import jax

    devices = jax.devices()[:n]
    t0 = _now()
    first = build_engine(cfg, seed, device=devices[0])
    engines = [first] + [build_engine(cfg, seed, device=d, params=first.params)
                         for d in devices[1:]]
    _log(f"  {n} engines on {[str(e.device) for e in engines]}: {_now() - t0:.2f}s")
    t0 = _now()
    with ThreadPoolExecutor(n) as pool:
        texts = list(pool.map(lambda e: e.compile_step().as_text(), engines))
    _log(f"  {n} decode-step compiles in parallel: {_now() - t0:.2f}s")
    _check(all("tpu_custom_call" in t for t in texts), "a replica lacks the fused kernel")
    events = burst_trace(seed, steps=32)
    run_frontend(engines[:1], events, "1 replica (comparison)")
    run_frontend(engines, events, f"{n} replicas")
    for eng in engines:
        held = _devices_of(eng)
        _log(f"  replica on {eng.device}: arrays on {sorted(str(d) for d in held)}")
        _check(held == {eng.device}, f"replica on {eng.device} has arrays elsewhere")
    _check(len({e.device for e in engines}) == n, "replicas share a device")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-replica phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU found (JAX platform {dev.platform!r}); nothing to run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}",
              file=sys.stderr)
        return 2

    import repro.configs as configs
    from repro.kernels import ops
    from repro.launch import compile_cache

    _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"jax {jax.__version__}; compile cache {compile_cache.enable()}")
    _check(ops.compiled(), "kernels would run in interpret mode")
    cfg = configs.get(ARCH)

    if args.chips == 1:
        state = {}
        phases = [
            ("kernels", lambda: check_kernels(cfg, args.seed)),
            ("engine", lambda: state.update(eng=serve_requests(cfg, args.seed))),
            ("frontend", lambda: frontend(state["eng"], args.seed)),
        ]
    else:
        phases = [("replicas", lambda: replicas(cfg, args.seed, args.chips))]

    failed = []
    for name, phase in phases:
        _log(f"[{name}]")
        t0 = _now()
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        _log(f"[{name}] {'FAILED' if name in failed else 'ok'} in {_now() - t0:.2f}s")
    if failed:
        print(f"failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
