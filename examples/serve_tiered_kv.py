"""End-to-end serving driver: batched requests through the tiered-KV engine.

The engine decodes against software-defined compressed KV tiers (warm int8 /
cold int4 device pools + host tiers), with per-page attention-mass telemetry
feeding the TierScape analytical placement model every window. Prints the
paper's metrics: TCO savings, placement distribution, migrations, and where
the engine's steps spent their time (its program spans).

    PYTHONPATH=src python examples/serve_tiered_kv.py --requests 4
"""

import argparse
import time

import jax
import numpy as np

import repro.configs as configs
from repro.configs.base import TierScapeRunConfig
from repro.launch import compile_cache
from repro.models import Model
from repro.serving import TieredEngine
from repro.serving.spans import totals
from repro.serving.kv_cache import COLD, HOST4, HOST8, WARM


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2_1_2b",
                    help="any smoke arch with attention")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--alpha", type=float, default=0.3,
                    help="TierScape knob: 1=perf, 0=max TCO savings")
    ap.add_argument("--policy", default="analytical",
                    choices=["analytical", "waterfall"])
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--serial-migration", action="store_true",
                    help="opt back into blocking window boundaries (async "
                         "overlapped migration is the default; this runs "
                         "the serial equivalence oracle instead)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable speculative staging of warming host pages "
                         "(prefetch is the default now that the fused decode "
                         "kernel feeds the predictor in-engine; it is a "
                         "no-op anyway with --serial-migration)")
    ap.add_argument("--vary-prompts", action="store_true",
                    help="submit unequal prompt lengths (per-slot decode)")
    args = ap.parse_args()
    prefetch = not args.no_prefetch and not args.serial_migration
    compile_cache.enable()

    cfg = configs.get_smoke(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = TieredEngine(
        model, params,
        batch_slots=args.slots, page_tokens=8,
        max_seq_len=args.prompt_len + args.new_tokens + 32,
        recent_window=16,
        ts=TierScapeRunConfig(enabled=True, policy=args.policy,
                              alpha=args.alpha, window_steps=8,
                              async_migration=not args.serial_migration,
                              prefetch=prefetch),
    )

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        plen = args.prompt_len
        if args.vary_prompts:  # per-slot lengths: each request its own size
            plen = max(args.prompt_len - 8 * (i % args.slots), 8)
        reqs.append(eng.submit(rng.integers(1, cfg.vocab_size, plen),
                               max_new_tokens=args.new_tokens))

    eng.spans.start()
    t0 = time.time()
    stats = eng.run(max_steps=args.requests * args.new_tokens * 2)
    wall = time.time() - t0
    spans = totals(eng.spans.stop())

    print(f"arch={args.arch} policy={args.policy} alpha={args.alpha}")
    print(f"completed {stats.completed}/{args.requests} requests in "
          f"{stats.steps} engine steps ({wall:.1f}s wall)")
    print(f"windows={stats.windows} migrations={stats.migrations} "
          f"overlapped_steps={stats.overlapped_steps}")
    step, wait = spans["tkv.step"], spans["tkv.wait"]
    print(f"engine steps: {step.total_ns * 1e-9:.2f}s, of which waiting for the device "
          f"{wait.total_ns * 1e-9:.2f}s and host work {(step.total_ns - wait.total_ns) * 1e-9:.2f}s "
          f"({step.self_ns * 1e-9:.2f}s outside the step's named spans)")
    if prefetch:
        print(f"prefetch: staged={stats.prefetch_staged} "
              f"hits={stats.prefetch_hits} misses={stats.prefetch_misses}")
    print(f"attn launches: {stats.attn_launches} "
          f"({stats.attn_launches / max(stats.steps, 1):.0f}/step, fused)")
    busy = {d: round(s * 1e6, 2)
            for d, s in eng.cache.pipeline.media_busy_s().items() if s > 0}
    if busy:
        print(f"media busy (us, executed): {busy}")
    pl = eng.cache.manager.placement[eng.cache._page_exists]
    hist = np.bincount(pl, minlength=5)
    names = {0: "dram", WARM: "warm-int8-hbm", COLD: "cold-int4-hbm",
             HOST8: "host-int8", HOST4: "host-int4"}
    live = ", ".join(f"{names[i]}={hist[i]}" for i in range(5) if hist[i])
    print("live page placement:", live or "(all requests done; pages freed)")
    print(f"peak KV memory TCO savings vs uncompressed HBM: "
          f"{stats.tco_savings_pct:.1f}%")
    for r in reqs[:2]:
        print(f"req{r.rid}: {r.out_tokens[:12]}...")


if __name__ == "__main__":
    main()
