"""Serving-engine benchmark (ours; the paper's technique live on a model):
tiered-KV engine vs dense-KV decoding on a smoke-scale arch — decode step
wall time (CPU-directional), KV HBM bytes, TCO savings, output fidelity.

A tiered row's time is the mean ``tkv.step`` span (the engine's program
spans, ``repro.serving.spans``): one whole engine step, through the wait for
its outputs. Its derived column splits that into the wait (``tkv.wait``)
and host work (the rest), and gives the step's time outside its named child
spans."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import Csv, time_us
import repro.configs as configs
from repro.configs.base import TierScapeRunConfig
from repro.models import Model
from repro.serving import TieredEngine
from repro.serving.spans import totals


def run(csv: Csv) -> None:
    cfg = configs.get_smoke("zamba2_1_2b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, 48)

    # Dense reference decode.
    state = model.init_cache(1, 96)
    batch = {"tokens": jnp.asarray(prompt[None], jnp.int32)}
    logits, state = model.prefill(params, batch, state)
    step = jax.jit(model.decode_step)
    tok = jnp.asarray([[int(jnp.argmax(logits[0, -1]))]], jnp.int32)
    lg, state2 = step(params, tok, state)  # warm
    dense_us = time_us(lambda: jax.block_until_ready(step(params, tok, state)[0]), iters=5)
    dense_bytes = state.k_cache.size * 2 * 2
    csv.add("dense-decode", dense_us, f"kv_bytes={dense_bytes}")

    for alpha in (0.5, 0.1):
        # Runs the async-migration default: window boundaries submit cohorts
        # and return, decode steps tick them, and run() drains stragglers —
        # so the mean step prices the overlapped path, not a blocked
        # boundary, and stats.migrations still counts every page moved.
        # The recorder keeps the engine's spans for the row's timings.
        eng = TieredEngine(
            model, params, batch_slots=1, page_tokens=8, max_seq_len=96,
            recent_window=16,
            ts=TierScapeRunConfig(enabled=True, policy="analytical", alpha=alpha,
                                  window_steps=8),
        )
        eng.submit(prompt, max_new_tokens=24)
        eng.spans.start()
        stats = eng.run(max_steps=32)
        spans = totals(eng.spans.stop())
        step, wait = spans["tkv.step"], spans["tkv.wait"]
        csv.add(
            f"tiered-decode-a{alpha}",
            step.total_ns / step.calls * 1e-3,
            f"peak_tco_savings_pct={stats.tco_savings_pct:.1f};"
            f"hbm_bytes={eng.cache.hbm_bytes()};migrations={stats.migrations};"
            f"wait_us={wait.total_ns / step.calls * 1e-3:.1f};"
            f"host_us={(step.total_ns - wait.total_ns) / step.calls * 1e-3:.1f};"
            f"step_self_us={step.self_ns / step.calls * 1e-3:.1f};"
            f"attn_launches_per_step={stats.attn_launches / max(stats.steps, 1):.0f}",
        )


def main() -> None:
    csv = Csv("serving")
    run(csv)
    csv.emit()


if __name__ == "__main__":
    main()
