"""Compile each configuration's decode step and prefill for a described
TPU v5e, at the shapes its cell serves, and print the compiler's memory
analysis. Nothing runs and no chip is needed:

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_compile.py [config[:slots] ...]

The decode step is the engine's (``make_tiered_decode_step`` with the
fused kernel); prefill is the longest prompt the cell's traffic sends.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

PREFILL = {"internlm2_20b_8l": 4096}  # configuration -> longest prompt its cell sends


def shapes_of(tree, sharding):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def rehearse(name: str, topo, slots: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chipbench import modelcfg, weights
    from repro.configs.base import ParallelConfig, TierScapeRunConfig
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.runtime import serve
    from repro.runtime.serve import init_tiered_kv_state

    cfg = modelcfg.load_json("configs", name)
    arch = modelcfg.arch(cfg)
    e = cfg["engine"]
    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    model = Model(modelcfg.program_config(cfg))
    ts = TierScapeRunConfig(enabled=True, window_steps=int(cfg["tierscape"]["window_steps"]))
    bs, pt, msl = slots or int(e["batch_slots"]), int(e["page_tokens"]), int(e["max_seq_len"])
    max_pages = msl // pt
    total = arch.n_layers * bs * max_pages
    state = jax.eval_shape(lambda: init_tiered_kv_state(
        model.cfg, bs, page_tokens=pt, warm_pages=max(int(total * 0.5), 8),
        cold_pages=max(total, 8), max_pages_per_seq=max_pages,
        recent_window=int(e["recent_window"]), n_attn_layers=arch.n_layers,
        host_slots=bs * max_pages))
    params = jax.eval_shape(lambda: weights.make(arch, 0))
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    compiled_before = ops.compiled
    ops.compiled = lambda: True  # compile the kernels, do not interpret them
    try:
        step = serve.make_tiered_decode_step(model, mesh, ParallelConfig(), ts, use_kernels=True)
        args = (shapes_of(params, one), jax.ShapeDtypeStruct((bs, 1), jnp.int32, sharding=one),
                shapes_of(state, one),
                shapes_of((jnp.zeros((0,)), jnp.zeros((0,))), one))
        t0 = time.perf_counter()
        c = jax.jit(step).lower(*args).compile()
        print(f"{name} decode step (B={bs}, max_seq_len={msl}): compiled in "
              f"{time.perf_counter() - t0:.1f}s, fused kernel: {'tpu_custom_call' in c.as_text()}")
        print(f"  {c.memory_analysis()}")
        s = PREFILL[name]
        cache = jax.eval_shape(lambda: model.init_cache(1, s + 1))
        t0 = time.perf_counter()
        c = jax.jit(model.prefill).lower(
            shapes_of(params, one), {"tokens": jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one)},
            shapes_of(cache, one)).compile()
        print(f"{name} prefill ({s} tokens): compiled in {time.perf_counter() - t0:.1f}s")
        print(f"  {c.memory_analysis()}")
    finally:
        ops.compiled = compiled_before


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for arg in argv or list(PREFILL):
        name, _, slots = arg.partition(":")  # "<config>[:<batch slots>]"
        rehearse(name, topo, int(slots or 0))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
