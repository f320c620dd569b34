"""Served-path Pallas kernels compile for a TPU v5e at served widths:
Qwen1.5-4B's, and for the fused attention kernel also InternLM2-20B's at
the benchmark cell's batch and table shapes.

Nothing runs: each test lowers a kernel with ``interpret=False`` against a
described (not attached) ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what the chip would refuse (block shapes off the
(8, 128) tiling, unsupported relayouts, strided gathers). The topology is
described inside a fixture, never at import, and every compile runs with
the persistent compile cache off (an entry compiled for a described chip
cannot be read back without one).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.kernels import paged_attention
from repro.kernels.dequant_page import dequant_pages
from repro.kernels.paged_attention import fused_tiered_attention
from repro.kernels.quant_page import quant_pages
from repro.kernels.transcode_page import transcode_pages

CFG = configs.get("qwen1_5_4b")
KV, HD, H = CFG.n_kv_heads, CFG.head_dim_(), CFG.n_heads
T = 16  # tokens per page, as served
PAGES = 64  # class-buffer rows
B, R = 4, 32  # batch slots, dense recent window
MP = 64  # table columns per pool (warm, cold, host)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _payload(bits):
    return ((PAGES, T, KV, HD if bits == 8 else HD // 2),
            jnp.int8 if bits == 8 else jnp.uint8)


SCALES = ((PAGES, T, KV), jnp.float32)


@pytest.mark.parametrize("bits", [8, 4])
def test_quant_pages_compiles(one_chip, bits):
    _compile(lambda x: quant_pages(x, bits, interpret=False), one_chip,
             ((PAGES, T, KV, HD), jnp.bfloat16))


@pytest.mark.parametrize("bits", [8, 4])
def test_dequant_pages_compiles(one_chip, bits):
    _compile(lambda p, s: dequant_pages(p, s, bits, interpret=False), one_chip,
             _payload(bits), SCALES)


@pytest.mark.parametrize("src,dst", [(8, 4), (4, 8)])
def test_transcode_pages_compiles(one_chip, src, dst):
    _compile(lambda p, s: transcode_pages(p, s, src, dst, interpret=False), one_chip,
             _payload(src), SCALES)


FUSED_SHAPES = {
    # name: (batch, KV heads, query heads per KV head, head dim, table
    # width, walk bound); 384 pages = max_seq_len 6144 in 16-token pages
    "internlm2_20b_8l": (16, 8, 6, 128, 3 * 384, 384),
    "qwen1_5_4b": (16, KV, H // KV, HD, 3 * 384, 384),
}


@pytest.mark.parametrize("name", sorted(FUSED_SHAPES))
def test_fused_tiered_attention_compiles(one_chip, name):
    """One launch over int8 + int4 class buffers, host sentinel rows and the
    recent window; the unified table has warm + cold + host columns and the
    kernel walks at most one sequence's pages. The kernel states its VMEM
    ceiling, v5e's default scoped VMEM, and the compiler refuses a kernel
    whose blocks and scratch outgrow it: the compile is the VMEM check."""
    assert paged_attention.VMEM_LIMIT_BYTES <= 16 << 20
    b, kv, group, hd, ms, walk = FUSED_SHAPES[name]
    h = kv * group
    payload = {bits: ((PAGES, T, kv, hd if bits == 8 else hd // 2),
                      jnp.int8 if bits == 8 else jnp.uint8) for bits in (8, 4)}
    scales = ((PAGES, T, kv), jnp.float32)
    fn = functools.partial(fused_tiered_attention, page_tokens=T, walk=walk, interpret=False)
    _compile(
        fn, one_chip,
        ((b, h, hd), jnp.bfloat16),
        payload[8], scales, payload[8], scales,
        payload[4], scales, payload[4], scales,
        ((PAGES, kv, hd), jnp.float32),
        ((b, R, kv, hd), jnp.bfloat16), ((b, R, kv, hd), jnp.bfloat16),
        ((b, ms), jnp.int32), ((b, ms), jnp.int32), ((b,), jnp.int32),
    )
