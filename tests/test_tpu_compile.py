"""Served-path Pallas kernels compile for a TPU v5e at Qwen1.5-4B widths.

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


def test_fused_tiered_attention_compiles(one_chip):
    """One launch over int8 + int4 class buffers, host sentinel rows and the
    recent window; the unified table has warm + cold + host columns."""
    fn = functools.partial(fused_tiered_attention, page_tokens=T, interpret=False)
    _compile(
        fn, one_chip,
        ((B, H, HD), jnp.bfloat16),
        _payload(8), SCALES, _payload(8), SCALES,
        _payload(4), SCALES, _payload(4), SCALES,
        ((PAGES, KV, HD), jnp.float32),
        ((B, R, KV, HD), jnp.bfloat16), ((B, R, KV, HD), jnp.bfloat16),
        ((B, 3 * MP), jnp.int32), ((B, 3 * MP), jnp.int32), ((B,), jnp.int32),
    )
