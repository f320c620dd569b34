"""Pallas TPU kernels: cxl_hw line codec (inline hardware compression model).

Grid over pages, one program per [T, KV, hd] page. Encode emits the dense
int8 payload + per-(token, kv-head) scales — exactly the int8 quant — plus
the per-hardware-line stored width (4 or 8 bits) the inline compressor
achieves, reduced over CXL_LINE_ELEMS-codeword lines on the 128-lane axis.
Decode is the plain int8 dequant: the controller decompresses inline, so the
VPU always sees the dense view. Oracles: kernels.ref.cxl_encode_kv_page /
cxl_decode_kv_page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.packing import QMAX
from repro.kernels.ref import CXL_LINE_ELEMS, CXL_NARROW_QMAX


def _cxl_encode_kernel(page_ref, payload_ref, scale_ref, bits_ref):
    x = page_ref[...].astype(jnp.float32)  # [1, T, KV, hd]
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(amax == 0.0, 1.0, amax / QMAX[8])
    q = jnp.clip(jnp.round(x / scale[..., None]), -QMAX[8], QMAX[8])
    payload_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale
    _, t, kv, hd = q.shape
    lines = q.astype(jnp.int32).reshape(1, t, kv, hd // CXL_LINE_ELEMS, CXL_LINE_ELEMS)
    narrow = jnp.max(jnp.abs(lines), axis=-1) <= CXL_NARROW_QMAX
    bits_ref[...] = jnp.where(narrow, 4, 8).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cxl_encode_pages(pages: jax.Array, *, interpret: bool):
    """pages [P, T, KV, hd] bf16 -> (payload int8, scales [P, T, KV] f32,
    line_bits [P, T, KV, hd // CXL_LINE_ELEMS] int32)."""
    p, t, kv, hd = pages.shape
    n_lines = hd // CXL_LINE_ELEMS
    return pl.pallas_call(
        _cxl_encode_kernel,
        grid=(p,),
        in_specs=[pl.BlockSpec((1, t, kv, hd), lambda i: (i, 0, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, t, kv, hd), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, t, kv), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, t, kv, n_lines), lambda i: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, t, kv, hd), jnp.int8),
            jax.ShapeDtypeStruct((p, t, kv), jnp.float32),
            jax.ShapeDtypeStruct((p, t, kv, n_lines), jnp.int32),
        ],
        interpret=interpret,
    )(pages)


def _cxl_decode_kernel(payload_ref, scale_ref, out_ref):
    q = payload_ref[...].astype(jnp.float32)  # [1, T, KV, hd]
    out_ref[...] = q * scale_ref[...][..., None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cxl_decode_pages(payload: jax.Array, scales: jax.Array, *, interpret: bool):
    """(payload [P, T, KV, hd] int8, scales [P, T, KV]) -> pages f32."""
    p, t, kv, hd = payload.shape
    return pl.pallas_call(
        _cxl_decode_kernel,
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, t, kv, hd), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, t, kv), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, kv, hd), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, t, kv, hd), jnp.float32),
        interpret=interpret,
    )(payload, scales)
