"""Pallas TPU kernel: KV-page block quantization (the tier *compress* path).

Grid over pages; each program quantizes one [T, KV, hd] page to int8 or
packed int4 with per-(token, kv-head) absmax scales. Blocks are VMEM-resident
(a 64-token x 8-head x 128-dim page is 128KB bf16 — comfortably within VMEM)
and hd=head_dim is the 128-lane axis, so the absmax reduce and the scale
multiply both vectorize cleanly on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.packing import QMAX, pack_int4


def _quant_kernel(page_ref, payload_ref, scale_ref, *, bits: int):
    x = page_ref[...].astype(jnp.float32)  # [1, T, KV, hd]
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(amax == 0.0, 1.0, amax / QMAX[bits])
    q = jnp.clip(jnp.round(x / scale[..., None]), -QMAX[bits], QMAX[bits])
    if bits == 8:
        payload_ref[...] = q.astype(jnp.int8)
    else:
        payload_ref[...] = pack_int4(q)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quant_pages(pages: jax.Array, bits: int, *, interpret: bool):
    """pages [P, T, KV, hd] bf16 -> (payload, scales [P, T, KV])."""
    p, t, kv, hd = pages.shape
    hd_out = hd if bits == 8 else hd // 2
    out_dtype = jnp.int8 if bits == 8 else jnp.uint8
    return pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits),
        grid=(p,),
        in_specs=[pl.BlockSpec((1, t, kv, hd), lambda i: (i, 0, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, t, kv, hd_out), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, t, kv), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, t, kv, hd_out), out_dtype),
            jax.ShapeDtypeStruct((p, t, kv), jnp.float32),
        ],
        interpret=interpret,
    )(pages)
