"""Pallas TPU kernel: KV-page dequantization (the tier *decompress* / fault
path). Inverse of ``quant_page``; one program per page."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.packing import unpack_int4


def _dequant_kernel(payload_ref, scale_ref, out_ref, *, bits: int, out_dtype):
    scale = scale_ref[...]  # [1, T, KV]
    if bits == 8:
        q = payload_ref[...].astype(jnp.float32)
    else:
        q = unpack_int4(payload_ref[...])
    out_ref[...] = (q * scale[..., None]).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bits", "out_dtype", "interpret"))
def dequant_pages(
    payload: jax.Array,
    scales: jax.Array,
    bits: int,
    out_dtype=jnp.bfloat16,
    *,
    interpret: bool,
):
    """payload [P, T, KV, hd(|//2)], scales [P, T, KV] -> pages [P, T, KV, hd]."""
    p, t, kv, hdp = payload.shape
    hd = hdp if bits == 8 else hdp * 2
    return pl.pallas_call(
        functools.partial(_dequant_kernel, bits=bits, out_dtype=out_dtype),
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, t, kv, hdp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, t, kv), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, kv, hd), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, t, kv, hd), out_dtype),
        interpret=interpret,
    )(payload, scales)
