"""Pallas TPU kernel: fused KV-page transcode (tier-to-tier requantization).

The migration hot path: moving a page between an int8 tier and an int4 tier
requires requantizing payload+scales. The naive path is two kernels and a
dense f32 round-trip through HBM (dequant_page -> quant_page); this kernel
fuses both so each page is read once (compressed), requantized entirely in
VMEM, and written once (compressed) — the software analogue of the paper's
"hardware-rate bulk (de)compression" requirement for compressed-tier
migrations.

Grid over pages; each program transcodes one [T, KV, hd] page. The dequant
multiply, absmax reduce and requant divide all vectorize on the VPU with hd
on the 128-lane axis. int4 payloads use the ``kernels.packing`` layout
(element i and i + hd/2 share a byte), matching quant_page/dequant_page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.packing import QMAX, pack_int4, unpack_int4


def _transcode_kernel(payload_ref, scale_ref, out_pay_ref, out_scale_ref,
                      *, src_bits: int, dst_bits: int):
    scale = scale_ref[...]  # [1, T, KV]
    if src_bits == 8:
        q = payload_ref[...].astype(jnp.float32)
    else:
        q = unpack_int4(payload_ref[...])
    x = q * scale[..., None]  # dense page, VMEM-resident only
    amax = jnp.max(jnp.abs(x), axis=-1)
    new_scale = jnp.where(amax == 0.0, 1.0, amax / QMAX[dst_bits])
    qn = jnp.clip(jnp.round(x / new_scale[..., None]), -QMAX[dst_bits], QMAX[dst_bits])
    if dst_bits == 8:
        out_pay_ref[...] = qn.astype(jnp.int8)
    else:
        out_pay_ref[...] = pack_int4(qn)
    out_scale_ref[...] = new_scale


@functools.partial(jax.jit, static_argnames=("src_bits", "dst_bits", "interpret"))
def transcode_pages(
    payload: jax.Array,
    scales: jax.Array,
    src_bits: int,
    dst_bits: int,
    *,
    interpret: bool,
):
    """payload [P, T, KV, hd(|//2)], scales [P, T, KV] ->
    (payload' [P, T, KV, hd'(|//2)], scales' [P, T, KV]) at dst_bits."""
    if src_bits == dst_bits:
        return payload, scales
    p, t, kv, hdp = payload.shape
    hd = hdp if src_bits == 8 else hdp * 2
    hd_out = hd if dst_bits == 8 else hd // 2
    out_dtype = jnp.int8 if dst_bits == 8 else jnp.uint8
    return pl.pallas_call(
        functools.partial(_transcode_kernel, src_bits=src_bits, dst_bits=dst_bits),
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, t, kv, hdp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, t, kv), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, kv, hd_out), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, t, kv), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, t, kv, hd_out), out_dtype),
            jax.ShapeDtypeStruct((p, t, kv), jnp.float32),
        ],
        interpret=interpret,
    )(payload, scales)
