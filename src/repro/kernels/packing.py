"""Shared int4 nibble-packing layout + quantization ranges.

The layout is a cross-kernel invariant: element ``i`` of the first head-dim
half and element ``i + hd/2`` of the second half pack into byte ``i``, the
first half in the LOW nibble, nibbles in two's complement. Both halves are
contiguous lane slices, so the TPU lowering needs no strided gather.
quant_page, dequant_page, transcode_page, the fused attention kernel and the
ref oracles all import these helpers so the convention lives in exactly one
place. Pure jnp ops — usable inside Pallas kernel bodies and in the oracles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QMAX = {8: 127.0, 4: 7.0}


def pack_int4(q: jax.Array) -> jax.Array:
    """[..., hd] integer values in [-7, 7] -> [..., hd//2] uint8."""
    qi = q.astype(jnp.int32)
    half = qi.shape[-1] // 2
    lo = qi[..., :half] & 0xF
    hi = qi[..., half:] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(payload: jax.Array) -> jax.Array:
    """[..., hd//2] uint8 -> [..., hd] f32 values in [-8, 7]."""
    p = payload.astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
