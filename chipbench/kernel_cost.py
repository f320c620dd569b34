"""Operations and bytes that one call of a kernel needs, from its shapes.

These are the algorithm's needs, not what the kernel happens to move: a
kernel that fetches more, or computes padding, shows it as a lower share of
its roofline.
"""

from __future__ import annotations

import numpy as np


def fused_tiered_attention(warm_n, cold_n, host_n, rlen, *, page_tokens: int,
                           kv_heads: int, head_dim: int, heads: int):
    """``kernels/paged_attention.fused_tiered_attention`` for one layer.

    Arguments are per batch row of the call (rows that serve no request
    are left out by the caller): int8 pages, int4 pages, host sentinel rows
    and dense recent-window tokens. Returns (flops, bytes) summed over rows.

    Per attended token each head does a q.k and a p.v product of ``hd``
    multiply-adds; a sentinel row scores one key centroid per head. Bytes:
    int8 and int4 K and V payloads with one f32 scale per token and head,
    an f32 centroid per sentinel, bf16 recent K and V, the bf16 query, the
    f32 output with its max and sum per head, and a mass and base per row.
    """
    t, kv, hd, h = page_tokens, kv_heads, head_dim, heads
    warm_n, cold_n, host_n, rlen = (np.asarray(x, np.int64) for x in (warm_n, cold_n, host_n, rlen))
    tokens = (warm_n + cold_n) * t + rlen
    flops = 4 * h * hd * tokens + 2 * h * hd * host_n
    scales = t * kv * 4
    nbytes = (
        warm_n * 2 * (t * kv * hd + scales)
        + cold_n * 2 * (t * kv * hd // 2 + scales)
        + host_n * kv * hd * 4
        + rlen * 2 * kv * hd * 2
        + h * hd * 2
        + h * hd * 4 + 2 * h * 4
        + (warm_n + cold_n + host_n) * 2 * 4
    )
    return int(flops.sum()), int(nbytes.sum())


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time on the chip: the larger of compute and memory time."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def decode_flops_per_token(arch, context: float) -> float:
    """Model operations to decode one token at a given context length:
    every matrix of the layers and the head, plus attention over context."""
    return 2.0 * arch.n_params() + 4.0 * arch.n_layers * arch.n_heads * arch.head_dim * context
