"""Pure-jnp oracles for every Pallas kernel in this package.

These define the exact semantics the kernels must reproduce (tests sweep
shapes/dtypes and assert_allclose kernel-vs-ref; ``chip_smoke.py`` compares
the compiled kernels against them on the chip). Off the TPU the serving
engine's decode step runs these oracles (see ``ops.compiled``); on the TPU
it never does.

KV-page quantization layout (serving hot path):
  page:    [T, KV, hd]  bf16 source (T tokens per page)
  int8:    payload [T, KV, hd] int8, scales [T, KV] f32 (absmax over hd)
  int4:    payload [T, KV, hd//2] uint8 (byte i = element i in the lo nibble,
           element i + hd/2 in the hi nibble; see kernels.packing), scales as int8

Paged attention partials follow flash-decoding: each tier's pool produces
(out_unnorm, m, l, page_mass); partials merge exactly via logsumexp. The
per-page attention mass is the paper's telemetry signal (exact hotness).
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.packing import QMAX, pack_int4, unpack_int4

Array = jax.Array

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# KV-page quant / dequant
# ---------------------------------------------------------------------------


def quant_kv_page(page: Array, bits: int) -> Tuple[Array, Array]:
    """page [..., T, KV, hd] -> (payload, scales [..., T, KV])."""
    x = page.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(amax == 0, 1.0, amax / QMAX[bits])
    q = jnp.clip(jnp.round(x / scale[..., None]), -QMAX[bits], QMAX[bits])
    if bits == 8:
        return q.astype(jnp.int8), scale
    # int4: pack adjacent pairs along hd into one uint8 (see kernels.packing).
    return pack_int4(q), scale


def dequant_kv_page(payload: Array, scales: Array, bits: int) -> Array:
    """Inverse of quant_kv_page (returns f32)."""
    if bits == 8:
        q = payload.astype(jnp.float32)
    else:
        q = unpack_int4(payload)
    return q * scales[..., None]


# -- cxl_hw: inline line-compressed far memory ------------------------------
# Software quantizes a page to dense int8 (same layout as the int8 codec);
# the expander's controller narrows each 64-codeword hardware line to 4-bit
# storage when every value fits int4 range. The engine always reads back the
# dense int8 view — line_bits only changes stored/wire bytes, never values.

CXL_LINE_ELEMS = 64  # int8 codewords per hardware cache line
CXL_NARROW_QMAX = 7  # |q| <= 7 -> the line is stored 4-bit


def cxl_encode_kv_page(page: Array) -> Tuple[Array, Array, Array]:
    """page [..., T, KV, hd] -> (payload int8, scales [..., T, KV],
    line_bits [..., T, KV, hd // CXL_LINE_ELEMS] in {4, 8})."""
    payload, scales = quant_kv_page(page, 8)
    return payload, scales, cxl_page_line_bits(payload)


def cxl_page_line_bits(payload: Array) -> Array:
    """Stored width of each hardware line of an int8 payload."""
    hd = payload.shape[-1]
    assert hd % CXL_LINE_ELEMS == 0, f"hd {hd} not a multiple of line size"
    lines = payload.astype(jnp.int32).reshape(
        *payload.shape[:-1], hd // CXL_LINE_ELEMS, CXL_LINE_ELEMS
    )
    narrow = jnp.max(jnp.abs(lines), axis=-1) <= CXL_NARROW_QMAX
    return jnp.where(narrow, 4, 8).astype(jnp.int32)


def cxl_decode_kv_page(payload: Array, scales: Array) -> Array:
    """Inverse of cxl_encode_kv_page (controller decompression is inline and
    value-exact, so decode is plain int8 dequant)."""
    return dequant_kv_page(payload, scales, 8)


def cxl_page_line_ratio(line_bits: Array) -> float:
    """Observed line-compression ratio over a batch of pages: nominal dense
    payload bits / stored line bits. In [1, 2]."""
    import numpy as np

    total = int(np.asarray(line_bits, dtype=np.int64).sum())
    return float(8 * line_bits.size) / float(max(total, 1))


def transcode_kv_page(
    payload: Array, scales: Array, src_bits: int, dst_bits: int
) -> Tuple[Array, Array]:
    """Requantize pages between codec widths (int8 <-> int4).

    Semantics are exactly the dequant -> quant composition; the Pallas
    kernel fuses the two so the dense f32 page never round-trips HBM.
    Same-width transcode is the identity (the same-codec fast path is a
    media copy and never calls this).
    """
    if src_bits == dst_bits:
        return payload, scales
    return quant_kv_page(dequant_kv_page(payload, scales, src_bits), dst_bits)


# ---------------------------------------------------------------------------
# Paged decode attention over one quantized pool
# ---------------------------------------------------------------------------


def paged_quant_attention(
    q: Array,  # [B, H, hd]
    k_pages: Array,  # [P, T, KV, hd(|//2)] int8/uint8
    k_scales: Array,  # [P, T, KV] f32
    v_pages: Array,
    v_scales: Array,
    page_table: Array,  # [B, MP] int32 (pool page id; entries >= n_pages ignored)
    n_pages: Array,  # [B] int32 valid page-table prefix length
    bits: int,
    slot_pos: Array = None,  # [B, MP] logical slot positions (default iota);
    # sequence-parallel shards pass their global positions so validity
    # against n_pages stays correct on a table slice.
) -> Tuple[Array, Array, Array, Array, Array]:
    """Flash-decoding partials over the pool's pages.

    Returns (out_unnorm [B,H,hd] f32, m [B,H], l [B,H],
             page_mass [B,MP], page_base [B,MP]).
    page_mass is the softmax mass of each page at its *local* base
    (page_base = that page's max score over heads and tokens); the true
    normalized hotness is  mass * exp(base - m_tot) / l_tot  once the global
    (m_tot, l_tot) is known after merging (see ops.page_hotness).
    softmax uses 1/sqrt(hd) scaling; GQA broadcast by kv head grouping.
    """
    b, h, hd = q.shape
    mp = page_table.shape[1]
    kv = k_pages.shape[2]
    t = k_pages.shape[1]
    g = h // kv

    qf = q.astype(jnp.float32).reshape(b, kv, g, hd) / (hd**0.5)

    # Scan over page-table chunks with online softmax — mirrors the kernel's
    # page-at-a-time pipeline: the working set stays O(chunk) instead of
    # materializing the whole dequantized pool (impossible at 500k context).
    chunk = min(mp, 128)
    pad = (-mp) % chunk
    if slot_pos is None:
        slot_pos = jnp.broadcast_to(jnp.arange(mp, dtype=jnp.int32)[None], (b, mp))
    if pad:
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, pad)), constant_values=2**30)
    n_chunks = (mp + pad) // chunk
    table_c = page_table.reshape(b, n_chunks, chunk)
    pos_c = jnp.moveaxis(slot_pos.reshape(b, n_chunks, chunk), 1, 0)

    def body(carry, xs):
        acc, m_run, l_run = carry
        tbl, pos = xs  # [B, C], [B, C]
        k = dequant_kv_page(k_pages[tbl], k_scales[tbl], bits)  # [B,C,T,KV,hd]
        v = dequant_kv_page(v_pages[tbl], v_scales[tbl], bits)
        scores = jnp.einsum("bkgh,bptkh->bkgpt", qf, k)  # [B,KV,G,C,T]
        valid = (pos < n_pages[:, None])[:, None, None, :, None]
        scores = jnp.where(valid, scores, -jnp.inf)

        c_max = jnp.max(scores, axis=(3, 4))  # [B,KV,G]
        c_max = jnp.where(jnp.isfinite(c_max), c_max, NEG_INF)
        m_new = jnp.maximum(m_run, c_max)
        shift = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        e = jnp.where(valid, jnp.exp(scores - shift[..., None, None]), 0.0)
        alpha = jnp.where(m_run > NEG_INF / 2, jnp.exp(m_run - shift), 0.0)
        l_new = l_run * alpha + jnp.sum(e, axis=(3, 4))
        acc_new = acc * alpha[..., None] + jnp.einsum("bkgpt,bptkh->bkgh", e, v)

        # Telemetry at each page's local max.
        p_base = jnp.max(scores, axis=(1, 2, 4))  # [B,C]
        b_safe = jnp.where(jnp.isfinite(p_base), p_base, 0.0)
        e_loc = jnp.where(valid, jnp.exp(scores - b_safe[:, None, None, :, None]), 0.0)
        p_mass = jnp.sum(e_loc, axis=(1, 2, 4))  # [B,C]
        p_base = jnp.where(jnp.isfinite(p_base), p_base, NEG_INF)
        return (acc_new, m_new, l_new), (p_mass, p_base)

    acc0 = jnp.zeros((b, kv, g, hd), jnp.float32)
    m0 = jnp.full((b, kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g), jnp.float32)
    (out, m, l), (masses, bases) = jax.lax.scan(
        body, (acc0, m0, l0), (jnp.moveaxis(table_c, 1, 0), pos_c)
    )
    page_mass = jnp.moveaxis(masses, 0, 1).reshape(b, mp + pad)[:, :mp]
    page_base = jnp.moveaxis(bases, 0, 1).reshape(b, mp + pad)[:, :mp]
    m_safe = jnp.where(m > NEG_INF / 2, m, 0.0)
    return (
        out.reshape(b, h, hd),
        m_safe.reshape(b, h),
        l.reshape(b, h),
        page_mass,
        page_base,
    )


def dense_recent_attention(
    q: Array,  # [B, H, hd]
    recent_k: Array,  # [B, R, KV, hd]
    recent_v: Array,
    recent_len: Array,  # scalar or [B]
) -> Tuple[Array, Array, Array]:
    """Partials over the dense (uncompressed) recent window."""
    b, h, hd = q.shape
    kv = recent_k.shape[2]
    g = h // kv
    qf = q.astype(jnp.float32).reshape(b, kv, g, hd) / (hd**0.5)
    scores = jnp.einsum("bkgh,brkh->bkgr", qf, recent_k.astype(jnp.float32))
    r = recent_k.shape[1]
    rl = jnp.broadcast_to(jnp.asarray(recent_len), (b,))
    valid = (jnp.arange(r)[None] < rl[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(valid, jnp.exp(scores - m_safe[..., None]), 0.0)
    l = jnp.sum(e, axis=-1)
    out = jnp.einsum("bkgr,brkh->bkgh", e, recent_v.astype(jnp.float32))
    return out.reshape(b, h, hd), m_safe.reshape(b, h), l.reshape(b, h)


def merge_partials(parts: List[Tuple[Array, Array, Array]]) -> Array:
    """Exact merge of flash partials [(out_unnorm, m, l), ...] -> out [B,H,hd]."""
    m_all = jnp.stack([p[1] for p in parts])  # [N,B,H]
    m_tot = jnp.max(m_all, axis=0)
    num = 0.0
    den = 0.0
    for out_u, m, l in parts:
        w = jnp.exp(m - m_tot)
        num = num + out_u * w[..., None]
        den = den + l * w
    den = jnp.maximum(den, 1e-30)
    return num / den[..., None]


def tiered_decode_attention(
    q: Array,
    pools: dict,
    recent_k: Array,
    recent_v: Array,
    recent_len,
    cfg=None,
) -> Array:
    """Full oracle: attention over N quantized tier pools + dense recent
    window, merged exactly. ``pools`` maps tier name -> dict with keys
    (k_pages, k_scales, v_pages, v_scales, page_table, n_pages, bits).
    Returns out [B, H, hd] (f32)."""
    parts = [dense_recent_attention(q, recent_k, recent_v, recent_len)]
    for name in sorted(pools):
        p = pools[name]
        out_u, m, l, _, _ = paged_quant_attention(
            q,
            p["k_pages"],
            p["k_scales"],
            p["v_pages"],
            p["v_scales"],
            p["page_table"],
            p["n_pages"],
            p["bits"],
        )
        parts.append((out_u, m, l))
    return merge_partials(parts)


def host_page_mass(
    q: Array,  # [B, H, hd]
    summaries: Array,  # [Hs, KV, hd] f32 per-page key centroids
    table: Array,  # [B, MPh] int32 summary-slot ids (sentinel rows)
    n_rows: Array,  # [B] int32 valid prefix length
    page_tokens: int,
) -> Tuple[Array, Array]:
    """Would-have-touched softmax mass for host-resident pages.

    Host pages are never read in-step (that access-skip is the best-TCO
    tiers' quality cost), so their exact attention mass is unknowable
    without paying the fetch. The sentinel proxy scores the page's stored
    key centroid (mean over its T tokens, computed from the dequantized K
    payload at evict time) against q and charges all ``page_tokens`` tokens
    at that score:

        mass = T * sum_{kv,g} exp(s - max s),   base = max s

    This is exactly what the fused kernel's sentinel rows emit; normalize
    with the merged (m, l) like any page mass (``ops.page_hotness``).
    Telemetry only — sentinels never contribute to (acc, m, l).
    """
    b, h, hd = q.shape
    kv = summaries.shape[1]
    g = h // kv
    mp = table.shape[1]
    qf = q.astype(jnp.float32).reshape(b, kv, g, hd) / (hd**0.5)
    kbar = summaries[table]  # [B, MPh, KV, hd]
    s = jnp.einsum("bkgh,bpkh->bkgp", qf, kbar.astype(jnp.float32))  # [B,KV,G,P]
    base = jnp.max(s, axis=(1, 2))  # [B, MPh]
    mass = page_tokens * jnp.sum(jnp.exp(s - base[:, None, None, :]), axis=(1, 2))
    valid = jnp.arange(mp, dtype=jnp.int32)[None] < n_rows[:, None]
    return jnp.where(valid, mass, 0.0), jnp.where(valid, base, NEG_INF)


def fused_tiered_attention(
    q: Array,
    pools: dict,
    recent_k: Array,
    recent_v: Array,
    recent_len,
    host: dict = None,
):
    """Oracle for the single-launch megakernel: attention over N quantized
    tier pools + dense recent window with an exact merge, plus per-pool
    page-mass telemetry and (when ``host`` is given) the would-have-touched
    mass of host sentinel rows.

    ``host`` is a dict with keys ``summary`` [Hs, KV, hd], ``table``
    [B, MPh], ``n`` [B] and ``page_tokens``. Returns
    (out [B,H,hd] normalized, m_tot [B,H], l_tot [B,H],
     masses {name: (mass, base)} incl. "host").
    """
    b = q.shape[0]
    rlen = jnp.broadcast_to(jnp.asarray(recent_len, jnp.int32), (b,))
    parts = [dense_recent_attention(q, recent_k, recent_v, rlen)]
    masses = {}
    for name in sorted(pools):
        p = pools[name]
        out_u, m, l, mass, base = paged_quant_attention(
            q, p["k_pages"], p["k_scales"], p["v_pages"], p["v_scales"],
            p["page_table"], p["n_pages"], p["bits"],
        )
        parts.append((out_u, m, l))
        masses[name] = (mass, base)
    out = merge_partials(parts)
    m_tot = jnp.max(jnp.stack([p[1] for p in parts]), axis=0)
    l_tot = sum(p[2] * jnp.exp(p[1] - m_tot) for p in parts)
    if host is not None:
        masses["host"] = host_page_mass(
            q, host["summary"], host["table"], host["n"], host["page_tokens"]
        )
    return out, m_tot, l_tot, masses


def tiered_page_masses(q, pools) -> dict:
    """Per-tier (page_mass, page_base) telemetry; normalize with
    ops.page_hotness after merging."""
    out = {}
    for name, p in pools.items():
        _, _, _, mass, base = paged_quant_attention(
            q,
            p["k_pages"],
            p["k_scales"],
            p["v_pages"],
            p["v_scales"],
            p["page_table"],
            p["n_pages"],
            p["bits"],
        )
        out[name] = (mass, base)
    return out
