"""Pallas TPU kernels: paged decode attention over quantized tier pools.

This is the paper's warm-data access path made cheap: instead of fault-and-
decompress (the 2-Tier cost model), the decode step *reads the compressed
pool directly* — pages are DMA'd to VMEM by the pipeline (page table drives
the BlockSpec index_map via scalar prefetch), dequantized in-kernel, and
consumed by an online-softmax accumulation. Per-page softmax mass is emitted
as exact hotness telemetry for the TierScape manager.

Two kernels live here:

``paged_quant_attention`` — flash partials over ONE pool. Mixed tiers run it
once per tier pool and merge the partials (exact logsumexp) together with
the dense recent-window partial post-hoc — the per-pool oracle path in
``ops.tiered_decode_attention``; one launch per tier.

``fused_tiered_attention`` — the single-launch megakernel. One unified page
table whose rows carry ``(pool_slot, tier_code)`` covers ALL compressed pages
of a sequence regardless of codec: tier codes select the int8/int4 dequant
path in-kernel, host-resident pages appear as sentinel rows that fetch only
a tiny per-page key centroid (no payload) and emit a "would-have-touched"
softmax mass as telemetry, the dense recent window runs as the final grid
step of the same launch, and the (acc, m, l) logsumexp merge happens in
VMEM scratch — one launch per decode step, O(1) in tier count.

Grids. ``paged_quant_attention``: (batch, pages); invalid table slots are
skipped with @pl.when. ``fused_tiered_attention`` walks only the slots that
hold a page: before the launch, plain XLA ops compact each row's valid
unified columns, in column order, into a walk list of at most ``walk``
entries (one sequence's page bound), scalar-prefetched with each row's
count. Its grid is 1-D and sized at run time: each row in turn takes one
step per held page, whose class-gated blocks the table names, and one last
step for the recent window, which keeps the last page's blocks so the
pipeline fetches nothing new for it. Per-page masses stay in a per-row
output block written back once per row, and are scattered back to the
unified layout after the launch. The grid is sequential ("arbitrary"):
VMEM scratch carries (acc, m, l) across a row's steps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packing import unpack_int4 as _unpack_int4

NEG_INF = -1e30

# Tier codes carried by the unified page table (``fused_tiered_attention``).
# Rows are (pool_slot, tier_code): the code picks the in-kernel dequant path
# (int8 vs int4 group buffer), marks host sentinels (summary fetch only, no
# payload), or invalidates the row entirely.
TIER_INT8 = 0
TIER_INT4 = 1
TIER_HOST = 2
TIER_INVALID = -1


def _paged_attn_kernel(
    # scalar-prefetch operands
    table_ref,  # [B, MP] int32
    npages_ref,  # [B] int32
    # array operands (blocked)
    q_ref,  # [1, H, hd]
    kp_ref,  # [1, T, KV, hd(|//2)]
    ks_ref,  # [1, T, KV]
    vp_ref,
    vs_ref,
    # outputs
    out_ref,  # [1, H, hd] f32 (unnormalized)
    m_ref,  # [1, H] f32
    l_ref,  # [1, H] f32
    mass_ref,  # [1, 1] f32 per (b, p): page softmax mass at its local base
    base_ref,  # [1, 1] f32 per (b, p): the local base (page max score)
    # scratch
    acc_ref,  # [KV, G, hd] f32
    run_m_ref,  # [KV, G] f32
    run_l_ref,  # [KV, G] f32
    *,
    bits: int,
    kv: int,
    group: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    mp = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        run_m_ref[...] = jnp.full_like(run_m_ref, NEG_INF)
        run_l_ref[...] = jnp.zeros_like(run_l_ref)

    valid = p < npages_ref[b]

    @pl.when(valid)
    def _accumulate():
        hd = acc_ref.shape[-1]
        q = q_ref[0].astype(jnp.float32).reshape(kv, group, hd) / (hd**0.5)
        if bits == 8:
            k = kp_ref[0].astype(jnp.float32)
            v = vp_ref[0].astype(jnp.float32)
        else:
            k = _unpack_int4(kp_ref[0].astype(jnp.int32))
            v = _unpack_int4(vp_ref[0].astype(jnp.int32))
        k = k * ks_ref[0][..., None]  # [T, KV, hd]
        v = v * vs_ref[0][..., None]

        scores = jnp.einsum("kgh,tkh->kgt", q, k)  # [KV, G, T]
        page_max = jnp.max(scores, axis=-1)  # [KV, G]
        m_old = run_m_ref[...]
        m_new = jnp.maximum(m_old, page_max)
        alpha = jnp.exp(m_old - m_new)  # rescale old accumulators
        e = jnp.exp(scores - m_new[..., None])  # [KV, G, T]
        l_new = run_l_ref[...] * alpha + jnp.sum(e, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum("kgt,tkh->kgh", e, v)
        run_m_ref[...] = m_new
        run_l_ref[...] = l_new
        # Exact per-page attention-mass telemetry at the page's local base
        # (rebased to the merged global max by ops.page_hotness).
        pbase = jnp.max(page_max)
        e_loc = jnp.exp(scores - pbase)
        mass_ref[0, 0] = jnp.sum(e_loc)
        base_ref[0, 0] = pbase

    @pl.when(jnp.logical_not(valid))
    def _skip():
        mass_ref[0, 0] = 0.0
        base_ref[0, 0] = NEG_INF

    @pl.when(p == mp - 1)
    def _finalize():
        hd = acc_ref.shape[-1]
        out_ref[0] = acc_ref[...].reshape(kv * group, hd)
        # Empty pools report m=0 (matching the ref's m_safe convention).
        m_fin = jnp.where(run_l_ref[...] > 0.0, run_m_ref[...], 0.0)
        m_ref[0] = m_fin.reshape(kv * group)
        l_ref[0] = run_l_ref[...].reshape(kv * group)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def paged_quant_attention(
    q: jax.Array,  # [B, H, hd]
    k_pages: jax.Array,  # [P, T, KV, hd(|//2)]
    k_scales: jax.Array,  # [P, T, KV]
    v_pages: jax.Array,
    v_scales: jax.Array,
    page_table: jax.Array,  # [B, MP] int32
    n_pages: jax.Array,  # [B] int32
    bits: int,
    *,
    interpret: bool,
):
    """Flash partials over one pool: (out_unnorm, m, l, page_mass)."""
    b, h, hd = q.shape
    pp, t, kv, hdp = k_pages.shape
    mp = page_table.shape[1]
    group = h // kv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda bi, pi, tab, np_: (bi, 0, 0)),
            pl.BlockSpec((1, t, kv, hdp), lambda bi, pi, tab, np_: (tab[bi, pi], 0, 0, 0)),
            pl.BlockSpec((1, t, kv), lambda bi, pi, tab, np_: (tab[bi, pi], 0, 0)),
            pl.BlockSpec((1, t, kv, hdp), lambda bi, pi, tab, np_: (tab[bi, pi], 0, 0, 0)),
            pl.BlockSpec((1, t, kv), lambda bi, pi, tab, np_: (tab[bi, pi], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, hd), lambda bi, pi, tab, np_: (bi, 0, 0)),
            pl.BlockSpec((1, h), lambda bi, pi, tab, np_: (bi, 0)),
            pl.BlockSpec((1, h), lambda bi, pi, tab, np_: (bi, 0)),
            pl.BlockSpec((1, 1), lambda bi, pi, tab, np_: (bi, pi)),
            pl.BlockSpec((1, 1), lambda bi, pi, tab, np_: (bi, pi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((kv, group, hd), jnp.float32),
            pltpu.VMEM((kv, group), jnp.float32),
            pltpu.VMEM((kv, group), jnp.float32),
        ],
    )
    out, m, l, mass, base = pl.pallas_call(
        functools.partial(_paged_attn_kernel, bits=bits, kv=kv, group=group),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
            jax.ShapeDtypeStruct((b, mp), jnp.float32),
            jax.ShapeDtypeStruct((b, mp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table, n_pages, q, k_pages, k_scales, v_pages, v_scales)
    return out, m, l, mass, base


# ---------------------------------------------------------------------------
# Single-launch multi-tier megakernel
# ---------------------------------------------------------------------------


def _fused_attn_kernel(
    # scalar-prefetch operands
    wslot_ref,  # [B, W] int32: class-buffer (or centroid) row of the row's j-th held page
    wtier_ref,  # [B, W] int32: its TIER_* code
    nwalk_ref,  # [B] int32: held pages per row (the walk's length)
    rlen_ref,  # [B] int32 dense recent-window fill
    step_row_ref,  # [S] int32: batch row of each grid step
    step_pos_ref,  # [S] int32: walk position of each step; == nwalk is the row's last
    # array operands (blocked)
    q_ref,  # [1, G, KV, hd] (head kv*G + g at [g, kv])
    k8_ref,  # [1, T, KV, hd] int8 group buffer
    s8k_ref,  # [1, T, KV]
    v8_ref,
    s8v_ref,
    k4_ref,  # [1, T, KV, hd//2] int4 group buffer
    s4k_ref,
    v4_ref,
    s4v_ref,
    sum_ref,  # [1, KV, hd] f32 host-page key centroid (sentinel rows)
    rk_ref,  # [1, R, KV, hd] dense recent window
    rv_ref,
    # outputs
    out_ref,  # [1, G, KV, hd] f32 (NORMALIZED — merge happens in-kernel)
    m_ref,  # [1, G, KV, hd] f32 merged running max (lane-replicated)
    l_ref,  # [1, G, KV, hd] f32 merged partition mass (lane-replicated)
    mass_ref,  # [1, 1, W] f32 per walked page: softmax mass at its local base
    base_ref,  # [1, 1, W] f32 per walked page: the local base
    # scratch
    acc_ref,  # [G, KV, hd] f32
    run_m_ref,  # [G, KV, hd] f32 (lane-replicated)
    run_l_ref,  # [G, KV, hd] f32 (lane-replicated)
    *,
    group: int,
    page_tokens: int,
):
    """A 1-D grid of each row's steps in turn: one step per held page of the
    row in walk order, then a last step (position == the row's count) for
    the dense recent window + in-VMEM finalization. Pool pages accumulate
    (acc, m, l) online; host sentinel pages touch no payload — they score
    the page's key centroid against q and emit
    ``page_tokens * sum(exp(s - max s))`` as the would-have-touched mass
    (telemetry only, never accumulated). Per-page masses stay in the row's
    [1, W] output block, resident across the row's steps and written back
    once per row.

    Every value keeps the KV heads on sublanes and head_dim on lanes: a
    score is a lane reduction of ``k * q`` kept as a trailing unit dim, and
    the token axis is the leading (vreg-stacking) dim, so the body needs no
    transpose, relayout or batched matmul. GQA groups unroll statically."""
    step = pl.program_id(0)
    b = step_row_ref[step]
    j = step_pos_ref[step]
    n = nwalk_ref[b]
    hd = acc_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        run_m_ref[...] = jnp.full_like(run_m_ref, NEG_INF)
        run_l_ref[...] = jnp.zeros_like(run_l_ref)
        mass_ref[0] = jnp.zeros(mass_ref.shape[1:], jnp.float32)
        base_ref[0] = jnp.full(base_ref.shape[1:], NEG_INF, jnp.float32)

    q = q_ref[0].astype(jnp.float32) / (hd**0.5)  # [G, KV, hd]
    tid = jnp.where(j < n, wtier_ref[b, jnp.minimum(j, mass_ref.shape[-1] - 1)], TIER_INVALID)

    def _scores(k):
        # [T, KV, hd] keys -> per-group scores [T, KV, 1].
        return [jnp.sum(k * q[g][None], axis=-1, keepdims=True) for g in range(group)]

    def _emit(value, base):
        at = jax.lax.broadcasted_iota(jnp.int32, mass_ref.shape[1:], 1) == j
        mass_ref[0] = jnp.where(at, value, mass_ref[0])
        base_ref[0] = jnp.where(at, base, base_ref[0])

    def _accumulate(k, v):
        # Online-softmax update over one full page ([T, KV, hd] f32 k/v).
        scores = _scores(k)
        for g, s in enumerate(scores):
            page_max = jnp.max(s, axis=0)  # [KV, 1]
            m_old = run_m_ref[g]
            m_new = jnp.maximum(m_old, page_max)
            alpha = jnp.exp(m_old - m_new)
            e = jnp.exp(s - m_new[None])  # [T, KV, 1]
            run_l_ref[g] = run_l_ref[g] * alpha + jnp.sum(e, axis=0)
            acc_ref[g] = acc_ref[g] * alpha + jnp.sum(e * v, axis=0)
            run_m_ref[g] = m_new
        pbase = _max_all(scores)
        _emit(sum(_total(jnp.exp(s - pbase), jnp.sum) for s in scores), pbase)

    @pl.when(tid == TIER_INT8)
    def _pool8():
        k = k8_ref[0].astype(jnp.float32) * s8k_ref[0][..., None]
        v = v8_ref[0].astype(jnp.float32) * s8v_ref[0][..., None]
        _accumulate(k, v)

    @pl.when(tid == TIER_INT4)
    def _pool4():
        k = _unpack_int4(k4_ref[0]) * s4k_ref[0][..., None]
        v = _unpack_int4(v4_ref[0]) * s4v_ref[0][..., None]
        _accumulate(k, v)

    @pl.when(tid == TIER_HOST)
    def _host_sentinel():
        kbar = sum_ref[0].astype(jnp.float32)  # [KV, hd]
        scores = [jnp.sum(q[g] * kbar, axis=-1, keepdims=True) for g in range(group)]
        pbase = _max_all(scores)
        _emit(page_tokens * sum(_total(jnp.exp(s - pbase), jnp.sum) for s in scores), pbase)

    @pl.when(j == n)
    def _recent_and_finalize():
        rk = rk_ref[0].astype(jnp.float32)  # [R, KV, hd]
        rv = rv_ref[0].astype(jnp.float32)
        valid = jax.lax.broadcasted_iota(jnp.int32, (rk.shape[0], 1, 1), 0) < rlen_ref[b]
        for g, s in enumerate(_scores(rk)):
            s = jnp.where(valid, s, NEG_INF)
            m_old = run_m_ref[g]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=0))
            # Safe shift: both the recent window (rlen may be 0) and the pools
            # (all-host / empty) can be vacuous, so NEG_INF never enters exp.
            shift = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
            e = jnp.where(valid, jnp.exp(s - shift[None]), 0.0)
            alpha = jnp.where(m_old > NEG_INF / 2, jnp.exp(m_old - shift), 0.0)
            l_new = run_l_ref[g] * alpha + jnp.sum(e, axis=0)
            acc = acc_ref[g] * alpha + jnp.sum(e * rv, axis=0)
            out_ref[0, g] = acc / jnp.maximum(l_new, 1e-30)
            m_ref[0, g] = jnp.where(l_new > 0.0, m_new, 0.0)
            l_ref[0, g] = l_new


def _total(x, op):
    """Reduce a [..., KV, 1] value to [1, 1] with ``op`` (jnp.max/jnp.sum),
    one leading axis at a time (a reshape would need a lane broadcast)."""
    while x.ndim > 2:
        x = op(x, axis=0)
    return op(x, axis=0, keepdims=True)


def _max_all(scores):
    """Max over every group's [..., KV, 1] scores, as a [1, 1] value."""
    out = _total(scores[0], jnp.max)
    for s in scores[1:]:
        out = jnp.maximum(out, _total(s, jnp.max))
    return out


# The fused kernel's VMEM ceiling, v5e's default scoped VMEM stated as the
# kernel's own: the compiler refuses a kernel whose blocks outgrow it.
VMEM_LIMIT_BYTES = 16 << 20


def compact_walk(uni_slot: jax.Array, uni_tier: jax.Array, walk: int):
    """Per row, the unified-table columns that hold a page (tier code >= 0),
    in column order, as the first ``walk`` entries of a walk list.

    Returns (wslot [B, W], wtier [B, W], nwalk [B], pick [B, W, MS]): the
    slot and tier code of each walked page (``TIER_INVALID`` past the row's
    count), the count, and the one-hot map from walk position to column
    that ``scatter_walk`` inverts. Plain XLA reductions over the one-hot
    map: no sort, gather or scatter."""
    held = uni_tier >= 0
    rank = jnp.cumsum(held, axis=1, dtype=jnp.int32) - 1
    pos = jnp.arange(walk, dtype=jnp.int32)[None, :, None]
    pick = held[:, None, :] & (rank[:, None, :] == pos)
    wslot = jnp.sum(jnp.where(pick, uni_slot[:, None, :], 0), axis=-1)
    wtier = jnp.sum(jnp.where(pick, uni_tier[:, None, :] - TIER_INVALID, 0), axis=-1)
    nwalk = jnp.minimum(jnp.sum(held, axis=1, dtype=jnp.int32), walk)
    return wslot, wtier + TIER_INVALID, nwalk, pick


def _ragged_steps(nwalk: jax.Array, max_steps: int):
    """The fused kernel's grid steps: each row in turn takes one step per
    held page and a last step. Returns (step_row [max_steps], step_pos
    [max_steps], steps): each step's row and walk position (entries past
    ``steps`` are never run) and the step count."""
    ends = jnp.cumsum(nwalk + 1)
    idx = jnp.arange(max_steps, dtype=jnp.int32)
    step_row = jnp.minimum(jnp.sum(ends[None, :] <= idx[:, None], axis=1, dtype=jnp.int32),
                           nwalk.shape[0] - 1)
    step_pos = idx - (ends - nwalk - 1)[step_row]
    return step_row, step_pos, ends[-1]


def scatter_walk(pick: jax.Array, mass: jax.Array, base: jax.Array):
    """Walk-ordered per-page (mass, base) [B, W] back to the unified-table
    layout [B, MS]: columns holding no page get 0 and ``NEG_INF``."""
    walked = jnp.any(pick, axis=1)
    mass_u = jnp.sum(jnp.where(pick, mass[:, :, None], 0.0), axis=1)
    base_u = jnp.sum(jnp.where(pick, base[:, :, None], 0.0), axis=1)
    return jnp.where(walked, mass_u, 0.0), jnp.where(walked, base_u, NEG_INF)


@functools.partial(jax.jit, static_argnames=("page_tokens", "walk", "interpret"))
def fused_tiered_attention(
    q: jax.Array,  # [B, H, hd]
    k8: jax.Array,  # [P8, T, KV, hd] int8 (concat of all int8 pools)
    s8k: jax.Array,  # [P8, T, KV] f32
    v8: jax.Array,
    s8v: jax.Array,
    k4: jax.Array,  # [P4, T, KV, hd//2] uint8 (concat of all int4 pools)
    s4k: jax.Array,
    v4: jax.Array,
    s4v: jax.Array,
    host_summary: jax.Array,  # [Hs, KV, hd] f32 per-page key centroids
    recent_k: jax.Array,  # [B, R, KV, hd]
    recent_v: jax.Array,
    uni_slot: jax.Array,  # [B, MS] int32
    uni_tier: jax.Array,  # [B, MS] int32 TIER_* codes
    recent_len: jax.Array,  # [B] int32
    *,
    page_tokens: int,
    walk: Optional[int] = None,
    interpret: bool,
):
    """Single launch over every tier + host sentinels + the recent window.

    Returns (out [B,H,hd] NORMALIZED f32, m [B,H], l [B,H],
             mass [B,MS], base [B,MS]) where (m, l) are the fully merged
    logsumexp stats (for hotness normalization) and mass/base follow the
    unified-table slot layout (pool pages: exact page mass at its local
    base; host sentinels: would-have-touched mass; invalid: 0 / NEG_INF).

    ``walk`` bounds the pages any one row holds (default: the table width
    MS). The kernel walks only held pages, so its work follows the pages
    held, not the table's width; a row holding more than ``walk`` pages
    would lose the excess (``ops`` checks this on eager calls).

    Heads enter the kernel group-major ([B, G, KV, hd]) and the per-head
    outputs carry trailing unit dims, so every block's last two dims equal
    the array's — the shape rule of the TPU lowering.
    """
    b, h, hd = q.shape
    t = k8.shape[1]
    kv = k8.shape[2]
    ms = uni_slot.shape[1]
    r = recent_k.shape[1]
    group = h // kv
    hd4 = k4.shape[-1]
    w = max(1, min(ms if walk is None else walk, ms))
    qg = q.reshape(b, kv, group, hd).transpose(0, 2, 1, 3)
    wslot, wtier, nwalk, pick = compact_walk(uni_slot, uni_tier, w)
    step_row, step_pos, steps = _ragged_steps(nwalk, b * (w + 1))

    def _gated(code, ndim):
        # Fetch the row the walked page names only when its tier matches,
        # else row 0. A row's last step keeps its last page's blocks, so
        # the pipeline fetches nothing new for it.
        def index_map(si, slot, tier, nw, rlen, srow, spos):
            bi = srow[si]
            jj = jnp.minimum(spos[si], nw[bi] - 1)
            jc = jnp.maximum(jj, 0)
            hit = (jj >= 0) & (tier[bi, jc] == code)
            return (jnp.where(hit, slot[bi, jc], 0),) + (0,) * (ndim - 1)

        return index_map

    def _per_seq(ndim):
        return lambda si, slot, tier, nw, rlen, srow, spos: (srow[si],) + (0,) * (ndim - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, group, kv, hd), _per_seq(4)),
            pl.BlockSpec((1, t, kv, hd), _gated(TIER_INT8, 4)),
            pl.BlockSpec((1, t, kv), _gated(TIER_INT8, 3)),
            pl.BlockSpec((1, t, kv, hd), _gated(TIER_INT8, 4)),
            pl.BlockSpec((1, t, kv), _gated(TIER_INT8, 3)),
            pl.BlockSpec((1, t, kv, hd4), _gated(TIER_INT4, 4)),
            pl.BlockSpec((1, t, kv), _gated(TIER_INT4, 3)),
            pl.BlockSpec((1, t, kv, hd4), _gated(TIER_INT4, 4)),
            pl.BlockSpec((1, t, kv), _gated(TIER_INT4, 3)),
            pl.BlockSpec((1, kv, hd), _gated(TIER_HOST, 3)),
            pl.BlockSpec((1, r, kv, hd), _per_seq(4)),
            pl.BlockSpec((1, r, kv, hd), _per_seq(4)),
        ],
        out_specs=[
            pl.BlockSpec((1, group, kv, hd), _per_seq(4)),
            pl.BlockSpec((1, group, kv, hd), _per_seq(4)),
            pl.BlockSpec((1, group, kv, hd), _per_seq(4)),
            pl.BlockSpec((1, 1, w), _per_seq(3)),
            pl.BlockSpec((1, 1, w), _per_seq(3)),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, kv, hd), jnp.float32),
            pltpu.VMEM((group, kv, hd), jnp.float32),
            pltpu.VMEM((group, kv, hd), jnp.float32),
        ],
    )
    out, m, l, mass, base = pl.pallas_call(
        functools.partial(_fused_attn_kernel, group=group, page_tokens=page_tokens),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, group, kv, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, group, kv, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, group, kv, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, w), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, w), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="fused_tiered_attention",
    )(wslot, wtier, nwalk, recent_len, step_row, step_pos, qg, k8, s8k, v8, s8v,
      k4, s4k, v4, s4v, host_summary, recent_k, recent_v)

    def heads(x):  # [B, G, KV, ...] -> [B, H, ...]
        return x.transpose(0, 2, 1, 3).reshape(b, h, -1)

    mass, base = scatter_walk(pick, mass[:, 0], base[:, 0])
    return heads(out), heads(m)[..., 0], heads(l)[..., 0], mass, base
