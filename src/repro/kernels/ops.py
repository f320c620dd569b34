"""jit'd public wrappers for the Pallas kernels.

``tiered_decode_attention`` is the serving hot path. Default mode is the
single-launch megakernel (``paged_attention.fused_tiered_attention``): one
unified page table walks every compressed page of a sequence regardless of
codec, the dense recent window rides the final grid step, host-resident
pages appear as sentinel rows emitting a "would-have-touched" mass, and the
logsumexp merge happens in VMEM scratch — exactly one Pallas launch per
decode step, O(1) in tier count.

``compiled()`` is the one platform switch: on a TPU every kernel compiles
(a kernel the compiler refuses raises — there is no fallback to interpret
mode or to the oracle) and the serving engine's decode step runs the fused
kernel; on any other backend the kernels run in Pallas interpret mode and
the engine's decode step runs the jnp oracle (tests and CPU tools only).

``use_fused(False)`` flips back to the legacy per-pool path (one kernel
launch per tier pool + a dense recent pass + a post-hoc jnp merge) — kept
as the equivalence oracle: outputs and normalized hotness must match the
fused path to fp32 tolerance. ``use_pallas`` independently toggles kernel
vs pure-jnp oracle (ref.py) for tests. The per-pool kernel is off the
served path and is not built for the TPU lowering.

``page_hotness`` turns per-page mass telemetry into the normalized hotness
the TierScape manager consumes. ``launch_count``/``reset_launch_count``
count actual Pallas launches issued through this module (the benchmark /
baseline-guard metric); ``decode_launches_per_step`` and
``decode_walk_slots`` are the modeled launches and walked page slots per
decode step that the serving cache bills, valid on the ref path too.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.dequant_page import dequant_pages as dequant_pages_kernel
from repro.kernels.paged_attention import (
    TIER_HOST,
    TIER_INT4,
    TIER_INT8,
    TIER_INVALID,
    fused_tiered_attention as fused_attn_kernel,
    paged_quant_attention as paged_attn_kernel,
)
from repro.kernels.quant_page import quant_pages as quant_pages_kernel
from repro.kernels.transcode_page import transcode_pages as transcode_pages_kernel

Array = jax.Array

_USE_PALLAS = True
_USE_FUSED = True

# Pallas launches issued through this module since the last reset (trace-time
# count; call the wrappers eagerly — as the benchmarks do — for a per-step
# reading).
_LAUNCHES = 0

# Device bytes materialized by per-step payload concatenation in
# ``_unified_operands`` since the last reset (trace-time count, same caveat
# as ``_LAUNCHES``). Zero on the class-major layout at ANY tier count —
# same-class pools share one buffer and the unified table addresses it
# directly. Non-zero only on the legacy standalone-buffer layout, which is
# kept for back-compat and the equivalence tests; the ``decode_fused``
# baseline guard pins this to 0.
_COPY_BYTES = 0


def compiled() -> bool:
    """True on a TPU backend: kernels compile and the engine decodes with
    the fused kernel. Elsewhere kernels run in interpret mode."""
    return jax.default_backend() == "tpu"


def use_pallas(flag: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = flag


def use_fused(flag: bool) -> None:
    """Toggle the single-launch megakernel (True, default) vs the per-pool
    launch loop (False — the equivalence oracle)."""
    global _USE_FUSED
    _USE_FUSED = flag


def reset_launch_count() -> None:
    global _LAUNCHES
    _LAUNCHES = 0


def launch_count() -> int:
    return _LAUNCHES


def reset_copy_bytes() -> None:
    global _COPY_BYTES
    _COPY_BYTES = 0


def concat_copy_bytes() -> int:
    """Device bytes copied by payload concatenation since the last reset."""
    return _COPY_BYTES


def _count_launch(n: int = 1) -> None:
    global _LAUNCHES
    if _USE_PALLAS:
        _LAUNCHES += n


def decode_launches_per_step(n_pools: int) -> int:
    """Modeled attention launches per (layer, decode step): 1 on the fused
    path regardless of tier count (host sentinels ride the same launch),
    one per tier pool on the legacy path. Mode-dependent, backend-agnostic:
    the jnp oracle mirrors the same launch structure, so the serving
    cache's dispatch proxy bills it identically."""
    if _USE_FUSED:
        return 1
    return int(n_pools)


def decode_walk_slots(held: np.ndarray, max_pages: int, n_pools: int) -> int:
    """Modeled page slots one decode step's attention walks, given the
    pages each (layer, batch row) holds (pools and host sentinels
    together): the fused kernel steps through exactly the held pages; the
    per-pool path walks every column of each pool's ``max_pages``-wide
    table. Like ``decode_launches_per_step``, valid on the ref path too."""
    held = np.asarray(held, np.int64)
    if _USE_FUSED:
        return int(held.sum())
    return int(n_pools) * max_pages * held.size


def quant_pages(pages: Array, bits: int) -> Tuple[Array, Array]:
    if _USE_PALLAS:
        out = quant_pages_kernel(pages, bits, interpret=not compiled())
        return out[0], out[1]
    return _ref.quant_kv_page(pages, bits)


def dequant_pages(payload: Array, scales: Array, bits: int, out_dtype=jnp.bfloat16) -> Array:
    if _USE_PALLAS:
        return dequant_pages_kernel(payload, scales, bits, out_dtype,
                                    interpret=not compiled())
    return _ref.dequant_kv_page(payload, scales, bits).astype(out_dtype)


def transcode_pages(
    payload: Array, scales: Array, src_bits: int, dst_bits: int
) -> Tuple[Array, Array]:
    """Fused tier-to-tier requantization of a [P, ...] page batch — the
    batched migration executor's single dispatch per transcoding cohort."""
    if src_bits == dst_bits:
        return payload, scales
    if _USE_PALLAS:
        out = transcode_pages_kernel(payload, scales, src_bits, dst_bits,
                                     interpret=not compiled())
        return out[0], out[1]
    return _ref.transcode_kv_page(payload, scales, src_bits, dst_bits)


def _pool_partials(q: Array, pool: Dict[str, Array]):
    _count_launch()
    args = (q, pool["k_pages"], pool["k_scales"], pool["v_pages"],
            pool["v_scales"], pool["page_table"], pool["n_pages"], pool["bits"])
    if _USE_PALLAS:
        return paged_attn_kernel(*args, interpret=not compiled())
    return _ref.paged_quant_attention(*args)


# ---------------------------------------------------------------------------
# Unified-table construction (fused path)
# ---------------------------------------------------------------------------


_CLASS_KEYS = ("k_pages", "k_scales", "v_pages", "v_scales")


def _validated_page_tokens(pools, host) -> int:
    """THE page-tokens value of a fused launch: every device pool's page
    shape and the host sentinels' ``page_tokens`` must agree, because one
    unified table walks them all and the sentinel would-have-touched mass
    multiplies by this count. A mismatch used to silently mis-scale
    sentinel mass (the host value rode a separate kernel argument); now it
    raises."""
    t = None
    src = None
    for n in sorted(pools):
        tn = int(pools[n]["k_pages"].shape[1])
        if t is None:
            t, src = tn, f"pool {n!r}"
        elif tn != t:
            raise ValueError(
                f"mixed page_tokens in fused launch: {src} has {t} "
                f"tokens/page but pool {n!r} has {tn} — every pool and the "
                f"host sentinels must share one page size (deploy unequal "
                f"page sizes as separate caches)"
            )
    if host is not None:
        ht = int(host["page_tokens"])
        if t is None:
            t = ht
        elif ht != t:
            raise ValueError(
                f"mixed page_tokens in fused launch: {src} has {t} "
                f"tokens/page but host sentinels declare {ht} — sentinel "
                f"would-have-touched mass would be mis-scaled"
            )
    return 1 if t is None else t


def _tier_col(table, n_rows, code):
    """Tier-code column for one table: entries past the valid prefix become
    ``TIER_INVALID``. This is the SINGLE enforcement point that keeps stale
    ``(slot, tier_code)`` rows — including rows whose slot would alias row 0
    of an empty codec class's dummy buffer — out of the fused kernel: the
    kernel contributes nothing for a row whose tier code matches no grid
    step, regardless of the slot value riding next to it."""
    mp = table.shape[1]
    valid = jnp.arange(mp, dtype=jnp.int32)[None] < n_rows[:, None]
    return jnp.where(valid, code, TIER_INVALID).astype(jnp.int32)


def _class_operands(sel, t, kv, dummy_dtype, last_dim):
    """One codec class's kernel operands + per-pool global-row offsets.

    Class-major layout (all same-class pools alias ONE buffer object —
    identity-checked): the shared buffer passes straight through with zero
    offsets, zero copies, at any pool count. Single standalone pool: also
    copy-free. Multiple standalone buffers: the legacy concat path, kept
    for back-compat and as the equivalence oracle's input layout; its
    copied bytes are counted in ``_COPY_BYTES`` (the baseline guard pins
    the serving layout to 0). Mixing shared and standalone buffers within
    a class is ambiguous (offsets would double-count pages) and raises."""
    global _COPY_BYTES
    if not sel:
        pay = jnp.zeros((1, t, kv, last_dim), dummy_dtype)
        sc = jnp.ones((1, t, kv), jnp.float32)
        return (pay, sc, pay, sc), []
    first = sel[0]
    if all(all(p[k] is first[k] for k in _CLASS_KEYS) for p in sel):
        return tuple(first[k] for k in _CLASS_KEYS), [0] * len(sel)
    for i in range(len(sel)):
        for j in range(i + 1, len(sel)):
            if any(sel[i][k] is sel[j][k] for k in _CLASS_KEYS):
                raise ValueError(
                    "same-class pools mix shared and standalone payload "
                    "buffers; either every pool of a codec class aliases "
                    "one class buffer (class-major layout) or none do"
                )
    offs, off = [], 0
    for p in sel:
        offs.append(off)
        off += int(p["k_pages"].shape[0])
    cat = tuple(jnp.concatenate([p[k] for p in sel]) for k in _CLASS_KEYS)
    _COPY_BYTES += sum(a.size * a.dtype.itemsize for a in cat)
    return cat, offs


def _check_class_bounds(uni_slot, uni_tier, rows8: int, rows4: int) -> None:
    """Eager-path guard: every VALID unified-table row must address a real
    class-buffer row. Stale rows are already ``TIER_INVALID`` (see
    ``_tier_col``) and exempt — notably an empty class's 1-row dummy buffer
    is unaddressable because no pool of that class exists to emit its tier
    code. Slot values are data, so this cannot run under tracing; eager
    callers (tests, benchmarks) get the hard check."""
    if isinstance(uni_slot, jax.core.Tracer) or isinstance(uni_tier, jax.core.Tracer):
        return
    slot = np.asarray(uni_slot)
    tier = np.asarray(uni_tier)
    for code, rows, cls in ((TIER_INT8, rows8, "int8"), (TIER_INT4, rows4, "int4")):
        sel = tier == code
        if sel.any():
            s = slot[sel]
            if int(s.min()) < 0 or int(s.max()) >= rows:
                raise IndexError(
                    f"unified table addresses {cls} class row "
                    f"{int(s.min())}..{int(s.max())} outside the class "
                    f"buffer's {rows} rows (stale slot with a live tier code?)"
                )


def _check_walk_bound(uni_tier, walk: int) -> None:
    """Eager-path guard: the fused kernel walks at most ``walk`` held pages
    per row (``paged_attention.compact_walk``), so a row holding more valid
    slots would silently lose the excess. Like ``_check_class_bounds``,
    tier codes are data: traced callers are exempt."""
    if isinstance(uni_tier, jax.core.Tracer):
        return
    held = (np.asarray(uni_tier) >= 0).sum(axis=1)
    if held.size and int(held.max()) > walk:
        raise ValueError(
            f"a unified-table row holds {int(held.max())} pages but the "
            f"fused kernel's walk bound is {walk} (rows "
            f"{np.flatnonzero(held > walk).tolist()})"
        )


def _unified_operands(q, pools, recent_k, host):
    """Assemble the megakernel's operands from N tier pools: two codec-class
    payload buffers plus the unified page table.

    Class-major layout: same-class pools share one class buffer (identity-
    aliased arrays) and their tables already hold global class-buffer rows,
    so this reduces to pure table assembly — zero payload copies at any
    tier count. Legacy standalone per-pool buffers still concatenate (the
    counted back-compat path, see ``_class_operands``). Host sentinel rows
    index the summary buffer. Returns the kernel operands plus the
    {name: (col_lo, col_hi)} slot layout used to slice per-pool hotness
    back out of the unified mass."""
    b = q.shape[0]
    hd = q.shape[-1]
    kv = recent_k.shape[2]
    names = sorted(pools)
    t = _validated_page_tokens(pools, host)

    by_bits = {
        bits: [n for n in names if int(pools[n]["bits"]) == bits] for bits in (8, 4)
    }
    ops8, offs8 = _class_operands([pools[n] for n in by_bits[8]], t, kv, jnp.int8, hd)
    ops4, offs4 = _class_operands(
        [pools[n] for n in by_bits[4]], t, kv, jnp.uint8, hd // 2
    )
    base = dict(zip(by_bits[8], offs8))
    base.update(zip(by_bits[4], offs4))

    slot_cols, tier_cols = [], []
    layout: Dict[str, Tuple[int, int]] = {}
    col = 0
    for n in names:
        p = pools[n]
        mp = p["page_table"].shape[1]
        code = TIER_INT8 if int(p["bits"]) == 8 else TIER_INT4
        slot_cols.append(p["page_table"].astype(jnp.int32) + base[n])
        tier_cols.append(_tier_col(p["page_table"], p["n_pages"], code))
        layout[n] = (col, col + mp)
        col += mp
    if host is not None:
        mp = host["table"].shape[1]
        slot_cols.append(host["table"].astype(jnp.int32))
        tier_cols.append(_tier_col(host["table"], host["n"], TIER_HOST))
        layout["host"] = (col, col + mp)
        col += mp
        summary = host["summary"].astype(jnp.float32)
    else:
        summary = jnp.zeros((1, kv, hd), jnp.float32)

    if col == 0:  # no pools, no host rows: recent-window-only launch
        uni_slot = jnp.zeros((b, 1), jnp.int32)
        uni_tier = jnp.full((b, 1), TIER_INVALID, jnp.int32)
    else:
        uni_slot = jnp.concatenate(slot_cols, axis=1)
        uni_tier = jnp.concatenate(tier_cols, axis=1)

    k8, s8k, v8, s8v = ops8
    k4, s4k, v4, s4v = ops4
    _check_class_bounds(uni_slot, uni_tier, int(k8.shape[0]), int(k4.shape[0]))
    return (k8, s8k, v8, s8v, k4, s4k, v4, s4v, summary, uni_slot, uni_tier, t, layout)


def _fused_path(q, pools, recent_k, recent_v, recent_len, host, with_telemetry,
                max_pages):
    b = q.shape[0]
    rlen = jnp.broadcast_to(jnp.asarray(recent_len, jnp.int32), (b,))
    if _USE_PALLAS:
        (k8, s8k, v8, s8v, k4, s4k, v4, s4v, summary,
         uni_slot, uni_tier, t, layout) = _unified_operands(q, pools, recent_k, host)
        walk = uni_slot.shape[1] if max_pages is None else int(max_pages)
        _check_walk_bound(uni_tier, walk)
        _count_launch()
        # ``t`` is the launch's single validated page-tokens value — the
        # sentinel mass multiplier and the device pools' page shape agree
        # by construction (``_validated_page_tokens``).
        out, m, l, mass, base = fused_attn_kernel(
            q, k8, s8k, v8, s8v, k4, s4k, v4, s4v, summary,
            recent_k, recent_v, uni_slot, uni_tier, rlen, page_tokens=t,
            walk=walk, interpret=not compiled(),
        )
        if not with_telemetry:
            return out
        hot = {
            name: page_hotness(mass[:, lo:hi], base[:, lo:hi], m, l)
            for name, (lo, hi) in layout.items()
        }
        return out, hot
    _validated_page_tokens(pools, host)  # same contract as the kernel path
    out, m, l, masses = _ref.fused_tiered_attention(
        q, pools, recent_k, recent_v, rlen, host=host
    )
    if not with_telemetry:
        return out
    hot = {name: page_hotness(ms, bs, m, l) for name, (ms, bs) in masses.items()}
    return out, hot


def tiered_decode_attention(
    q: Array,  # [B, H, hd]
    pools: Dict[str, Dict[str, Array]],
    recent_k: Array,  # [B, R, KV, hd]
    recent_v: Array,
    recent_len,
    cfg=None,
    with_telemetry: bool = False,
    host: Optional[Dict[str, Array]] = None,
    max_pages: Optional[int] = None,
):
    """Attention over tiered compressed KV pools + dense recent window.

    Returns out [B, H, hd] f32; with_telemetry=True also returns
    {tier: normalized page hotness [B, MP]} (softmax mass per page). When
    ``host`` is given (dict with ``summary`` [Hs, KV, hd], ``table``
    [B, MPh], ``n`` [B], ``page_tokens``), the hotness dict additionally
    carries "host": the normalized would-have-touched mass of host-resident
    pages — telemetry for the prefetch predictor, never part of the output.

    Fused mode (default): one Pallas launch per call, O(1) in tier count.
    ``max_pages`` bounds the pages one row holds across all pools and host
    sentinels (the serving cache passes one sequence's page count); the
    kernel's walk is sized by it, and defaults to the unified table width.
    ``use_fused(False)``: one launch per pool + post-hoc merge (the
    equivalence oracle; outputs/hotness match to fp32 tolerance).
    """
    if _USE_FUSED:
        return _fused_path(q, pools, recent_k, recent_v, recent_len, host, with_telemetry,
                           max_pages)
    parts = [_ref.dense_recent_attention(q, recent_k, recent_v, recent_len)]
    masses = {}
    for name in sorted(pools):
        out_u, m, l, mass, base = _pool_partials(q, pools[name])
        parts.append((out_u, m, l))
        masses[name] = (mass, base)
    out = _ref.merge_partials(parts)
    if not with_telemetry:
        return out
    # Global (m_tot, l_tot) for exact normalization of page masses.
    m_tot = jnp.max(jnp.stack([p[1] for p in parts]), axis=0)  # [B,H]
    l_tot = sum(p[2] * jnp.exp(p[1] - m_tot) for p in parts)  # [B,H]
    if host is not None:
        masses["host"] = _ref.host_page_mass(
            q, host["summary"], host["table"], host["n"], host["page_tokens"]
        )
    hot = {
        name: page_hotness(mass, base, m_tot, l_tot)
        for name, (mass, base) in masses.items()
    }
    return out, hot


def page_hotness(mass: Array, base: Array, m_tot: Array, l_tot: Array) -> Array:
    """Rebase per-page local-max masses to the merged global softmax.

    Heads were collapsed in the mass telemetry; normalize by the summed
    head partition function at the global max base."""
    z = jnp.sum(l_tot * jnp.exp(m_tot - jnp.max(m_tot, -1, keepdims=True)), -1)
    mref = jnp.max(m_tot, -1)
    return mass * jnp.exp(base - mref[:, None]) / jnp.maximum(z[:, None], 1e-30)
