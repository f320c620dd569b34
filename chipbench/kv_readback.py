"""Read back the KV pages a request holds in the program's cache and compare
each with the reference's keys and values.

A request's cache, per layer, is a set of full pages (``page_tokens`` tokens
each) spread over the int8 and int4 pools on the device and the host tiers,
plus a dense window of its newest tokens. Attention does not depend on the
order of pages, so the cache keeps no page order, and pages are matched to
the reference's by content: each held page to its nearest reference page.
Every reference page has to be matched exactly once.

Each matched page reads its error relative to the reference page, the
larger of K's and V's. A sound page reads the rounding of the coarsest
codec it has passed through (a page demoted to int4 and promoted back
keeps its int4 rounding as int8), plus what the program's bfloat16
arithmetic adds in deeper layers: about 0.12 at most. A page that is
corrupted, swapped or read with the wrong codec reads about 1 or more. The
codec arithmetic is the benchmark's own copy of the payload layout: a
symmetric absmax scale per token and head, int8 values, or int4 values
packed two to a byte with element ``i`` of the first half of the head in
the low nibble and element ``i + hd/2`` in the high one.

The control is the reference held one precision lower than the program
holds each page: int4 where the program holds int8, int2 where it holds
int4 (a 2-bit cache is the step a cheaper tier would take next).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

QMAX = {8: 127.0, 4: 7.0, 2: 1.0}
LOWER = {8: 4, 4: 2}  # the control's precision for a page held at each width


def quantize(x: np.ndarray, bits: int) -> np.ndarray:
    """``x`` [..., hd] rounded by the codec of ``bits`` and read back."""
    amax = np.abs(x).max(-1, keepdims=True)
    scale = np.where(amax == 0, 1.0, amax / QMAX[bits])
    return np.clip(np.round(x / scale), -QMAX[bits], QMAX[bits]) * scale


def unpack_int4(p: np.ndarray) -> np.ndarray:
    p = p.astype(np.int16)
    lo, hi = p & 0xF, (p >> 4) & 0xF
    lo = np.where(lo >= 8, lo - 16, lo)
    hi = np.where(hi >= 8, hi - 16, hi)
    return np.concatenate([lo, hi], axis=-1).astype(np.float32)


def dequant(payload: np.ndarray, scales: np.ndarray) -> Tuple[int, np.ndarray]:
    """(bits, values) of a payload: int8 values, or uint8 holding int4 pairs."""
    if payload.dtype == np.int8:
        return 8, payload.astype(np.float32) * scales[..., None]
    if payload.dtype == np.uint8:
        return 4, unpack_int4(payload) * scales[..., None]
    raise TypeError(f"no codec for a payload of {payload.dtype}")


@dataclasses.dataclass
class Held:
    """One request's cache as read back: per layer the width of each page,
    its keys and values [n, T, KV, hd], and the dense window's length."""

    bits: List[np.ndarray]
    k: List[np.ndarray]
    v: List[np.ndarray]
    recent_len: int
    total_len: int


def read(eng, slot: int) -> Held:
    """Every page the engine holds for ``slot``: the int8 pool
    (``warm_table`` rows of the int8 class buffers), the int4 pool
    (``cold_table`` rows of the int4 class buffers) and the host tiers."""
    st = eng.cache.state
    n_layers = int(st.warm_n.shape[0])
    parts: Dict[int, List[Tuple[np.ndarray, np.ndarray, np.ndarray]]] = {
        li: [] for li in range(n_layers)}
    for table, count, cls in (("warm_table", "warm_n", "c8"), ("cold_table", "cold_n", "c4")):
        n = np.asarray(getattr(st, count))[:, slot]
        rows = np.asarray(getattr(st, table))[:, slot]
        flat = np.concatenate([rows[li, : n[li]] for li in range(n_layers)]).astype(np.int32)
        if flat.size == 0:
            continue
        fields = [np.asarray(getattr(st, f"{cls}_{f}")[flat])
                  for f in ("k", "k_scales", "v", "v_scales")]
        start = 0
        for li in range(n_layers):
            sl = slice(start, start + int(n[li]))
            start += int(n[li])
            if sl.stop > sl.start:
                parts[li].append(_pages(*(f[sl] for f in fields)))
    cache = eng.cache
    for rid, payload in cache.host_pages.items():
        layer, s, _ = cache.rid_coords(int(rid))
        if s == slot:
            parts[int(layer)].append(_pages(*(np.asarray(x)[None] for x in payload)))
    held = Held([], [], [], int(np.asarray(st.recent_len)[slot]),
                int(np.asarray(st.total_len)[slot]))
    for li in range(n_layers):
        got = parts[li]
        held.bits.append(np.concatenate([g[0] for g in got]) if got else np.zeros(0, int))
        held.k.append(np.concatenate([g[1] for g in got]) if got else None)
        held.v.append(np.concatenate([g[2] for g in got]) if got else None)
    return held


def _pages(kp, ks, vp, vs):
    bits, k = dequant(np.asarray(kp), np.asarray(ks))
    bits_v, v = dequant(np.asarray(vp), np.asarray(vs))
    assert bits == bits_v
    return np.full(k.shape[0], bits), k, v


def _rel(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Per page: ||a - b|| / ||b|| over all but the first axis."""
    ax = tuple(range(1, a.ndim))
    return np.sqrt(((a - b) ** 2).sum(ax) / np.maximum((b ** 2).sum(ax), 1e-30))


@dataclasses.dataclass
class Reading:
    rel_err: float  # worst page: its error relative to the reference page
    control_rel_err: float  # the same, of the reference held one precision lower
    unmatched: int  # reference pages matched no held page, or more than one
    pages: int  # held pages compared
    by_bits: Dict[int, int]


def compare(held: Held, ref_kv: List[Tuple[np.ndarray, np.ndarray]], page_tokens: int) -> Reading:
    """``ref_kv``: per layer the reference's (k, v) [tokens, KV, hd] of the
    tokens the request has fed, prompt and served tokens but the newest."""
    worst, worst_ctrl, unmatched, pages = 0.0, 0.0, 0, 0
    by_bits: Dict[int, int] = {}
    for li, (rk, rv) in enumerate(ref_kv):
        paged = held.total_len - held.recent_len
        n_ref = paged // page_tokens
        if paged % page_tokens or rk.shape[0] != held.total_len:
            unmatched += max(n_ref, 1)
            continue
        shape = (n_ref, page_tokens) + rk.shape[1:]
        ref_k = rk[:paged].reshape(shape).astype(np.float32)
        ref_v = rv[:paged].reshape(shape).astype(np.float32)
        hk, hv, bits = held.k[li], held.v[li], held.bits[li]
        if hk is None or n_ref == 0:
            unmatched += n_ref + (0 if hk is None else hk.shape[0])
            continue
        # Nearest reference page of each held page, on keys and values.
        a = np.concatenate([hk.reshape(len(hk), -1), hv.reshape(len(hv), -1)], 1)
        b = np.concatenate([ref_k.reshape(n_ref, -1), ref_v.reshape(n_ref, -1)], 1)
        d = (a * a).sum(1)[:, None] + (b * b).sum(1)[None, :] - 2.0 * a @ b.T
        match = d.argmin(1)
        used = len(np.unique(match))
        unmatched += (n_ref - used) + (len(match) - used)
        mk, mv = ref_k[match], ref_v[match]
        err = np.maximum(_rel(hk, mk), _rel(hv, mv))
        ctrl = np.zeros(len(match))
        for w in np.unique(bits):
            sel = bits == w
            low = LOWER[int(w)]
            ctrl[sel] = np.maximum(_rel(quantize(mk[sel], low), mk[sel]),
                                   _rel(quantize(mv[sel], low), mv[sel]))
            by_bits[int(w)] = by_bits.get(int(w), 0) + int(sel.sum())
        worst = max(worst, float(err.max()))
        worst_ctrl = max(worst_ctrl, float(ctrl.max()))
        pages += len(match)
    return Reading(worst, worst_ctrl, unmatched, pages, by_bits)


def combine(readings: List[Reading]) -> Optional[Reading]:
    if not readings:
        return None
    by_bits: Dict[int, int] = {}
    for r in readings:
        for w, c in r.by_bits.items():
            by_bits[w] = by_bits.get(w, 0) + c
    return Reading(max(r.rel_err for r in readings), max(r.control_rel_err for r in readings),
                   sum(r.unmatched for r in readings), sum(r.pages for r in readings), by_bits)
