"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time, the
time of each device operation, and the device's idle gaps attributed to the
benchmark's host spans.

The window is the extent of the benchmark's own host spans (names starting
with ``cb.``). Busy time is the union of the intervals of the operations on
the device's "XLA Ops" line, clipped to the window; an idle gap is a stretch
of the window with no operation running, named after the innermost
benchmark span that covers its middle. Uses only JAX's ``ProfileData``.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "cb."
OPS_LINE = "XLA Ops"


def find_trace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """``%fused_tiered_attention.19 = (f32[...]) custom-call(...)`` ->
    ``fused_tiered_attention``: the HLO instruction's name without the
    numeric and rematerialization suffixes the compiler adds (a device op
    event is named by the instruction's whole text)."""
    name = text.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"(\.(\d+|remat\d*))+$", "", name)


def _intervals(events) -> List[Tuple[float, float, str]]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in events]


def load(path: str):
    """(device op intervals per device plane, benchmark host spans)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CUSTOM"):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if ops:
                devices[plane.name] = [(s, e, op_name(n)) for s, e, n in
                                       _intervals(e for ln in ops for e in ln.events)]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(iv for iv in _intervals(ln.events) if iv[2].startswith(SPAN_PREFIX))
    return devices, spans


def union_length(ivs: List[Tuple[float, float]], lo: float, hi: float) -> Tuple[float, list]:
    """Covered length of [lo, hi] and the uncovered gaps, in ns."""
    covered, gaps, cur = 0.0, [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi):
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            covered += e - max(s, cur)
            cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return covered, gaps


def _innermost(spans, t) -> Optional[str]:
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else None


def reduce(path: str, kernels: Tuple[str, ...] = ("fused_tiered_attention",)) -> Dict:
    devices, spans = load(path)
    if not devices:
        raise ValueError(f"{path}: no device plane with an '{OPS_LINE}' line")
    if not spans:
        raise ValueError(f"{path}: no benchmark host spans ({SPAN_PREFIX}*)")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    busy, op_time, gap_time = [], collections.Counter(), collections.Counter()
    kernel_ns = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    for ivs in devices.values():
        inside = [(s, e, n) for s, e, n in ivs if e > lo and s < hi]
        covered, gaps = union_length([(s, e) for s, e, _ in inside], lo, hi)
        busy.append(covered)
        for s, e, n in inside:
            op_time[n] += e - s
            for k in kernels:
                if n == k:
                    kernel_ns[k] += e - s
                    kernel_calls[k] += 1
        for s, e in gaps:
            gap_time[_innermost(spans, (s + e) / 2) or "outside benchmark spans"] += e - s
    n = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "kernel_s": {k: v / n * 1e-9 for k, v in kernel_ns.items()},
        "kernel_calls": {k: v // n for k, v in kernel_calls.items()},
        "device_ops": [[k, v / n * 1e-9] for k, v in op_time.most_common(10)],
        "idle_gaps": [[k, v / n * 1e-9] for k, v in gap_time.most_common(10)],
    }
