"""Put a traced window's device idle time down to the program's own spans,
and its device time down to programs.

``TieredEngine`` wraps its host work in ``tkv.*`` spans
(``repro.serving.spans``) that land in the profiler's trace beside the
benchmark's ``cb.*`` spans and the device's operations, on one clock. This
reduction reads a trace that ``trace_reduce.reduce`` reads, and adds:

- ``idle_gaps``: each idle stretch of the device named after the innermost
  of all ``cb.*`` and ``tkv.*`` spans covering its middle; the window is
  still the extent of the ``cb.*`` spans alone, so ``window_s``,
  ``busy_s``, ``kernel_s`` and ``device_ops`` are those of
  ``trace_reduce.reduce``;
- ``device_programs``: device time per program (the device's "XLA
  Modules" line: ``jit_step`` for the decode step, one name per eager
  program), top 10, and ``op_programs``: for each of the top device
  operations, the programs it ran in;
- ``step_host_idle_ms``: idle time whose innermost span is a ``tkv.*``
  span other than ``tkv.wait``, per traced step;
- ``steps``: per traced step, from the spans' own edges: ``tkv.step``,
  its ``tkv.wait``, the ``cb.*`` step span around it, and its
  ``tkv.end_window`` where it closed a window.

    python3 chipbench/span_reduce.py <trace dir or .xplane.pb>

prints the reduction as one JSON object.
"""

from __future__ import annotations

import bisect
import collections
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, List, Tuple

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from chipbench import trace_reduce  # noqa: E402

PROGRAM_PREFIX = "tkv."
MODULES_LINE = "XLA Modules"
STEP_SPANS = ("cb.step", "cb.boundary_step")


def program_name(text: str) -> str:
    """``jit_step(4277022539905334306)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", text)


def load(path: str):
    """(device op intervals and program intervals, per device plane;
    ``cb.*`` spans; ``tkv.*`` spans), each interval (start, end, name)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, programs, cb, tkv = {}, {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CUSTOM"):
            lines = {ln.name: ln for ln in plane.lines}
            if trace_reduce.OPS_LINE in lines:
                ops[plane.name] = [(s, e, trace_reduce.op_name(n)) for s, e, n in
                                   trace_reduce._intervals(lines[trace_reduce.OPS_LINE].events)]
                mods = lines.get(MODULES_LINE)
                programs[plane.name] = [] if mods is None else [
                    (s, e, program_name(n)) for s, e, n in trace_reduce._intervals(mods.events)]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for iv in trace_reduce._intervals(ln.events):
                    if iv[2].startswith(trace_reduce.SPAN_PREFIX):
                        cb.append(iv)
                    elif iv[2].startswith(PROGRAM_PREFIX):
                        tkv.append(iv)
    return ops, programs, cb, tkv


def idle_by_span(ops, spans, lo: float, hi: float) -> collections.Counter:
    """Idle time of one device in [lo, hi] (ns, no operation of ``ops``
    running), per name of the innermost of ``spans`` covering each idle
    stretch's middle."""
    _, gaps = trace_reduce.union_length([(s, e) for s, e, _ in ops], lo, hi)
    out = collections.Counter()
    for s, e in gaps:
        out[trace_reduce._innermost(spans, (s + e) / 2) or "outside benchmark spans"] += e - s
    return out


def _program_at(progs, starts, t) -> str:
    """The program running at ``t``: ``progs`` sorted by start, one device's
    programs, which do not overlap."""
    i = bisect.bisect_right(starts, t) - 1
    return progs[i][2] if i >= 0 and t < progs[i][1] else "no program"


def _inside(spans, outer) -> List[tuple]:
    s0, e0 = outer[0], outer[1]
    return [iv for iv in spans if s0 <= iv[0] and iv[1] <= e0 and iv is not outer]


def step_rows(cb, tkv) -> List[dict]:
    """One row per ``tkv.step``, in order, with its times in ms: ``step``,
    ``wait`` (its ``tkv.wait``), ``outside`` (the ``cb.*`` span around it),
    ``end_window`` (or None) and ``boundary`` (whether it closed a window)."""
    rows = []
    for st in sorted(iv for iv in tkv if iv[2] == "tkv.step"):
        kids = _inside(tkv, st)
        waits = [k for k in kids if k[2] == "tkv.wait"]
        ends = [k for k in kids if k[2] == "tkv.end_window"]
        outer = [c for c in cb if c[2] in STEP_SPANS and c[0] <= st[0] and st[1] <= c[1]]
        rows.append({
            "step": (st[1] - st[0]) * 1e-6,
            "wait": sum(e - s for s, e, _ in waits) * 1e-6,
            "outside": (outer[0][1] - outer[0][0]) * 1e-6 if outer else None,
            "end_window": sum(e - s for s, e, _ in ends) * 1e-6 if ends else None,
            "boundary": bool(ends),
        })
    return rows


def _top(counter, n=10) -> List[list]:
    return [[k, v * 1e-9] for k, v in counter.most_common(n)]


def reduce(path: str, kernels: Tuple[str, ...] = ("fused_tiered_attention",)) -> Dict:
    base = trace_reduce.reduce(path, kernels)
    ops, programs, cb, tkv = load(path)
    lo = min(s for s, _, _ in cb)
    hi = max(e for _, e, _ in cb)
    named = cb + tkv
    gap_time, prog_time = collections.Counter(), collections.Counter()
    op_prog = collections.defaultdict(collections.Counter)
    top_ops = {name for name, _ in base["device_ops"]}
    for dev, ivs in ops.items():
        inside = [(s, e, n) for s, e, n in ivs if e > lo and s < hi]
        gap_time.update(idle_by_span(inside, named, lo, hi))
        progs = sorted((s, e, n) for s, e, n in programs[dev] if e > lo and s < hi)
        starts = [s for s, _, _ in progs]
        for s, e, n in progs:
            prog_time[n] += e - s
        for s, e, n in inside:
            if n in top_ops:
                op_prog[n][_program_at(progs, starts, (s + e) / 2)] += e - s
    n_dev = len(ops)
    for c in (gap_time, prog_time, *op_prog.values()):
        for k in c:
            c[k] /= n_dev
    n_steps = sum(1 for _, _, name in cb if name in STEP_SPANS)
    host_idle = sum(v for k, v in gap_time.items()
                    if k.startswith(PROGRAM_PREFIX) and k != "tkv.wait")
    return {
        **base,
        "idle_gaps": _top(gap_time, len(gap_time)),
        "device_programs": _top(prog_time),
        "op_programs": {k: _top(op_prog[k], 3) for k, _ in base["device_ops"]},
        "step_host_idle_ms": host_idle * 1e-6 / n_steps if n_steps else None,
        "steps": step_rows(cb, tkv),
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_trace(path)
    print(json.dumps(reduce(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
