"""The span reduction (``chipbench/span_reduce.py``) on hand-made intervals
and on two small traces recorded on a TPU v5e: the first one, which has
no program spans, and one with the engine's ``tkv.*`` spans (a 4-step window
cycle of ``internlm2_20b_8l.longctx``, ``data/longctx_spans_trace.json``
for its page counts). Reads files; touches no TPU."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import span_reduce, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "longctx_trace.xplane.pb"
NEW = DATA / "longctx_spans_trace.xplane.pb"


def _raw(path):
    """Device ops and programs of TPU 0, and host spans, straight from the file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, mods, spans = [], [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(iv)
                elif plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                    mods.append(iv)
                elif plane.name.startswith("/host:") and e.name.startswith(("cb.", "tkv.")):
                    spans.append(iv)
    return ops, mods, spans


@pytest.fixture(scope="module")
def new():
    return span_reduce.reduce(str(NEW)), _raw(NEW)


# ----------------------------------------------------------- hand-made
def test_program_name():
    assert span_reduce.program_name("jit_step(4277022539905334306)") == "jit_step"
    assert span_reduce.program_name("jit__argmax(12)") == "jit__argmax"
    assert span_reduce.program_name("jit_f(x)") == "jit_f(x)"


@pytest.mark.parametrize("spans, want", [
    # the benchmark's spans alone
    ([(0, 100, "cb.step"), (100, 120, "cb.meter")],
     {"cb.step": 65, "cb.meter": 5, "outside benchmark spans": 5}),
    # program spans inside them take the gaps whose middles they cover
    ([(0, 100, "cb.step"), (100, 120, "cb.meter"), (5, 95, "tkv.step"),
      (15, 50, "tkv.wait"), (60, 90, "tkv.page_out")],
     {"tkv.step": 20, "tkv.wait": 10, "tkv.page_out": 35, "cb.meter": 5,
      "outside benchmark spans": 5}),
])
def test_idle_attribution_by_innermost_span(spans, want):
    # busy [20,40] [50,60] [95,105] [110,125] of the window [0,130]; idle
    # [0,20] [40,50] [60,95] [105,110] [125,130], middles 10 45 77.5 107.5 127.5
    ops = [(20, 40, "a"), (50, 60, "b"), (95, 105, "c"), (110, 125, "d")]
    got = span_reduce.idle_by_span(ops, spans, 0, 130)
    assert dict(got) == want
    assert sum(got.values()) == 130 - 55


def test_step_rows_pair_each_step_with_its_spans():
    cb = [(0, 100, "cb.step"), (100, 110, "cb.meter"), (110, 300, "cb.boundary_step")]
    tkv = [(2, 95, "tkv.step"), (10, 80, "tkv.wait"), (112, 290, "tkv.step"),
           (115, 200, "tkv.wait"), (210, 280, "tkv.end_window")]
    a, b = span_reduce.step_rows(cb, tkv)
    assert (a["boundary"], a["end_window"], b["boundary"]) == (False, None, True)
    assert [a["step"], a["wait"], a["outside"]] == pytest.approx([93e-6, 70e-6, 100e-6])
    assert [b["step"], b["wait"], b["outside"], b["end_window"]] == pytest.approx(
        [178e-6, 85e-6, 190e-6, 70e-6])


# ------------------------------------------- the trace without tkv spans
def test_without_program_spans_the_reduction_is_trace_reduce():
    base = trace_reduce.reduce(str(OLD))
    red = span_reduce.reduce(str(OLD))
    for k, v in base.items():
        assert red[k] == v, k
    assert red["step_host_idle_ms"] == 0.0 and red["steps"] == []


# ------------------------------------------- the trace with tkv spans
def test_program_spans_nest_inside_the_benchmark_steps(new):
    _, (_, _, spans) = new
    cb = [s for s in spans if s[2].startswith("cb.")]
    tkv = [s for s in spans if s[2].startswith("tkv.")]
    steps = [s for s in tkv if s[2] == "tkv.step"]
    outer = [s for s in cb if s[2] in ("cb.step", "cb.boundary_step")]
    assert len(steps) == len(outer) >= 4
    for s, e, _ in tkv:
        assert any(c0 <= s and e <= c1 for c0, c1, n in outer), (s, e)
    for st, (c0, c1, n) in zip(sorted(steps), sorted(outer)):
        assert c0 <= st[0] and st[1] <= c1
        kids = [k[2] for k in tkv if st[0] <= k[0] and k[1] <= st[1] and k is not st]
        assert kids.count("tkv.wait") == 1 and kids.count("tkv.dispatch") == 1
        assert ("tkv.end_window" in kids) == (n == "cb.boundary_step")


def test_idle_in_steps_is_put_down_to_program_spans(new):
    red, _ = new
    gaps = dict(red["idle_gaps"])
    in_steps = sum(v for k, v in gaps.items() if k == "cb.step" or k.startswith("tkv."))
    assert in_steps > 0
    assert gaps.get("cb.step", 0.0) <= 0.25 * in_steps
    assert sum(gaps.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    steps = sum(1 for r in red["steps"])
    host = sum(v for k, v in gaps.items() if k.startswith("tkv.") and k != "tkv.wait")
    assert red["step_host_idle_ms"] == pytest.approx(host * 1e3 / steps)


def test_window_is_the_benchmark_spans_alone(new):
    red, (ops, _, spans) = new
    base = trace_reduce.reduce(str(NEW))
    cb = [s for s in spans if s[2].startswith("cb.")]
    lo, hi = min(s for s, _, _ in cb), max(e for _, e, _ in cb)
    assert red["window_s"] == base["window_s"] == pytest.approx((hi - lo) * 1e-9)
    for k in ("busy_s", "kernel_s", "kernel_calls", "device_ops"):
        assert red[k] == base[k], k
    # the program spans reach no further than the benchmark's
    tkv = [s for s in spans if s[2].startswith("tkv.")]
    assert lo <= min(s for s, _, _ in tkv) and max(e for _, e, _ in tkv) <= hi


def test_device_time_per_program(new):
    red, (ops, mods, spans) = new
    cb = [s for s in spans if s[2].startswith("cb.")]
    lo, hi = min(s for s, _, _ in cb), max(e for _, e, _ in cb)
    total = {}
    for s, e, n in mods:
        if e > lo and s < hi:
            name = span_reduce.program_name(n)
            total[name] = total.get(name, 0) + (e - s)
    assert red["device_programs"]
    for name, secs in red["device_programs"]:
        assert secs == pytest.approx(total[name] * 1e-9, rel=1e-12)
    assert red["device_programs"][0][0] == "jit_step"
    # each top op's time is spread over the programs it ran in
    for op, secs in red["device_ops"]:
        progs = red["op_programs"][op]
        assert sum(v for _, v in progs) <= secs * (1 + 1e-9)
        assert progs[0][1] >= secs / 3


def test_step_rows_agree_with_the_benchmark_steps(new):
    red, _ = new
    fixture = json.loads((DATA / "longctx_spans_trace.json").read_text())
    rows = red["steps"]
    assert len(rows) == len(fixture["metered"])
    assert [r["boundary"] for r in rows] == [False] * (len(rows) - 1) + [True]
    for r in rows:
        assert 0 < r["wait"] < r["step"] <= r["outside"]
    ratio = np.median([r["step"] / r["outside"] for r in rows if not r["boundary"]])
    assert ratio >= 0.9
