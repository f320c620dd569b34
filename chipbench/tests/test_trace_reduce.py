"""The trace reduction on a small trace recorded on a TPU v5e: one 4-step
window cycle of the ``internlm2_20b_8l.longctx`` cell, with the page counts
of those steps (``data/longctx_trace.json``). Reads a file; touches no TPU."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness, kernel_cost, modelcfg, trace_reduce
from chipbench.metrics import decode_mfu_pct, device_idle_pct, fused_tiered_attention_roofline

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "longctx_trace.xplane.pb"
KERNEL = "fused_tiered_attention"


@pytest.fixture(scope="module")
def fixture():
    return json.loads((DATA / "longctx_trace.json").read_text())


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(str(TRACE))


@pytest.fixture(scope="module")
def raw():
    """Device op events and benchmark spans, read straight from the file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(TRACE))
    ops, spans = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                elif plane.name.startswith("/host:") and e.name.startswith("cb."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return ops, spans


def test_op_names():
    assert trace_reduce.op_name("%fused_tiered_attention.19 = (f32[16]) custom-call()") == KERNEL
    assert trace_reduce.op_name("%copy.288.remat2 = s8[2] copy(%a)") == "copy"
    assert trace_reduce.op_name("%copy-done = s32[32] copy-done(%b)") == "copy-done"


def test_union_length():
    covered, gaps = trace_reduce.union_length([(2, 4), (3, 6), (8, 9), (0, 1)], 1, 10)
    assert covered == 5 and gaps == [(1, 2), (6, 8), (9, 10)]


def test_idle_share(reduced, raw):
    ops, spans = raw
    lo, hi = min(s for s, _, _ in spans), max(e for _, e, _ in spans)
    # busy time by sweeping sorted boundaries (independent of union_length)
    marks = sorted([(max(s, lo), 1) for s, e, _ in ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e, _ in ops if e > lo and s < hi])
    busy, depth, last = 0.0, 0, lo
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert reduced["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.0 < idle < 1.0
    gaps = sum(v for _, v in reduced["idle_gaps"])
    assert gaps <= reduced["window_s"] - reduced["busy_s"] + 1e-9


def test_kernel_time(reduced, raw, fixture):
    ops, _ = raw
    calls = [e - s for s, e, n in ops if n.startswith("%" + KERNEL + ".") or n.startswith(KERNEL)]
    assert reduced["kernel_calls"][KERNEL] == len(calls)
    assert reduced["kernel_s"][KERNEL] == pytest.approx(sum(calls) * 1e-9)
    cfg = modelcfg.load_json("configs", fixture["config"])
    # one call per layer for each traced step
    assert len(calls) == modelcfg.arch(cfg).n_layers * len(fixture["metered"])


def test_roofline_arithmetic(reduced, fixture):
    cfg = modelcfg.load_json("configs", fixture["config"])
    a = modelcfg.arch(cfg)
    peaks = json.loads((harness.HERE / "peaks.json").read_text())["TPU v5 lite"]
    metered = [tuple(np.asarray(x) for x in m) for m in fixture["metered"]]
    t, kv, hd, h = fixture["page_tokens"], a.n_kv_heads, a.head_dim, a.n_heads
    # the bytes of one call, written out per batch row
    warm, cold, host, rlen, _ = metered[0]
    nbytes = 0
    for w, c, s, r in zip(warm[0], cold[0], host[0], rlen[0]):
        nbytes += w * 2 * (t * kv * hd + 4 * t * kv) + c * 2 * (t * kv * hd // 2 + 4 * t * kv)
        nbytes += s * kv * hd * 4 + r * 2 * kv * hd * 2 + h * hd * 2 + h * hd * 4 + 8 * h
        nbytes += (w + c + s) * 8
    flops, b = kernel_cost.fused_tiered_attention(
        warm[:1], cold[:1], host[:1], rlen[:1], page_tokens=t, kv_heads=kv, head_dim=hd, heads=h)
    assert b == nbytes and flops == 4 * h * hd * int(((warm[0] + cold[0]) * t + rlen[0]).sum()) \
        + 2 * h * hd * int(host[0].sum())

    run = SimpleNamespace(arch=a, peaks=peaks, trace=reduced, cell=SimpleNamespace(config=cfg),
                          win=SimpleNamespace(traced={"metered": metered}))
    share = fused_tiered_attention_roofline.read(run)
    assert 0.0 < share <= 100.0
    assert share == pytest.approx(fixture["metrics"]["fused_tiered_attention_roofline"]["value"])
    assert device_idle_pct.read(run) == pytest.approx(fixture["metrics"]["device_idle_pct"]["value"])
    assert decode_mfu_pct.read(run) == pytest.approx(fixture["metrics"]["decode_mfu_pct"]["value"])
