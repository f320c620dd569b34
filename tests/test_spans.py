"""Program spans (``repro.serving.spans``): the recorder on its own, and the
span tree a ``TieredEngine`` run leaves, on a smoke-sized model."""

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig, TierScapeRunConfig
from repro.models import Model
from repro.serving import TieredEngine
from repro.serving.spans import Span, SpanRecorder, self_ns, totals

WINDOW = 4


# ------------------------------------------------------------ the recorder
def test_recorder_nests_and_indexes_parents():
    rec = SpanRecorder()
    rec.start()
    with rec.span("a"):
        with rec.span("b", rid=7):
            pass
        with rec.span("c"):
            with rec.span("d"):
                pass
    with rec.span("e"):
        pass
    spans = rec.stop()
    assert [s.name for s in spans] == ["a", "b", "c", "d", "e"]
    assert [s.parent for s in spans] == [-1, 0, 0, 2, -1]
    assert spans[1].rid == 7 and spans[0].rid is None
    for s in spans:
        assert s.t0_ns <= s.t1_ns
        if s.parent >= 0:
            p = spans[s.parent]
            assert p.t0_ns <= s.t0_ns and s.t1_ns <= p.t1_ns
    assert rec.stop() == []  # handed out once


def test_recorder_off_records_nothing():
    rec = SpanRecorder()
    with rec.span("a"):
        with rec.span("b"):
            pass
    assert rec.stop() == []
    rec.start()
    rec.stop()
    with rec.span("c"):
        pass
    assert rec.stop() == []


def test_stop_inside_a_span_ends_it_then():
    rec = SpanRecorder()
    rec.start()
    with rec.span("outer"):
        with rec.span("inner"):
            spans = rec.stop()
    assert [s.name for s in spans] == ["outer", "inner"]
    assert all(s.t1_ns >= s.t0_ns for s in spans)
    assert rec.stop() == []


def test_span_closes_when_its_body_raises():
    rec = SpanRecorder()
    rec.start()
    with pytest.raises(KeyError):
        with rec.span("a"):
            with rec.span("b"):
                raise KeyError("x")
    with rec.span("c"):
        pass
    spans = rec.stop()
    assert [(s.name, s.parent) for s in spans] == [("a", -1), ("b", 0), ("c", -1)]


def test_self_time_and_totals_on_hand_built_spans():
    spans = [
        Span("tkv.step", 0, 100, -1, None),
        Span("tkv.wait", 10, 70, 0, None),
        Span("tkv.sample", 75, 90, 0, None),
        Span("tkv.step", 100, 150, -1, None),
        Span("tkv.wait", 100, 140, 3, None),
    ]
    assert self_ns(spans) == [25, 60, 15, 10, 40]
    t = totals(spans)
    assert t["tkv.step"] == (2, 150, 35)
    assert t["tkv.wait"] == (2, 100, 100)
    assert t["tkv.sample"].calls == 1


# ----------------------------------------------------------- the engine
def _engine():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=128, head_dim=16)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return TieredEngine(model, params, batch_slots=2, page_tokens=8, max_seq_len=96,
                        recent_window=16,
                        ts=TierScapeRunConfig(enabled=True, policy="analytical", alpha=0.3,
                                              window_steps=WINDOW))


def _serve(record: bool):
    """Three requests through two slots (one slot is reused), step by step;
    returns the engine, its spans, each request's tokens and the physical
    placement after every step."""
    eng = _engine()
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=m)
            for n, m in ((40, 14), (33, 6), (48, 9))]
    if record:
        eng.spans.start()
    placements = []
    while any(s is not None for s in eng.slots) or eng.queue:
        eng._fill_slots()
        eng.step()
        placements.append(eng.cache.physical.copy())
    eng.finish()
    return eng, eng.spans.stop(), [list(r.out_tokens) for r in reqs], placements


@pytest.fixture(scope="module")
def served():
    return _serve(record=True)


def _children(spans, i):
    return [j for j, s in enumerate(spans) if s.parent == i]


def test_every_step_holds_one_dispatch_and_one_wait(served):
    eng, spans, _, _ = served
    steps = [i for i, s in enumerate(spans) if s.name == "tkv.step"]
    assert len(steps) == eng.stats.steps
    for i in steps:
        names = [spans[j].name for j in _children(spans, i)]
        assert names.count("tkv.dispatch") == 1 and names.count("tkv.wait") == 1
        assert names.count("tkv.telemetry") == 1 and names.count("tkv.sample") == 1
        assert names.count("tkv.pipeline") + names.count("tkv.prefetch") == 1
        assert names.index("tkv.dispatch") < names.index("tkv.wait") < names.index(
            "tkv.telemetry")
    assert sum(s.name == "tkv.page_out" and spans[s.parent].name == "tkv.step"
               for s in spans) > 0


def test_children_lie_inside_their_parents(served):
    _, spans, _, _ = served
    own = self_ns(spans)
    assert all(o >= 0 for o in own)
    for i, s in enumerate(spans):
        kids = _children(spans, i)
        assert sum(spans[j].t1_ns - spans[j].t0_ns for j in kids) <= s.t1_ns - s.t0_ns
        for j in kids:
            assert s.t0_ns <= spans[j].t0_ns <= spans[j].t1_ns <= s.t1_ns
        if s.parent < 0:
            assert s.name in ("tkv.step", "tkv.prefill", "tkv.finish")
    top = [s for s in spans if s.parent < 0]
    assert all(a.t1_ns <= b.t0_ns for a, b in zip(top, top[1:]))


def test_boundary_steps_and_only_those_end_a_window(served):
    eng, spans, _, _ = served
    steps = [i for i, s in enumerate(spans) if s.name == "tkv.step"]
    closing = []
    for n, i in enumerate(steps):
        ends = [j for j in _children(spans, i) if spans[j].name == "tkv.end_window"]
        if (n + 1) % WINDOW == 0:
            assert len(ends) == 1
            names = [spans[j].name for j in _children(spans, ends[0])]
            assert names.count("tkv.plan") == 1 and names.count("tkv.drain") == 1
            assert names.index("tkv.drain") < names.index("tkv.plan")
            closing.append(n)
        else:
            assert ends == []
    assert len(closing) == eng.stats.windows >= 3
    assert sum(s.name == "tkv.end_window" for s in spans) == eng.stats.windows
    assert all(spans[s.parent].name == "tkv.end_window"
               for s in spans if s.name in ("tkv.drain", "tkv.plan", "tkv.submit"))


def test_prefill_spans_carry_the_request_id(served):
    _, spans, _, _ = served
    pre = [i for i, s in enumerate(spans) if s.name == "tkv.prefill"]
    assert [spans[i].rid for i in pre] == [0, 1, 2]
    for i in pre:
        kids = [spans[j] for j in _children(spans, i)]
        assert [k.name for k in kids] == ["tkv.prefill.compute", "tkv.prefill.page_in"]
        assert all(k.rid == spans[i].rid for k in kids)
    # the third request waits for a slot: its prefill follows a step
    assert spans[pre[2]].t0_ns > spans[[i for i, s in enumerate(spans)
                                        if s.name == "tkv.step"][0]].t1_ns


def test_recorder_off_serves_the_same_tokens_and_placements(served):
    _, _, tokens, placements = served
    eng, spans, tokens_off, placements_off = _serve(record=False)
    assert spans == []
    assert tokens_off == tokens
    assert len(placements_off) == len(placements)
    for a, b in zip(placements, placements_off):
        np.testing.assert_array_equal(a, b)


def test_preempt_and_resume_are_spans_with_the_request_id():
    eng = _engine()
    rng = np.random.default_rng(5)
    req = eng.make_request(rng.integers(1, 128, 40), max_new_tokens=8)
    eng.start_request(0, req)
    eng.step()
    eng.spans.start()
    pre = eng.preempt_slot(0)
    eng.resume_into(1, pre)
    spans = eng.spans.stop()
    assert [(s.name, s.rid, s.parent) for s in spans] == [
        ("tkv.preempt", req.rid, -1), ("tkv.resume", req.rid, -1)]
