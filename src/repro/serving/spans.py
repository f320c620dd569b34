"""Program spans: named host work on the profiler's clock.

Every span enters ``jax.profiler.TraceAnnotation(name)``, so while a
profiler trace is running it lands in the same ``.xplane.pb`` as the
device's operations, on the same clock; a trace reduction can then name the
host work that each idle stretch of the device falls in. Outside a trace an
annotation costs well under a microsecond.

A ``SpanRecorder`` also keeps the spans in memory, but only between
``start()`` and ``stop()`` (it is off by default, and then a span costs its
annotation and one test). Each recorded span is a ``Span``: its name, its
start and end on ``time.perf_counter_ns``, the index of the span it sits in
(-1 at the top) and the request id it belongs to, if any. Nothing is
written anywhere: ``stop()`` hands the list to the caller.

``TieredEngine`` owns one recorder as ``engine.spans`` and shares it with
its ``TieredKVCache``. The names all start with ``tkv.``:

- ``tkv.step``: one engine step, parent of ``tkv.dispatch`` (building the
  token input and enqueueing the jitted step), ``tkv.wait`` (waiting for
  the step's outputs, where the first host read of them would wait
  anyway), ``tkv.telemetry`` (host reads of tables and masses, the numpy
  fold, the manager), ``tkv.pipeline`` or ``tkv.prefetch`` (one tick of
  the media pipeline, or a prefetch tick when it is idle), ``tkv.sample``
  (argmax, reading the tokens back, request bookkeeping, slot release),
  ``tkv.page_out`` (only on steps that page the recent window out) and
  ``tkv.end_window`` (window boundaries only);
- ``tkv.end_window``: parent of ``tkv.drain`` (finishing the previous
  window's cohorts and speculative stages), ``tkv.plan`` (the placement
  model) and ``tkv.submit`` (cohorts planned and queued, or executed in
  serial mode);
- ``tkv.prefill`` (with the request's id): parent of ``tkv.prefill.compute``
  (the jitted prefill and the read-back of its keys and values) and
  ``tkv.prefill.page_in`` (the prompt's pages into the warm tier);
- ``tkv.finish``, ``tkv.preempt``, ``tkv.resume``: the public calls that
  drain the pipeline, park a slot and restore one.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    parent: int  # index of the enclosing span in the same list; -1 at the top
    rid: Optional[int]  # the request the work belongs to, where there is one


class _Open:
    """One span while it runs: the profiler annotation, and the recorder's
    row when the recorder is on."""

    __slots__ = ("rec", "name", "rid", "ann", "row")

    def __init__(self, rec: "SpanRecorder", name: str, rid: Optional[int]):
        self.rec = rec
        self.name = name
        self.rid = rid
        self.ann = TraceAnnotation(name)
        self.row = None

    def __enter__(self):
        self.ann.__enter__()
        rec = self.rec
        if rec.on:
            parent = rec._stack[-1] if rec._stack else -1
            self.row = len(rec._rows)
            rec._rows.append([self.name, time.perf_counter_ns(), None, parent, self.rid])
            rec._stack.append(self.row)
        return self

    def __exit__(self, *exc):
        row = self.row
        if row is not None:
            rec = self.rec
            if rec._stack and rec._stack[-1] == row:
                rec._stack.pop()
                rec._rows[row][2] = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        return False


class SpanRecorder:
    """Spans of one engine. ``span(name, rid=None)`` is a context manager;
    ``start()`` begins recording, ``stop()`` ends it and returns the spans
    recorded since, in the order they began."""

    def __init__(self):
        self.on = False
        self._rows: List[list] = []
        self._stack: List[int] = []

    def span(self, name: str, rid: Optional[int] = None) -> _Open:
        return _Open(self, name, rid)

    def start(self) -> None:
        self._rows, self._stack = [], []
        self.on = True

    def stop(self) -> List[Span]:
        """The spans recorded since ``start()``; a span still open ends now."""
        end = time.perf_counter_ns()
        rows, self._rows, self._stack = self._rows, [], []
        self.on = False
        return [Span(n, t0, end if t1 is None else t1, p, rid) for n, t0, t1, p, rid in rows]


def self_ns(spans: List[Span]) -> List[int]:
    """Each span's own time: its length less the lengths of its children."""
    own = [s.t1_ns - s.t0_ns for s in spans]
    for s in spans:
        if s.parent >= 0:
            own[s.parent] -= s.t1_ns - s.t0_ns
    return own


class NameTotal(NamedTuple):
    calls: int
    total_ns: int
    self_ns: int


def totals(spans: List[Span]) -> Dict[str, NameTotal]:
    """Per span name: calls, summed length and summed own time."""
    calls, total, own = collections.Counter(), collections.Counter(), collections.Counter()
    for s, o in zip(spans, self_ns(spans)):
        calls[s.name] += 1
        total[s.name] += s.t1_ns - s.t0_ns
        own[s.name] += o
    return {n: NameTotal(calls[n], total[n], own[n]) for n in calls}
