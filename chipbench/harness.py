"""One run of one cell: set-up, the timed window, the check of ``correct``.

The window drives ``TieredEngine`` through its public API, the calls the
frontend scheduler makes (``free_slots``, ``make_request``,
``start_request``, ``step``, ``finish``), with FIFO placement. With
``trace`` off it adds no device sync of its own: the engine already waits
for each step's tokens. With ``trace`` on, every prefill and step is a
host span that ends in ``block_until_ready`` of the cache state, and one
window cycle (boundary to boundary) is recorded by the profiler.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

from chipbench import generators, kv_readback, modelcfg, tco
from chipbench.generators import Item

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def now() -> float:
    return time.perf_counter()


# ------------------------------------------------------------------ chips
def open_chip(chips: int) -> Optional[tuple]:
    """Start JAX on this machine's chips for a run: the persistent compile
    cache at ``<checkout>/.jax_cache`` (set before JAX is imported, so that
    two checkouts never share one), and every program cached there, eager
    ops too, so that only a cell's first run in a checkout compiles.
    Returns (devices, the peaks of their kind from ``peaks.json``), or None
    after saying on standard error why nothing can be measured."""
    if "jax" in sys.modules:
        raise RuntimeError("open_chip() has to run before JAX is imported")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU found (JAX platform {devices[0].platform!r}); nothing measured",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"{chips} chips needed, {len(devices)} found", file=sys.stderr)
        return None
    peaks = json.loads((HERE / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"device kind {kind!r} has no entry in chipbench/peaks.json", file=sys.stderr)
        return None
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices, peaks[kind]


# ------------------------------------------------------------------ cells
def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    chips: int


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None, root: Path = HERE) -> Cell:
    """The cell's files, found by the names in ``BENCHMARK.json`` under
    ``root`` (the benchmark's directory)."""
    bench = bench or load_benchmark()
    match = [w for w in bench["workloads"] if w["name"] == name]
    if not match:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    w = match[0]
    return Cell(
        name=name,
        config=modelcfg.load_json("configs", w["config"], root),
        mix=modelcfg.load_json("traffic", w["traffic"], root),
        limits=modelcfg.load_json("cells", name, root),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        chips=int(w["chips"]),
    )


# ----------------------------------------------------------------- engine
def build_engine(cfg: dict, params, device):
    from repro.configs.base import TierScapeRunConfig
    from repro.models import Model
    from repro.serving import TieredEngine

    e, t = cfg["engine"], cfg["tierscape"]
    ts = TierScapeRunConfig(
        enabled=True, policy=t["policy"], alpha=float(t["alpha"]),
        window_steps=int(t["window_steps"]), async_migration=bool(t["async_migration"]),
        prefetch=bool(t["prefetch"]), faults=False,
    )
    return TieredEngine(
        Model(modelcfg.program_config(cfg)), params, batch_slots=int(e["batch_slots"]),
        page_tokens=int(e["page_tokens"]), max_seq_len=int(e["max_seq_len"]),
        recent_window=int(e["recent_window"]), ts=ts, device=device,
    )


# ----------------------------------------------------------------- client
@dataclasses.dataclass
class Served:
    """One request as the client saw it."""

    item: Item
    due: Optional[float]  # absolute due time; None for set-up traffic
    req: object = None  # the engine's Request once placed
    times: List[float] = dataclasses.field(default_factory=list)  # token times
    first_in_window: int = 0  # index of its first token emitted in the window


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    served: List[Served]
    steps: List[tuple]  # (seconds, closed_a_window, synced)
    prefills: List[tuple]  # (seconds, synced)
    tco_samples: List[float]
    compiles: int  # programs compiled or loaded from the cache in the window
    traced: Optional[dict] = None  # profiler window: page counts of each traced step

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


class Client:
    """Drives one engine. ``trace`` turns on spans, syncs and metering."""

    def __init__(self, eng, window_steps: int, usd: np.ndarray, trace: bool = False):
        self.eng = eng
        self.w = window_steps
        self.usd = usd
        self.trace = trace
        self.active: List[Served] = []

    # -- primitives
    def _span(self, name: str, **kw):
        import contextlib

        import jax

        if not self.trace:
            return contextlib.nullcontext()
        if "step_num" in kw:
            return jax.profiler.StepTraceAnnotation(name, **kw)
        return jax.profiler.TraceAnnotation(name)

    def _sync(self):
        if self.trace:
            import jax

            jax.block_until_ready(self.eng.cache.state)

    def place(self, s: Served, slot: int) -> float:
        eng = self.eng
        s.req = eng.make_request(s.item.prompt, s.item.max_new)
        t0 = now()
        with self._span("cb.prefill"):
            eng.start_request(slot, s.req)
            self._sync()
        t = now()
        s.times.append(t)
        self.active.append(s)
        return t - t0

    def step(self, n: int) -> tuple:
        eng = self.eng
        before = eng.stats.windows
        counts = [(s, len(s.req.out_tokens)) for s in self.active]
        t0 = now()
        closing = eng.stats.steps % self.w == self.w - 1
        with self._span("cb.boundary_step" if closing else "cb.step", step_num=n):
            eng.step()
            self._sync()
        t = now()
        for s, c in counts:
            if len(s.req.out_tokens) > c:
                s.times.append(t)
        self.active = [s for s in self.active if not s.req.done]
        return t - t0, eng.stats.windows != before

    def tco_sample(self) -> Optional[float]:
        return tco.savings_pct(self.eng.cache.physical, self.usd)

    def meter(self) -> tuple:
        """Operand sizes of this step's fused attention calls, per layer and
        serving slot: int8 pages, int4 pages, host rows, recent tokens, and
        each slot's context length."""
        st = self.eng.cache.state
        live = np.array([r is not None for r in self.eng.slots])
        with self._span("cb.meter"):
            warm, cold, host = (np.asarray(a)[:, live] for a in (st.warm_n, st.cold_n, st.host_n))
            rlen = np.asarray(st.recent_len)[live] + 1
            ctx = np.asarray(st.total_len)[live] + 1
        return warm, cold, host, np.broadcast_to(rlen, warm.shape), ctx

    # -- phases
    def setup(self, warm: List[Item], min_steps: int) -> None:
        """Serve set-up traffic, admitted in order as slots free, for
        ``min_steps`` decode steps (fewer only if the traffic runs out)."""
        queue = collections.deque(Served(it, None) for it in warm)
        n = 0
        while n < min_steps:
            for slot in self.eng.free_slots():
                if queue:
                    self.place(queue.popleft(), slot)
            if not self.active:
                break
            self.step(n)
            n += 1

    def window(self, timed: List[Item], seconds: float, trace_dir: Optional[str] = None) -> Window:
        import jax

        compiles = []

        def on_compile(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(duration)

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        try:
            return self._window(timed, seconds, trace_dir, compiles)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_compile)

    def _window(self, timed, seconds, trace_dir, compiles) -> Window:
        import jax

        eng = self.eng
        t0 = now()
        end = t0 + seconds
        served = [Served(it, t0 + it.due_s) for it in timed]
        for s in self.active:
            s.first_in_window = len(s.times)
        carried = list(self.active)
        steps, prefills, samples = [], [], []
        queue = collections.deque()
        nxt = 0
        n = 0
        tracing = None  # None: not yet; dict while/after tracing
        while now() < end:
            t = now()
            while nxt < len(served) and served[nxt].due <= t:
                queue.append(served[nxt])
                nxt += 1
            for slot in eng.free_slots():
                if not queue:
                    break
                prefills.append((self.place(queue.popleft(), slot), self.trace))
            if not self.active:
                wake = min(served[nxt].due if nxt < len(served) else end, end)
                with self._span("cb.wait"):
                    time.sleep(max(wake - now(), 0.0))
                continue
            if eng.stats.steps % self.w == self.w - 1:
                value = self.tco_sample()
                if value is not None:
                    samples.append(value)
            metered = self.meter() if tracing is not None and tracing.get("on") else None
            dt, closed = self.step(n)
            n += 1
            steps.append((dt, closed, self.trace))
            if metered is not None:
                tracing["metered"].append(metered)
            if self.trace and trace_dir and closed:
                if tracing is None:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    tracing = {"on": True, "metered": []}
                elif tracing["on"]:
                    jax.profiler.stop_trace()
                    tracing["on"] = False
        t_end = now()
        if tracing is not None and tracing["on"]:
            jax.profiler.stop_trace()
            tracing["on"] = False
        return Window(t0=t0, t_end=t_end, served=carried + served, steps=steps,
                      prefills=prefills, tco_samples=samples, compiles=len(compiles),
                      traced=tracing)


# ------------------------------------------------------------ correctness
def sample_for_check(win: Window, k: int, seed: int) -> List[Served]:
    """Up to ``k`` requests that emitted tokens in the window, the longest
    among them; finished ones first, then those still decoding."""
    cands = [s for s in win.served if s.req is not None and len(s.times) > s.first_in_window]
    if not cands:
        return []
    done = [s for s in cands if s.req.done]
    pool = done if len(done) >= k else cands
    length = [len(s.item.prompt) + len(s.req.out_tokens) for s in pool]
    longest = int(np.argmax(length))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
    rest = [i for i in rng.permutation(len(pool)) if i != longest][: k - 1]
    return [pool[longest]] + [pool[i] for i in rest]


def logit_gaps(reference, params, arch, sample: List[Served], fp8: bool = False,
               held: Optional[list] = None, page_tokens: int = 0) -> tuple:
    """For every served token of the sample: how far its reference logit
    lies below the reference's best. With ``fp8`` the token is the one
    the float8 control ranks first, read against the float32 reference.
    ``held`` (per request, its cache as read back, or None) adds the
    comparison of its KV pages with the reference's. Returns the gaps and
    the combined page reading (None when no page was read)."""
    gaps, readings = [], []
    for i, s in enumerate(sample):
        prompt = np.asarray(s.item.prompt, np.int32)
        out = np.asarray(s.req.out_tokens, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        h = held[i] if held is not None else None
        ref = reference.logits(params, arch, seq, len(prompt) - 1, kv=h is not None)
        if h is not None:
            ref, kv = ref
            readings.append(kv_readback.compare(h, kv, page_tokens))
            del kv
        if fp8:
            ctrl = reference.logits(params, arch, seq, len(prompt) - 1, fp8=True)
            out = ctrl.argmax(-1)
        gaps.append(ref.max(-1) - ref[np.arange(len(out)), out])
    return (np.concatenate(gaps) if gaps else np.zeros(0)), kv_readback.combine(readings)


def read_back(eng, sample: List[Served]) -> list:
    """The cache each sampled request holds, for those still in a slot."""
    slot_of = {id(r): i for i, r in enumerate(eng.slots) if r is not None}
    return [kv_readback.read(eng, slot_of[id(s.req)]) if id(s.req) in slot_of else None
            for s in sample]


def kv_length_errors(eng) -> int:
    """Requests in a slot whose cache does not hold exactly the tokens fed
    so far: the prompt and every served token but the newest."""
    held = np.asarray(eng.cache.state.total_len)
    return sum(int(held[i] != len(r.prompt) + len(r.out_tokens) - 1)
               for i, r in enumerate(eng.slots) if r is not None)


def checks(limits: dict, gaps: np.ndarray, bad_tokens: int, kv_errors: int,
           pages: Optional[kv_readback.Reading]) -> dict:
    return {
        "max_logit_gap": {"value": float(gaps.max()) if gaps.size else float("nan"),
                          "limit": float(limits["max_logit_gap"]), "holds": "<="},
        "compared_tokens": {"value": int(gaps.size),
                            "limit": int(limits["min_compared_tokens"]), "holds": ">="},
        "tokens_out_of_vocab": {"value": int(bad_tokens), "limit": 0, "holds": "<="},
        "kv_length_errors": {"value": int(kv_errors), "limit": 0, "holds": "<="},
        "kv_page_rel_err": {"value": pages.rel_err if pages else float("nan"),
                            "limit": float(limits["max_kv_page_rel_err"]), "holds": "<="},
        "kv_pages_unmatched": {"value": pages.unmatched if pages else 0, "limit": 0,
                               "holds": "<="},
        "kv_pages_compared": {"value": pages.pages if pages else 0,
                              "limit": int(limits["min_kv_pages"]), "holds": ">="},
    }


def passed(c: dict) -> bool:
    ok = True
    for v in c.values():
        if v["holds"] == "<=":
            ok &= bool(v["value"] <= v["limit"])
        else:
            ok &= bool(v["value"] >= v["limit"])
    return ok


# ------------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    cell: Cell
    arch: modelcfg.Arch
    seed: int
    setup_s: float
    win: Window
    peaks: Optional[dict]
    trace: Optional[dict]  # reduced profiler window
    device: dict
    sample: List[Served] = dataclasses.field(default_factory=list)  # compared requests
    gaps: Optional[np.ndarray] = None  # per compared token
    pages: Optional[kv_readback.Reading] = None  # compared KV pages


def measure(cell: Cell, seed: int, seconds: float, trace: bool, device, peaks: Optional[dict],
            keep_trace: Optional[str] = None,
            before_window: Optional[Callable] = None,
            t_start: Optional[float] = None) -> tuple:
    """Set-up, window, reference check. Returns (Run, checks, weights).
    ``before_window(engine)`` lets a test break the timed path; set-up is
    timed from ``t_start`` (the process start) when given."""
    import jax

    t_setup = now() if t_start is None else t_start
    cfg = cell.config
    arch = modelcfg.arch(cfg)
    from chipbench import weights

    params = weights.make(arch, seed, device)
    eng = build_engine(cfg, params, device)
    e = cfg["engine"]
    w = int(cfg["tierscape"]["window_steps"])
    traffic = generators.generate(cell.mix, seed, seconds, arch.vocab,
                                  {"window_steps": w, "max_seq_len": int(e["max_seq_len"])})
    usd = tco.level_usd(tco.load_costs(), int(e["page_tokens"]), arch.n_kv_heads, arch.head_dim)
    client = Client(eng, w, usd, trace=trace)
    client.setup(traffic.warm, traffic.warm_steps)
    jax.block_until_ready(eng.cache.state)
    setup_s = now() - t_setup

    if before_window is not None:
        before_window(eng)
    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="chipbench_trace_")
    win = client.window(traffic.timed, seconds, trace_dir)
    eng.finish()
    jax.block_until_ready(eng.cache.state)

    reduced = None
    if trace and win.traced is not None:
        from chipbench import trace_reduce

        reduced = trace_reduce.reduce(trace_reduce.find_trace(trace_dir))
    if trace and keep_trace is None and trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)

    stats = device.memory_stats() or {}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    # Free the program's state before the reference runs on the same chip.
    sample = sample_for_check(win, int(cell.limits["sample_requests"]), seed)
    kv_errors = kv_length_errors(eng)
    held = read_back(eng, sample)
    del eng, client
    gc.collect()
    reference = importlib.import_module(f"chipbench.references.{cfg['reference']}")
    gaps, pages = logit_gaps(reference, params, arch, sample, held=held,
                             page_tokens=int(e["page_tokens"]))
    del held
    bad = sum(int(((np.asarray(s.req.out_tokens) < 0) | (np.asarray(s.req.out_tokens) >= arch.vocab)).sum())
              for s in sample)
    c = checks(cell.limits, gaps, bad, kv_errors, pages)
    run = Run(cell=cell, arch=arch, seed=seed, setup_s=setup_s, win=win, peaks=peaks,
              trace=reduced, device=dev, sample=sample, gaps=gaps, pages=pages)
    return run, c, params


def metric_values(run: Run, trace: bool) -> dict:
    out = {}
    for m in (run.cell.per_layer if trace else run.cell.end_to_end):
        mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
