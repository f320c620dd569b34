"""Every cell of BENCHMARK.json resolves its files by name; traffic is a
pure function of the seed; a mix, a cell or a metric is added as a file."""

import importlib
import json
import re
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import generators, harness, modelcfg

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _engine(cell):
    e = cell.config["engine"]
    return {"window_steps": int(cell.config["tierscape"]["window_steps"]),
            "max_seq_len": int(e["max_seq_len"])}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.load_cell(name)
    arch = modelcfg.arch(cell.config)
    assert cell.config["name"] == [w for w in BENCH["workloads"] if w["name"] == name][0]["config"]
    importlib.import_module(f"chipbench.references.{cell.config['reference']}")
    importlib.import_module(f"chipbench.generators.{cell.mix['kind']}")
    for m in cell.end_to_end + cell.per_layer:
        assert callable(importlib.import_module(f"chipbench.metrics.{m['name']}").read)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert arch.d_model % arch.n_heads == 0 and arch.n_heads % arch.n_kv_heads == 0
    for key in ("max_logit_gap", "sample_requests", "min_compared_tokens",
                "max_kv_page_rel_err", "min_kv_pages"):
        assert key in cell.limits


def test_benchmark_names_and_references():
    cells = set(CELLS)
    configs = {c["name"] for c in BENCH["configs"]}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for item in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(item["name"]), item["name"]
    for c in BENCH["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            movers = [x for x in BENCH["end_to_end"] if x["name"] == m["moves"]]
            assert w in movers[0].get("workloads", cells), (m["name"], w)
    assert all(0 < m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_traffic_is_a_function_of_the_seed(name):
    cell = harness.load_cell(name)
    vocab = int(cell.config["vocab_size"])
    seed = 2**33 + 17
    a = generators.generate(cell.mix, seed, 51.0, vocab, _engine(cell))
    b = generators.generate(cell.mix, seed, 51.0, vocab, _engine(cell))
    c = generators.generate(cell.mix, seed + 1, 51.0, vocab, _engine(cell))
    for x, y in ((a.warm, b.warm), (a.timed, b.timed)):
        assert len(x) == len(y)
        for i, j in zip(x, y):
            assert i.due_s == j.due_s and i.max_new == j.max_new
            assert np.array_equal(i.prompt, j.prompt)
    # another seed: the same work (arrivals and sizes), other token ids
    assert [(i.due_s, len(i.prompt), i.max_new) for i in a.timed + a.warm] == \
        [(i.due_s, len(i.prompt), i.max_new) for i in c.timed + c.warm]
    assert not np.array_equal(a.warm[0].prompt[:8], c.warm[0].prompt[:8]) or \
        len(a.warm[0].prompt) != len(c.warm[0].prompt)
    for it in a.warm + a.timed:
        assert 0 < it.prompt.min() and it.prompt.max() < vocab
        assert len(it.prompt) + it.max_new <= int(cell.config["engine"]["max_seq_len"])


def test_new_mix_cell_and_metric_are_files_alone(tmp_path, monkeypatch):
    root = tmp_path / "chipbench"
    for kind in ("configs", "traffic", "cells"):
        shutil.copytree(harness.HERE / kind, root / kind)
    mix = json.loads((root / "traffic" / "longctx.json").read_text())
    mix.update(sessions=8, contexts=[512, 1024])
    (root / "traffic" / "midctx.json").write_text(json.dumps(mix))
    (root / "cells" / "internlm2_20b_8l.midctx.json").write_text(
        (root / "cells" / "internlm2_20b_8l.longctx.json").read_text())
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    (metrics / "prompt_tokens.py").write_text(
        "def read(run):\n    return sum(len(s.item.prompt) for s in run.win.served)\n")
    import chipbench.metrics

    monkeypatch.setattr(chipbench.metrics, "__path__", list(chipbench.metrics.__path__) + [str(metrics)])
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "internlm2_20b_8l.midctx", "config": "internlm2_20b_8l",
                               "traffic": "midctx", "chips": 1, "why": "mid-length sessions"})
    bench["per_layer"].append({"name": "prompt_tokens", "unit": "tokens", "better": "higher",
                               "source": "host_clock", "layer": "client",
                               "moves": "tokens_per_s", "workloads": ["internlm2_20b_8l.midctx"]})
    cell = harness.load_cell("internlm2_20b_8l.midctx", bench, root)
    t = generators.generate(cell.mix, 5, 20.0, int(cell.config["vocab_size"]), _engine(cell))
    assert {len(i.prompt) for i in t.warm} <= {512, 1024} and len(t.warm) == 8
    assert [m["name"] for m in cell.per_layer if m["name"] == "prompt_tokens"]
    mod = importlib.import_module("chipbench.metrics.prompt_tokens")

    run = SimpleNamespace(win=SimpleNamespace(served=[harness.Served(i, None) for i in t.warm]))
    assert mod.read(run) == sum(len(i.prompt) for i in t.warm)
