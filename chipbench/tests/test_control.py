"""The control of ``correct``: the float32 reference one precision below
the program, read on the same prompts, served tokens and held pages as the
program, at a CPU size (the longctx cell's head size, grouping and 8 layers
with few heads, 64-step windows). With every matrix product in
float8_e4m3 its widest logit gap, and with each KV page held one codec
lower than the program holds it its worst page error, have to exceed the
cell's limits on every seed, and the program's own readings."""

import importlib

import jax
import pytest

from chipbench import harness
from chipbench.tests.smoke import smoke_cell

CELL = "internlm2_20b_8l.longctx"


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(seed):
    cell = smoke_cell(CELL, window_steps=64, layers=8)
    run, _, params = harness.measure(cell, seed, 3.0, False, jax.devices()[0], None)
    reference = importlib.import_module(f"chipbench.references.{cell.config['reference']}")
    control, _ = harness.logit_gaps(reference, params, run.arch, run.sample, fp8=True)
    limit = cell.limits["max_logit_gap"]
    assert control.size == run.gaps.size >= cell.limits["min_compared_tokens"]
    assert control.max() > limit
    assert control.max() > run.gaps.max()
    pages = run.pages
    assert pages.pages >= cell.limits["min_kv_pages"] and pages.unmatched == 0
    assert pages.control_rel_err > cell.limits["max_kv_page_rel_err"] > pages.rel_err
