"""Readings that the limits of ``correct`` are set from, on the chip.

For each seed, in one process: the cell's set-up and a short window at its
own load through the timed path, then the program's readings (the widest
logit gap of the served tokens against the float32 reference, and the worst
error of a KV page it holds) and the control's on the same prompts, tokens
and pages: the widest gap of the tokens the float8 reference ranks first,
and the worst error of the reference's pages held one codec lower than the
program holds them. ``run.py`` never runs the control.

    python3 chipbench/measure_limits.py --workload <cell> --seconds <s> --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    opened = harness.open_chip(cell.chips)
    if opened is None:
        return 2
    dev = opened[0][0]
    import numpy as np

    reference = importlib.import_module(f"chipbench.references.{cell.config['reference']}")
    for seed in args.seeds:
        t0 = time.perf_counter()
        run, c, params = harness.measure(cell, seed, args.seconds, False, dev, None)
        t1 = time.perf_counter()
        ctrl, _ = harness.logit_gaps(reference, params, run.arch, run.sample, fp8=True)
        pages = run.pages
        print(json.dumps({
            "seed": seed, "program_max_gap": float(run.gaps.max()),
            "program_gap_p99": float(np.percentile(run.gaps, 99)),
            "program_changed_share": float((run.gaps > 0).mean()),
            "control_max_gap": float(ctrl.max()),
            "control_gap_p99": float(np.percentile(ctrl, 99)),
            "control_changed_share": float((ctrl > 0).mean()),
            "program_kv_page_rel_err": pages.rel_err if pages else None,
            "control_kv_page_rel_err": pages.control_rel_err if pages else None,
            "kv_pages_by_bits": pages.by_bits if pages else None,
            "compared_tokens": int(run.gaps.size), "checks": c,
            "requests_compared": len(run.sample), "run_s": t1 - t0,
            "control_s": time.perf_counter() - t1,
            "memory_peak_bytes": run.device["memory_peak_bytes"],
        }), flush=True)
        del run, params, ctrl
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
