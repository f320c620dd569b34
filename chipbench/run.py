"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared for ``correct`` beside its limit, also printed as the last
lines of standard error.

The persistent compile cache is kept at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    return ap.parse_args(argv)


def result_line(run, c: dict, trace: bool) -> dict:
    from chipbench import harness

    win = run.win
    out = {
        "correct": harness.passed(c),
        "attempted": sum(1 for s in win.served),
        "failed": 0,
        "metrics": harness.metric_values(run, trace),
        "device": dict(run.device),
    }
    if trace and run.trace:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in c.items()}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("--seed must be non-negative", file=sys.stderr)
        return 2
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    opened = harness.open_chip(cell.chips)
    if opened is None:
        return 2
    devices, peaks = opened
    run, c, _ = harness.measure(cell, args.seed, args.seconds, bool(args.trace), devices[0],
                                peaks, keep_trace=args.keep_trace, t_start=T_START)
    line = result_line(run, c, bool(args.trace))
    steps = sorted(s[0] for s in run.win.steps)
    if steps:
        print(f"window: {len(steps)} steps in {run.win.seconds:.3f} s, median "
              f"{1e3 * steps[len(steps) // 2]:.1f} ms, longest {1e3 * steps[-1]:.1f} ms; "
              f"{run.win.compiles} programs compiled or loaded", file=sys.stderr)
    for name, v in c.items():
        print(f"check {name}: {v['value']} ({v['holds']} limit {v['limit']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
