"""99th percentile of every gap between consecutive tokens of a request,
both tokens emitted inside the window."""

import numpy as np


def read(run):
    win = run.win
    gaps = []
    for s in win.served:
        t = [x for x in s.times if win.t0 <= x <= win.t_end]
        gaps.extend(np.diff(t))
    if not gaps:
        return None
    return float(np.percentile(gaps, 99) * 1e3)
