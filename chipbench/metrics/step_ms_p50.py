"""Median of decode-step spans (``step`` through a sync of the cache state)
over the steps that close no window."""

import numpy as np


def read(run):
    xs = [dt for dt, closed, synced in run.win.steps if synced and not closed]
    return float(np.median(xs) * 1e3) if xs else None
