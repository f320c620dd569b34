"""Median of the spans of steps that close a window (``stats.windows``
advances): placement, migrations and the decode step, through a sync."""

import numpy as np


def read(run):
    xs = [dt for dt, closed, synced in run.win.steps if synced and closed]
    return float(np.median(xs) * 1e3) if xs else None
