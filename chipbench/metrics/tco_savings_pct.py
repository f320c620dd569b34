"""Memory-TCO savings of the live KV pages against all of them as bf16 in
HBM, priced where each page physically is (``chipbench/tco.py``), averaged
over the window boundaries inside the window (sampled just before each)."""

import numpy as np


def read(run):
    samples = run.win.tco_samples
    return float(np.mean(samples)) if samples else None
