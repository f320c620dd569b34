"""Traffic generators, one module per ``kind`` of ``traffic/<mix>.json``.

Every generator returns a ``Traffic``: the requests the set-up serves
(admitted as slots free, no due times) and the requests due in the timed
window (due times in seconds after it opens). Sizes, their order and the
arrival times come from the mix's own ``schedule_seed``, so every run seed
gets the same work, a fixed replayed schedule; the run seed draws the token
ids (and the weights).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    due_s: float  # seconds after the window opens (0 for set-up items)
    prompt: np.ndarray  # int32 token ids
    max_new: int


@dataclasses.dataclass(frozen=True)
class Traffic:
    warm: List[Item]  # served in set-up, admitted in order as slots free
    timed: List[Item]  # open-loop arrivals inside the window, by due time
    warm_steps: int  # decode steps the set-up runs at least


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, index])
    return rng.integers(1, vocab, size=length).astype(np.int32)


def generate(mix: dict, seed: int, seconds: float, vocab: int, engine: dict) -> Traffic:
    mod = importlib.import_module(f"chipbench.generators.{mix['kind']}")
    return mod.generate(mix, seed, seconds, vocab, engine)
