"""A replayed set of long sessions, one per slot, that decode for the whole
window (document and agent traffic holding long contexts).

Mix keys: ``sessions``; ``contexts`` (context lengths, drawn uniformly);
``headroom_tokens`` (kept free below ``max_seq_len``);
``warm_windows`` (window boundaries the set-up passes);
``warm_tail_steps`` (decode steps after the last of them, so that the
migration that boundary starts has finished, and the programs it runs are
loaded, before the window opens); ``schedule_seed``. Each session decodes
until the configuration's ``max_seq_len`` would be reached, which no window
reaches.
"""

from __future__ import annotations

import numpy as np

from chipbench.generators import Traffic, Item, prompt_ids


def contexts(mix: dict) -> np.ndarray:
    choices = np.asarray(mix["contexts"], np.int64)
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    return rng.choice(choices, size=int(mix["sessions"]))


def generate(mix: dict, seed: int, seconds: float, vocab: int, engine: dict) -> Traffic:
    ctx = contexts(mix)
    limit = int(engine["max_seq_len"]) - int(mix["headroom_tokens"])
    warm = [Item(0.0, prompt_ids(seed, i, int(n), vocab), limit - int(n))
            for i, n in enumerate(ctx)]
    return Traffic(warm=warm, timed=[],
                   warm_steps=int(mix["warm_windows"]) * int(engine["window_steps"]) + 1
                   + int(mix["warm_tail_steps"]))
