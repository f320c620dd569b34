"""Memory-TCO savings of the live KV pages, priced where they physically are.

Each page stores K and V for ``page_tokens`` tokens of one layer. Its bytes
at each placement level follow the payload layout the cache writes: a
``bits``-wide payload of ``T x KV x hd`` values plus one f32 scale per
token and head (none at level 0, the bf16 baseline), for K and for V. The
price per byte of each medium is in ``tco_costs.json``. Savings are
``100 * (1 - cost / cost_if_all_bf16_in_HBM)`` over the live pages.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

import numpy as np

COSTS_FILE = Path(__file__).resolve().parent / "tco_costs.json"


def load_costs() -> dict:
    return json.loads(COSTS_FILE.read_text())


def level_bytes(costs: dict, page_tokens: int, kv_heads: int, head_dim: int) -> np.ndarray:
    """Stored bytes of one page (K and V) at each level, index = level."""
    levels = costs["levels"]
    out = np.zeros(len(levels), np.float64)
    vals = page_tokens * kv_heads * head_dim
    for key, lv in levels.items():
        payload = vals * lv["bits"] // 8
        scales = 0 if lv["bits"] == 16 else page_tokens * kv_heads * costs["scale_bytes"]
        out[int(key)] = 2 * (payload + scales)
    return out


def level_usd(costs: dict, page_tokens: int, kv_heads: int, head_dim: int) -> np.ndarray:
    per_byte = {m: usd / 1024**3 for m, usd in costs["usd_per_gb"].items()}
    media = [costs["levels"][str(i)]["media"] for i in range(len(costs["levels"]))]
    nbytes = level_bytes(costs, page_tokens, kv_heads, head_dim)
    return nbytes * np.array([per_byte[m] for m in media])


def savings_pct(physical: np.ndarray, usd: np.ndarray) -> Optional[float]:
    """Savings over the live pages of a placement vector (0 = no page).
    Pages in flight between tiers (level -1) are left out; None when no
    page lives."""
    phys = np.asarray(physical)
    live = phys[(phys > 0)]
    if live.size == 0:
        return None
    cost = float(usd[live].sum())
    full = float(usd[0]) * live.size
    return 100.0 * (full - cost) / full
