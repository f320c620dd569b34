"""Persistent compile cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
this module sets nothing else. Otherwise the cache lives at one fixed path
inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is part of
the cache key, so it must not move between runs. Entry points call
``enable()`` before their first compile; importing this module changes
nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir  # JAX read it already
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
