"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — ``pod`` is a
second data-parallel axis crossing the inter-pod (DCN) boundary.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run pins the fake-device count before any init).
"""

from __future__ import annotations

import jax
import jax.sharding as jsh


def make_mesh(shape, axes, devices=None):
    """Mesh over ``devices`` (default: the first prod(shape) devices)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(jsh.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_abstract_mesh(shape, axes):
    """Device-free mesh (spec logic only needs axis sizes, not devices)."""
    return jsh.AbstractMesh(tuple(shape), tuple(axes))
