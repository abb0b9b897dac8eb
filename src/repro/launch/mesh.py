"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches JAX
device state.  Production target: TPU v5e pods of 256 chips, 16×16
("data", "model"); the multi-pod variant stacks a leading "pod" axis
(2×16×16 = 512 chips) used for cross-pod data parallelism (or pipeline
stages — see distributed/pipeline.py).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh"]


def _make(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes):
    """Small meshes for tests (e.g. (2, 4) on 8 forced host devices)."""
    return _make(shape, axes)
