"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets the placeholder device count
before jax initializes, and tests import this with 1 real device.

Production topology (TPU v5e): 16×16 = 256 chips per pod; the multi-pod
mesh adds a leading "pod" axis (2 pods = 512 chips) used for pure data
parallelism across the DCN boundary (DESIGN.md §4).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes):
    """Generic helper (tests, examples, distributed DBSCAN)."""
    return _mk(shape, axes)
