"""Device-mesh construction for the sharded DBSCAN driver.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — tests and the CPU rehearsals fix the device
count (``--xla_force_host_platform_device_count``) before jax initializes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Mesh of ``shape`` over ``axes`` (tests, examples, distributed DBSCAN)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
