"""Production mesh builders.

Kept as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* jax
initializes, and smoke tests must see 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh_auto", "make_production_mesh", "POD_SHAPE",
           "MULTIPOD_SHAPE"]

POD_SHAPE = (16, 16)                 # 256 chips / pod (v5e-256)
MULTIPOD_SHAPE = (2, 16, 16)         # 2 pods = 512 chips


def make_mesh_auto(shape, axes):
    """``jax.make_mesh`` with Auto axis types: we shard via
    in_shardings + constraints (GSPMD), not the explicit-sharding API."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)
