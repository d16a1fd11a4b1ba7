"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state.  The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real (single) device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2 pods x 256 = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over host devices (tests / CPU smoke runs)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
