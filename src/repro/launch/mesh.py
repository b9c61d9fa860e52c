"""Production mesh construction.

A *function*, not a module-level constant — importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices.
"""
from __future__ import annotations

from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(
        shape, axes,
        axis_types=(AxisType.Auto,) * len(axes),
    )


def make_worker_mesh(tp: int = 4):
    """Mesh for one serving worker (TP-only sub-slice)."""
    return make_mesh((1, tp), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)


def make_host_mesh():
    """Single-device mesh for CPU tests/examples."""
    return make_mesh((1, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
