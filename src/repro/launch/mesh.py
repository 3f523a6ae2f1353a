"""Production mesh construction.

A function, not a module-level constant: importing this module never
touches jax device state (required so smoke tests see 1 CPU device while
the dry-run sees 512 placeholder devices)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """Mesh whose axes are all Auto: the engines place data with
    ``with_sharding_constraint``, which only accepts Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = one v5e pod; (2,16,16) = two pods, 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1):
    """(data, 1) mesh over this host's first `data` devices: 1 for CPU
    tests of the sharded code path, 4 for one four-chip host."""
    return _auto_mesh((data, 1), ("data", "model"), jax.devices()[:data])
