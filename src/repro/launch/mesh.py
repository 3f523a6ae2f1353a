"""Host mesh construction.

A function, not a module-level constant: importing this module never
touches jax device state, so a process can set its device count (e.g.
XLA_FLAGS=--xla_force_host_platform_device_count) before the first
mesh is built."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """Mesh whose axes are all Auto: the engines place data with
    ``with_sharding_constraint``, which only accepts Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh(data: int = 1):
    """(data, 1) mesh over this host's first `data` devices: 1 for CPU
    tests of the sharded code path, 4 for one four-chip host."""
    return _auto_mesh((data, 1), ("data", "model"), jax.devices()[:data])
