"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init).

Every mesh is built with ``AxisType.Auto`` axes: the sharding rules
(dist/sharding.py) are written for GSPMD propagation and ``shard_map``,
not for sharding-in-types, which ``jax.make_mesh`` now defaults to.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """An Auto-axis mesh of ``shape`` over ``axes`` on the first
    prod(shape) local devices."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / examples): 1D 'data' mesh."""
    return make_mesh((len(jax.devices()),), ("data",))
