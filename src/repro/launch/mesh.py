"""Mesh construction.

`make_production_mesh` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and smoke tests must keep seeing 1 device.

Production target: TPU v5e pods, 256 chips each.
  single-pod: (16, 16)      axes ("data", "model")
  multi-pod:  (2, 16, 16)   axes ("pod", "data", "model")
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["AxisType", "make_production_mesh", "make_test_mesh",
           "batch_axes", "dp_size"]


def _mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (works with 1 real device when shape=(1,1))."""
    return _mesh(shape, axes)


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def dp_size(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
