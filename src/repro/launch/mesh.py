"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single device.
"""
from __future__ import annotations

import jax

from repro.configs.base import MULTI_POD, SINGLE_POD, MeshConfig


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the sharding rules place arrays with with_sharding_constraint,
    # which refuses the Explicit axes jax.make_mesh makes by default
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_host_mesh() -> jax.sharding.Mesh:
    """1-device mesh for smoke runs through the same code path."""
    return _auto_mesh((1, 1), ("data", "model"))
