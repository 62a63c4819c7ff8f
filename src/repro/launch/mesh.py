"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module-level constant)
so importing this module never touches jax device state. The dry-run
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import to get placeholder devices.

Single pod: (16, 16) = (data, model) — 256 chips.
Multi-pod:  (2, 16, 16) = (pod, data, model) — 512 chips; the 'pod'
axis is the DiLoCo axis (slow inter-pod fabric).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis Auto: the programs here place
    shardings with ``shard_map`` and sharding constraints, not with
    explicit-axis types."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int = 1, axis: str = "data"):
    """Small local mesh for tests/examples on CPU devices."""
    return make_mesh((n,), (axis,))
