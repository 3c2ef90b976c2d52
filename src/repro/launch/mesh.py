"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
does not touch jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before *any* jax
initialisation, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: shardings propagate through the
    partitioner, so ``jit`` and ``shard_map`` over this mesh need no
    ``jax.set_mesh`` context (``jax.make_mesh`` defaults to Explicit axes,
    which type every array by its sharding)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (v5e-256); the multi-pod mesh adds a leading
    2-pod data-parallel axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices the host actually has (tests)."""
    return make_mesh((data, model), ("data", "model"))


MESH_NAMES = {"pod": False, "multipod": True}
