"""Production meshes. Functions, not module constants — importing this
module must never touch jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the sharding rules place
    arrays through ``with_sharding_constraint``, which ``Explicit``
    axes (``jax.make_mesh``'s default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 (256 chips/pod); 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh(*, multi_pod: bool = False):
    """CI-sized mesh (8 devices) with the same axis names."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
