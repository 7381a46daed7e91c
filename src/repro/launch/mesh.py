"""Mesh construction for the production pod slice.

``make_production_mesh`` is a FUNCTION (not a module constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds the mesh.
"""
from __future__ import annotations

from typing import Tuple

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_smoke_mesh():
    """1x1 mesh with the production axis names for CPU tests."""
    return _mk((1, 1), ("data", "model"))


def manual_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes handled manually by shard_map (everything
    except the auto TP axis)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def data_axes(mesh) -> Tuple[str, ...]:
    """FSDP axes: the intra-pod data axes (excludes ``pod``)."""
    return tuple(a for a in mesh.axis_names
                 if a != "model" and a != "pod")


def pod_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a == "pod")


def axis_size(mesh, axes) -> int:
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s
