"""Production meshes.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is an outer data-parallel axis whose collectives cross DCN.

Defined as functions (never module-level constants) so importing this
module does not touch JAX device state.
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_debug_mesh(shape: Tuple[int, ...] = (1, 1), axes=("data", "model")):
    """Small mesh for CPU tests (requires matching host device count)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """All batch-parallel axes (the 'pod' axis is outer data parallelism)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: jax.sharding.Mesh) -> str:
    return "model"
