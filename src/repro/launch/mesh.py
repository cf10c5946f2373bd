"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model);
multi-pod: 2x16x16 = 512 chips (pod, data, model).
"""
from __future__ import annotations

import jax


def mesh_with_auto_axes(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis of Auto type."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return mesh_with_auto_axes(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / CPU smoke runs)."""
    n = len(jax.devices())
    data = n // model
    return mesh_with_auto_axes((data, model), ("data", "model"))
