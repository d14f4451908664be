"""Production meshes (brief: MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module constant — importing this module never touches
jax device state (device count is locked at first jax init, and only
dryrun.py sets the 512-device XLA flag).
"""
from __future__ import annotations

import jax

# the cells are written for GSPMD propagation; jax.make_mesh defaults to
# Explicit axes
_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh over forced host devices (tests)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(_AUTO, _AUTO))


def mesh_devices(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
