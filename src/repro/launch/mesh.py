"""Production meshes.

Kept as FUNCTIONS so importing this module never touches jax device state
(the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over the actually-available devices (tests / examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
