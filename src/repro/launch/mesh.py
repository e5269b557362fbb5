"""Mesh construction.

Importing this module never touches jax device state; meshes are built by
functions, from ``jax.devices()``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """Mesh of ``shape`` over named ``axes``; every axis is ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
