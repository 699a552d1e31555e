"""Mesh construction.

Meshes are built by FUNCTIONS, never at import time: smoke tests must see 1
device while the dry-run sees 512 placeholder devices via XLA_FLAGS.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """Arbitrary mesh (tests, elastic re-scale, production grids)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(int(s) for s in shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The dry-run/production grid: 256 chips per pod, 16-way model axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
