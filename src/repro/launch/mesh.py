"""Mesh construction.

FUNCTIONS, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh whose axes are all ``Auto``.  The sharding plans pin layouts
    with sharding constraints at layer boundaries and leave the rest to XLA's
    propagation; ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    every gather and contraction would have to name its output sharding."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) chips over ("data", "model").
    Multi-pod: 2 pods × 256 chips over ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
