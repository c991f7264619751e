"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets the 512-device flag before any
jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes. The model code places
    activations with ``with_sharding_constraint``, which refuses the
    ``Explicit`` axes ``jax.make_mesh`` makes by default."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes))
