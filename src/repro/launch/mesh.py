"""Device mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A device mesh whose axes are all Auto.  `jax.make_mesh` defaults to
    Explicit axes, under which array shardings become part of the type and
    ops whose sharding the model leaves to XLA (reshapes of sharded
    codes, cache updates) raise `ShardingTypeError`; this repo places
    arrays with `device_put` / sharding constraints and lets XLA
    propagate the rest, which is what Auto axes mean."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for subprocess tests (8 forced host devices)."""
    return make_mesh(shape, axes)
