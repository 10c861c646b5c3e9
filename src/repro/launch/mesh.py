"""Mesh construction. A FUNCTION (not module-level constant) so importing
never touches jax device state."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(axes):
    # Auto axes: GSPMD propagates shardings from the params and the
    # with_sharding_constraint calls. jax.make_mesh defaults to Explicit
    # axes, which sharding-in-types code would need instead.
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    """Assignment mesh: 16x16 single pod (256 chips) or 2x16x16 (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh from the first prod(shape) available devices
    (used by reduced-device tests, e.g. 8 host devices -> (2,2,2))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(axes))
