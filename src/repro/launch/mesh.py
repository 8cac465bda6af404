"""Production mesh construction (kept free of import-time device access)."""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # Auto axes: sharding constraints and GSPMD propagation apply to them,
    # which JAX's default Explicit axes refuse.
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic rescale targets, tests)."""
    return _auto_mesh(shape, axes)
