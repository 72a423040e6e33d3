"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches JAX device state — the dry-run must set XLA_FLAGS before any
device initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the scale-out 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices exist locally, as a (data, model) mesh (model=1)."""
    n = len(jax.devices())
    return jax.make_mesh(
        (n, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
