"""The one place that names JAX APIs which moved between versions.

The repo targets JAX 0.9: ``jax.shard_map`` (``check_vma=``),
``jax.lax.axis_size`` and ``jax.experimental.pallas.tpu.CompilerParams``.
Kernel and model code imports these from ``repro.compat`` and never probes
``jax.*`` attributes itself (the ``no-jax-probe-outside-compat`` lint
rule), so the next API move is a one-file fix.
"""

from __future__ import annotations

from typing import Any

import jax

__all__ = [
    "axis_size",
    "shard_map",
    "tpu_compiler_params",
]

axis_size = jax.lax.axis_size
shard_map = jax.shard_map


def tpu_compiler_params(**kwargs: Any):
    """``pltpu.CompilerParams(**kwargs)``.  Imported on first use, so the
    consumers of ``shard_map``/``axis_size`` never load Pallas."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)
