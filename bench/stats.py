"""Order statistics the benchmark reports, kept with the benchmark.

``percentile`` is a copy of ``repro.core.accounting.percentile`` (linear
interpolation between closest ranks, numpy's default ``method="linear"``),
so that a change to the program cannot move the yardstick.
"""

from __future__ import annotations

from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile, ``q`` in [0, 100]."""
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("percentile of no values")
    if len(vals) == 1:
        return vals[0]
    q = min(max(float(q), 0.0), 100.0)
    pos = (len(vals) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    frac = pos - lo
    return vals[lo] * (1.0 - frac) + vals[hi] * frac


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)
