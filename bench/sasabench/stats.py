"""Percentile arithmetic of the benchmark (kept here, not taken from the
program or from numpy's defaults, so that it cannot drift)."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (rank ``q/100 * (n-1)``).  ``inf`` entries, which
    stand for requests that failed or never came, sort last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = q / 100.0 * (len(xs) - 1)
    lo, hi = math.floor(rank), math.ceil(rank)
    if lo == hi or xs[lo] == xs[hi]:
        return float(xs[lo])
    if math.isinf(xs[hi]):
        return math.inf
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))
