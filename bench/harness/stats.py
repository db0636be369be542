"""Statistics the metric readers share: percentiles and spreads.

A percentile is taken over every sample given, with linear interpolation
between the two nearest ranks (NumPy's default), so the median of an even
count is the mean of the middle two.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """The ``p``-th percentile (0..100) of ``values``; None when empty."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile {p} outside 0..100")
    h = (len(xs) - 1) * p / 100.0
    lo = math.floor(h)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (h - lo) * (xs[hi] - xs[lo])


def mean(values: Sequence[float]) -> Optional[float]:
    xs = [float(v) for v in values]
    return sum(xs) / len(xs) if xs else None


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, the quartiles as ``statistics.quantiles(values, n=4)`` gives
    them."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
