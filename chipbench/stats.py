"""Percentiles and rates, on plain lists of numbers."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    the closest ranks (numpy's default): 0 the least, 100 the most."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: int, seconds: float) -> float:
    """Completed work per second of the window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds
