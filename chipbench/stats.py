"""The benchmark's own arithmetic on samples (no NumPy: what a reviewer can
check by hand)."""
from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest order statistics; None of nothing."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def gaps(times):
    """Distances between successive instants of one request's tokens."""
    return [b - a for a, b in zip(times, times[1:])]


def iqr_share(values):
    """Distance between the first and third quartile as a share of the
    median, as the contract measures a spread."""
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("inf")
