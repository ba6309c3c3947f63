"""Percentiles from raw samples, and the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics


def percentile(samples, p: float) -> float:
    """The p-th percentile (0 < p < 100) of raw samples, linear between
    the two nearest order statistics (NumPy's default rule)."""
    s = sorted(samples)
    if not s:
        raise ValueError("no samples")
    k = (len(s) - 1) * p / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def median(samples) -> float:
    return percentile(samples, 50.0)


def highest_percentile(n: int, beyond: int = 10) -> float:
    """The highest percentile that has at least ``beyond`` samples above
    it, of the usual ladder; 50 when even p90 has not."""
    for p in (99.9, 99.0, 95.0, 90.0):
        if n * (100.0 - p) / 100.0 >= beyond - 1e-9:
            return p
    return 50.0


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median: the spread the contract sets bounds from."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
