"""Percentile and spread arithmetic, kept with the benchmark."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks of the sorted values (numpy's default method)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    h = (len(xs) - 1) * q / 100.0
    lo = math.floor(h)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (h - lo) * (xs[hi] - xs[lo])


def samples_beyond(n: int, q: float) -> float:
    """How many of `n` samples lie beyond the q-th percentile."""
    return n * (100.0 - q) / 100.0


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of `statistics.quantiles(values, n=4)`."""
    q1, med, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / med
