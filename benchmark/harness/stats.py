"""Percentile and spread arithmetic, kept with the benchmark."""

from __future__ import annotations

import math
import statistics


def percentile(samples, q: float) -> float:
    """The q-quantile (0..1) of `samples`, linear between order
    statistics (numpy's default).  Raises on an empty sample: a metric
    with nothing under it is not reported as 0."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile {q} outside 0..1")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def supported_tail(n: int) -> float:
    """The highest of 0.5/0.9/0.95/0.99 that leaves at least ten samples
    beyond it (choosing-metrics guide, section 1)."""
    best = 0.5
    for q in (0.9, 0.95, 0.99):
        if round(n * (1.0 - q), 6) >= 10:
            best = q
    return best


def iqr_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — the spread the bounds are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
