"""Percentiles and spreads, the arithmetic every metric of the benchmark uses."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100): the smallest value
    with at least ``q`` percent of the samples at or below it.  A missing
    sample (a request that failed or never answered) is ``math.inf`` and
    sorts last."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie above the nearest-rank ``q``-th
    percentile."""
    return n - max(1, math.ceil(q / 100.0 * n))


def spread(values) -> float:
    """Interquartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
