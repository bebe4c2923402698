"""Percentile, spread and failure arithmetic (plain Python, no numpy magic)."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """q-th percentile (0..100), linear interpolation between order
    statistics; ``inf`` entries (failed requests count as the worst) sort
    last and make the tail infinite when they reach it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]) and pos > lo:
        return math.inf
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values) -> float:
    return float(statistics.median(values))


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` as the contract says."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def latencies_with_failures(latencies_s, n_failed: int):
    """A request that failed, was shed or refused counts as the worst: it
    joins the sample as +inf."""
    return list(latencies_s) + [math.inf] * int(n_failed)
