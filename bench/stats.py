"""Percentiles and spreads, exactly as the benchmark reports them.

Tails are exact over every sample (no reservoir), by nearest rank: the
p-th percentile of n samples is the ceil(p/100 * n)-th smallest.  A
failed request enters a latency tail as ``math.inf`` -- it missed every
limit -- and :func:`percentile` returns ``inf`` when the rank lands on
one, which the caller then reports as a failed tail.
"""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List


def percentile(values: Iterable[float], p: float) -> float:
    """Nearest-rank percentile; ``nan`` for no samples."""
    xs = sorted(values)
    if not xs:
        return math.nan
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]


def mean(values: Iterable[float]) -> float:
    xs = list(values)
    return sum(xs) / len(xs) if xs else math.nan


def spread(values: List[float]) -> float:
    """Inter-quartile distance over the median, by Python's
    ``statistics.quantiles(values, n=4)`` (the contract's definition)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else math.inf


def union_length(intervals: Iterable[tuple]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
