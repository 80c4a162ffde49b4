"""Order statistics over every sample of a window, and run-to-run spread.

A tail is taken over all samples, never over a reservoir: ``percentile``
sorts the whole list.  ``spread`` is the distance between the first and
third quartile as ``statistics.quantiles(values, n=4)`` gives them, as a
share of the median -- the measure the bounds in ``BENCHMARK.json`` are
set from.
"""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of every value, linear between
    the two nearest ranks (numpy's default rule), over the full sample.
    An empty sample has no percentile."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)                   # a missing sample (inf) sorts last
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    frac = pos - lo
    if frac == 0.0 or math.isinf(xs[lo]):
        return xs[lo]
    if math.isinf(xs[lo + 1]):
        return math.inf
    return xs[lo] + (xs[lo + 1] - xs[lo]) * frac


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median (``statistics.quantiles``
    with n=4, its default exclusive method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def union_length(intervals: Sequence[tuple[float, float]]) -> float:
    """Total length covered by ``[start, end)`` intervals, overlaps
    counted once."""
    total = 0.0
    cur_s = cur_e = None
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
