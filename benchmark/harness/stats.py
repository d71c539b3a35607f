"""Percentiles as the benchmark reports them: nearest rank, on the values
as measured."""
import math


def percentile(values, q):
    """Nearest-rank ``q`` (0..1) of ``values``; None when empty."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[rank - 1]


def median(values):
    return percentile(values, 0.5)
