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


BEYOND_THE_RANK = 10    # values a judged percentile leaves past its rank
STALL_MEDIANS = 3.0     # a step of over so many median steps is a stall


def least_samples(q):
    """The fewest values whose nearest-rank ``q`` leaves
    ``BEYOND_THE_RANK`` values past the rank (the ``choosing-metrics``
    guide asks for ten): 200 for a p95.  A tail over fewer is set by which
    few requests fell there."""
    n = 1
    while n - max(1, math.ceil(q * n)) < BEYOND_THE_RANK:
        n += 1
    return n


def judged_percentile(values, q):
    """``percentile(values, q)`` where at least ``least_samples(q)`` values
    were counted; None, which a run does not report as a judged metric
    (``run.py``: no result line), where there were fewer."""
    if len(values) < least_samples(q):
        return None
    return percentile(values, q)


def over_medians(values):
    """The values over ``STALL_MEDIANS`` x the median: of step times, the
    stalls."""
    limit = STALL_MEDIANS * median(values) if values else 0.0
    return [v for v in values if v > limit]
