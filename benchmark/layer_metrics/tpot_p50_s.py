"""Median, over the requests due inside the window, of a request's mean gap
between tokens, ``(last - first) / (tokens - 1)``: the middle of what the
cell's judged tail, ``tpot_p95_s``, is the 95th percentile of.  A pause of
the serve thread is felt by the requests then in flight, some 3 % of a
window's, and moves the tail; the median moves with the engine's step alone
(PERF.md, section 6, PR 45), so the two together say which of them a change
moved."""
from harness.stats import median


def read(observed):
    return median(observed.get("tpot_s") or [])
