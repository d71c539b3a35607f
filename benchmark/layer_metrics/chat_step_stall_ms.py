"""Milliseconds of the window spent in engine steps that took over three
times the median step (call to return, the benchmark's clock): what a host
pause, a collector's walk or a stalled fetch adds to the token gaps of the
requests in flight.  0.0 in a window without one; nothing where the driver
recorded no steps."""
from harness.stats import over_medians


def read(observed):
    steps = observed.get("step_s")
    return 1e3 * sum(over_medians(steps)) if steps else None
