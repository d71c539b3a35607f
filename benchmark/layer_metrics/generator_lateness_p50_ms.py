"""How late the benchmark's own generator ran: the median, over the requests
due inside the window, of submit time minus DUE time.  The generator and
the engine's host loop share one thread, so a request is submitted between
two engine steps and the median is about half a step; over a step, the
generator was starved and the rate offered was not the rate stated (the
latencies still count from the due time, so they show it too)."""
from harness.stats import median


def read(observed):
    value = median(observed.get("generator_lateness_s") or [])
    return None if value is None else 1e3 * value
