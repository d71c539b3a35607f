"""Median, over the requests due inside the window, of first token time
minus DUE time."""
from harness.stats import median


def read(observed):
    return median(observed.get("ttft_s") or [])
