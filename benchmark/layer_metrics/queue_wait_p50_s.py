"""Median time from a request's due time to the start of the engine step
that admitted it (the ``admitted`` event of ``InferenceEngine.step``)."""
from harness.stats import median


def read(observed):
    return median(observed.get("queue_wait_s") or [])
