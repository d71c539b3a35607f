"""Host time of one ``engine.train_batch`` call up to its return (before
the device has finished): median over the steps of the window, from the
benchmark's own clock round the call."""
from harness.stats import median


def read(observed):
    return median(observed.get("dispatch_ms") or [])
