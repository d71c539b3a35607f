"""Median of the engine's ``prefill_tick`` host spans that dispatched a
chunk (a tick with nothing to admit lasts microseconds and is left out)."""
from harness.stats import median

DISPATCHED_MS = 0.2


def read(observed):
    spans = (observed.get("spans") or {}).get("prefill_tick") or []
    return median([s["ms"] for s in spans if s["ms"] > DISPATCHED_MS])
