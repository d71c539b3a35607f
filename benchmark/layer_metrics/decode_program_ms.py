"""Median of the engine's ``run_decode`` spans: from the entry of the decode
dispatch, with nothing else in flight, to the return of its token fetch.
The decode program alone, at whatever the step held."""
from harness.stats import median


def read(observed):
    spans = (observed.get("spans") or {}).get("run_decode") or []
    return median([s["ms"] for s in spans if s["a0"] > 0])
