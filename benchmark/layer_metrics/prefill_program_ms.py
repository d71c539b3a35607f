"""Median of the engine's ``run_prefill`` spans: from the entry of a final
chunk's dispatch, with nothing else in flight, to the return of its fetch.
One prefill program alone (a0 is the chunk's bucket)."""
from harness.stats import median


def read(observed):
    spans = (observed.get("spans") or {}).get("run_prefill") or []
    return median([s["ms"] for s in spans])
