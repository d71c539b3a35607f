"""Median of the engine's ``run_prefill_decode`` spans under which a lane
decoded (a0 > 0): from the entry of a non-final chunk's dispatch to the
return of the decode tick's fetch, which waits for both programs."""
from harness.stats import median


def read(observed):
    spans = (observed.get("spans") or {}).get("run_prefill_decode") or []
    return median([s["ms"] for s in spans if s["a0"] > 0])
