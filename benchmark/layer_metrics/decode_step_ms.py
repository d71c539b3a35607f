"""Median of the engine's ``decode_step`` host spans that carried at least
one lane (each ends in the step's one token fetch)."""
from harness.stats import median


def read(observed):
    spans = (observed.get("spans") or {}).get("decode_step") or []
    return median([s["ms"] for s in spans if s["a0"] > 0])
