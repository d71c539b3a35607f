"""Median of the engine's ``tokens`` phase spans: from the return of a fetch
to the end of its tick (positions, tokens and the bookkeeping of every
lane)."""
from harness.spans import span_median

read = span_median("tokens")
