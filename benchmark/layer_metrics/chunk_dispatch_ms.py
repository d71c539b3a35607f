"""Median of the engine's ``dispatch`` phase spans with a0 > 0 (the bucket):
from the entry of the call that sends a prefill chunk to the decode tick
that follows (or to the chunk's own fetch, where it is final)."""
from harness.spans import span_median

read = span_median("dispatch", lambda a0: a0 > 0)
