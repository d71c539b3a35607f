"""Median of the engine's ``fetch`` phase spans: the serve thread inside
``jax.device_get`` and nothing else, what is left of the programs in flight
and the way back."""
from harness.spans import span_median

read = span_median("fetch")
