"""Median of the engine's ``step_host_us`` counter, in ms: from one fetch's
return to the next one's entry; the serve thread's time a step that is not
a wait for the device."""
from harness.spans import span_median

read = span_median("step_host_us", of="a0", per=1e3)
