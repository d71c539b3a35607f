"""Median of the engine's ``step_end`` phase spans: from the end of the
decode tick to the end of ``serving_step`` (the journal's commit, pool and
queue metrics, the step's report)."""
from harness.spans import span_median

read = span_median("step_end")
