"""Median of the engine's ``tables`` phase spans with a0 > 0 (the lanes): a
decode tick from its entry to its dispatch (pages grown, a table row a
lane, the program's arguments); a0 = 0 is a tick with no lane to decode."""
from harness.spans import span_median

read = span_median("tables", lambda a0: a0 > 0)
