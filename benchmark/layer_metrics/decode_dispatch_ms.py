"""Median of the engine's ``dispatch`` phase spans with a0 = 0: from the entry
of the call that sends a decode (or verify) program to the entry of its
fetch, the program's arguments handed to the device among it (and the
~0.07 ms of ``_rebind`` and ``_note_program`` after the call returns).  The
serving engine's, not the trainer's (``train_dispatch_ms``)."""
from harness.spans import span_median

read = span_median("dispatch", lambda a0: a0 == 0)
