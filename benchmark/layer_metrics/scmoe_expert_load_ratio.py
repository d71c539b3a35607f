"""Rows of the busiest held expert over the mean of the held experts, a
block of a prefill chunk, over the window: the engine's
``moe_busiest_scaled_rows`` counter (the busiest expert's rows times the
experts held, summed over blocks) over ``moe_held_rows``, prefill programs
of a model that counts zero-compute choices.  1 under even routing."""
from harness.roofline import programs, total


def read(observed):
    progs = [p for p in programs(observed.get("spans"))
             if "moe_zero_rows" in p]
    busiest = total(progs, "moe_busiest_scaled_rows", "prefill")
    held = total(progs, "moe_held_rows", "prefill")
    return busiest / held if busiest is not None and held else None
