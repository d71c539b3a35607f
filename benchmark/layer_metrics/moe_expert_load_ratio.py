"""Rows of the busiest held expert over the mean of the held experts, a
layer of a prefill chunk, over the window: the engine's
``moe_busiest_scaled_rows`` counter (the busiest expert's rows times the
experts held, summed over layers) over ``moe_held_rows``, prefill programs.
1 under even routing."""
from harness.roofline import programs, total


def read(observed):
    progs = programs(observed.get("spans"))
    busiest = total(progs, "moe_busiest_scaled_rows", "prefill")
    held = total(progs, "moe_held_rows", "prefill")
    return busiest / held if busiest is not None and held else None
