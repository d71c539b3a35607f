"""Rows of the busiest held expert over the mean of the held experts, a
routed layer of a prefill chunk, over the window: the engine's
``moe_busiest_scaled_rows`` counter (the busiest expert's rows times the
experts held, summed over the routed layers) over ``moe_held_rows``, prefill
programs of THIS configuration (``counters_are_of``).  1 under even
routing."""
from harness import roofline

CONFIGURATION = ("motif", "motif-3-beta-ep8")


def read(observed):
    progs = roofline.programs(observed.get("spans"))
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    progs = [p for p in progs if arch.counters_are_of(config, p)]
    busiest = roofline.total(progs, "moe_busiest_scaled_rows", "prefill")
    held = roofline.total(progs, "moe_held_rows", "prefill")
    return busiest / held if busiest is not None and held else None
