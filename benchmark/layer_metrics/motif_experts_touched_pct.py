"""Of the (routed layer, held expert) pairs a decode program could read, the
share that received a row, over the window: the engine's
``moe_experts_touched`` over ``moe_expert_slots`` counters of the decode
programs of THIS configuration (``counters_are_of``).  What the decode
program's bytes follow: an expert nobody chose is not read."""
from harness import roofline

CONFIGURATION = ("motif", "motif-3-beta-ep8")


def read(observed):
    progs = roofline.programs(observed.get("spans"))
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    progs = [p for p in progs if arch.counters_are_of(config, p)]
    touched = roofline.total(progs, "moe_experts_touched", "decode")
    slots = roofline.total(progs, "moe_expert_slots", "decode")
    return 100.0 * touched / slots if touched is not None and slots else None
