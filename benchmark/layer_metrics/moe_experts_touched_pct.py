"""Of the held experts a decode program could read (experts held x layers),
the share that received any row: the engine's ``moe_experts_touched`` over
``moe_expert_slots`` counters of its decode programs over the window.  The
decode program's expert bytes are this share of the held experts'
weights."""
from harness.roofline import programs, total


def read(observed):
    progs = programs(observed.get("spans"))
    touched = total(progs, "moe_experts_touched", "decode")
    slots = total(progs, "moe_expert_slots", "decode")
    return 100.0 * touched / slots if touched is not None and slots else None
