"""Of the held experts a decode program could read (experts held x blocks),
the share that received any row: the engine's ``moe_experts_touched`` over
``moe_expert_slots`` counters of the decode programs, over the window, of a
model that counts zero-compute choices.  The decode program's expert bytes
are this share of the held experts' weights; it moves with how many choices
fell on zero-compute experts."""
from harness.roofline import programs, total


def read(observed):
    progs = [p for p in programs(observed.get("spans"))
             if "moe_zero_rows" in p]
    touched = total(progs, "moe_experts_touched", "decode")
    slots = total(progs, "moe_expert_slots", "decode")
    return 100.0 * touched / slots if touched is not None and slots else None
