"""Of the experts a decode program could read (experts x layers), the share
that received any row: the engine's ``moe_experts_touched`` over
``moe_expert_slots`` counters of the decode programs of THIS configuration
(``counters_are_of``), over the window.  The decode program's expert bytes
are this share of the experts' weights: with all 64 held and 8 a token, ~30
lanes touch nearly all of them."""
from harness import roofline

CONFIGURATION = ("mellum", "mellum2-12b-a2.5b-8of28")


def read(observed):
    progs = roofline.programs(observed.get("spans"))
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    progs = [p for p in progs if arch.counters_are_of(config, p)]
    touched = roofline.total(progs, "moe_experts_touched", "decode")
    slots = roofline.total(progs, "moe_expert_slots", "decode")
    return 100.0 * touched / slots if touched is not None and slots else None
