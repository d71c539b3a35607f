"""Of the (token, chosen expert) pairs the prefill chunks of the window
routed, the share that went to experts this chip holds: the engine's
``moe_held_rows`` over ``moe_routed_rows`` counters of the prefill programs
of THIS configuration (``counters_are_of``).  12.5 under even routing over a
held eighth."""
from harness import roofline

CONFIGURATION = ("motif", "motif-3-beta-ep8")


def read(observed):
    progs = roofline.programs(observed.get("spans"))
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    progs = [p for p in progs if arch.counters_are_of(config, p)]
    held = roofline.total(progs, "moe_held_rows", "prefill")
    routed = roofline.total(progs, "moe_routed_rows", "prefill")
    return 100.0 * held / routed if held is not None and routed else None
