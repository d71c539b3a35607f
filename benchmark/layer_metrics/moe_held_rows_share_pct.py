"""Of the (token, chosen expert) pairs the prefill chunks of the window
routed, the share that went to experts this chip holds: the engine's
``moe_held_rows`` over ``moe_routed_rows`` counters of its prefill programs
(tokens x experts a token, summed over the layers).  25 under even routing
over a held quarter."""
from harness.roofline import programs, total


def read(observed):
    progs = programs(observed.get("spans"))
    held = total(progs, "moe_held_rows", "prefill")
    routed = total(progs, "moe_routed_rows", "prefill")
    return 100.0 * held / routed if held is not None and routed else None
