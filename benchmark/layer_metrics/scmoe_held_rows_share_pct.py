"""Of the (token, choice) pairs the programs of the window put on ROUTED
experts (all pairs less those on zero-compute experts), the share that went
to experts this chip holds: the engine's ``moe_held_rows`` over
``moe_routed_rows - moe_zero_rows`` counters of its prefill and decode
programs.  3.125 under even routing over a held thirty-second.  Nothing
where the program counts no zero-compute choices (a model without them)."""
from harness.roofline import programs


def read(observed):
    progs = [p for p in programs(observed.get("spans"))
             if "moe_zero_rows" in p and "moe_held_rows" in p]
    on_experts = sum(p["moe_routed_rows"] - p["moe_zero_rows"] for p in progs)
    return 100.0 * sum(p["moe_held_rows"] for p in progs) / on_experts \
        if on_experts else None
