"""Of the (token, choice) pairs the programs of the window routed, the share
that fell on ZERO-COMPUTE (identity) experts: the engine's ``moe_zero_rows``
over ``moe_routed_rows`` counters (tokens x choices a token, summed over the
blocks) of its prefill and decode programs.  33.3 under even routing over
512 routed and 256 zero-compute choices.  Nothing where the program counts
no such choices (a model without them)."""
from harness.roofline import programs


def read(observed):
    progs = [p for p in programs(observed.get("spans"))
             if "moe_zero_rows" in p and "moe_routed_rows" in p]
    routed = sum(p["moe_routed_rows"] for p in progs)
    return 100.0 * sum(p["moe_zero_rows"] for p in progs) / routed \
        if routed else None
