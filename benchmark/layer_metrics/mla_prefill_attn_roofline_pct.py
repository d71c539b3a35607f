"""The rectangle attention of chunked prefill (kernel ``mla_prefill_attn``)
against its roofline, over the traced stretch: per prefill program fetched
in it, operations and bytes of the CAUSAL (query, key) pairs it attended
(the engine's ``attn_pairs`` counter) times the layers, from
``architectures/mistral4.py``; over the kernel's seconds in the device trace
(``harness/roofline.py``).  A bucket is its own program and its own
operation in the trace (told apart by the query rows in its shape); one the
trace reduction did not keep is left out on both sides.  Entered for ONE
configuration (``CONFIGURATION``; another enters a reader of its own).
Nothing where the program's counters are not this configuration's (GPT-2
records the pairs too, and no routed counters beside them) or the trace
holds no such kernel."""
from harness import roofline
from harness.device import PEAKS

CONFIGURATION = ("mistral4", "mistral-small-4-ep4")
KERNEL = "mla_prefill_attn"
MIN_ROWS = 128      # the kernel pads a smaller bucket's queries to a tile


def read(observed):
    trace = observed.get("trace")
    progs = [p for p in roofline.in_stretch(
        roofline.programs(observed.get("spans")), trace)
        if "attn_pairs" in p]
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    if not all(arch.counters_are_of(config, p) for p in progs):
        return None
    layers, heads = config["num_hidden_layers"], \
        config["num_attention_heads"]
    least = {}
    for p in progs:
        rows = max(int(p["group"].split("_")[1]), MIN_ROWS)
        flops, moved = arch.prefill_attn_cost(config, pairs=p["attn_pairs"])
        least[rows] = least.get(rows, 0.0) + roofline.least_seconds(
            layers * flops, layers * moved, PEAKS["TPU v5 lite"])
    return roofline.share_pct(trace, [
        (KERNEL, f"[{heads},{rows},{config['v_head_dim']}]", seconds)
        for rows, seconds in least.items()])
