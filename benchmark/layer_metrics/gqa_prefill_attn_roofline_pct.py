"""The rectangle attention of chunked prefill over grouped-query heads
(kernels ``gqa_prefill_attn_full`` and ``gqa_prefill_attn_window``: one call
site each, the full layer of a period and the scan of its sliding layers)
against its roofline, over the traced stretch: per prefill program fetched
in it, operations and bytes of the (query, key) pairs it attended that are
causal AND inside the window (the engine's ``attn_pairs_full`` /
``attn_pairs_window`` counters) times the layers of that kind, from
``architectures/mellum.py`` ``prefill_attn_cost``; over the two kernels'
seconds in the device trace (``harness/roofline.py``; a kernel whose
operation the reduction did not keep is left out on both sides).  Entered
for ONE configuration (``CONFIGURATION``).  Nothing where the program's
counters are not this configuration's or the trace holds no such kernel."""
from harness import roofline
from harness.device import PEAKS

CONFIGURATION = ("mellum", "mellum2-12b-a2.5b-8of28")
KERNEL = "gqa_prefill_attn"


def read(observed):
    trace = observed.get("trace")
    progs = [p for p in roofline.in_stretch(
        roofline.programs(observed.get("spans")), trace)
        if "attn_pairs_full" in p]
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    if not all(arch.counters_are_of(config, p) for p in progs):
        return None
    layers = dict(zip(("full", "window"), arch.layers_of(config)))
    least = dict.fromkeys(layers, 0.0)
    for p in progs:
        queries = int(p["group"].split("_")[1])
        for group, n in layers.items():
            flops, moved = arch.prefill_attn_cost(
                config, pairs=p[f"attn_pairs_{group}"], queries=queries)
            least[group] += roofline.least_seconds(
                n * flops, n * moved, PEAKS["TPU v5 lite"])
    return roofline.share_pct(trace, [
        (f"{KERNEL}_{group}", "", seconds)
        for group, seconds in least.items()])
