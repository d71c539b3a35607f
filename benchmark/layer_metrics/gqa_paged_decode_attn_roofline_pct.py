"""The paged decode attention over grouped-query heads (kernels
``gqa_paged_decode_attn_full`` and ``gqa_paged_decode_attn_window``: one
call site each, the full layer of a period and the scan of its sliding
layers) against its roofline, over the traced stretch: per decode program
fetched in it, the operations and bytes of the cached rows its live lanes
attended (``attn_keys_full``; ``attn_keys_window``, a lane at most the
window) times the layers of that kind, from ``architectures/mellum.py``
``decode_attn_cost``, the larger of the two times; over the two kernels'
seconds in the device trace (a kernel whose operation the reduction did not
keep is left out on both sides).  Entered for ONE configuration
(``CONFIGURATION``).  Nothing where the program records no such counters,
they are another configuration's, or the trace holds no such kernel."""
from harness import roofline
from harness.device import PEAKS

CONFIGURATION = ("mellum", "mellum2-12b-a2.5b-8of28")
KERNEL = "gqa_paged_decode_attn"


def read(observed):
    trace = observed.get("trace")
    progs = [p for p in roofline.in_stretch(
        roofline.programs(observed.get("spans")), trace)
        if p["group"] == "decode" and "attn_keys_full" in p]
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    if not all(arch.counters_are_of(config, p) for p in progs):
        return None
    layers = dict(zip(("full", "window"), arch.layers_of(config)))
    least = dict.fromkeys(layers, 0.0)
    for p in progs:
        for group, n in layers.items():
            flops, moved = arch.decode_attn_cost(
                config, keys=p[f"attn_keys_{group}"])
            least[group] += roofline.least_seconds(
                n * flops, n * moved, PEAKS["TPU v5 lite"])
    return roofline.share_pct(trace, [
        (f"{KERNEL}_{group}", "", seconds)
        for group, seconds in least.items()])
