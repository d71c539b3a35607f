"""The routed experts' grouped matmuls (kernels
``moe_grouped_matmul_<prefill|decode>_<up|down>``) against their roofline,
over the traced stretch: per program fetched in it and per call, the
operations of the rows it routed to held experts and the bytes of the
experts its counter says were TOUCHED (not all held), from
``architectures/longcat_flash.py``, the larger of the two times; over the
kernels' seconds in the device trace (``harness/roofline.py``; a call whose
operation the reduction did not keep is left out on both sides).  Entered
for ONE configuration (``CONFIGURATION``: its sizes are what the costs are
counted at).  Nothing where the program records no such counters, its
counters are another configuration's, or the trace holds no such kernel."""
from harness import roofline
from harness.device import PEAKS

CONFIGURATION = ("longcat_flash", "longcat-flash-chat-ep32")
KERNEL = "moe_grouped_matmul"


def read(observed):
    trace = observed.get("trace")
    progs = [p for p in roofline.in_stretch(
        roofline.programs(observed.get("spans")), trace)
        if "moe_held_rows" in p]
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    if not all(arch.counters_are_of(config, p) for p in progs):
        return None
    least = {}
    for p in progs:
        for call in ("up", "down"):
            kernel = f"{KERNEL}_{p['group'].split('_')[0]}_{call}"
            least[kernel] = least.get(kernel, 0.0) \
                + roofline.least_seconds(*arch.grouped_matmul_cost(
                    config, held_rows=p["moe_held_rows"],
                    experts_touched=p["moe_experts_touched"], call=call),
                    PEAKS["TPU v5 lite"])
    return roofline.share_pct(trace, [(kernel, "", seconds)
                                      for kernel, seconds in least.items()])
