"""The routed experts' grouped matmuls (kernels
``moe_grouped_matmul_<prefill|decode>_<up|down>``) against their roofline,
over the traced stretch: per program fetched in it and per call, the
operations of the rows it routed and the bytes of the experts its counter
says were TOUCHED, from ``architectures/mellum.py``, the larger of the two
times; over the kernels' seconds in the device trace
(``harness/roofline.py``; a call whose operation the reduction did not keep
is left out on both sides).  A period's sliding layers are one traced layer
and its full layer another, so each kernel has TWO call sites of one name:
the least time is counted over all layers and scaled by the call sites the
reduction kept over the two there are (as
``paged_latent_decode_attn_roofline_pct`` does).  Entered for ONE
configuration (``CONFIGURATION``).  Nothing where the program records no
such counters, they are another configuration's, or the trace holds no such
kernel."""
from harness import roofline
from harness.device import PEAKS

CONFIGURATION = ("mellum", "mellum2-12b-a2.5b-8of28")
KERNEL = "moe_grouped_matmul"
CALL_SITES = 2      # the sliding layers' scan, the full layer


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
    full, sliding = arch.layers_of(config)
    least = {}
    for p in progs:
        for call in ("up", "down"):
            kernel = f"{KERNEL}_{p['group'].split('_')[0]}_{call}"
            least[kernel] = least.get(kernel, 0.0) \
                + roofline.least_seconds(*arch.grouped_matmul_cost(
                    config, held_rows=p["moe_held_rows"],
                    experts_touched=p["moe_experts_touched"], call=call),
                    PEAKS["TPU v5 lite"])
    # a call site runs its kind's layers: the sites kept, by the layers
    # they run (told apart by nothing in the label, so by their seconds:
    # the larger is the sliding layers' where there are more of them)
    parts = []
    for kernel, seconds in least.items():
        sites = sorted((s for label, s in trace["device_ops"]
                        if label.startswith((kernel + ".", kernel + " "))),
                       reverse=True)
        if not sites:
            continue
        shares = sorted((sliding, full), reverse=True)[:len(sites)]
        parts.append((kernel, "", seconds * sum(shares) / (full + sliding)))
    return roofline.share_pct(trace, parts)
