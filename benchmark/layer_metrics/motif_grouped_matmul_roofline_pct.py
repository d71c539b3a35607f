"""The routed experts' grouped matmuls (kernels
``moe_grouped_matmul_<prefill|decode>_<up|down>``) against their roofline,
over the traced stretch: per program fetched in it and per call, the
operations of the rows it routed to held experts and the bytes of the
experts its counter says were TOUCHED, from ``architectures/motif.py``, the
larger of the two times; over the kernels' seconds in the device trace
(``harness/roofline.py``; a call whose operation the reduction did not keep
is left out on both sides).  The stage's routed sliding layers are one
traced layer and its routed full layer another, so each kernel has TWO call
sites of one name: the least time is counted over all routed layers and
scaled by the layers whose call sites the reduction kept
(``harness/sites.py``).  A chunk program that is not a prompt's last has
no use for the LAST layer's feed-forward, so the compiler leaves its
experts' matmuls out while its router still counts its rows: the chunks'
least time is counted over the other routed layers alone, which reads up to
a quarter LOW where a prompt's last chunk falls into the stretch and never
high.  Entered for ONE configuration (``CONFIGURATION``).
Nothing where the program records no such counters, they are another
configuration's, or the trace holds no such kernel."""
from harness import roofline, sites
from harness.device import PEAKS

CONFIGURATION = ("motif", "motif-3-beta-ep8")
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
    routed = sum(arch.routed_call_sites(config))

    def ran(kernel):
        """The share of the counted layers whose matmuls ran and were
        kept."""
        by_site = arch.routed_call_sites(
            config, final="_decode_" in kernel)
        return sites.kept_share(trace, kernel, by_site) \
            * sum(by_site) / routed

    return roofline.share_pct(trace, [
        (kernel, "", seconds * ran(kernel))
        for kernel, seconds in least.items()])
