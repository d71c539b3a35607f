"""The paged latent decode attention over grouped differential latent heads
(kernels ``gdla_paged_decode_attn_full`` and
``gdla_paged_decode_attn_window``: 80 heads a lane over ITS pages of raw
latent rows, the window's call from each lane's first visible row,
``starts``) against its roofline, over the traced stretch: per decode
program fetched in it, operations and bytes of the latent rows its live
lanes attended (the engine's ``attn_keys_full`` / ``attn_keys_window``
counters, a window lane at most its window) times the layers of that kind,
from ``architectures/motif.py`` ``decode_attn_cost``; over the two kernels'
seconds in the device trace (a kernel whose operation the reduction did not
keep is left out on both sides, one with several call sites is scaled by
the layers whose sites it kept: ``harness/sites.py``).  Entered for ONE
configuration (``CONFIGURATION``).  Nothing where the program's counters are
not this configuration's or the trace holds no such kernel."""
from harness import roofline, sites
from harness.device import PEAKS

CONFIGURATION = ("motif", "motif-3-beta-ep8")
KERNEL = "gdla_paged_decode_attn"


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
    by_site = arch.attention_call_sites(config)
    least = dict.fromkeys(by_site, 0.0)
    for p in progs:
        for group, site_layers in by_site.items():
            flops, moved = arch.decode_attn_cost(
                config, keys=p[f"attn_keys_{group}"])
            n = sum(site_layers)
            least[group] += roofline.least_seconds(
                n * flops, n * moved, PEAKS["TPU v5 lite"])
    return roofline.share_pct(trace, [
        (f"{KERNEL}_{group}", "", seconds * sites.kept_share(
            trace, f"{KERNEL}_{group}", by_site[group]))
        for group, seconds in least.items()])
