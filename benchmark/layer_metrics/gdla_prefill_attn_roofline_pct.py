"""The rectangle attention of chunked prefill over grouped differential
latent heads (kernels ``gdla_prefill_attn_full`` and
``gdla_prefill_attn_window``: 80 query heads over 16 expanded key/value
heads, the rotary key shared, the window's call with ``k_start`` and
``window``) against its roofline, over the traced stretch: per prefill
program fetched in it, operations and bytes of the (query, key) pairs it
attended that are causal AND inside the window (the engine's
``attn_pairs_full`` / ``attn_pairs_window`` counters) times the layers of
that kind, from ``architectures/motif.py`` ``prefill_attn_cost``; over the
two kernels' seconds in the device trace (``harness/roofline.py``; a kernel
whose operation the reduction did not keep is left out on both sides, and a
kernel with several call sites, the stage's runs of sliding layers, is
scaled by the layers whose sites it kept: ``harness/sites.py``).  Entered
for ONE configuration (``CONFIGURATION``).  Nothing where the program's
counters are not this configuration's or the trace holds no such kernel."""
from harness import roofline, sites
from harness.device import PEAKS

CONFIGURATION = ("motif", "motif-3-beta-ep8")
KERNEL = "gdla_prefill_attn"


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
    by_site = arch.attention_call_sites(config)
    least = dict.fromkeys(by_site, 0.0)
    for p in progs:
        queries = int(p["group"].split("_")[1])
        for group, site_layers in by_site.items():
            flops, moved = arch.prefill_attn_cost(
                config, pairs=p[f"attn_pairs_{group}"], queries=queries)
            n = sum(site_layers)
            least[group] += roofline.least_seconds(
                n * flops, n * moved, PEAKS["TPU v5 lite"])
    return roofline.share_pct(trace, [
        (f"{KERNEL}_{group}", "", seconds * sites.kept_share(
            trace, f"{KERNEL}_{group}", by_site[group]))
        for group, seconds in least.items()])
