"""The paged latent decode attention (kernel ``paged_latent_decode_attn``)
against its roofline, over the traced stretch: per decode program fetched in
it, the operations and bytes of the keys its live lanes attended
(``attn_keys``), from ``architectures/longcat_flash.py``
``latent_decode_attn_cost``, the larger of the two times; over the kernel's
seconds in the device trace.  A block has TWO attentions, so the compiled
program has two call sites, two operations of one name and shape in the
trace: the least time is counted a call site, times the call sites the
reduction kept (it keeps the ten largest operations; one kept of two would
otherwise read twice its share).  Entered for ONE configuration
(``CONFIGURATION``).  Nothing where the program records no such counters,
they are another configuration's, or the trace holds no such kernel."""
from harness import roofline
from harness.device import PEAKS

CONFIGURATION = ("longcat_flash", "longcat-flash-chat-ep32")
KERNEL = "paged_latent_decode_attn"


def read(observed):
    trace = observed.get("trace")
    progs = [p for p in roofline.in_stretch(
        roofline.programs(observed.get("spans")), trace)
        if p["group"] == "decode" and "attn_keys" in p]
    seconds = roofline.kernel_seconds(trace, KERNEL)
    if not progs or not seconds:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    if not all(arch.counters_are_of(config, p) for p in progs):
        return None
    kept = sum(label.startswith((KERNEL + ".", KERNEL + " "))
               for label, _ in trace["device_ops"])
    least = sum(roofline.least_seconds(
        *arch.latent_decode_attn_cost(config, keys=p["attn_keys"]),
        PEAKS["TPU v5 lite"]) for p in progs)
    return 100.0 * kept * least / seconds
