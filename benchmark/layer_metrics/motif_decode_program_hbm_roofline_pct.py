"""The decode program against the bytes it has to move, over the window:
per decode program the engine fetched, every weight outside the routed
experts once, the head, the held experts its counter says were TOUCHED, and
the latent rows its live lanes attended, the full layer's ``attn_keys_full``
and the sliding layers' ``attn_keys_window`` (a lane at most the window),
from ``architectures/motif.py`` ``decode_step_bytes``, at the chip's HBM
rate; the mean of that over the median ``run_decode`` span (the decode
program alone, dispatch to fetch: ``decode_program_ms``).  The share is the
whole program's; its decode attention kernels have a share of their own
beside it (``gdla_paged_decode_attn_roofline_pct``).  Entered for ONE
configuration (``CONFIGURATION``).  Nothing where the program records no
such counters or they are another configuration's."""
from harness import roofline
from harness.cells import sibling_reader
from harness.device import PEAKS

CONFIGURATION = ("motif", "motif-3-beta-ep8")

_program_ms = sibling_reader(__file__, "decode_program_ms")


def read(observed):
    progs = [p for p in roofline.programs(observed.get("spans"))
             if p["group"] == "decode" and "attn_keys_full" in p]
    program_ms = _program_ms(observed)
    if not progs or not program_ms:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    if not all(arch.counters_are_of(config, p) for p in progs):
        return None
    routed = arch.layers_of(config)[3]
    moved = [arch.decode_step_bytes(
        config, keys_full=p["attn_keys_full"],
        keys_window=p["attn_keys_window"], weight_bytes=2, kv_bytes=2,
        experts_touched=p["moe_experts_touched"] / routed) for p in progs]
    least_ms = 1e3 * sum(moved) / len(moved) \
        / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / program_ms
