"""The decode program against the bytes it has to move, over the window:
per decode program the engine fetched, every weight of the double blocks
outside the routed experts once, the head, the experts its counter says
were TOUCHED, and the latent rows of the keys its live lanes attended, once
for EACH of a block's two attentions (the engine's ``attn_keys`` counter
counts one attention's), from ``architectures/longcat_flash.py``
``decode_step_bytes``, at the chip's HBM rate; the mean of that over the
median ``run_decode`` span (the decode program alone, dispatch to fetch:
``decode_program_ms``).  The share is the whole program's; its decode
attention kernel has a share of its own beside it
(``paged_latent_decode_attn_roofline_pct``).
Entered for ONE configuration (``CONFIGURATION``; another enters a reader
of its own).  Nothing where the program records no such counters or they
are another configuration's."""
from harness import roofline
from harness.cells import sibling_reader
from harness.device import PEAKS

CONFIGURATION = ("longcat_flash", "longcat-flash-chat-ep32")

_program_ms = sibling_reader(__file__, "decode_program_ms")


def read(observed):
    progs = [p for p in roofline.programs(observed.get("spans"))
             if p["group"] == "decode" and "attn_keys" in p]
    program_ms = _program_ms(observed)
    if not progs or not program_ms:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    if not all(arch.counters_are_of(config, p) for p in progs):
        return None
    blocks = config["num_layers"]
    moved = [arch.decode_step_bytes(
        config, lanes=1, context_positions=p["attn_keys"], weight_bytes=2,
        kv_bytes=2, experts_touched=p["moe_experts_touched"] / blocks)
        for p in progs]
    least_ms = 1e3 * sum(moved) / len(moved) \
        / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / program_ms
