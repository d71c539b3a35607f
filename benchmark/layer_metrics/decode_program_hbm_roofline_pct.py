"""GPT-2's decode program against the bytes it has to move, over the window:
per decode program the engine fetched, every weight once at the width the
engine HOLDS it in (the configuration's ``assumed.served_weight_dtype``:
bf16, 2 bytes, since PR 33; until PR 45 this file counted 4 and read twice
the share) and the keys and values of the positions its live lanes attended
(the engine's ``attn_keys`` counter) in every layer, from
``architectures/gpt2.py`` ``decode_step_bytes``, at the chip's HBM rate; the
mean of that over the median ``run_decode`` span (the decode program alone,
dispatch to fetch: ``decode_program_ms``).  The share is the whole
program's: matmuls, the paged decode-attention kernel and the fetch.
Entered for ONE configuration (``CONFIGURATION``; another enters a reader of
its own).  Nothing where the program records no such counter."""
import jax.numpy as jnp

from harness import roofline
from harness.cells import sibling_reader
from harness.device import PEAKS

CONFIGURATION = ("gpt2", "gpt2-350m")

_program_ms = sibling_reader(__file__, "decode_program_ms")


def held_bytes(config, what):
    """Bytes a value of ``assumed.<what>`` of the configuration's file."""
    return jnp.dtype(config["assumed"][what]).itemsize


def read(observed):
    progs = [p for p in roofline.programs(observed.get("spans"))
             if p["group"] == "decode" and "attn_keys" in p]
    program_ms = _program_ms(observed)
    if not progs or not program_ms:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    moved = [arch.decode_step_bytes(
        config, lanes=1, context_positions=p["attn_keys"],
        weight_bytes=held_bytes(config, "served_weight_dtype"),
        kv_bytes=held_bytes(config, "compute_dtype")) for p in progs]
    least_ms = 1e3 * sum(moved) / len(moved) \
        / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / program_ms
