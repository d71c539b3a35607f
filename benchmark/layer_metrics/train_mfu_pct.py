"""Model FLOP/s utilisation: tokens per second x operations a token
requires (forward and backward, attention included, recomputation not;
``architectures/<name>.py``) over chips x the chip's bf16 peak."""


def read(observed):
    if not observed.get("peaks") or "tokens_per_s" not in observed:
        return None
    return 100.0 * observed["tokens_per_s"] * observed["flops_per_token"] \
        / (observed["chips"] * observed["peaks"]["bf16_flops_per_s"])
