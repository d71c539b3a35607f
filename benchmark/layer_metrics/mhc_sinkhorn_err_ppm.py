"""How far ``H_res`` is from doubly stochastic after the last Sinkhorn
iteration: the model's ``mhc_sinkhorn_err_ppm`` counter (the largest |row or
column sum - 1| over a program's valid tokens, in parts per million, summed
over the sublayers), a sublayer, mean over the programs of the window of
THIS configuration (``counters_are_of``).  It tells a Sinkhorn run in lower
precision, or cut short, from the one the configuration states."""
from harness import roofline

CONFIGURATION = ("motif", "motif-3-beta-ep8")


def read(observed):
    progs = roofline.programs(observed.get("spans"))
    if not progs:
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    errs = [p["mhc_sinkhorn_err_ppm"] for p in progs
            if arch.counters_are_of(config, p)]
    if not errs:
        return None
    return sum(errs) / len(errs) / (2 * config["num_hidden_layers"])
