"""Live cache bytes over what the same lanes would hold if EVERY layer kept
every position, mean over the engine steps of the window: the mechanism's
saving.  The engine's ``kv_pages_full`` / ``kv_pages_window`` counters (live
pages by cache group after a step) times the layers of each kind, from the
configuration's file, over the full group's pages times all layers (a page
of latent rows costs every layer the same bytes).  Entered for ONE
configuration (``CONFIGURATION``): nothing where the program records no such
counters or no ``mhc_sinkhorn_err_ppm`` (another model of two groups)."""
from harness import roofline

CONFIGURATION = ("motif", "motif-3-beta-ep8")


def read(observed):
    spans = observed.get("spans") or {}
    full, window = spans.get("kv_pages_full"), spans.get("kv_pages_window")
    if not full or not window or len(full) != len(window) \
            or not any(n.startswith("mhc_sinkhorn_err_ppm_") for n in spans):
        return None
    arch, config = roofline.cell_files(*CONFIGURATION)
    n_full, n_sliding = arch.layers_of(config)[:2]
    kept = sum(n_full * f["a0"] + n_sliding * w["a0"]
               for f, w in zip(full, window))
    whole = sum((n_full + n_sliding) * f["a0"] for f in full)
    return 100.0 * kept / whole if whole else None
