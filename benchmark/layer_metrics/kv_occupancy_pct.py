"""KV pool blocks in use over usable blocks, mean over the engine steps of
the window."""


def read(observed):
    counters = observed.get("counters") or {}
    if not counters.get("steps"):
        return None
    return 100.0 * counters["kv_occupancy_mean"]
