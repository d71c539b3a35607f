"""Live pages of the WINDOW cache group over its usable pages, mean over the
engine steps of the window: the engine's ``kv_pages_window`` over
``kv_pool_pages_window`` counters, recorded after every step by a model of
several cache groups (``serving/kv_cache.py``).  The group is sized for what
its lanes can hold at once (a window a lane, a chunk's pages for the one
lane mid-chunk), so this reads how near the traffic comes to that.  Nothing
where the program records no such counters (a model of one group, the
parent)."""


def read(observed):
    spans = observed.get("spans") or {}
    live, pool = spans.get("kv_pages_window"), \
        spans.get("kv_pool_pages_window")
    if not live or not pool or len(live) != len(pool):
        return None
    return 100.0 * sum(s["a0"] for s in live) / sum(s["a0"] for s in pool)
