"""Share of the serve thread's time, from the first dispatch to the last
fetch, in which the device had nothing of the engine's queued and the
engine had work: the engine's ``host_gap`` spans with a0 = 1 (a0 = 0: the
engine was empty, which is want of demand) over those and the three
``run_*`` spans, which together partition that time.  Nothing unless the
program records ``host_gap`` and at least one ``run_*`` span."""
RUNS = ("run_decode", "run_prefill", "run_prefill_decode")


def read(observed):
    spans = observed.get("spans") or {}
    if "host_gap" not in spans or not any(name in spans for name in RUNS):
        return None
    gap = sum(s["ms"] for s in spans["host_gap"] if s["a0"] == 1)
    run = sum(s["ms"] for name in RUNS for s in spans.get(name) or [])
    return 100.0 * gap / (gap + run) if gap + run > 0 else None
