"""Prompt tokens dispatched per engine step of the window: a per-layer
metric added by the rehearsal cell alone, as a later PR would add one."""


def read(observed):
    counters = observed.get("counters") or {}
    if not counters.get("steps"):
        return None
    return counters["prefill_tokens_computed"] / counters["steps"]
