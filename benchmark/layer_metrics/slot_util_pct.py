"""Decode lanes that carried a request over decode lanes dispatched, inside
the window (``ServingMetrics.active_slot_steps / slot_steps``)."""


def read(observed):
    counters = observed.get("counters") or {}
    if not counters.get("slot_steps"):
        return None
    return 100.0 * counters["active_slot_steps"] / counters["slot_steps"]
