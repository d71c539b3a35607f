"""Readers of the engine's spans as ``drive_serve`` hands them over:
``observed["spans"][name]`` is a list of ``{"ms", "a0"}``, one a span that
ended inside the window."""
from harness.stats import median


def span_median(name, a0=None, of="ms", per=1.0):
    """A reader: the median of ``of`` over the spans called ``name`` (those
    whose a0 the predicate ``a0`` takes, where one is given), divided by
    ``per``.  It gives None, never an error, where the run records no such
    span: the driver runs a PR's readers over the parent's program too."""
    def read(observed):
        spans = (observed.get("spans") or {}).get(name) or []
        value = median([s[of] for s in spans
                        if a0 is None or a0(s["a0"])])
        return None if value is None else value / per
    return read
