"""From a traced serving run to a kernel's share of its roofline, and the
engine's per-program counters it is computed from.

The trace covers the last seconds of the window and gives, per operation,
the seconds it ran (``trace_reduce``: the ten largest by label, a Mosaic
kernel's label beginning with the ``name=`` of its ``pallas_call``).  The
engine's ring gives, per PROGRAM it fetched, what the program was asked to
do: counters recorded as zero-length spans ``<counter>_<group>`` (value in
a0; ``group``: ``decode`` or ``prefill_<bucket>``) and, beside them,
``clock_ms_<group>``, the tracer's clock at the fetch.  The driver hands a
reader the spans of the whole window without their times, so the clock
counter is what tells which programs fell into the traced stretch: those
fetched in the last ``trace["window_s"]`` seconds before the last fetch of
the window.  A share is then the least time those programs' kernel calls
could take over the seconds they took, both of one stretch (a program at
the stretch's edge is in one and not the other: a few percent, either
way).  Nothing here knows a model: operations and bytes are the caller's.
"""
import json
import os

from harness import cells

CLOCK = "clock_ms_"


def cell_files(architecture, config):
    """The architecture's module and the configuration's file: where a
    kernel's operations and bytes are counted, and the sizes they are
    counted at."""
    with open(os.path.join(cells.BENCH_DIR, "configs",
                           config + ".json")) as f:
        held = json.load(f)
    return cells.load_module(
        os.path.join(cells.BENCH_DIR, "architectures", architecture + ".py"),
        f"bench_arch_{architecture}"), held


def programs(spans):
    """One dict a program the engine fetched in the window: ``group``,
    ``clock_ms`` and its counters by name.  [] where the program records
    no clock (the parent's)."""
    out = []
    for name, clocks in (spans or {}).items():
        if not name.startswith(CLOCK):
            continue
        group = name[len(CLOCK):]
        counters = {n[:-len(group) - 1]: events
                    for n, events in spans.items()
                    if n.endswith("_" + group) and n != name
                    and len(events) == len(clocks)}
        out += [dict({k: events[i]["a0"] for k, events in counters.items()},
                     group=group, clock_ms=clock["a0"])
                for i, clock in enumerate(clocks)]
    return out


def in_stretch(progs, trace):
    """The programs fetched inside the traced stretch."""
    if not progs or not trace:
        return []
    start = max(p["clock_ms"] for p in progs) - 1e3 * trace["window_s"]
    return [p for p in progs if p["clock_ms"] > start]


def total(progs, counter, kind):
    """Sum of a counter over the programs of a kind (``prefill``: every
    bucket; ``decode``); None where none carries it."""
    values = [p[counter] for p in progs
              if p["group"].split("_")[0] == kind and counter in p]
    return sum(values) if values else None


def kernel_seconds(trace, kernel, shape=""):
    """Seconds in the traced stretch under operations whose label begins
    with ``kernel`` followed by ``.`` or a space (and holds ``shape``, as
    ``[32,2048,128]``, where one kernel has several programs): None where
    the reduction kept no such operation, or there is no trace."""
    if not trace:
        return None
    found = [s for label, s in trace.get("device_ops") or []
             if label.startswith((kernel + ".", kernel + " "))
             and shape in label]
    return sum(found) if found else None


def least_seconds(flops, moved, peaks):
    """The roofline: the larger of the compute time and the memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def share_pct(trace, parts):
    """100 x least seconds / kernel seconds over ``parts``: ``(kernel,
    shape, least seconds of the stretch's programs)`` of each kind of
    program the kernel runs in.  A kind whose operation the reduction did
    not keep (it keeps the ten largest) is left out on BOTH sides; None
    where nothing is left."""
    least_s = in_trace = 0.0
    for kernel, shape, least in parts:
        seconds = kernel_seconds(trace, kernel, shape)
        if seconds is not None and least:
            least_s, in_trace = least_s + least, in_trace + seconds
    return 100.0 * least_s / in_trace if in_trace else None
