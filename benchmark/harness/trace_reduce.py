"""From the profiler's trace to the numbers the benchmark reports.

Two steps, kept apart so that the arithmetic can be checked by hand:

``read_xplane(path)`` reads a ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain events ``[name, start_ns, duration_ns]``: per chip the operations
on the device's op line, and the benchmark's own ``bench:*`` host
annotations.  ``reduce_events(events)`` does the rest: busy and idle share,
time by op group, exposed collective time, the operations that took most
time and the longest idle gaps by what the host was doing.

Checked against ``testdata/hand_trace.json`` (hand-computed answers) and a
small trace recorded on the chip (``testdata/small_tpu.xplane.pb``).
"""
import functools
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "ragged-all-to-all",
               "collective-broadcast")
GROUPS = ("pallas", "collective", "other")

# On the TPU an op event is named by its whole HLO instruction:
#   %fusion.3 = bf16[16,1024]{1,0:T(8,128)(2,1)} fusion(...), kind=kOutput
# elsewhere (and in the hand-written fixture) by the instruction's name alone.
_INSTRUCTION = re.compile(r"^%?(?P<instr>\S+) = (?P<rest>.*)$", re.DOTALL)
_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@functools.lru_cache(maxsize=None)
def parse_op(name):
    """``(instruction name, opcode, short label)`` of an op event."""
    match = _INSTRUCTION.match(name)
    if not match:
        instr = name.lstrip("%")
        return instr, re.sub(r"\.\d+$", "", instr), instr
    rest = match["rest"]
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else "?"
    shape = _LAYOUT.sub("", rest[:op.start()] if op else "").strip()
    return match["instr"], opcode, f"{match['instr']} = {shape} {opcode}"[:120]


@functools.lru_cache(maxsize=None)
def op_group(name):
    """``collective``; ``pallas`` for a Mosaic kernel, which is a custom
    call to ``tpu_custom_call`` in the compiled program (no kernel of this
    repo carries a name of its own yet: PERF.md, open questions); else
    ``other``."""
    _, opcode, _ = parse_op(name)
    if opcode.startswith(COLLECTIVES):
        return "collective"
    if opcode == "custom-call" and ("custom_call_target" not in name
                                    or "tpu_custom_call" in name):
        return "pallas"
    return "other"


def read_xplane(path):
    """``{"chips": {"0": [[name, start_ns, dur_ns], ...]}, "host": [...]}``"""
    from jax.profiler import ProfileData

    chips, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                if line.name == OP_LINE:
                    chips.setdefault(match.group(1), []).extend(
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"chips": chips, "host": host}


def _flatten(events, lo, hi):
    """Events that may nest -> disjoint ``(start, end, name)`` segments
    inside [lo, hi], each instant given to the innermost event covering it
    (a ``while`` op covers the ops of its body; an annotation may cover
    another)."""
    ordered = sorted(((max(s, lo), min(s + d, hi), n) for n, s, d in events
                      if s < hi and s + d > lo),
                     key=lambda e: (e[0], -e[1]))
    out, stack, cursor = [], [], lo

    def emit(until):
        nonlocal cursor
        if stack and until > cursor:
            out.append((cursor, until, stack[-1][1]))
        cursor = max(cursor, until)

    for start, end, name in ordered:
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        cursor = max(cursor, start)
        if stack:
            end = min(end, stack[-1][0])    # an overlap that is no nesting
        stack.append((end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def _union(segments):
    """Sorted disjoint ``(start, end)`` covering the segments."""
    out = []
    for start, end in sorted((s[0], s[1]) for s in segments):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _minus(a, b):
    """Sorted disjoint intervals ``a`` with the parts covered by ``b``
    taken out."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, cursor = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append([cursor, b[k][0]])
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append([cursor, end])
    return out


def _in_flight(ops, lo, hi):
    """``(start, end)`` of every collective inside [lo, hi]: a synchronous
    one lasts as long as its op; an asynchronous one from the beginning of
    its ``<kind>-start`` op to the end of the next ``<kind>-done`` op of
    the same name suffix (first started, first done)."""
    out, open_starts = [], {}
    for name, start, dur in sorted(ops, key=lambda e: e[1]):
        if op_group(name) != "collective" or start >= hi or start + dur <= lo:
            continue
        instr, opcode, _ = parse_op(name)
        kind = next(k for k in COLLECTIVES if opcode.startswith(k))
        key = (kind, instr.rsplit(".", 1)[1] if "." in instr else "")
        if opcode.endswith("-start"):
            open_starts.setdefault(key, []).append(start)
        elif opcode.endswith("-done"):
            began = open_starts.get(key) or next(
                (v for k, v in open_starts.items() if k[0] == kind and v),
                None)
            out.append((max(began.pop(0) if began else start, lo),
                        min(start + dur, hi)))
        else:
            out.append((max(start, lo), min(start + dur, hi)))
    return out


def _overlap_by_name(segments, intervals):
    """Per name, how much of the sorted disjoint ``(start, end, name)``
    segments lies inside the sorted disjoint ``intervals``: one pass over
    both."""
    out, j = {}, 0
    for start, end, name in segments:
        while j < len(intervals) and intervals[j][1] <= start:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < end:
            inside = min(end, intervals[k][1]) - max(start, intervals[k][0])
            out[name] = out.get(name, 0) + inside
            k += 1
    return out


def _top(by_name, n=10):
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce_events(events):
    """The reduction.  Times in seconds; shares of the window in percent,
    means over the chips.  Returns None when no operation ran on a device
    inside the window."""
    chips, host = events["chips"], events["host"]
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        spans = [(s, s + d) for ops in chips.values() for _, s, d in ops]
        if not spans:
            return None
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    window = hi - lo
    if window <= 0 or not chips:
        return None

    busy, exposed, in_flight = [], [], []
    group_ns = {g: [] for g in GROUPS}
    op_ns, first_idle = {}, None
    for chip in sorted(chips, key=int):
        flat = _flatten(chips[chip], lo, hi)
        by_group = {g: 0 for g in GROUPS}
        for start, end, name in flat:
            by_group[op_group(name)] += end - start
            label = parse_op(name)[2]
            op_ns[label] = op_ns.get(label, 0) + end - start
        for g in GROUPS:
            group_ns[g].append(by_group[g])
        covered = _union(flat)
        busy.append(_length(covered))
        # a collective is in flight from its start (for an asynchronous
        # pair, the ``-start`` op) to its end (the ``-done`` op); it is
        # exposed while no other operation runs on the chip
        flying = _union(_in_flight(chips[chip], lo, hi))
        others = _union([s for s in flat if op_group(s[2]) != "collective"])
        in_flight.append(_length(flying))
        exposed.append(_length(_minus(flying, others)))
        if first_idle is None:
            first_idle = _minus([[lo, hi]], covered)
    if not any(busy):
        return None

    # idle gaps of the first chip, by the benchmark's span the host was in
    spans = _flatten([e for e in host if e[0] != WINDOW_SPAN], lo, hi)
    gap_ns = _overlap_by_name(spans, first_idle)
    rest = _length(first_idle) - sum(gap_ns.values())
    if rest > 0:
        gap_ns["(no span)"] = rest

    n = len(busy)
    mean = lambda xs: sum(xs) / n                       # noqa: E731
    return {
        "window_s": window / 1e9,
        "busy_s": mean(busy) / 1e9,
        "chips": n,
        "idle_pct": 100.0 * (1.0 - mean(busy) / window),
        "group_pct_of_window": {g: 100.0 * mean(group_ns[g]) / window
                                for g in GROUPS},
        "group_pct_of_busy": {g: 100.0 * mean(group_ns[g]) / mean(busy)
                              for g in GROUPS},
        "collective_pct": 100.0 * mean(in_flight) / window,
        "collective_exposed_pct": 100.0 * mean(exposed) / window,
        "longest_idle_gap_s": max((e - s for s, e in first_idle),
                                  default=0) / 1e9,
        "device_ops": _top({k: v / n for k, v in op_ns.items()}),
        "idle_gaps": _top(gap_ns),
    }
