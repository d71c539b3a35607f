"""Host-side span tracer: preallocated ring buffer, Chrome-trace export.

The hot path never touches a device or forces a transfer (the graftlint
host-sync bar): one clock read at ``begin()``, one clock read plus
one row store at ``complete()``/``instant()``.  The event payload is
one preallocated buffer of packed 41-byte rows (timestamp, duration,
interned name id, lane id, phase, two integer args), a row written by ONE
``struct`` store at a wrapping ring index under a lock (the async
checkpoint-commit thread and the training thread share one tracer; a
row is one or two cache lines where a column each was seven).

``span()`` opens a span that is two things at once: the ring event, and
a ``jax.profiler.TraceAnnotation`` named ``dstpu:<lane>/<name>``, begun
together and ended together by ``Span.end()``.  Inside a profiler
session that puts the program's span on the device trace's clock, on
the calling thread's line above the device's op line; outside one
none is made (``TraceAnnotation.is_enabled()``: a flag test), for
nothing would see it.  It is host-side too: a ``TraceMe``, no device
call.

Disarmed is exactly free: engines hold ``self._tracer = None`` and every
instrumentation site is a single attribute-load-and-``is None`` branch —
no null-object dispatch, no clock reads, no recording, and (since
tracing is purely host-side) bit-identical device programs either way.

Export is the Chrome trace-event JSON format (``chrome://tracing`` /
Perfetto ``ui.perfetto.dev``): one process, one thread ("lane") per
logical actor — the training engine emits on ``train``/``ckpt`` lanes,
the PipelineEngine interpreter on one ``stage<N>`` lane per physical
stage (so an exported trace *renders* the 1F1B/interleaved/ZB schedule),
the serving engine on ``serve``.  Spans export as complete ``"X"``
events, instants as ``"i"``.

``lane_utilization(events)`` computes measured per-lane busy/idle
fractions from an event list — the wall-clock side of the
measured-vs-analytic bubble cross-check
(``runtime/pipe/bubble_accounting.replay_trace`` is the cost-model
side).
"""
import json
import os
import struct
import threading
import time

import numpy as np

_PH_SPAN = 0
_PH_INSTANT = 1

# one event of the ring, as it is stored and as the read side views it
_ROW = struct.Struct("<ddiibqq")
_ROW_DTYPE = np.dtype([("ts", "<f8"), ("dur", "<f8"), ("name", "<i4"),
                       ("lane", "<i4"), ("ph", "i1"), ("a0", "<i8"),
                       ("a1", "<i8")])
assert _ROW.size == _ROW_DTYPE.itemsize == 41

# a traced serving step records ~25 events (docs/tutorials/observability.md,
# the overhead contract): a benchmark run of ~4,500 steps fits twice over
DEFAULT_CAPACITY = 1 << 18
MIN_CAPACITY = 256
ANNOTATION_PREFIX = "dstpu:"


class Span:
    """One open span of :meth:`Tracer.span`.  ``t0`` is its start on the
    tracer's clock; ``end()`` records the ring event, leaves the
    profiler annotation and returns the end time, so that the next span
    can begin at the very same instant."""

    __slots__ = ("_tracer", "name", "lane", "t0", "_annotation")

    def __init__(self, tracer, name, lane, t0, annotation):
        self._tracer = tracer
        self.name = name
        self.lane = lane
        self.t0 = t0
        self._annotation = annotation

    def end(self, a0=-1, a1=-1, at=None):
        tr = self._tracer
        if at is None:
            at = tr.clock()
        tr._record(_PH_SPAN, self.name, self.lane, self.t0, at - self.t0,
                   a0, a1)
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        return at


class Tracer:
    """Ring-buffer span/instant recorder (see module docstring).

    ``capacity`` bounds host memory (41 B/event: ~11 MB at the default);
    once exceeded the OLDEST events are overwritten and ``dropped``
    counts them — the tracer never grows and never throws on overflow.
    """

    def __init__(self, capacity=DEFAULT_CAPACITY, clock=time.perf_counter):
        capacity = max(MIN_CAPACITY, int(capacity))
        self.capacity = capacity
        self.clock = clock
        self._rows = bytearray(capacity * _ROW.size)
        self._n = 0                     # total events ever recorded
        self._names = []                # id -> name
        self._name_ids = {}             # name -> id
        self._arg_labels = {}           # name id -> (label0, label1)
        self._lanes = []                # id -> lane name
        self._lane_ids = {}             # lane name -> id
        self._lane_labels = []          # id -> "dstpu:<lane>/"
        self._lock = threading.Lock()
        # resolved when a tracer is armed, never on a disarmed path
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation

    # -- interning ------------------------------------------------------
    def lane(self, name):
        """Intern a lane (exported as a named Chrome thread); returns its
        integer id — cache it at arming time, pass it on the hot path."""
        with self._lock:
            lid = self._lane_ids.get(name)
            if lid is None:
                lid = len(self._lanes)
                self._lanes.append(str(name))
                self._lane_ids[name] = lid
                self._lane_labels.append(f"{ANNOTATION_PREFIX}{name}/")
            return lid

    def intern(self, name, args=()):
        """Intern an event name (optionally labelling its two integer
        args for export); returns the integer name id."""
        with self._lock:
            nid = self._name_ids.get(name)
            if nid is None:
                nid = len(self._names)
                self._names.append(str(name))
                self._name_ids[name] = nid
            if args:
                self._arg_labels[nid] = tuple(str(a) for a in args[:2])
            return nid

    # -- hot path -------------------------------------------------------
    def begin(self):
        """Timestamp for a span start; pair with :meth:`complete`."""
        return self.clock()

    def complete(self, name, lane, t0, a0=-1, a1=-1):
        """Record one finished span [t0, now] on ``lane``."""
        self._record(_PH_SPAN, name, lane, t0, self.clock() - t0, a0, a1)

    def span(self, name, lane, t0=None):
        """Open a span in the ring AND in the profiler's trace (module
        docstring); ``t0`` hands over the instant a previous span ended
        at.  Close it with :meth:`Span.end`, also from another call."""
        # a TraceAnnotation begins when it is made: __enter__ adds nothing;
        # one made outside a profiler session is never seen, so none is
        annotation = self._annotate(self._lane_labels[lane] + name) \
            if self._annotate.is_enabled() else None
        return Span(self, name, lane, self.clock() if t0 is None else t0,
                    annotation)

    def instant(self, name, lane, a0=-1, a1=-1):
        """Record a zero-duration marker event."""
        self._record(_PH_INSTANT, name, lane, self.clock(), 0.0, a0, a1)

    def count(self, name, lane, value, at=None):
        """Record a counter's value as a ZERO-LENGTH SPAN at ``at`` (now
        when None): readers that take spans only (``ph == "X"``, a0) see
        it, and a sum over a stretch of time is a sum over its events."""
        self._record(_PH_SPAN, name, lane,
                     self.clock() if at is None else at, 0.0, value, -1)

    def _record(self, ph, name, lane, ts, dur, a0, a1):
        with self._lock:
            nid = self._name_ids.get(name)
            if nid is None:
                nid = len(self._names)
                self._names.append(str(name))
                self._name_ids[name] = nid
            at = (self._n % self.capacity) * _ROW.size
            try:
                _ROW.pack_into(self._rows, at, ts, dur, nid, lane, ph, a0,
                               a1)
            except struct.error:        # an arg that is no plain integer
                _ROW.pack_into(self._rows, at, ts, dur, nid, lane, ph,
                               int(a0), int(a1))
            self._n += 1

    # -- read side ------------------------------------------------------
    @property
    def recorded(self):
        """Total events ever recorded (including overwritten ones)."""
        return self._n

    @property
    def dropped(self):
        """Events overwritten by ring wrap-around."""
        return max(0, self._n - self.capacity)

    def events(self):
        """Retained events oldest-first, as plain dicts:
        ``{name, lane, ph ('X'|'i'), ts, dur, a0, a1}`` (times in
        seconds; ``a0``/``a1`` are the caller's integer args, -1 =
        unset)."""
        with self._lock:
            rows = self._retained()
            names, lanes = list(self._names), list(self._lanes)
        return [{"name": names[nid], "lane": lanes[lane],
                 "ph": "X" if ph == _PH_SPAN else "i",
                 "ts": ts, "dur": dur, "a0": a0, "a1": a1}
                for ts, dur, nid, lane, ph, a0, a1 in rows.tolist()]

    def _retained(self):
        """A copy of the retained rows, oldest first (hold the lock)."""
        rows = np.frombuffer(self._rows, _ROW_DTYPE)
        if self._n <= self.capacity:
            return rows[:self._n].copy()
        head = self._n % self.capacity
        return np.concatenate((rows[head:], rows[:head]))

    def reset(self):
        with self._lock:
            self._n = 0

    def summary(self):
        """Small host-side status dict for reports."""
        return {"recorded": self.recorded, "retained": min(self._n,
                                                           self.capacity),
                "dropped": self.dropped, "capacity": self.capacity,
                "lanes": list(self._lanes)}

    # -- export ---------------------------------------------------------
    def _event_args(self, nid, a0, a1):
        labels = self._arg_labels.get(nid, ("a0", "a1"))
        args = {}
        if a0 != -1:
            args[labels[0] if len(labels) > 0 else "a0"] = int(a0)
        if a1 != -1:
            args[labels[1] if len(labels) > 1 else "a1"] = int(a1)
        return args

    def export_chrome_trace(self, path, pid=0,
                            process_name="deepspeed_tpu"):
        """Write the retained events as Chrome-trace-event JSON (loadable
        in chrome://tracing and Perfetto).  Spans become complete ``X``
        events, instants ``i`` with thread scope.  The write is atomic
        (temp file + rename) so a crash mid-export never leaves a torn
        trace.  Returns ``path``."""
        with self._lock:
            rows = self._retained().tolist()
            trace_events = [{
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": process_name},
            }]
            for lid, lname in enumerate(self._lanes):
                trace_events.append({
                    "ph": "M", "name": "thread_name", "pid": pid,
                    "tid": lid, "args": {"name": lname}})
                trace_events.append({
                    "ph": "M", "name": "thread_sort_index", "pid": pid,
                    "tid": lid, "args": {"sort_index": lid}})
            for ts, dur, nid, lane, ph, a0, a1 in rows:
                base = {"name": self._names[nid], "cat": "telemetry",
                        "pid": pid, "tid": lane,
                        "args": self._event_args(nid, a0, a1)}
                if ph == _PH_INSTANT:
                    trace_events.append(dict(base, ph="i", s="t",
                                             ts=round(ts * 1e6, 3)))
                else:
                    trace_events.append(dict(
                        base, ph="X", ts=round(ts * 1e6, 3),
                        dur=round(dur * 1e6, 3)))
            payload = {"traceEvents": trace_events,
                       "displayTimeUnit": "ms",
                       "otherData": {"dropped_events": self.dropped}}
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        return path


def lane_utilization(events, lanes=None):
    """Measured wall-clock utilization per lane from an event list (the
    output of :meth:`Tracer.events`): summed span durations over the
    global [first start, last end] window.

    Returns ``{lane: {busy_s, idle_fraction, spans}}`` plus the window
    under ``"_window_s"``.  This is the *measured* half of the bubble
    cross-check; on a host-dispatch-bound CPU mesh the wall numbers are
    dominated by dispatch, so the transferable tier-1 comparison is the
    cost-model replay (``bubble_accounting.replay_trace``) — both are
    reported side by side by ``PipelineEngine.measured_bubble_report``.
    """
    spans = [e for e in events if e["ph"] == "X"
             and (lanes is None or e["lane"] in lanes)]
    if not spans:
        return {"_window_s": 0.0}
    t0 = min(e["ts"] for e in spans)
    t1 = max(e["ts"] + e["dur"] for e in spans)
    window = max(t1 - t0, 1e-12)
    out = {"_window_s": window}
    by_lane = {}
    for e in spans:
        by_lane.setdefault(e["lane"], []).append(e)
    for lane, evs in by_lane.items():
        busy = sum(e["dur"] for e in evs)
        out[lane] = {"busy_s": busy,
                     "idle_fraction": 1.0 - min(busy, window) / window,
                     "spans": len(evs)}
    return out
