"""Idle gaps of the device by the PROGRAM's own spans, by hand: the serving
engine's ``dstpu:serve/*`` annotations are in the profiler's trace beside the
benchmark's ``bench:*`` ones, and the reduction keeps only the latter
(``trace_reduce.SPAN_PREFIX``; widening it there is a benchmark PR's).

    BENCH_KEEP_TRACE=<dir> python3 benchmark/run.py --workload ... --trace 1
    python3 benchmark/tools/gaps_by_program_span.py <dir>/<file>.xplane.pb

Two lines: the gaps by the innermost span of either kind, and by
``host_gap`` and ``run_*`` alone.  Those four partition the serve thread's
time, so idle time under ``host_gap`` is the host's, and idle time under a
``run_*`` span is a program that was sent and had not begun, or had ended
and was not fetched yet.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace_reduce    # noqa: E402

PARTITION = ("dstpu:serve/host_gap", "dstpu:serve/run_")


def main(path):
    trace_reduce.SPAN_PREFIX = ("bench:", "dstpu:")
    events = trace_reduce.read_xplane(path)
    for by, keep in (("innermost span", trace_reduce.SPAN_PREFIX),
                     ("host_gap and run_* alone", PARTITION)):
        host = [e for e in events["host"]
                if e[0].startswith((trace_reduce.WINDOW_SPAN,) + keep)]
        reduced = trace_reduce.reduce_events({"chips": events["chips"],
                                              "host": host})
        print(json.dumps({"by": by, **(
            {k: reduced[k] for k in ("window_s", "idle_pct", "idle_gaps")}
            if reduced else {"no operation ran on a device": path})}))


if __name__ == "__main__":
    main(sys.argv[1])
