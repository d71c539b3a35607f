"""The operations of a kept trace (``BENCH_KEEP_TRACE=<dir>`` on a traced
run) by the time each took inside the window, innermost first, as many as
asked: ``trace_reduce``'s own reading, not cut to ten.

    python3 benchmark/tools/top_ops.py <file.xplane.pb> [how many]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace_reduce                    # noqa: E402


def main(path, top=40):
    events = trace_reduce.read_xplane(path)
    windows = [(s, s + d) for n, s, d in events["host"]
               if n == trace_reduce.WINDOW_SPAN]
    ops = events["chips"][sorted(events["chips"], key=int)[0]]
    lo, hi = windows[0] if windows else (
        min(s for _, s, _ in ops), max(s + d for _, s, d in ops))
    by_label, count = {}, {}
    for start, end, name in trace_reduce._flatten(ops, lo, hi):
        label = trace_reduce.parse_op(name)[2]
        by_label[label] = by_label.get(label, 0) + end - start
        count[label] = count.get(label, 0) + 1
    busy = sum(by_label.values())
    print(f"window {(hi - lo) / 1e9:.3f} s, busy {busy / 1e9:.3f} s, "
          f"{len(by_label)} operations")
    for label, ns in sorted(by_label.items(), key=lambda kv: -kv[1])[:top]:
        print(f"{ns / 1e6:10.2f} ms {100 * ns / busy:5.1f} %  "
              f"{count[label]:6d} x  {label}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 40)
