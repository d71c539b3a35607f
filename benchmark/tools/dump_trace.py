"""Prints what a ``.xplane.pb`` holds: planes, lines, and on each line the
names that took most time with their stats.  For looking at a trace by hand
before trusting the reduction (the on-chip-measurement guide, section 6).

    python3 benchmark/tools/dump_trace.py <file.xplane.pb> [names per line]
"""
import sys

from jax.profiler import ProfileData


def main(path, top=12):
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            by_name, example, n, lo, hi = {}, {}, 0, None, None
            for e in line.events:
                n += 1
                by_name[e.name] = by_name.get(e.name, 0) + e.duration_ns
                example.setdefault(e.name, e)
                lo = e.start_ns if lo is None else min(lo, e.start_ns)
                hi = e.start_ns + e.duration_ns if hi is None \
                    else max(hi, e.start_ns + e.duration_ns)
            print(f"  LINE {line.name!r}: {n} events, span "
                  f"{lo}..{hi} ns")
            for name, ns in sorted(by_name.items(),
                                   key=lambda kv: -kv[1])[:top]:
                stats = {k: (v if not isinstance(v, str) else v[:80])
                         for k, v in list(example[name].stats)[:8]}
                print(f"    {ns / 1e6:10.3f} ms  {name[:90]}  {stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
