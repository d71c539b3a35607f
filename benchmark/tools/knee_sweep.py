"""Finds the knee of an open-loop serving cell: offers its mix at each of
a few rates for ``--seconds`` each, under each of ``--seeds``, in one
process on the chip, and prints for each run the tails, the backlog and
whether it GREW, then the table and the knee: the highest swept rate under
which no seed's backlog grew, below the lowest under which one did.  The
cell then runs at 0.8 of it, written into its traffic file as a number
beside the table.  Run once, when the cell is defined or its engine
settings change (PERF.md, section 6).

    python3 benchmark/tools/knee_sweep.py <workload> --rates 30,40,50 \
        --seeds 11,12 --seconds 20

The backlog at an instant is the requests due by then whose first token
had not come: waiting for a lane, or for the one prefill lane once they
hold one.  Under the knee it wanders about a level; over it, it climbs by
the excess of the offered rate over what the engine serves.  ``grows``:
its mean over the last third of the window lies over its mean over the
first third by ``GROWS_BY`` requests or more: one whole request, the
smallest thing a backlog can rise by.  (On the chip the means of a rate
that holds lie within 0.3 of a request of each other and those of one that
does not 2.7 and more apart, PERF.md section 6, PR 45: the rule reads the
same anywhere between.)
"""
import argparse
import copy
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from harness.stats import median, over_medians, percentile   # noqa: E402

GROWS_BY = 1.0      # requests, between the window's first and last third
SAMPLES = 64        # instants a third of the window is averaged over


def backlog(due, first_token_at, at):
    """Requests due by ``at`` whose first token had not come by then
    (``first_token_at`` None: it never came)."""
    return sum(1 for d, f in zip(due, first_token_at)
               if d <= at and (f is None or f > at))


def backlog_growth_per_s(due, first_token_at, window):
    """Requests a second by which the backlog rose over the window: its
    mean over the last third less its mean over the first, over the time
    between the thirds' middles."""
    start, end = window
    third = (end - start) / 3.0

    def mean_from(t0):
        return sum(backlog(due, first_token_at, t0 + third * (k + 0.5)
                           / SAMPLES) for k in range(SAMPLES)) / SAMPLES

    return (mean_from(end - third) - mean_from(start)) / (2.0 * third)


def grows(due, first_token_at, window):
    """(whether the backlog grew, by how many requests a second, of how
    many a second offered inside the window)."""
    offered = sum(1 for d in due if window[0] <= d < window[1]) \
        / (window[1] - window[0])
    growth = backlog_growth_per_s(due, first_token_at, window)
    between_thirds_s = 2.0 * (window[1] - window[0]) / 3.0
    return growth * between_thirds_s >= GROWS_BY, growth, offered


def knee(rows):
    """From ``(rate, grew)`` of every run: the highest rate under which no
    run's backlog grew and which lies under every rate where one did; None
    where even the lowest rate grew (sweep lower)."""
    grew_at = [rate for rate, grew in rows if grew]
    held = [rate for rate, grew in rows
            if rate not in grew_at and (not grew_at or rate < min(grew_at))]
    return max(held, default=None)


def _step_ms(step_s):
    """The engine's steps of the window, call to return: median, tails,
    and the seconds spent in steps of over three medians (stalls)."""
    if not step_s:
        return None
    slow = over_medians(step_s)
    return dict({f"p{round(100 * q)}": 1e3 * percentile(step_s, q)
                 for q in (.5, .9, .99, 1.0)},
                over_3_medians=len(slow), seconds_in_them=sum(slow))


def sweep(cell, devices, rates, seeds, seconds, out=print):
    """One run of the cell's own driver a (seed, rate): the cell's mix
    with the rate replaced and nothing else.  Returns the rows."""
    mix = copy.deepcopy(cell.traffic)
    ramp = float(mix["ramp_s"])
    window = (ramp, ramp + seconds)
    rows = []
    for seed in seeds:
        for rate in rates:
            cell.traffic = copy.deepcopy(mix)
            cell.traffic["arrivals"]["rate_per_s"] = rate
            seen = {}
            run = cell.driver().run(cell, devices, seed=seed,
                                    seconds=seconds, trace=False,
                                    process_start=time.perf_counter(),
                                    log=seen.update)
            observed = run["observed"]
            due = observed["due_s"]
            # a request that never finished waited to the end of the load
            # (``latencies``): its first token is taken as never come
            never = seen["loop_s"]
            first = [None if d + t >= never - 1e-6 else d + t
                     for d, t in zip(due, observed["ttft_s"])]
            grew, growth, offered = grows(due, first, window)
            slots = observed["counters"]
            row = {
                "seed": seed, "rate_per_s": rate, "offered_per_s": offered,
                "backlog_growth_per_s": growth, "grows": grew,
                "backlog_at_window_end": backlog(due, first, window[1]),
                "queue_depth_at_window_end":
                    seen["queue_depth_at_window_end"],
                "requests": seen["requests"],
                "slot_util_pct": 100.0 * slots["active_slot_steps"]
                    / max(1, slots["slot_steps"]),
                "steps_per_s": slots["steps"] / seconds,
                "ttft_s": seen["ttft_s"], "tpot_s": seen["tpot_s"],
                "lateness_p50_s": median(observed["generator_lateness_s"]),
                "step_ms": _step_ms(observed["step_s"]),
                "failed": run["failed"], "correct": run["correct"],
                "memory_peak_gb": observed["memory_peak_bytes"] / 1e9,
                "setup_s": run["end_to_end"]["setup_s"],
            }
            out(json.dumps(row, default=float))
            rows.append(row)
            del run, observed
            gc.collect()
    cell.traffic = mix
    return rows


def table(rows):
    """The sweep as the traffic file's ``knee.note`` keeps it."""
    return "; ".join(
        f"{r['rate_per_s']:g}/s seed {r['seed']}: backlog "
        f"{r['backlog_growth_per_s']:+.2f}/s"
        f"{' GROWS' if r['grows'] else ''}, slots "
        f"{r['slot_util_pct']:.0f} %, tpot p95 "
        f"{1e3 * r['tpot_s']['p95']:.2f} ms" for r in rows)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("workload")
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seeds", default="0")
    args = parser.parse_args()

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from harness import cells, device as device_lib

    cell = cells.Cell(cells.load_benchmark(withheld=True), args.workload)
    devices = device_lib.require_tpu(cell.chips)
    enable_compile_cache()

    def out(line):
        print(line, flush=True)

    rows = sweep(cell, devices, [float(r) for r in args.rates.split(",")],
                 [int(s) for s in args.seeds.split(",")], args.seconds, out)
    found = knee([(r["rate_per_s"], r["grows"]) for r in rows])
    out(json.dumps({"knee_per_s": found,
                    "four_fifths": None if found is None else 0.8 * found,
                    "grows_means": f"backlog up by {GROWS_BY:g} request or "
                                   f"more between the window's first and "
                                   f"last third",
                    "table": table(rows)}))


if __name__ == "__main__":
    main()
