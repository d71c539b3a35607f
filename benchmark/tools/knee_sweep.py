"""Finds the knee of an open-loop serving cell: offers its mix at each of
a few rates for ``--seconds`` each, in one process on the chip, and prints
for each rate the tails and the backlog left when the window ended.  The
knee is the highest rate whose backlog does not grow; the cell then runs at
0.8 of it, written into its traffic file as a number.  Run once, when the
cell is defined (PERF.md, section 6).

    python3 benchmark/tools/knee_sweep.py <workload> --rates 3,4,5 --seconds 20
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


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("workload")
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from harness import cells, device as device_lib

    cell = cells.Cell(cells.load_benchmark(), args.workload)
    devices = device_lib.require_tpu(cell.chips)
    enable_compile_cache()
    mix = copy.deepcopy(cell.traffic)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = copy.deepcopy(mix)
        cell.traffic["arrivals"]["rate_per_s"] = rate
        seen = {}
        run = cell.driver().run(cell, devices, seed=args.seed,
                                seconds=args.seconds, trace=False,
                                process_start=time.perf_counter(),
                                log=seen.update)
        print(json.dumps({
            "rate_per_s": rate, "requests": seen["requests"],
            "queue_depth_at_window_end": seen["queue_depth_at_window_end"],
            "ttft_s": seen["ttft_s"], "tpot_s": seen["tpot_s"],
            "failed": run["failed"], "correct": run["correct"],
            "lateness_s": seen["generator_lateness_s"]}, default=float),
            flush=True)
        del run
        gc.collect()


if __name__ == "__main__":
    main()
