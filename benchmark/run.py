"""Runs one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
traced, ``breakdown``, a serving run's ``requests_counted``, and last
``compared``: each number the verdict compared with its limit (also the
last lines of standard error).  Earlier lines are JSON too, and are
information.
The run measures on the TPUs of the machine it is started on and on
nothing else: without them, or without the program, it prints no result
and exits with a code other than 0.
"""
import time

PROCESS_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)                       # harness
sys.path.insert(0, os.path.dirname(BENCH_DIR))      # the program

EXIT_NO_DEVICE = 3
EXIT_NO_PROGRAM = 4


def log(record):
    print(json.dumps(record, default=float), flush=True)


def result_line(cell, run, device, trace):
    """The contract's last line.  Refuses a device that is not a TPU: a
    time from a CPU is never written under the name of a device metric."""
    from harness.device import NoDevice

    if device["platform"] != "tpu":
        raise NoDevice(f"no result line for platform {device['platform']!r}")
    metrics = {}
    if trace:
        for spec in cell.per_layer:
            value = cell.reader(spec["name"])(run["observed"])
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        for spec in cell.end_to_end:
            value = run["end_to_end"][spec["name"]]
            if value is None:
                counted = run.get("requests_counted")
                raise RuntimeError(
                    f"the run could not measure {spec['name']}: no result"
                    + (f" ({counted} requests counted; a judged percentile "
                       f"needs ten beyond its rank)"
                       if counted is not None else ""))
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]), "failed": int(run["failed"]),
            "metrics": metrics, "device": dict(device)}
    reduction = run["observed"].get("trace")
    if trace:
        if not reduction:
            raise RuntimeError("the traced run saw no operation on a device")
        line["device"]["busy_s"] = reduction["busy_s"]
        line["device"]["window_s"] = reduction["window_s"]
        line["breakdown"] = {"device_ops": reduction["device_ops"],
                             "idle_gaps": reduction["idle_gaps"]}
    if "requests_counted" in run:       # what a percentile was taken over
        line["requests_counted"] = int(run["requests_counted"])
    # last: each number the verdict compared, [as read, its limit]
    line["compared"] = run["compared"]
    return line


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    from harness import cells

    cell = cells.Cell(cells.load_benchmark(), args.workload)
    try:
        import deepspeed_tpu    # noqa: F401
    except ImportError as e:
        print(f"the program is not in this directory: {e}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    from harness import device as device_lib

    try:
        devices = device_lib.require_tpu(cell.chips)
    except (device_lib.NoDevice, RuntimeError) as e:
        print(f"no device to measure on: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    log({"workload": cell.name, "seed": args.seed, "seconds": args.seconds,
         "trace": args.trace, "compile_cache": enable_compile_cache()})
    run = cell.driver().run(cell, devices, seed=args.seed,
                            seconds=args.seconds, trace=bool(args.trace),
                            process_start=PROCESS_START, log=log)
    if run["observed"].get("trace"):
        log({"trace": {k: v for k, v in run["observed"]["trace"].items()
                       if k not in ("device_ops", "idle_gaps")}})
    for name, (value, limit) in run["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    log(result_line(cell, run, device_lib.describe(
        devices, run["observed"]["memory_peak_bytes"]), args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
