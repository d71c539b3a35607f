"""Tells apart what stops the serve thread for 0.1 s in some windows of an
open-loop serving cell (PERF.md section 7, PR 45): one run of ``run.py``'s
``main`` in this process, a cell that ``benchmark/withheld/`` holds
included, with

- every ``InferenceEngine.step`` snapshotted at entry and return: the
  clock, the thread's ``/proc/thread-self/schedstat`` (nanoseconds on a
  core, nanoseconds waiting for one; zeros where the kernel has no such
  file, as the chip machines' sandbox kernel has not) and
  ``getrusage(RUSAGE_THREAD)``
  (user and system time, page faults, context switches), so that a stretch
  of over 40 ms says whether the thread RAN (a collection, a retrace),
  WAITED FOR A CORE (the machine took it) or SLEPT (a lock, the runtime);
- every collection timed (``gc.callbacks``: start, length, generation);
- with ``--watchdog 1`` a second thread that wakes every 10 ms and, when a
  step has lasted 40 ms, takes every thread's stack
  (``sys._current_frames``) and notes its own late wake-ups (late with the
  step: the interpreter's lock was held, or the process stopped);
- a sidecar PROCESS without JAX that sleeps 1 ms at a time and notes its
  own stalls by the same monotonic clock: a stall in both is the machine's.

``--gc`` runs the window as the mix states it (``asis``), with
``freeze_setup_objects`` ignored (``nofreeze``) or with the collector off
besides (``off``).  Everything is written to ``<out>`` as JSON (and
``<out>.sidecar``); the run's own lines go where ``run.py`` sends them.

    python3 benchmark/tools/pause_probe.py <out.json> --watchdog 1 -- \\
        --workload gpt2-350m.serve-chat --seed 7 --seconds 30 --trace 0
"""
import argparse
import gc
import json
import os
import resource
import subprocess
import sys
import threading
import time
import traceback

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

SLOW_S = 0.040      # a stretch between two snapshots that is reported

SIDECAR = r"""
import sys, time
out = open(sys.argv[1], "w")
last = time.monotonic()
while True:
    time.sleep(0.001)
    now = time.monotonic()
    if now - last > 0.02:
        out.write("%.6f %.6f\n" % (last, now))
        out.flush()
    last = now
"""


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("out")
    parser.add_argument("--watchdog", type=int, choices=(0, 1), default=0)
    parser.add_argument("--gc", choices=("asis", "nofreeze", "off"),
                        default="asis")
    own = sys.argv[1:]
    run_args = own[own.index("--") + 1:] if "--" in own else []
    args = parser.parse_args(own[:own.index("--")] if "--" in own else own)

    # before anything touches JAX: the sidecar must not hold the chip, and
    # ``run`` reads the clock at import for ``setup_s``
    sidecar = subprocess.Popen([sys.executable, "-c", SIDECAR,
                                args.out + ".sidecar"])
    import run as bench_run
    from harness import cells
    from deepspeed_tpu.serving import InferenceEngine

    entered = cells.load_benchmark
    cells.load_benchmark = lambda path=None, withheld=True: \
        entered(path, withheld)

    clock = time.perf_counter
    collections, started = [], [0.0]

    def on_collection(phase, info):
        if phase == "start":
            started[0] = clock()
        elif clock() - started[0] > 0.002 or info["generation"] == 2:
            collections.append({"at": started[0],
                                "ms": 1e3 * (clock() - started[0]),
                                "generation": info["generation"],
                                "collected": info["collected"]})

    gc.callbacks.append(on_collection)
    freeze, unfreeze = gc.freeze, gc.unfreeze
    if args.gc == "nofreeze":
        gc.freeze = gc.unfreeze = lambda: None
    elif args.gc == "off":
        gc.freeze = lambda: (freeze(), gc.disable())
        gc.unfreeze = lambda: (gc.enable(), unfreeze())

    snapshots, schedstat = [], []
    step = {"began": None, "dumped": False, "over": False}

    def snapshot(kind):
        if not schedstat:
            try:
                schedstat.append(os.open("/proc/thread-self/schedstat",
                                         os.O_RDONLY))
            except OSError:     # a sandbox kernel (gVisor) has no such file
                schedstat.append(None)
        on_core, waiting = (0, 0) if schedstat[0] is None else \
            os.pread(schedstat[0], 100, 0).split()[:2]
        r = resource.getrusage(resource.RUSAGE_THREAD)
        snapshots.append((kind, clock(), int(on_core), int(waiting),
                          r.ru_utime, r.ru_stime, r.ru_minflt, r.ru_majflt,
                          r.ru_nvcsw, r.ru_nivcsw))

    engine_step = InferenceEngine.step

    def probed_step(self):
        snapshot("step")
        step["began"], step["dumped"] = clock(), False
        try:
            return engine_step(self)
        finally:
            step["began"] = None
            snapshot("between")

    InferenceEngine.step = probed_step

    stacks, late_wakeups = [], []

    def watchdog():
        last = clock()
        while not step["over"]:
            time.sleep(0.01)
            now = clock()
            if now - last > SLOW_S:
                late_wakeups.append({"from": last, "to": now})
            last = now
            began = step["began"]
            if began is not None and now - began > SLOW_S \
                    and not step["dumped"]:
                step["dumped"] = True
                names = {t.ident: t.name for t in threading.enumerate()}
                stacks.append({"at": now, "step_began": began, "threads": {
                    f"{names.get(ident, '?')}:{ident}":
                        traceback.format_stack(frame)[-14:]
                    for ident, frame in sys._current_frames().items()}})

    if args.watchdog:
        threading.Thread(target=watchdog, daemon=True,
                         name="pause-probe-watchdog").start()

    code = 1
    try:
        code = bench_run.main(run_args)
    finally:
        step["over"] = True
        sidecar.terminate()
        sidecar.wait()
        keys = ("on_core_ms", "waiting_for_a_core_ms", "user_ms",
                "system_ms", "minor_faults", "major_faults",
                "voluntary_switches", "involuntary_switches")
        scale = (1e-6, 1e-6, 1e3, 1e3, 1, 1, 1, 1)
        slow = [dict({"in": a[0], "from": a[1], "to": b[1],
                      "s_after_process_start":
                          a[1] - bench_run.PROCESS_START,
                      "ms": 1e3 * (b[1] - a[1])},
                     **{k: f * (y - x) for k, f, x, y
                        in zip(keys, scale, a[2:], b[2:])})
                for a, b in zip(snapshots, snapshots[1:])
                if b[1] - a[1] > SLOW_S]
        with open(args.out, "w") as f:
            json.dump({"run_args": run_args, "exit_code": code,
                       "gc": args.gc, "watchdog": bool(args.watchdog),
                       "process_start": bench_run.PROCESS_START,
                       "snapshots": len(snapshots),
                       "slow_stretches": slow, "collections": collections,
                       "stacks": stacks, "late_wakeups": late_wakeups,
                       "threads_at_end": [t.name for t
                                          in threading.enumerate()]},
                      f, indent=1)
    return code


if __name__ == "__main__":
    sys.exit(main())
