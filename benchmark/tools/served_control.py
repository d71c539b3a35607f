"""The control of a serving cell's token rule, on the chip at the cell's own
size (the benchmark's own runs never run it):

    python3 benchmark/tools/served_control.py <workload> <seconds> <seed> ...

For each seed: one run of the cell through its driver as ``run.py`` makes
it, but with a window of ``seconds`` (long enough to finish the mix's
longest requests), and beside every reference forward of the check the
CONTROL's forward of the same prompt and served tokens: the architecture's
``reference_logits(..., control_bits=4)``, the reference in the nearest
precision under the configuration's bf16.  The control need not decode:
at each judged position the token it puts first is read as if it had been
served.  Prints, per seed, what the rule saw of the program and of the
control, and at the end the program's largest and the control's smallest
reading of each number: a limit stands between the two, with room on both
sides.  One process for all seeds: one chip, one compile.
"""
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import numpy as np                                  # noqa: E402

from harness import cells                           # noqa: E402
from harness import device as device_lib            # noqa: E402

CONTROL_BITS = 4


class WithControl:
    """An architecture whose every judged reference forward is followed by
    the control's, on the same ids and rows."""

    def __init__(self, arch, row_gaps):
        self.arch, self.row_gaps = arch, row_gaps
        self.spacings, self.sigmas = [], []

    def __getattr__(self, name):
        return getattr(self.arch, name)

    def reference_logits(self, weights, config, ids, rows=None):
        logits = self.arch.reference_logits(weights, config, ids, rows)
        if rows is not None:
            low = self.arch.reference_logits(weights, config, ids, rows,
                                             control_bits=CONTROL_BITS)
            below, in_sigma = self.row_gaps(
                np.asarray(logits[0]), np.asarray(low[0]).argmax(axis=-1))
            self.spacings.append(below)
            self.sigmas.append(in_sigma)
        return logits


def readings(cell, devices, seconds, seeds, log=print):
    """Per seed one run of ``cell`` with the control beside its check:
    (what the rule saw of the program, of the control, whether the control
    was held), each a list over the seeds."""
    driver, arch = cell.driver(), cell.architecture()
    rule = arch.served_check(cell.config)["rule"]
    program, control, control_held = [], [], []
    for seed in seeds:
        logged = {}
        with_control = WithControl(arch, driver.row_gaps)
        cell.architecture = lambda: with_control
        run = driver.run(cell, devices, seed=seed, seconds=seconds,
                         trace=False, process_start=time.perf_counter(),
                         log=logged.update)
        held, seen = driver.judge_gaps(
            np.concatenate(with_control.spacings),
            np.concatenate(with_control.sigmas), rule)
        program.append(logged["reference"])
        control.append(seen)
        control_held.append(held)
        log(json.dumps({
            "seed": seed, "program_correct": run["correct"],
            "program": {k: v for k, v in logged["reference"].items()
                        if k not in ("rule", "failures", "over_near_best")},
            "control_held": held, "control": seen}, default=float))
    return program, control, control_held


def main(workload, seconds, seeds):
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cell = cells.Cell(cells.load_benchmark(withheld=True), workload)
    devices = device_lib.require_tpu(cell.chips)
    enable_compile_cache()
    rule = cell.architecture().served_check(cell.config)["rule"]
    program, control, control_held = readings(cell, devices, seconds, seeds)
    names = ("worst_spacings_below_best", "share_within",
             "worst_sigma_below_best")
    print(json.dumps({
        "workload": workload, "seeds": len(seeds), "rule": rule,
        "program_largest": {n: max(p[n] for p in program) for n in names},
        "program_smallest": {n: min(p[n] for p in program) for n in names},
        "control_smallest": {n: min(c[n] for c in control) for n in names},
        "control_largest": {n: max(c[n] for c in control) for n in names},
        "control_held_on_seeds": sum(control_held)}, default=float))


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), [int(s) for s in sys.argv[3:]])
