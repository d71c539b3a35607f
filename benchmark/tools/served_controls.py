"""``served_control.py`` with SEVERAL controls beside one run of a serving
cell, on the chip at the cell's own size (the benchmark's own runs never run
it):

    python3 benchmark/tools/served_controls.py <workload> <seconds> <seed> ...

For each seed: one run of the cell through its driver as ``run.py`` makes
it, with a window of ``seconds``, and beside every reference forward of the
check each CONTROL's forward of the same prompt and served tokens:

``bits4``
    ``reference_logits(..., control_bits=4)``: the reference in the nearest
    precision under the configuration's bf16 (what ``served_control.py``
    runs alone);
``no_window``
    the reference handed a copy of the configuration whose
    ``sliding_window`` is the whole context: every sliding layer sees
    everything.  A check that cannot tell a window from none guards
    nothing.  Run only where the configuration states a window.

A control need not decode: at each judged position the token it puts first
is read as if it had been served.  Prints, per seed, what the rule saw of
the program and of each control, and at the end the program's largest and
each control's smallest reading of each number: a limit stands between the
two, with room on both sides.  One process for all seeds: one chip, one
compile.
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

NAMES = ("worst_spacings_below_best", "share_within",
         "worst_sigma_below_best")


def controls_of(config):
    """name -> (configuration the control's reference is handed, bits)."""
    out = {"bits4": (config, 4)}
    if config.get("sliding_window"):
        out["no_window"] = (dict(
            config, sliding_window=config["max_position_embeddings"]), None)
    return out


class WithControls:
    """An architecture whose every judged reference forward is followed by
    each control's, on the same ids and rows."""

    def __init__(self, arch, row_gaps, controls):
        self.arch, self.row_gaps, self.controls = arch, row_gaps, controls
        self.gaps = {name: ([], []) for name in controls}

    def __getattr__(self, name):
        return getattr(self.arch, name)

    def reference_logits(self, weights, config, ids, rows=None):
        logits = self.arch.reference_logits(weights, config, ids, rows)
        if rows is not None:
            for name, (held, bits) in self.controls.items():
                low = self.arch.reference_logits(weights, held, ids, rows,
                                                 control_bits=bits)
                below, in_sigma = self.row_gaps(
                    np.asarray(logits[0]),
                    np.asarray(low[0]).argmax(axis=-1))
                self.gaps[name][0].append(below)
                self.gaps[name][1].append(in_sigma)
        return logits


def main(workload, seconds, seeds):
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cell = cells.Cell(cells.load_benchmark(withheld=True), workload)
    devices = device_lib.require_tpu(cell.chips)
    enable_compile_cache()
    driver, arch = cell.driver(), cell.architecture()
    rule = arch.served_check(cell.config)["rule"]
    controls = controls_of(cell.config)
    program, seen_of = [], {name: [] for name in controls}
    held_of = {name: 0 for name in controls}
    for seed in seeds:
        logged = {}
        with_controls = WithControls(arch, driver.row_gaps, controls)
        cell.architecture = lambda: with_controls
        run = driver.run(cell, devices, seed=seed, seconds=seconds,
                         trace=False, process_start=time.perf_counter(),
                         log=logged.update)
        program.append(logged["reference"])
        line = {"seed": seed, "program_correct": run["correct"],
                "tokens_per_s": run["end_to_end"]["serve_tokens_per_s"],
                "program": {k: v for k, v in logged["reference"].items()
                            if k not in ("rule", "failures",
                                         "over_near_best")}}
        for name, (spacings, sigmas) in with_controls.gaps.items():
            held, seen = driver.judge_gaps(np.concatenate(spacings),
                                           np.concatenate(sigmas), rule)
            seen_of[name].append(seen)
            held_of[name] += held
            line[name] = dict(seen, held=held)
        print(json.dumps(line, default=float), flush=True)
    summary = {
        "workload": workload, "seeds": len(seeds), "rule": rule,
        "program_largest": {n: max(p[n] for p in program) for n in NAMES},
        "program_smallest": {n: min(p[n] for p in program) for n in NAMES}}
    for name, seen in seen_of.items():
        summary[name] = {
            "smallest": {n: min(c[n] for c in seen) for n in NAMES},
            "largest": {n: max(c[n] for c in seen) for n in NAMES},
            "held_on_seeds": held_of[name]}
    print(json.dumps(summary, default=float))


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), [int(s) for s in sys.argv[3:]])
