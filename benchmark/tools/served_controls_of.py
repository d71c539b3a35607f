"""``served_controls.py`` for an architecture that brings its OWN controls
(``controls_of(config)`` in its architecture file: name -> (the configuration
the control's forward is handed, ``control_bits``)): beside the precision
control, one control a mechanism of the model, the reference with it taken
out.  Everything else is ``served_controls.py``'s: one run of the cell a
seed through its driver, beside every reference forward of the check each
control's forward of the same prompt and served tokens, and the rule's
verdict on each.

    python3 benchmark/tools/served_controls_of.py <workload> <seconds> <seed> ...
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import served_controls                              # noqa: E402
from harness import cells                           # noqa: E402


def main(workload, seconds, seeds):
    cell = cells.Cell(cells.load_benchmark(withheld=True), workload)
    served_controls.controls_of = cell.architecture().controls_of
    served_controls.main(workload, seconds, seeds)


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), [int(s) for s in sys.argv[3:]])
