"""The benchmark's use of ``jax.profiler``: one traced stretch of a run,
written inside the checkout, reduced and thrown away."""
import contextlib
import glob
import os
import shutil

import jax

from harness import trace_reduce
from harness.cells import CHECKOUT

TRACE_ROOT = os.path.join(CHECKOUT, ".benchmark_out", "trace")


def span(name, on):
    """A host annotation on the profiler's clock, or nothing when the run
    is not traced.  Idle gaps of the device are named by these."""
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


class TracedStretch:
    """One traced stretch of a run under a ``bench:window`` annotation:
    ``start()``, the work, ``stop()``, and later, outside anything timed,
    ``reduce()``: the reduced trace, or None where nothing ran on a
    device."""

    def __init__(self, cell_name):
        self.dir = os.path.join(TRACE_ROOT, cell_name)
        self.running = False

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._window.__enter__()
        self.running = True

    def stop(self):
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False

    def reduce(self):
        files = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        reduction = None
        if files:
            reduction = trace_reduce.reduce_events(
                trace_reduce.read_xplane(files[0]))
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:                # for looking at a trace by hand
                os.makedirs(keep, exist_ok=True)
                shutil.copy(files[0], keep)
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduction
