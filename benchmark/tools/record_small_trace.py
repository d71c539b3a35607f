"""Records the small trace the tests check ``read_xplane`` against
(``testdata/small_tpu.xplane.pb``): three steps of a toy program on one
chip, a nested loop inside (a ``while`` op covering its body's ops), a host
pause between steps (an idle gap), under the benchmark's annotations.

    python3 benchmark/tools/record_small_trace.py <output directory>
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import trace_reduce                    # noqa: E402


def main(out_dir):
    assert jax.devices()[0].platform == "tpu", "record on the chip"

    @jax.jit
    def step(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, None
        return jax.lax.scan(body, x, None, length=3)[0]

    x = jnp.ones((512, 512), jnp.bfloat16)
    step(x).block_until_ready()
    work = os.path.join(out_dir, "_recording")
    shutil.rmtree(work, ignore_errors=True)
    jax.profiler.start_trace(work)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:train_batch"):
                y = step(x)
            with jax.profiler.TraceAnnotation("bench:fetch_loss"):
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench:wait_arrival"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(work, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    shutil.copy(found, os.path.join(out_dir, "small_tpu.xplane.pb"))
    shutil.rmtree(work, ignore_errors=True)
    print(trace_reduce.reduce_events(trace_reduce.read_xplane(
        os.path.join(out_dir, "small_tpu.xplane.pb"))))


if __name__ == "__main__":
    main(sys.argv[1])
