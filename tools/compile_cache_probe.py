"""Does a jit over a subset of the chips survive the persistent compile cache?

    chiprun --chips 4 -- bash -c 'export JAX_COMPILATION_CACHE_DIR=/tmp/p; \
        python tools/compile_cache_probe.py; python tools/compile_cache_probe.py'

The first run compiles every case and fills the cache, the second reads them
back; a halted core ends the process, so name the cases to go on with.  Seen
on four TPU v5 lite, jax 0.9.0 (PR 21): read back from the cache, [0, 1],
[2] and [0, 2] run; [2, 3] (with a collective, without one, donated or not)
and [1, 3] halt their cores — "Invalid logical z: enhanced-barrier-parent-
phase-1".  Compiled in the process, all of them run.  PipelineEngine turns
the cache off for that reason (runtime/pipe/engine.py); when a run of this
probe passes twice, it no longer has to.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CASES = {"01-allreduce": ("allreduce", [0, 1]),
         "2-elementwise": ("elementwise", [2]),
         "02-allreduce": ("allreduce", [0, 2]),
         "23-elementwise": ("elementwise", [2, 3]),
         "23-donated": ("donated", [2, 3]),
         "23-allreduce": ("allreduce", [2, 3]),
         "13-allreduce": ("allreduce", [1, 3]),
         "12-allreduce": ("allreduce", [1, 2])}


def allreduce(x, w):
    return x @ w


def elementwise(x):
    return x * 2.0 + 1.0


def run(kind, ids):
    devices = jax.devices()
    mesh = Mesh(np.array([devices[i] for i in ids]), ("model",))

    def on(*spec):
        return NamedSharding(mesh, P(*spec))

    n = len(ids)
    if kind == "allreduce":
        x = jax.device_put(jnp.ones((8, 256 * n)), on(None, "model"))
        w = jax.device_put(jnp.ones((256 * n, 128)), on("model", None))
        out, want = jax.jit(allreduce, out_shardings=on())(x, w), 256.0 * n
    else:
        x = jax.device_put(jnp.ones((8 * n, 128)), on("model"))
        donate = (0,) if kind == "donated" else ()
        out, want = jax.jit(elementwise, donate_argnums=donate)(x), 3.0
    return float(np.asarray(jax.device_get(out)).ravel()[0]) == want


def main(names):
    # cache every program, however small or quick to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    events = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name.rsplit("/", 1)[-1])
        if "compilation_cache/cache_" in name else None)
    for name in names or CASES:
        kind, ids = CASES[name]
        seen = len(events)
        print(f"{name}: devices {ids} ...", flush=True)
        ok = run(kind, ids)
        print(f"{name}: {'ok' if ok else 'WRONG VALUE'} {events[seen:]}",
              flush=True)
        if not ok:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
