"""``serve_rehearsal.py`` for a serving cell whose model caches in SEVERAL
groups (``kv_cache.cache_groups``: layers that keep every position beside
layers that keep a window): the same compile-only rehearsal on a described
v5e, with a pool, a page table and a base per group as the engine builds
them (``engine.default_pool_blocks`` / ``group_table_widths``, nothing spelt
by hand).  Prints the weights, the pool BY GROUP, ``memory_analysis()`` of
the decode program and of the non-final and final prefill chunk at
``prefill_chunk`` (the temporaries by program), what the whole engine would
hold beside the largest program's temporaries, and the names the Mosaic
kernels carry in the compiled program.  A one-group cell gives what
``serve_rehearsal.py`` gives.

    JAX_PLATFORMS=cpu python3 benchmark/tools/serve_rehearsal_groups.py <workload>
"""
import dataclasses
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
from jax.sharding import SingleDeviceSharding       # noqa: E402

from harness import cells                           # noqa: E402

GB = 1e9
_CUSTOM_CALL = re.compile(
    r"%(\S+?)(?:\.\d+)? = \S+ custom-call\([^\n]*\"tpu_custom_call\"")


def _nbytes(tree):
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


def programs_of(cell, device):
    """``(params, pools by group, {name: (program, streams)})`` of the
    cell's engine as shapes on ``device``."""
    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving import kv_cache

    arch, config, mix = cell.architecture(), cell.config, cell.traffic
    model = arch.build_model(config, mix["model_overrides"])
    if hasattr(model.config, "pallas_interpret"):
        model = type(model)(dataclasses.replace(model.config,
                                                pallas_interpret=False))
    cfg = model.config
    one = SingleDeviceSharding(device)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree_util.tree_map(
        lambda l: struct(l.shape, l.dtype),
        jax.eval_shape(lambda: arch.init_params(model, 0)))
    e = mix["engine"]
    S, bs, W, C = e["max_slots"], e["kv_block_size"], \
        e["max_blocks_per_seq"], e["prefill_chunk"]
    groups = kv_cache.cache_groups(cfg)
    widths = serving.group_table_widths(cfg, W, bs, C)
    blocks = serving.default_pool_blocks(cfg, 1, S, W, bs, C)
    pools = [[struct(shape, cfg.dtype) for shape in
              kv_cache.pool_shapes(cfg, n, bs, False, g)
              if shape is not None] for g, n in enumerate(blocks)]
    several = len(groups) > 1

    def tables(rows, which):
        each = tuple(struct((rows, w[which]), jnp.int32) for w in widths)
        if not several:
            return each[0]
        return each, (None,) + tuple(struct((rows,), jnp.int32)
                                     for _ in widths[1:])

    programs = {"decode_step": (
        serving._make_decode_step(cfg, W, bs, False, 0.0, 0, 0.0, None,
                                  "data"),
        (tables(S, 0), struct((S,), jnp.int32),
         struct((S,), jnp.int32), struct((S,), jnp.bool_),
         struct((S,), jnp.int32), struct((S,), jnp.float32)))}
    for final in (False, True):
        programs[f"prefill_chunk{C}" + "_final" * final] = (
            serving._make_prefill_chunk(cfg, C, W, bs, False, final, 0.0, 0,
                                        0.0, None, "data"),
            (tables(1, 1), struct((C,), jnp.int32),
             struct((), jnp.int32), struct((1,), jnp.int32),
             struct((), jnp.int32)))
    return params, dict(zip((g.name for g in groups), pools)), programs


def rehearse(cell, device):
    params, pools, programs = programs_of(cell, device)
    held = _nbytes(params) + _nbytes(pools)
    print({"cell": cell.name, "weights_gb": _nbytes(params) / GB,
           "pool_gb": {name: _nbytes(p) / GB for name, p in pools.items()}},
          flush=True)
    flat = [t for p in pools.values() for t in p]
    worst = 0
    for name, (program, streams) in programs.items():
        compiled = program.lower(params, *flat, *streams).compile()
        mem = compiled.memory_analysis()
        worst = max(worst, mem.temp_size_in_bytes)
        print({"program": name,
               "argument_gb": mem.argument_size_in_bytes / GB,
               "temp_gb": mem.temp_size_in_bytes / GB,
               "output_gb": mem.output_size_in_bytes / GB,
               "alias_gb": mem.alias_size_in_bytes / GB,
               "kernels": sorted(set(_CUSTOM_CALL.findall(
                   compiled.as_text())))[:16]}, flush=True)
    print({"cell": cell.name, "engine_peak_gb": (held + worst) / GB,
           "what": "weights + pool + the largest program's temporaries"},
          flush=True)


def main(names):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    benchmark = cells.load_benchmark()
    for name in names:
        cell = cells.Cell(benchmark, name)
        assert cell.traffic["driver"] == "serve", name
        rehearse(cell, topo.devices[0])


if __name__ == "__main__":
    main(sys.argv[1:])
