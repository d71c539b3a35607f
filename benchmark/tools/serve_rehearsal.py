"""Compile-only rehearsal of ANY serving cell: ``compile_rehearsal.py``'s
serving half with no shape spelt by hand.  The weights' shapes come from the
architecture file's own ``init_params`` (``jax.eval_shape``), the pool's
from ``kv_cache.pool_shapes`` (one tensor where the model caches one row a
token, two for keys and values), so a configuration that is not GPT-2 is
rehearsed by the same lines.  Prints ``memory_analysis()`` of the decode
program and of the non-final and final prefill chunk at ``prefill_chunk``,
what the whole engine would hold beside the largest program's temporaries,
and the names the Mosaic kernels carry in the compiled program (what the
device trace will call them).

    JAX_PLATFORMS=cpu python3 benchmark/tools/serve_rehearsal.py <workload>
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


def rehearse(cell, device):
    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving import kv_cache

    arch, config, mix = cell.architecture(), cell.config, cell.traffic
    # the default backend is the CPU here, the target a described chip:
    # a model whose kernels take ``pallas_interpret`` lowers the Mosaic
    # kernels themselves, not the interpreter's loops
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
    # the engine's default pool: every slot's pages and the trash block
    pools = [struct(shape, cfg.dtype) for shape in
             kv_cache.pool_shapes(cfg, e.get("kv_blocks") or 1 + S * W, bs,
                                  False) if shape is not None]
    held = _nbytes(params) + _nbytes(pools)
    print({"cell": cell.name, "weights_gb": _nbytes(params) / GB,
           "pool_gb": _nbytes(pools) / GB}, flush=True)
    programs = {"decode_step": (
        serving._make_decode_step(cfg, W, bs, False, 0.0, 0, 0.0, None,
                                  "data"),
        (struct((S, W), jnp.int32), struct((S,), jnp.int32),
         struct((S,), jnp.int32), struct((S,), jnp.bool_),
         struct((S,), jnp.int32), struct((S,), jnp.float32)))}
    for final in (False, True):
        programs[f"prefill_chunk{C}" + "_final" * final] = (
            serving._make_prefill_chunk(cfg, C, W, bs, False, final, 0.0, 0,
                                        0.0, None, "data"),
            (struct((1, W), jnp.int32), struct((C,), jnp.int32),
             struct((), jnp.int32), struct((1,), jnp.int32),
             struct((), jnp.int32)))
    worst = 0
    for name, (program, streams) in programs.items():
        compiled = program.lower(params, *pools, *streams).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        worst = max(worst, mem.temp_size_in_bytes)
        print({"program": name,
               "argument_gb": mem.argument_size_in_bytes / GB,
               "temp_gb": mem.temp_size_in_bytes / GB,
               "output_gb": mem.output_size_in_bytes / GB,
               "alias_gb": mem.alias_size_in_bytes / GB,
               "kernels": sorted(set(
                   n for n in _CUSTOM_CALL.findall(text)))[:12]},
              flush=True)
    print({"cell": cell.name,
           "engine_peak_gb": (held + worst) / GB,
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
