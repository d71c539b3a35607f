"""Compile-only rehearsal: a cell's programs at real size for a DESCRIBED
v5e 2x2 host, with no chip attached (the on-chip-measurement guide,
section 2).  Prints ``memory_analysis()`` per chip and what the compiled
program holds (Mosaic kernels, collectives).  Nothing runs, so this gives no
time and no result; it says whether the chip's compiler accepts the program
and whether it fits, which is what sizes a cell before chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_rehearsal.py <workload>

A tool of the one who defines a cell, not part of a run: it reaches into
the engines (their abstract state, their jitted functions) where ``run.py``
uses only their entry points.
"""
import os
import re
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P      # noqa: E402
from jax.sharding import SingleDeviceSharding       # noqa: E402

from harness import cells                           # noqa: E402

GB = 1e9


def report(name, compiled):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    collectives = {}
    for kind in re.findall(r"= \S+ (all-reduce|all-gather|reduce-scatter|"
                           r"collective-permute|all-to-all)", text):
        collectives[kind] = collectives.get(kind, 0) + 1
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print({"program": name,
           "argument_gb": mem.argument_size_in_bytes / GB,
           "output_gb": mem.output_size_in_bytes / GB,
           "alias_gb": mem.alias_size_in_bytes / GB,
           "temp_gb": mem.temp_size_in_bytes / GB,
           "code_gb": mem.generated_code_size_in_bytes / GB,
           "per_chip_total_gb": total / GB,
           "tpu_custom_calls": text.count("tpu_custom_call"),
           "collectives": collectives}, flush=True)


def with_sharding(tree, shardings):
    return jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def rehearse_train(cell, devices):
    import deepspeed_tpu
    from deepspeed_tpu.runtime.engine import TrainState

    arch, config, job = cell.architecture(), cell.config, cell.traffic
    chips = cell.chips
    micro, gas = int(job["micro_batch_per_chip"]), \
        int(job["gradient_accumulation"])
    model = arch.build_model(config, job["model_overrides"])
    ds_config = dict(job["ds_config"],
                     train_batch_size=micro * gas * chips,
                     train_micro_batch_size_per_gpu=micro,
                     gradient_accumulation_steps=gas,
                     mesh={"data": chips, "model": 1, "pipe": 1,
                           "allow_partial": True},
                     steps_per_print=10 ** 9)
    with mock.patch.object(jax, "devices", lambda *a, **k: list(devices)):
        engine = deepspeed_tpu.initialize(model=model,
                                          config_params=ds_config)[0]
    mesh = engine.mesh
    rep = NamedSharding(mesh, P())
    seq = int(job["seq_len"])
    rows = micro * chips
    ids = jax.ShapeDtypeStruct((rows, seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", None)))
    template = jax.eval_shape(
        lambda r, b: engine.module.init(r, b), jax.random.PRNGKey(0),
        {"input_ids": ids, "labels": ids})
    f32 = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), template)
    engine._build_shardings(f32)
    sh = engine._shardings
    master = with_sharding(f32, sh.master)
    params = with_sharding(jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, engine.compute_dtype),
        template), sh.params)
    opt_state = with_sharding(jax.eval_shape(engine.optimizer.init_state,
                                             f32), sh.opt_state)
    accum = with_sharding(f32, sh.accum)

    def scalar(dtype, shape=()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    state = TrainState(step=scalar(jnp.int32), micro_step=scalar(jnp.int32),
                       params=params, opt_state=opt_state, master=master,
                       accum=accum, scaler=None,
                       skipped_steps=scalar(jnp.int32),
                       rng=scalar(jnp.uint32, (2,)))
    stacked = jax.ShapeDtypeStruct(
        (gas, rows, seq), jnp.int32,
        sharding=NamedSharding(mesh, P(None, "data", None)))
    engine._compile()
    with jax.set_mesh(mesh):
        compiled = engine._fused_callable().lower(
            state, {"input_ids": stacked, "labels": stacked},
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)).compile()
    report(f"{cell.name}: fused_train_step", compiled)
    # the program ``engine.eval_loss`` jits for the reference check
    sample = jax.ShapeDtypeStruct(
        (int(job["reference_rows"]), seq), jnp.int32,
        sharding=NamedSharding(mesh, P("data", None)))
    with jax.set_mesh(mesh):
        compiled = jax.jit(lambda st, b: engine.module.loss(
            st.params, b, st.rng, train=False)[0]).lower(
                state, {"input_ids": sample, "labels": sample}).compile()
    report(f"{cell.name}: eval_loss", compiled)


def rehearse_serve(cell, devices):
    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving import kv_cache

    arch, config, mix = cell.architecture(), cell.config, cell.traffic
    model = arch.build_model(config, mix["model_overrides"])
    cfg = model.config
    one = SingleDeviceSharding(devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ids = np.zeros((1, 8), np.int32)
    params = jax.tree_util.tree_map(
        lambda l: struct(l.shape, l.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       {"input_ids": ids, "labels": ids}))
    e = mix["engine"]
    S, bs, W, C = e["max_slots"], e["kv_block_size"], \
        e["max_blocks_per_seq"], e["prefill_chunk"]
    # the engine's default pool: every slot's pages and the trash block
    pool = struct(kv_cache.pool_shapes(cfg, 1 + S * W, bs, False)[0],
                  cfg.dtype)
    decode = serving._make_decode_step(cfg, W, bs, False, 0.0, 0, 0.0,
                                       None, "data")
    report(f"{cell.name}: decode_step", decode.lower(
        params, pool, pool, struct((S, W), jnp.int32),
        struct((S,), jnp.int32), struct((S,), jnp.int32),
        struct((S,), jnp.bool_), struct((S,), jnp.int32),
        struct((S,), jnp.float32)).compile())
    for final in (False, True):
        prefill = serving._make_prefill_chunk(cfg, C, W, bs, False, final,
                                              0.0, 0, 0.0, None, "data")
        report(f"{cell.name}: prefill_chunk{C}" + ("_final" * final),
               prefill.lower(params, pool, pool, struct((1, W), jnp.int32),
                             struct((C,), jnp.int32), struct((), jnp.int32),
                             struct((1,), jnp.int32),
                             struct((), jnp.int32)).compile())


def main(names):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    benchmark = cells.load_benchmark()
    for name in names or [w["name"] for w in benchmark["workloads"]]:
        cell = cells.Cell(benchmark, name)
        kind = cell.traffic["driver"]
        {"train": rehearse_train, "serve": rehearse_serve}[kind](
            cell, topo.devices[:cell.chips])


if __name__ == "__main__":
    main(sys.argv[1:])
