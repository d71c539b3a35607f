"""Memory observability (runtime/memory_accounting.py, ISSUE 15).

The load-bearing acceptance properties:

- **Measured peaks per jit on every engine**: `memory_report()` carries
  `memory_analysis()` (argument/output/temp/alias + derived peak) for
  every registered step jit on the stage-2, stage-3, ZB-stash and
  serving-decode configs, with the analytic argument model matching the
  compiler within 15% (shard-shape-exact in practice).
- **One compile per jit**: arming MFU and memory together shares one
  lazily-compiled object; reading the memory report after the MFU
  report costs ZERO extra XLA compiles.
- **Disarmed is free**: engines without telemetry still report the
  analytic side, and the compiled programs are bit-identical with zero
  extra compiles (covered jointly with the telemetry pin).
- **Cross-check is load-bearing**: an analytic claim >15% under the
  compiler's measured bytes warns loudly at report time.
"""
import logging as _logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime import memory_accounting as ma
from deepspeed_tpu.runtime.comm_accounting import LeafSpec
from deepspeed_tpu.serving.metrics import CompilationCounter
from deepspeed_tpu.utils.logging import logger as ds_logger
from tests.unit.simple_model import (SimpleModel, make_stack_specs,
                                     random_dataloader)

HIDDEN = 16


# ---------------------------------------------------------------------------
# normalizers
# ---------------------------------------------------------------------------

def test_normalize_memory_analysis_real_compiled():
    f = jax.jit(lambda x, w: jnp.tanh(x @ w).sum())
    compiled = f.lower(jnp.ones((8, 16)), jnp.ones((16, 16))).compile()
    m = ma.normalize_memory_analysis(compiled)
    assert m["modeled"]
    assert m["argument_bytes"] == (8 * 16 + 16 * 16) * 4
    assert m["output_bytes"] == 4
    assert m["temp_bytes"] is not None and m["temp_bytes"] >= 0
    # the backend's own peak wins where it reports one (buffers that are
    # never live together share room, so it may be under the sum); the
    # derived footprint is the fallback, and then it IS the sum
    derived = (m["argument_bytes"] + m["output_bytes"]
               - m["alias_bytes"] + m["temp_bytes"])
    own = getattr(compiled.memory_analysis(), "peak_memory_in_bytes", None)
    if own is None:
        assert m["peak_bytes"] == derived
    else:
        assert m["peak_bytes"] == own and 0 < own <= derived


def test_normalize_memory_analysis_variants():
    # backend reports nothing
    empty = ma.normalize_memory_analysis(None)
    assert not empty["modeled"] and empty["peak_bytes"] is None
    # dict with the xla field names
    d = ma.normalize_memory_analysis({
        "argument_size_in_bytes": 10, "output_size_in_bytes": 4,
        "temp_size_in_bytes": 2, "alias_size_in_bytes": 4,
        "generated_code_size_in_bytes": 0})
    assert d["peak_bytes"] == 10 + 4 - 4 + 2
    # dict with plain *_bytes names and an explicit backend peak
    d2 = ma.normalize_memory_analysis(
        {"argument_bytes": 1, "peak_memory_in_bytes": 99})
    assert d2["argument_bytes"] == 1 and d2["peak_bytes"] == 99
    assert d2["modeled"]

    # object whose memory_analysis raises (plugin backend quirk)
    class Broken:
        def memory_analysis(self):
            raise NotImplementedError("no stats on this backend")

    b = ma.normalize_memory_analysis(Broken())
    assert not b["modeled"] and "no stats" in b["error"]

    # object missing attributes entirely
    class Bare:
        pass

    assert not ma.normalize_memory_analysis(Bare())["modeled"]


def test_normalize_memory_stats_variants():
    # the real CPU device reports nothing — honest None, not a crash
    assert ma.normalize_memory_stats(jax.devices()[0]) is None
    assert ma.normalize_memory_stats(None) is None
    assert ma.normalize_memory_stats({}) is None
    got = ma.normalize_memory_stats(
        {"bytes_in_use": 7, "bytes_limit": 100})
    assert got == {"bytes_in_use": 7, "peak_bytes_in_use": None,
                   "bytes_limit": 100}

    class Angry:
        def memory_stats(self):
            raise RuntimeError("unimplemented")

    assert ma.normalize_memory_stats(Angry()) is None


def test_device_memory_report_cpu_honest_nones():
    rep = ma.device_memory_report()
    assert len(rep) == len(jax.local_devices())
    for entry in rep:
        assert entry["platform"] == "cpu"
        assert entry["bytes_in_use"] is None
        assert entry["headroom_bytes"] is None

    class Fake:
        id, device_kind, platform = 0, "tpu v5e", "tpu"

        def memory_stats(self):
            return {"bytes_in_use": 30, "peak_bytes_in_use": 40,
                    "bytes_limit": 100}

    entry = ma.device_memory_report([Fake()])[0]
    assert entry["headroom_bytes"] == 70
    assert entry["peak_bytes_in_use"] == 40


# ---------------------------------------------------------------------------
# analytic component model (pure shape math)
# ---------------------------------------------------------------------------

def _leaves(dp=8):
    shapes = [("w1", (64, 64)), ("b1", (64,)), ("w2", (64, 8))]
    from deepspeed_tpu.runtime.comm_accounting import zero_shard_dim

    return [LeafSpec(name=n, shape=s, shard_dim=zero_shard_dim(s, dp))
            for n, s in shapes]


def test_train_memory_report_zero_ladder():
    leaves = _leaves()
    peaks = {}
    for stage in (0, 1, 2, 3):
        rep = ma.train_memory_report(leaves, 8, zero_stage=stage,
                                     compute_dtype="bfloat16")
        peaks[stage] = rep["peak_bytes"]
        assert rep["persistent_bytes"] == sum(rep["components"].values())
    assert peaks[0] > peaks[1] > peaks[2] > peaks[3]
    # offload: no device accum/master/optimizer state at all
    off = ma.train_memory_report(leaves, 8, zero_stage=2,
                                 compute_dtype="bfloat16",
                                 cpu_offload=True)
    assert off["components"]["optimizer_state_bytes"] == 0
    assert off["components"]["grad_accum_bytes"] == 0
    assert off["peak_bytes"] == off["components"]["params_bytes"]
    # fp32 compute has no master; bf16 carries a sharded fp32 master
    fp32 = ma.train_memory_report(leaves, 8, zero_stage=2,
                                  compute_dtype="float32")
    assert fp32["components"]["master_bytes"] == 0
    bf16 = ma.train_memory_report(leaves, 8, zero_stage=2,
                                  compute_dtype="bfloat16")
    assert bf16["components"]["master_bytes"] > 0
    # qgZ scratch is transient and scales with the largest leaf
    q = ma.train_memory_report(leaves, 8, zero_stage=2,
                               compute_dtype="bfloat16",
                               quantized_gradients=True)
    assert q["transient"]["quantization_scratch_bytes"] > 0
    assert q["peak_bytes"] > bf16["peak_bytes"]
    # indivisible leaves stay whole: dp=7 shards nothing of (64, 64)
    odd = ma.train_memory_report(leaves, 7, zero_stage=3,
                                 compute_dtype="bfloat16")
    assert odd["components"]["params_bytes"] == \
        sum(l.elements for l in leaves) * 2


def test_leaf_device_bytes_shard_exact():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(8), ("d",))
    x = jax.device_put(jnp.zeros((16, 4), jnp.float32),
                       NamedSharding(mesh, P("d")))
    assert ma.leaf_device_bytes(x) == 16 * 4 * 4 // 8
    rep = jax.device_put(jnp.zeros((5,), jnp.float32),
                         NamedSharding(mesh, P()))
    assert ma.leaf_device_bytes(rep) == 20
    assert ma.leaf_device_bytes(np.zeros((3, 3), np.int8)) == 9


def test_kv_pool_bytes_exact_vs_allocated_pool():
    from deepspeed_tpu.models.gpt2 import GPT2Config
    from deepspeed_tpu.serving.kv_cache import PagedKVPool

    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2,
                     n_head=4, dtype=jnp.float32)
    for quant in (False, True):
        pool = PagedKVPool(cfg, num_blocks=10, block_size=4,
                           quantize_kv=quant)
        actual = sum(int(np.prod(t.shape)) * t.dtype.itemsize
                     for t in pool.tensors.arrays)
        assert pool.device_bytes() == actual, quant
        assert pool.stats()["pool_device_bytes"] == actual


def test_kv_pool_bytes_prices_shared_blocks_once():
    """ISSUE 17: under prefix sharing, a logical demand of N blocks
    where S blocks carry R references each needs only
    N - S*(R-1) physical blocks — shared storage is priced ONCE, and
    the no-sharing defaults reproduce the un-extended builder exactly
    (the checked-in budget entries must not move)."""
    from deepspeed_tpu.runtime.memory_accounting import kv_pool_bytes

    base = dict(n_layer=2, n_head=4, block_size=4, head_dim=8,
                kv_dtype="bfloat16")
    for quant in (False, True):
        plain = kv_pool_bytes(2, 64, 4, 4, 8, kv_dtype="bfloat16",
                              quantized=quant)
        shared = kv_pool_bytes(2, 64, 4, 4, 8, kv_dtype="bfloat16",
                               quantized=quant, shared_blocks=8,
                               shared_refs=5)
        physical = kv_pool_bytes(2, 64 - 8 * 4, 4, 4, 8,
                                 kv_dtype="bfloat16", quantized=quant)
        assert shared == physical < plain, (quant, base)
        # shared_refs=1 (nothing actually shared) is the identity
        assert kv_pool_bytes(2, 64, 4, 4, 8, kv_dtype="bfloat16",
                             quantized=quant, shared_blocks=8,
                             shared_refs=1) == plain


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def _cfg(tele=True, **over):
    c = {
        "train_batch_size": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
    }
    if tele:
        c["telemetry"] = {"enabled": True,
                          "peak_tflops_per_device": 0.001}
    c.update(over)
    return c


def _engine(tele=True, **over):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(HIDDEN), config_params=_cfg(tele, **over))
    return engine


def _train(engine, n, seed=0):
    it = random_dataloader(
        HIDDEN, 64,
        engine.train_micro_batch_size_per_gpu() * engine.dp_world_size,
        seed=seed)
    losses = []
    for _ in range(n):
        loss = engine.forward(next(it))
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return losses


def _assert_measured_contract(rep, expect_jits):
    """ACCEPTANCE: every expected step jit reports measured peaks, the
    analytic argument model never UNDERESTIMATES the compiler by >15%,
    and no armed cross-check finds an underestimate."""
    for name in expect_jits:
        m = rep["measured"][name]
        assert m["modeled"], (name, m.get("error"))
        assert m["peak_bytes"] and m["peak_bytes"] > 0, name
        assert m["argument_bytes"] is not None
        assert m["argument_bytes"] <= \
            m["analytic_argument_bytes"] * 1.15, (name, m)
    for name, check in rep["cross_check"].items():
        assert not check["underestimated"], (name, check)


def test_stage2_memory_report_measured_and_analytic():
    e = _engine(zero_optimization={"stage": 2})
    _train(e, 3)
    rep = e.memory_report()
    assert rep["armed"]
    _assert_measured_contract(rep, ["micro_step", "apply_step"])
    # argument pricing is shard-shape exact (alignment slack only)
    assert abs(rep["measured"]["micro_step"]["argument_delta"]) <= 0.15
    ana = rep["analytic"]
    assert ana["components"]["params_bytes"] > 0
    # stage 2: accum + optimizer state sharded 8-way, params replicated
    assert ana["components"]["grad_accum_bytes"] < \
        ana["components"]["params_bytes"]
    assert ana["peak_bytes"] == ana["persistent_bytes"]
    # device watermark entries exist for the whole mesh (CPU: honest
    # Nones, never a crash or a fake zero)
    assert len(rep["devices"]) == len(e.mesh.devices.reshape(-1))
    # and the unified report embeds the same builder's output
    assert e.telemetry_report()["memory"]["armed"]


def test_stage3_memory_report_gathered_transient():
    e = _engine(zero_optimization={"stage": 3})
    _train(e, 2)
    assert e._s3_sched_armed
    rep = e.memory_report()
    _assert_measured_contract(rep, ["s3_fwd", "s3_bwd", "apply_step"])
    ana = rep["analytic"]
    assert ana["transient"]["gathered_stage3_bytes"] == \
        e._s3_plan.gathered_bytes > 0
    assert ana["peak_bytes"] == \
        ana["persistent_bytes"] + ana["transient_bytes"]
    # the staged forward's cross-check is armed with the budget claim
    assert "s3_fwd" in rep["cross_check"]


def test_one_compile_per_jit_shared_between_mfu_and_memory():
    """Arming both ledgers costs ONE compile per jit: the MFU report
    pays the lazy lower().compile(), the memory report reuses the
    cached compiled objects — zero additional XLA compiles."""
    e = _engine()
    _train(e, 2)
    with CompilationCounter() as c_mfu:
        e.telemetry_report()          # compiles each registered jit once
    assert c_mfu.count >= 1
    with CompilationCounter() as c_mem:
        rep = e.memory_report()
    assert c_mem.count == 0, \
        f"memory report recompiled {c_mem.count} jits the MFU ledger " \
        f"already compiled"
    assert rep["measured"]["micro_step"]["modeled"]
    # and the report is cached: a second read is free too
    with CompilationCounter() as c_again:
        e.memory_report()
    assert c_again.count == 0


def test_disarmed_engine_reports_analytic_only():
    e = _engine(tele=False)
    _train(e, 2)
    rep = e.memory_report()
    assert not rep["armed"] and "measured" not in rep
    assert rep["analytic"]["peak_bytes"] > 0
    assert "memory" in e.telemetry_report()


def test_memory_channel_off_warns_disarmed(caplog):
    old = ds_logger.propagate
    ds_logger.propagate = True
    try:
        with caplog.at_level(_logging.WARNING):
            e = _engine(telemetry={"enabled": True, "memory": False,
                                   "peak_tflops_per_device": 0.001})
    finally:
        ds_logger.propagate = old
    assert e._memacct is None
    assert any("DISARMED" in r.message and "memory" in r.message
               for r in caplog.records)
    _train(e, 1)
    assert "measured" not in e.memory_report()


def test_cross_check_warns_on_rigged_underestimate(caplog):
    e = _engine()
    _train(e, 2)
    # rig an absurdly small analytic claim on a jit with no auto
    # expectation: the cross-check must call it out loudly
    e._memacct.expect("apply_step", "rigged claim", 1,
                      field="output_bytes")
    old = ds_logger.propagate
    ds_logger.propagate = True
    try:
        with caplog.at_level(_logging.WARNING):
            rep = e.memory_report()
    finally:
        ds_logger.propagate = old
    assert rep["cross_check"]["apply_step"]["underestimated"]
    assert any("UNDERESTIMATES" in r.message for r in caplog.records)
    # verdicts are cached: the warning fires once, not per report
    caplog.clear()
    with caplog.at_level(_logging.WARNING):
        e.memory_report()
    assert not any("UNDERESTIMATES" in r.message for r in caplog.records)


def test_mem_gauges_set_when_backend_reports(monkeypatch):
    e = _engine()
    _train(e, 1)
    # the CPU backend reports no memory_stats: the probe disarms itself
    assert e._mem_stats_available is False
    snap = e.telemetry.registry.snapshot()
    assert "mem_bytes_in_use" not in snap.get("gauges", {})
    # a backend that DOES report: gauges + the `mem` lane instant land
    monkeypatch.setattr(
        ma, "normalize_memory_stats",
        lambda d: {"bytes_in_use": 7, "peak_bytes_in_use": 9,
                   "bytes_limit": 100})
    e._mem_stats_available = None
    e._memory_step_gauges()
    snap = e.telemetry.registry.snapshot()
    n_dev = len(e.mesh.devices.reshape(-1))
    assert snap["gauges"]["mem_bytes_in_use"] == 7 * n_dev
    assert snap["gauges"]["mem_peak_bytes_in_use"] == 9
    assert any(ev["name"] == "hbm_in_use"
               for ev in e.telemetry.tracer.events())


# ---------------------------------------------------------------------------
# pipeline engine: per-stage analytic + zb-stash cross-check
# ---------------------------------------------------------------------------

def test_pipe_zb_stash_memory_report():
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    specs, loss_fn, input_fn = make_stack_specs(8, 8, tied_head=False)
    module = PipelineModule(specs, loss_fn=loss_fn, input_fn=input_fn,
                            partition_method="uniform")
    cfg = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
        "steps_per_print": 100,
        "mesh": {"pipe": 4, "data": 2, "model": 1, "allow_partial": True},
        "pipeline": {"schedule": "zb-h1"},
        "telemetry": {"enabled": True, "peak_tflops_per_device": 0.001},
    }
    e, _, _, _ = deepspeed_tpu.initialize(model=module,
                                          config_params=cfg)
    data = random_dataloader(8, 64, 2, seed=0)
    for _ in range(2):
        e.train_batch(data_iter=data)
    assert e._stash_armed
    rep = e.memory_report()
    ana = rep["analytic"]
    assert len(ana["per_stage"]) == 4
    # the stash transient is live on every stage and the worst stage's
    # peak is the fleet watermark
    assert all(s["transient"]["stash_bytes"] > 0
               for s in ana["per_stage"])
    assert ana["peak_bytes"] == max(
        s["peak_bytes"] for s in ana["per_stage"])
    stash_jits = [f"chunk{q}:fwd_stash" for q in range(4)]
    _assert_measured_contract(rep, stash_jits)
    # every stash chunk's budget claim is cross-checked, none breached
    for name in stash_jits:
        assert name in rep["cross_check"]
    # telemetry_report nests the same memory section
    assert e.telemetry_report()["memory"]["analytic"]["per_stage"]


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_toy():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                     n_head=4, dtype=jnp.float32, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    return model, params


def test_serving_memory_report_and_zero_recompiles(serving_toy):
    from deepspeed_tpu.serving.engine import InferenceEngine

    model, params = serving_toy
    eng = InferenceEngine(model, params, max_slots=3, kv_block_size=4,
                          prefill_chunk=8, max_blocks_per_seq=8,
                          telemetry={"peak_tflops_per_device": 0.001})
    eng.warmup()
    rng = np.random.default_rng(1)
    with CompilationCounter() as cc:
        for _ in range(3):
            eng.submit(rng.integers(0, 97, 5).astype(np.int32), 4)
        eng.serve()
    # memory accounting armed must not break the zero-recompile pin
    assert cc.count == 0
    rep = eng.memory_report()
    _assert_measured_contract(rep, ["decode_step"])
    # prefill-chunk jits join the ledger too
    assert any(k.startswith("prefill_chunk") for k in rep["measured"])
    # the pool is priced through the shared builder, byte-exact
    assert rep["analytic"]["components"]["kv_pool_bytes"] == \
        eng.pool.device_bytes()
    assert rep["cross_check"]["decode_step"]["underestimated"] is False
    # unified serving report carries the same section
    assert eng.telemetry_report()["memory"]["armed"]
    # disarmed serving still reports the analytic pool
    eng2 = InferenceEngine(model, params, max_slots=2, kv_block_size=4,
                           prefill_chunk=8, max_blocks_per_seq=8)
    rep2 = eng2.memory_report()
    assert not rep2["armed"] and "measured" not in rep2
    assert rep2["analytic"]["components"]["kv_pool_bytes"] > 0
