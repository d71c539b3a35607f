"""Quantized ZeRO collectives (qwZ/qgZ) tests.

Three layers of proof, none needing TPU hardware:
 1. numerics — blockwise int8 round-trips within the per-block scale bound,
    and the quantized reduce-scatter matches the dense mean within int8
    tolerance (flat and hierarchical) on the 8-device CPU mesh;
 2. engine — stage-2 training with quantized_gradients follows the dense
    trajectory to within the ZeRO++ paper's parity expectations, overflow
    still trips the loss scaler, qwZ offload matches dense offload;
 3. bytes — the analytic comm accounting (deterministic, shape math only)
    asserts the >=3.5x gradient-exchange reduction, cross-checked against
    the compiled HLO's collective payloads.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime import quantization as qz
from deepspeed_tpu.runtime.custom_collectives import quantized_reduce_scatter
from simple_model import SimpleModel, random_dataloader

HIDDEN = 32
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# quantization numerics
# ---------------------------------------------------------------------------

def test_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = rng.standard_normal(1000).astype(np.float32) * 3.0
    q, scales = qz.quantize_blockwise(jnp.asarray(x), block_size=128)
    deq = np.asarray(qz.dequantize_blockwise(q, scales, (1000,)))
    # per-element error <= half an int8 step of its block's scale
    bs, nb, npad = qz.block_layout(1000, 128)
    bounds = np.repeat(np.asarray(scales), bs)[:1000] * 0.5 + 1e-7
    assert (np.abs(deq - x) <= bounds).all()


def test_block_layout_clamps_small_rows():
    # a 32-element row must not pad to a 128 block (wire waste > fp32)
    assert qz.block_layout(32, 128) == (32, 1, 32)
    assert qz.block_layout(1000, 128) == (128, 8, 1024)
    assert qz.block_layout(128, 128) == (128, 1, 128)


def test_zero_and_constant_blocks():
    x = jnp.zeros(64)
    q, s = qz.quantize_blockwise(x, 32)
    np.testing.assert_array_equal(np.asarray(qz.dequantize_blockwise(
        q, s, (64,))), np.zeros(64))
    x = -jnp.ones(64) * 5
    q, s = qz.quantize_blockwise(x, 32)
    np.testing.assert_allclose(np.asarray(qz.dequantize_blockwise(
        q, s, (64,))), np.full(64, -5.0), rtol=1e-6)


def test_numpy_matches_jnp():
    rng = np.random.default_rng(1)
    x = rng.standard_normal(300).astype(np.float32)
    qj, sj = qz.quantize_blockwise(jnp.asarray(x), 64)
    qn, sn = qz.quantize_blockwise_np(x, 64)
    np.testing.assert_array_equal(np.asarray(qj), qn)
    np.testing.assert_allclose(np.asarray(sj), sn, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(qz.dequantize_blockwise(qj, sj, (300,))),
        qz.dequantize_blockwise_np(qn, sn, 300), rtol=1e-6)


def test_error_feedback_reduces_bias():
    """Residual carry: the running average of repeated EF-quantizations of a
    constant converges to it (same property the 1-bit scheme relies on)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(64) * 1e-3, jnp.float32)
    res = jnp.zeros(64)
    acc = np.zeros(64)
    steps = 50
    for _ in range(steps):
        q, s, res = qz.quantize_blockwise_ef(x, res, 64)
        acc += np.asarray(qz.dequantize_blockwise(q, s, (64,)))
    err = np.linalg.norm(acc / steps - np.asarray(x)) \
        / np.linalg.norm(np.asarray(x))
    assert err < 0.05, err


def test_nonfinite_inputs_stay_nonfinite():
    """Overflow safety: quantization must not launder inf/nan into finite
    gradients — the scale carries the marker through the wire."""
    for bad in (np.inf, -np.inf, np.nan):
        x = np.ones(64, np.float32)
        x[17] = bad
        q, s = qz.quantize_blockwise(jnp.asarray(x), 32)
        deq = np.asarray(qz.dequantize_blockwise(q, s, (64,)))
        assert not np.isfinite(deq).all(), f"{bad} vanished"
        qn, sn = qz.quantize_blockwise_np(x, 32)
        deqn = qz.dequantize_blockwise_np(qn, sn, 64)
        assert not np.isfinite(deqn).all(), f"np: {bad} vanished"


# ---------------------------------------------------------------------------
# quantized reduce-scatter collective (the qgZ wire)
# ---------------------------------------------------------------------------

def _run_qrs(xs, intra_size, dim=0, block=64):
    w = xs.shape[0]
    mesh = Mesh(np.asarray(jax.devices()[:w]), ("data",))

    def body(x):
        out = quantized_reduce_scatter(x[0], "data", dim=dim,
                                       block_size=block,
                                       intra_size=intra_size)
        return out[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    return np.asarray(jax.jit(fn)(xs))


@pytest.mark.parametrize("intra", [0, 2, 4])
def test_quantized_reduce_scatter_matches_dense_mean(eight_devices, intra):
    w, n = 8, 256
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((w, n)).astype(np.float32)
    out = _run_qrs(xs, intra)                       # (w, n//w): shard r
    mean = xs.mean(0)
    tol = np.abs(xs).max() / 127 * (3 if intra else 2)  # 2 quant hops
    for r in range(w):
        np.testing.assert_allclose(out[r], mean[r * (n // w):
                                                (r + 1) * (n // w)],
                                    atol=tol)


def test_quantized_reduce_scatter_dim1(eight_devices):
    """Sharding dim 1 (the ZeRO spec picks the largest divisible dim, which
    is rarely dim 0 for weight matrices)."""
    w = 8
    rng = np.random.default_rng(4)
    xs = rng.standard_normal((w, 3, 16)).astype(np.float32)
    mesh = Mesh(np.asarray(jax.devices()[:w]), ("data",))

    def body(x):
        return quantized_reduce_scatter(x[0], "data", dim=1,
                                        block_size=32)[None]

    out = np.asarray(jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P("data"), out_specs=P("data")))(xs))
    mean = xs.mean(0)                               # (3, 16)
    tol = np.abs(xs).max() / 127 * 2
    for r in range(w):
        np.testing.assert_allclose(out[r], mean[:, r * 2:(r + 1) * 2],
                                    atol=tol)


# ---------------------------------------------------------------------------
# engine wiring (qgZ)
# ---------------------------------------------------------------------------

def _engine(hidden=HIDDEN, **zero_over):
    zero = {"stage": 2}
    zero.update(zero_over)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=hidden), config_params={
            "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 0.02}},
            "zero_optimization": zero,
            "mesh": {"data": 8}, "steps_per_print": 10 ** 9})
    return engine


def _train(engine, steps=20, hidden=HIDDEN, seed=0):
    it = random_dataloader(hidden, 64, 8, seed=seed)
    losses = []
    for _ in range(steps):
        loss = engine(next(it))
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return losses


def test_qgz_armed_only_where_layout_survives(eight_devices):
    def armed(**kw):
        e = _engine(**kw)
        _train(e, steps=1)
        return e._qgz_armed

    assert armed(quantized_gradients=True)
    assert not armed(quantized_gradients=False)
    # stage 1 keeps the accumulator replicated: nothing to reduce-scatter
    assert not armed(quantized_gradients=True, stage=1)
    # offload streams grads D2H, no collective to quantize
    assert not armed(quantized_gradients=True, cpu_offload=True)


def test_qgz_disarmed_warns_loudly(eight_devices, caplog):
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    ds_logger.propagate = True
    try:
        with caplog.at_level(logging.WARNING):
            e = _engine(quantized_gradients=True, stage=1)
            _train(e, steps=1)
    finally:
        ds_logger.propagate = False
    msgs = [r.message for r in caplog.records if "qgZ" in r.message]
    assert msgs and "stage=1" in msgs[0]


def test_qgz_convergence_parity(eight_devices):
    """Acceptance: a toy model trained with quantized_gradients reaches
    within 2% of the dense baseline loss."""
    dense = _train(_engine(quantized_gradients=False))
    quant = _train(_engine(quantized_gradients=True))
    assert np.isfinite(quant).all()
    assert quant[-1] < quant[0]
    assert abs(quant[-1] - dense[-1]) / dense[-1] < 0.02, (dense[-1],
                                                          quant[-1])


def test_qgz_hierarchical_parity(eight_devices):
    dense = _train(_engine(quantized_gradients=False))
    hier = _train(_engine(quantized_gradients=True,
                          hierarchical_allreduce=True,
                          hierarchical_intra_size=4))
    e = _engine(quantized_gradients=True, hierarchical_allreduce=True,
                hierarchical_intra_size=4)
    _train(e, steps=1)
    assert e._qgz_intra == 4
    assert abs(hier[-1] - dense[-1]) / dense[-1] < 0.02


def _qgz_fused_program():
    """The program of test_qgz_fused_train_batch_with_accumulation; what it
    returns is what the test asserts on."""
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN), config_params={
            "train_batch_size": 16, "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 0.02}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2, "quantized_gradients": True,
                                  "hierarchical_allreduce": True,
                                  "hierarchical_intra_size": 2},
            "mesh": {"data": 8}, "steps_per_print": 10 ** 9})
    it = random_dataloader(HIDDEN, 64, 8)
    losses = [float(jax.device_get(engine.train_batch(data_iter=it)))
              for _ in range(8)]
    rep = engine.comm_volume_report()
    return {"armed": bool(engine._qgz_armed), "intra": engine._qgz_intra,
            "losses": losses,
            "qgz_counts_per_step": [c["count_per_step"]
                                    for c in rep["collectives"]
                                    if c["name"].startswith("qgz_")],
            "metric_bytes": engine._last_metrics["comm_bytes_per_step"],
            "report_bytes": rep["total_bytes_per_step"]}


def test_qgz_fused_train_batch_with_accumulation():
    """The fused path (lax.scan over micro-batches + apply in one jit) runs
    the quantized exchange per micro-step; bf16 compute + gas 2 +
    hierarchical two-hop all compose, and the report scales by gas.

    Runs in a child process: under the installed jax, XLA's CPU compiler
    aborts the interpreter on this program (rc 134), and in-process that
    ended the whole run — here it is one failed test."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [REPO] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, \
        f"child exited {proc.returncode}:\n{proc.stderr[-2000:]}"
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["armed"] and res["intra"] == 2
    assert np.isfinite(res["losses"]).all()
    assert res["losses"][-1] < res["losses"][0]
    assert res["qgz_counts_per_step"] and \
        all(c == 2 for c in res["qgz_counts_per_step"])
    assert res["metric_bytes"] == res["report_bytes"]


def test_qgz_overflow_still_trips_loss_scaler(eight_devices):
    """int8 quantization must not mask an fp16 overflow: non-finite grads
    survive the quantized wire, the step is skipped, the scale halves."""
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN), config_params={
            "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 0.02}},
            "zero_optimization": {"stage": 2, "quantized_gradients": True},
            "fp16": {"enabled": True, "initial_scale_power": 4,
                     "hysteresis": 1},
            "mesh": {"data": 8}, "steps_per_print": 10 ** 9})
    rng = np.random.default_rng(0)
    good = {"x": rng.standard_normal((8, HIDDEN)).astype(np.float32),
            "y": rng.integers(0, 4, (8,)).astype(np.int32)}
    loss = engine(good)
    engine.backward(loss)
    engine.step()
    assert engine._qgz_armed
    scale_before = engine.loss_scale()
    bad = {"x": np.full((8, HIDDEN), np.nan, np.float32),
           "y": good["y"].copy()}
    loss = engine(bad)
    engine.backward(loss)
    engine.step()
    assert engine.skipped_steps >= 1
    assert engine.loss_scale() == scale_before / 2


# ---------------------------------------------------------------------------
# bytes: analytic accounting (the acceptance numbers) + HLO cross-check
# ---------------------------------------------------------------------------

def test_qgz_bytes_at_most_two_sevenths_of_fp32_rs(eight_devices):
    """Acceptance: the quantized gradient exchange moves <= 2/7 the bytes
    of the fp32 reduce-scatter (>= 3.5x reduction), per the analytic
    accounting."""
    e = _engine(quantized_gradients=True)
    _train(e, steps=1)
    rep = e.comm_volume_report()
    assert rep["config"]["quantized_gradients"]
    grad = rep["grad_exchange_bytes_per_step"]
    base_rs = rep["baseline"]["fp32_reduce_scatter_bytes_per_step"]
    assert grad * 7 <= base_rs * 2, (grad, base_rs)
    assert rep["grad_reduction_vs_fp32"] >= 3.5
    # dense engine reports the baseline numbers as its own
    e0 = _engine(quantized_gradients=False)
    _train(e0, steps=1)
    rep0 = e0.comm_volume_report()
    assert rep0["grad_exchange_bytes_per_step"] == \
        rep["baseline"]["fp32_grad_exchange_bytes_per_step"]


def test_hierarchical_shrinks_inter_group_bytes(eight_devices):
    """The point of the two-hop qgZ: cross-group (DCN) traffic is a small
    fraction of the flat exchange."""
    e = _engine(quantized_gradients=True, hierarchical_allreduce=True,
                hierarchical_intra_size=4)
    _train(e, steps=1)
    rep = e.comm_volume_report()
    inter = rep["inter_bytes_per_step"]
    assert 0 < inter < rep["grad_exchange_bytes_per_step"] / 2
    assert inter * 3.5 <= \
        rep["baseline"]["fp32_reduce_scatter_bytes_per_step"] / 4


def test_comm_bytes_surface_in_metrics_and_profiler(eight_devices):
    e = _engine(quantized_gradients=True)
    _train(e, steps=1)
    assert e._last_metrics["comm_bytes_per_step"] == \
        e.comm_volume_report()["total_bytes_per_step"]
    from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler

    prof = FlopsProfiler(engine=e)
    prof.profile_comm(e.comm_volume_report())
    text = prof.print_model_profile()
    assert "Comm bytes/step" in text and "vs fp32" in text


def test_comm_metric_withheld_for_unmodeled_paths(eight_devices):
    """The accounting models the dense/quantized ZeRO exchange only: with
    the CSR-sparse wire armed the dense number would overstate traffic, so
    the report flags itself and the per-step metric is withheld."""
    from tests.unit.simple_model import SimpleEmbedModel

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleEmbedModel(vocab=4096, dim=8), config_params={
            "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 0.05}},
            "sparse_gradients": True,
            "mesh": {"data": 8}, "steps_per_print": 10 ** 9})
    rng = np.random.default_rng(0)
    engine.train_batch(batch={
        "ids": rng.integers(0, 4096, (1, 8, 4)),
        "y": rng.integers(0, 4, (1, 8)).astype(np.int32)})
    assert engine._csr_dp_flags is not None
    assert engine.comm_volume_report()["grad_path_modeled"] is False
    assert "comm_bytes_per_step" not in engine._last_metrics


def test_qgz_hlo_moves_fewer_gradient_bytes(eight_devices):
    """HLO cross-check of the analytic claim: the compiled quantized micro
    step's gradient collectives move several times fewer bytes than the
    dense build's, and no fp32 gradient-sized collective survives."""
    from tests.unit.test_onebit import _collective_bytes

    def hlo(quantized):
        e = _engine(quantized_gradients=quantized)
        rng = np.random.default_rng(0)
        batch = {"x": rng.standard_normal((8, HIDDEN)).astype(np.float32),
                 "y": rng.integers(0, 4, (8,)).astype(np.int32)}
        loss = e(batch)
        e.backward(loss)
        e.step()
        dev = e._shard_batch(batch)
        with jax.set_mesh(e.mesh):
            lowered = e._jit_micro.lower(e.state, dev)
        return e, lowered.compile().as_text()

    e, dense_text = hlo(False)
    _, quant_text = hlo(True)
    dense_bytes, _ = _collective_bytes(dense_text)
    quant_bytes, quant_ops = _collective_bytes(quant_text)
    n_params = sum(int(l.size) for l in
                   jax.tree_util.tree_leaves(e.state.params))
    big_f32 = [o for o in quant_ops if o[1] == "f32" and o[2] >= n_params]
    assert not big_f32, f"fp32 gradient-sized collective survived: {big_f32}"
    assert quant_bytes * 2 <= dense_bytes, (quant_bytes, dense_bytes)


# ---------------------------------------------------------------------------
# qwZ: quantized offload parameter push
# ---------------------------------------------------------------------------

def _offload_engine(qw, hidden=HIDDEN):
    return _engine(hidden=hidden, cpu_offload=True, quantized_weights=qw)


def test_qwz_armed_and_parity(eight_devices):
    def run(qw):
        e = _offload_engine(qw)
        it = random_dataloader(HIDDEN, 64, 8)
        losses = [float(jax.device_get(e.train_batch(batch={
            k: v[None] for k, v in next(it).items()})))
            for _ in range(12)]
        return e, losses

    e0, dense = run(False)
    e1, quant = run(True)
    assert not e0._qwz_armed and e1._qwz_armed
    # eligible leaves ride int8; the non-divisible bias stays dense
    metas = e1._qwz_leaf_meta()
    assert any(m is not None for m in metas)
    assert np.isfinite(quant).all() and quant[-1] < quant[0]
    assert abs(quant[-1] - dense[-1]) / dense[-1] < 0.02


def test_qwz_shrinks_param_gather_bytes(eight_devices):
    e1 = _offload_engine(True)
    rng = np.random.default_rng(0)
    e1.train_batch(batch={
        "x": rng.standard_normal((1, 8, HIDDEN)).astype(np.float32),
        "y": rng.integers(0, 4, (1, 8)).astype(np.int32)})
    rep = e1.comm_volume_report()
    e0 = _offload_engine(False)
    e0.train_batch(batch={
        "x": rng.standard_normal((1, 8, HIDDEN)).astype(np.float32),
        "y": rng.integers(0, 4, (1, 8)).astype(np.int32)})
    rep0 = e0.comm_volume_report()
    # fp32 compute dtype -> int8+scales: >= 3x less gather traffic
    assert rep["param_gather_bytes_per_step"] * 3 <= \
        rep0["param_gather_bytes_per_step"]
    names = [c["name"] for c in rep["collectives"]]
    assert any(n.startswith("qwz_ag") for n in names)


def test_int8_allgather_rides_the_wire_as_int8(eight_devices):
    """The sharding-constraint trick the qwZ gather relies on: forcing the
    int8 array replicated BEFORE dequantizing pins the all-gather to the
    1-byte payload (s8 in HLO), not the dequantized f32."""
    from jax.sharding import NamedSharding

    from tests.unit.test_onebit import _collective_bytes

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    n = 1024

    def gather_dequant(q, s):
        q = jax.lax.with_sharding_constraint(q, rep)
        return q.astype(jnp.float32).reshape(8, -1) * s[:, None]

    q = jax.device_put(np.ones(n, np.int8), sharded)
    s = jax.device_put(np.ones(8, np.float32), rep)
    with jax.set_mesh(mesh):
        text = jax.jit(gather_dequant).lower(q, s).compile().as_text()
    total, ops = _collective_bytes(text)
    s8 = [o for o in ops if o[0] == "all-gather" and o[1] == "s8"]
    f32_big = [o for o in ops if o[1] == "f32" and o[2] >= n]
    assert s8, ops
    assert not f32_big, ops


if __name__ == "__main__":
    # the child of test_qgz_fused_train_batch_with_accumulation, which
    # sets the 8-device CPU mesh in its environment
    print(json.dumps(_qgz_fused_program()))
