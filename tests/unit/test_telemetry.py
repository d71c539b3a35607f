"""Telemetry subsystem (deepspeed_tpu/telemetry/, ISSUE 10).

The load-bearing acceptance properties:

- **Trace fidelity**: an exported pipe=4/gas=8 zb-h1+stash trace replays
  (bubble_accounting.replay_trace) to measured per-stage idle fractions
  within tolerance of the analytic ``simulate`` — the engine executed
  the plan it compiled.
- **MFU populated on both engines** from ``compiled.cost_analysis()``.
- **Disarmed is free**: training with telemetry off is BIT-identical to
  telemetry on (host-side tracing never touches the compiled programs)
  with zero extra XLA compilations, and the ARMED per-event overhead is
  a pinned small fraction of the measured step time.
- **Stream durability**: the step-metrics JSONL replays past a torn
  final record (the PR-9 journal idiom).
"""
import json
import os

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.serving.metrics import CompilationCounter
from deepspeed_tpu.telemetry import (Histogram, MetricsRegistry,
                                     MetricsStream, Telemetry, Tracer,
                                     lane_utilization, model_flops_per_step,
                                     nearest_rank, normalize_cost_analysis,
                                     peak_flops_per_device)
from deepspeed_tpu.telemetry.mfu import MfuAccounting
from tests.unit.simple_model import (SimpleModel, make_stack_specs,
                                     random_dataloader)

HIDDEN = 16


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_tracer_records_spans_and_instants():
    t = [0.0]
    tr = Tracer(capacity=256, clock=lambda: t[0])
    lane = tr.lane("work")
    t0 = tr.begin()
    t[0] = 0.25
    tr.complete("fwd", lane, t0, a0=3, a1=7)
    tr.instant("mark", lane)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["fwd", "mark"]
    assert evs[0]["ph"] == "X" and evs[0]["dur"] == 0.25
    assert evs[0]["a0"] == 3 and evs[0]["a1"] == 7
    assert evs[1]["ph"] == "i"
    assert tr.recorded == 2 and tr.dropped == 0


def test_tracer_ring_wraps_and_counts_drops():
    tr = Tracer(capacity=256)
    lane = tr.lane("l")
    for i in range(300):
        tr.instant("e", lane, a0=i)
    assert tr.recorded == 300 and tr.dropped == 44
    evs = tr.events()
    assert len(evs) == 256
    # oldest retained first, newest last
    assert evs[0]["a0"] == 44 and evs[-1]["a0"] == 299


def test_tracer_capacity_floor():
    assert Tracer(capacity=1).capacity == 256


def test_chrome_export_schema_x_events(tmp_path):
    tr = Tracer(capacity=256)
    lane = tr.lane("stage0")
    tr.intern("ForwardPass", args=("chunk", "micro"))
    t0 = tr.begin()
    tr.complete("ForwardPass", lane, t0, a0=0, a1=2)
    tr.instant("overflow_skip", lane, a0=5)
    path = tr.export_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        doc = json.load(f)              # loadable event stream
    evs = doc["traceEvents"]
    # process + thread metadata (lane naming) present
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    names = [e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert "stage0" in names
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == 1 and spans[0]["dur"] >= 0
    assert spans[0]["args"] == {"chunk": 0, "micro": 2}
    inst = [e for e in evs if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["s"] == "t"
    for e in evs:
        assert {"ph", "name", "pid", "tid"} <= set(e)


def test_lane_utilization_measured_idle():
    t = [0.0]
    tr = Tracer(capacity=256, clock=lambda: t[0])
    a, b = tr.lane("a"), tr.lane("b")
    t0 = tr.begin()
    t[0] = 1.0
    tr.complete("x", a, t0)            # lane a busy the whole window
    t0 = tr.begin()                    # == 1.0? no: begin at t=1.0
    # lane b busy only the second half of a 2s window
    t[0] = 2.0
    tr.complete("y", b, t0)
    util = lane_utilization(tr.events())
    assert util["_window_s"] == pytest.approx(2.0)
    assert util["a"]["idle_fraction"] == pytest.approx(0.5)
    assert util["b"]["idle_fraction"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# metrics: shared percentile, histogram, registry, JSONL stream
# ---------------------------------------------------------------------------

def test_nearest_rank_matches_serving_pct_contract():
    """The shared implementation pins the exact _pct edge-case contract
    test_serving_reliability.py relies on."""
    from deepspeed_tpu.serving.metrics import _pct

    for xs, q in ([], .5), ([3.0], .95), ([1.0, 2.0], 0.0), \
            ([1.0, 2.0], 1.0), ([1.0, 2.0], 7.5), ([5., 1., 3.], .5):
        assert nearest_rank(xs, q) == _pct(xs, q)
    assert nearest_rank([], .5) is None
    assert nearest_rank([3.0], .01) == 3.0
    assert nearest_rank([1.0, 2.0], 9.9) == 2.0   # clamped


def test_histogram_windowed_percentiles_exact_aggregates():
    h = Histogram(max_samples=8)
    for i in range(20):
        h.add(i)
    assert h.count == 20
    assert h.mean() == pytest.approx(np.mean(range(20)))
    assert h.max() == 19.0                      # exact beyond the window
    assert h.pct(0.0) == 12.0                   # window = last 8 samples
    assert Histogram().mean() is None and Histogram().pct(.5) is None


def test_registry_snapshot_schema():
    reg = MetricsRegistry()
    reg.counter("steps").inc(3)
    reg.gauge("scale").set(2.0)
    reg.histogram("lat").add(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["steps"] == 3
    assert snap["gauges"]["scale"] == 2.0
    assert snap["histograms"]["lat"]["count"] == 1
    assert reg.counter("steps") is reg.counter("steps")


def test_metrics_stream_emit_and_replay(tmp_path):
    path = str(tmp_path / "m.jsonl")
    s = MetricsStream(path)
    s.emit(1, {"loss": 2.0, "np_scalar": np.float32(1.5)})
    s.emit(2, {"loss": 1.0})
    s.close()
    rows = MetricsStream.replay(path)
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[0]["np_scalar"] == 1.5           # numpy degrades to JSON


def test_metrics_stream_torn_tail_tolerated(tmp_path):
    path = str(tmp_path / "m.jsonl")
    s = MetricsStream(path)
    for i in range(4):
        s.emit(i, {"v": i})
    s.close()
    with open(path, "a") as f:                   # crash mid-emit
        f.write('{"step": 4, "v":')
    rows = MetricsStream.replay(path)
    assert [r["step"] for r in rows] == [0, 1, 2, 3]


def test_metrics_stream_midstream_corruption_raises(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write('{"step": 0}\nGARBAGE\n{"step": 2}\n')
    with pytest.raises(ValueError, match="mid-stream"):
        MetricsStream.replay(path)


# ---------------------------------------------------------------------------
# mfu accounting
# ---------------------------------------------------------------------------

def test_normalize_cost_analysis_real_compiled():
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    compiled = f.lower(jnp.ones((32, 32))).compile()
    cost = normalize_cost_analysis(compiled)
    assert cost["flops"] and cost["flops"] > 2 * 32 ** 3 * 0.5
    assert cost["bytes_accessed"] and cost["bytes_accessed"] > 0


def test_mfu_report_math():
    import jax.numpy as jnp

    acc = MfuAccounting(peak_tflops_per_device=1e-6)  # 1e6 FLOPS/dev
    f = jax.jit(lambda x: (x @ x).sum())
    acc.register("mm", lambda: f.lower(jnp.ones((16, 16))).compile(),
                 calls_per_step=2.0)
    rep = acc.report(step_time_s=1.0, n_devices=2,
                     model_flops=1e6, device_kind="cpu")
    flops = rep["per_jit"]["mm"]["flops"]
    assert rep["hw_flops_per_step"] == pytest.approx(2.0 * flops)
    # mfu = model_flops / (t * n_dev * peak) = 1e6 / (1*2*1e6) = 0.5
    assert rep["mfu"] == pytest.approx(0.5)
    # hw flops are per-device (sharding-preserving capture compiles the
    # SPMD executable): hfu = hw / (t * peak), no n_devices factor
    assert rep["hfu"] == pytest.approx(2 * flops / 1e6)
    assert rep["peak_known"] and rep["hw_flops_complete"]


def test_mfu_peak_table_matches_benchmark_peaks():
    """Every kind the benchmark's table knows has the same peak here,
    and a kind without a row is unknown, not its neighbour."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "_benchmark_device",
        os.path.join(repo, "benchmark", "harness", "device.py"))
    device = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(device)
    assert "TPU v5 lite" in device.PEAKS
    for kind, row in device.PEAKS.items():
        got, known = peak_flops_per_device(kind)
        assert known and got == pytest.approx(row["bf16_flops_per_s"])
    for kind, expect in (("TPU v5 lite", 197.0), ("tpuv5lite", 197.0),
                         ("TPU v5p", 459.0), ("TPU v4", 275.0)):
        got, known = peak_flops_per_device(kind)
        assert known and got == pytest.approx(expect * 1e12)
    for kind in ("TPU v5 ultra", "TPU v5x", "v5", "weird-cpu", None):
        assert peak_flops_per_device(kind) == (None, False)
    assert model_flops_per_step(10, 5) == 300.0
    assert model_flops_per_step(10, 5, fwd_only=True) == 100.0


def test_mfu_report_survives_broken_lowering():
    acc = MfuAccounting()

    def boom():
        raise RuntimeError("no lowering for you")

    acc.register("bad", boom)
    rep = acc.report(step_time_s=0.1, n_devices=1, model_flops=None)
    assert "no lowering" in rep["per_jit"]["bad"]["error"]
    assert rep["hw_flops_per_step"] is None
    assert not rep["hw_flops_complete"]


# ---------------------------------------------------------------------------
# config validation + DISARMED discipline
# ---------------------------------------------------------------------------

def _cfg(tele=None, **over):
    c = {
        "train_batch_size": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
    }
    if tele is not None:
        c["telemetry"] = tele
    c.update(over)
    return c


def _engine(tele=None, **over):
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


def test_telemetry_config_validation():
    with pytest.raises(ValueError, match="trace_capacity"):
        _engine(tele={"enabled": True, "trace_capacity": 10})
    with pytest.raises(ValueError, match="peak_tflops"):
        _engine(tele={"enabled": True, "peak_tflops_per_device": -1})


def test_disarmed_with_subknobs_warns_loudly(tmp_path, caplog):
    import logging as _logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    old = ds_logger.propagate
    ds_logger.propagate = True
    try:
        with caplog.at_level(_logging.WARNING):
            e = _engine(tele={"enabled": False,
                              "metrics_jsonl": str(tmp_path / "m.jsonl")})
    finally:
        ds_logger.propagate = old
    assert e.telemetry is None
    assert any("DISARMED" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# base-engine integration: report parity, mfu, stream, bit-identity
# ---------------------------------------------------------------------------

def test_engine_telemetry_report_parity_and_mfu(tmp_path):
    path = str(tmp_path / "steps.jsonl")
    e = _engine(tele={"enabled": True, "metrics_jsonl": path,
                      "peak_tflops_per_device": 0.001})
    _train(e, 4)
    rep = e.telemetry_report()
    # parity with the legacy builders — consolidation, not replacement
    assert rep["last_metrics"] == e._last_metrics
    assert rep["comm"] == e.comm_volume_report()
    assert rep["telemetry_armed"] and rep["metrics"]["counters"]["steps"] == 4
    # mfu populated from cost_analysis on the training engine
    mfu = rep["mfu"]
    assert mfu["per_jit"]["micro_step"]["flops"] > 0
    assert mfu["hw_flops_per_step"] > 0 and mfu["hw_flops_complete"]
    assert mfu["model_flops_per_step"] > 0
    assert mfu["mfu"] > 0 and mfu["hfu"] > 0
    assert mfu["hfu"] >= mfu["mfu"] * 0.5   # same ballpark ledgers
    # step-aligned JSONL: one record per optimizer step, step numbers
    rows = MetricsStream.replay(path)
    assert [r["step"] for r in rows] == [1, 2, 3, 4]
    assert "grad_norm" in rows[-1]
    # trace exported and loadable
    out = e.export_trace(str(tmp_path / "t.json"))
    evs = json.load(open(out))["traceEvents"]
    names = {ev["name"] for ev in evs}
    assert {"forward_micro", "backward_micro", "optimizer_step"} <= names


def test_disarmed_bit_identical_and_zero_extra_compiles():
    """The armed/disarmed contract: telemetry never touches the compiled
    programs — losses are BITWISE equal and the XLA compile count is
    identical."""
    with CompilationCounter() as c_off:
        e_off = _engine()
        off = _train(e_off, 3)
    assert e_off.telemetry is None and e_off.export_trace("/tmp/x") is None
    with CompilationCounter() as c_on:
        e_on = _engine(tele={"enabled": True})
        on = _train(e_on, 3)
    assert on == off                      # float() of fp32 loss: bitwise
    assert c_on.count == c_off.count, \
        f"telemetry changed compile count: {c_on.count} != {c_off.count}"
    # disarmed telemetry_report still consolidates the legacy builders
    rep = e_off.telemetry_report()
    assert rep["telemetry_armed"] is False and "mfu" not in rep


def test_armed_overhead_is_small_fraction_of_step_time():
    """The tier-1 overhead contract, measured without wall-clock racing:
    per-event tracer cost (microbenchmark mean over 20k events) times
    the observed events-per-step must stay under 5% of the measured mean
    step time on the CPU mesh."""
    import timeit

    e = _engine(tele={"enabled": True})
    _train(e, 5)
    tel = e.telemetry
    step_s = tel.step_time_s()
    assert step_s and step_s > 0
    events_per_step = tel.tracer.recorded / 5
    assert events_per_step <= 16          # bounded instrumentation

    tr = Tracer(capacity=4096)
    lane = tr.lane("bench")
    n = 20000
    per_event_s = timeit.timeit(
        lambda: tr.complete("ev", lane, tr.begin(), a0=1, a1=2), number=n) / n
    budget = 0.05 * step_s
    assert per_event_s * events_per_step < budget, \
        (per_event_s, events_per_step, step_s)


def test_overflow_and_watchdog_events_land_in_trace():
    from deepspeed_tpu.runtime.fp16 import loss_scaler  # noqa: F401

    e = _engine(tele={"enabled": True},
                fp16={"enabled": True, "initial_scale_power": 32,
                      "loss_scale_window": 1000, "hysteresis": 1},
                resilience={"watchdog": {"enabled": True,
                                         "max_skipped_steps": 0}})
    _train(e, 3)        # scale 2^32 overflows immediately -> skips
    names = [ev["name"] for ev in e.telemetry.tracer.events()]
    assert "overflow_skip" in names


# ---------------------------------------------------------------------------
# pipeline engine: schedule trace + measured-vs-analytic bubble
# ---------------------------------------------------------------------------

def _pipe_engine(pipe=4, gas=8, schedule="zb-h1", tele=True):
    specs, loss_fn, input_fn = make_stack_specs(8, 8, tied_head=False)
    module = PipelineModule(specs, loss_fn=loss_fn, input_fn=input_fn,
                            partition_method="uniform")
    cfg = {
        "train_batch_size": gas * 2,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
        "steps_per_print": 100,
        "mesh": {"pipe": pipe, "data": 2, "model": 1,
                 "allow_partial": True},
        "pipeline": {"schedule": schedule},
    }
    if tele:
        cfg["telemetry"] = {"enabled": True,
                            "peak_tflops_per_device": 0.001}
    engine, _, _, _ = deepspeed_tpu.initialize(model=module,
                                               config_params=cfg)
    return engine


def test_pipe_trace_replays_to_analytic_bubble(tmp_path):
    """ACCEPTANCE: a traced pipe=4/gas=8 zb-h1+stash batch replays to
    measured per-stage idle fractions within tolerance of
    bubble_accounting.simulate, and the exported trace renders one lane
    per stage with one span per compiled instruction."""
    e = _pipe_engine()
    data = random_dataloader(8, 64, 2, seed=0)
    for _ in range(2):
        e.train_batch(data_iter=data)
    assert e._stash_armed and e.pipe_schedule == "zb-h1"
    rep = e.measured_bubble_report()
    assert rep["max_abs_idle_error"] <= 1e-9, rep["max_abs_idle_error"]
    assert rep["measured"]["idle_fraction"] == \
        pytest.approx(rep["analytic"]["idle_fraction"])
    assert rep["analytic"]["stash"] and rep["measured"]["stash"]
    # wall-clock lanes exist for every stage (values are host-dispatch
    # bound on CPU — reported, not gated)
    for s in range(4):
        assert f"stage{s}" in rep["wall_clock"]
    # the full unified report nests pipeline + measured + mfu
    full = e.telemetry_report()
    assert full["pipeline"]["measured"]["max_abs_idle_error"] <= 1e-9
    assert full["pipeline"]["schedule"] == "zb-h1"
    assert full["mfu"]["hw_flops_per_step"] > 0
    assert any(k.startswith("chunk0:") for k in full["mfu"]["per_jit"])
    # exported trace: one lane per stage, instruction spans with args
    out = e.export_trace(str(tmp_path / "pipe.json"))
    evs = json.load(open(out))["traceEvents"]
    lanes = {ev["args"]["name"] for ev in evs
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {"stage0", "stage1", "stage2", "stage3"} <= lanes
    fwd = [ev for ev in evs if ev.get("name") == "ForwardPass"]
    assert len(fwd) == 2 * 8 * 4          # 2 batches x gas x stages
    assert all("micro" in ev["args"] for ev in fwd)


def test_pipe_replay_trace_rejects_empty_trace():
    from deepspeed_tpu.runtime.pipe import bubble_accounting as ba
    from deepspeed_tpu.runtime.pipe import schedule as sched_lib

    compiled = sched_lib.compile_schedule("1f1b", 4, 2)
    with pytest.raises(ValueError, match="no pipeline instruction"):
        ba.replay_trace([], compiled)


def test_pipe_disarmed_has_no_trace_and_matches():
    e0 = _pipe_engine(pipe=2, gas=2, schedule="1f1b", tele=False)
    e1 = _pipe_engine(pipe=2, gas=2, schedule="1f1b", tele=True)
    d0 = random_dataloader(8, 64, 2, seed=3)
    d1 = random_dataloader(8, 64, 2, seed=3)
    l0 = [e0.train_batch(data_iter=d0) for _ in range(2)]
    l1 = [e1.train_batch(data_iter=d1) for _ in range(2)]
    assert l0 == l1                        # host floats: bitwise
    assert e0.measured_bubble_report() is None
    assert "measured" not in e0.telemetry_report()["pipeline"]


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_toy():
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                     n_head=4, dtype=jnp.float32, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    return model, params


def _serve_engine(model, params, **tele):
    from deepspeed_tpu.serving.engine import InferenceEngine

    return InferenceEngine(model, params, max_slots=3, kv_block_size=4,
                           prefill_chunk=8, max_blocks_per_seq=8,
                           telemetry=tele or None)


def test_serving_telemetry_report_and_zero_recompiles(serving_toy,
                                                      tmp_path):
    model, params = serving_toy
    path = str(tmp_path / "serve.jsonl")
    eng = _serve_engine(model, params, metrics_jsonl=path,
                        peak_tflops_per_device=0.001)
    eng.warmup()
    rng = np.random.default_rng(1)
    with CompilationCounter() as cc:
        for _ in range(3):
            eng.submit(rng.integers(0, 97, 5).astype(np.int32), 4)
        eng.serve()
    # telemetry armed must not break the zero-recompile contract
    assert cc.count == 0
    rep = eng.telemetry_report()
    # parity: the unified report embeds the full legacy serving_report
    legacy = eng.serving_report()
    for key in ("requests", "ttft_s", "throughput", "queue_depth"):
        assert rep[key] == legacy[key]
    # serving mfu from the decode jit's cost_analysis (outside the
    # recompile-guard window: the lazy lower+compile runs at report time)
    assert rep["mfu"]["per_jit"]["decode_step"]["flops"] > 0
    assert rep["mfu"]["mfu"] is not None and rep["mfu"]["mfu"] > 0
    names = {e["name"] for e in eng.telemetry.tracer.events()}
    assert {"serving_step", "decode_step", "prefill_tick", "admit",
            "host_gap", "run_decode", "run_prefill"} <= names
    rows = MetricsStream.replay(path)
    assert rows and all("queue_depth" in r for r in rows)
    assert eng.export_trace(str(tmp_path / "s.json"))


def test_serving_telemetry_disarmed_warns(serving_toy, caplog):
    import logging as _logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    model, params = serving_toy
    old = ds_logger.propagate
    ds_logger.propagate = True
    try:
        with caplog.at_level(_logging.WARNING):
            from deepspeed_tpu.serving.engine import InferenceEngine

            eng = InferenceEngine(model, params, max_slots=2,
                                  kv_block_size=4, prefill_chunk=8,
                                  max_blocks_per_seq=8,
                                  telemetry={"enabled": False})
    finally:
        ds_logger.propagate = old
    assert eng.telemetry is None
    assert any("DISARMED" in r.message for r in caplog.records)


def test_serving_abort_events_traced(serving_toy):
    model, params = serving_toy
    from deepspeed_tpu.serving.reliability import ReliabilityConfig

    eng = _serve_engine(model, params, trace=True)
    # deadline_s=0 is now rejected at admission (not a budget at all);
    # a vanishingly small positive one expires at the first sweep
    eng.reliability.config = ReliabilityConfig(default_deadline_s=1e-9)
    eng.warmup()
    eng.submit(np.zeros(4, np.int32), 4, deadline_s=1e-9)
    eng.step()
    names = [e["name"] for e in eng.telemetry.tracer.events()]
    assert "abort_expired" in names


# ---------------------------------------------------------------------------
# Telemetry session
# ---------------------------------------------------------------------------

def test_telemetry_session_step_time_and_stream(tmp_path):
    t = [0.0]
    tel = Telemetry(metrics_jsonl=str(tmp_path / "s.jsonl"),
                    clock=lambda: t[0])
    tel.on_step(1, {"a": 1})
    t[0] = 0.5
    tel.on_step(2, {"a": 2})
    t[0] = 1.5
    tel.on_step(3, {"a": 3})
    assert tel.step_time_s() == pytest.approx(0.75)   # mean(0.5, 1.0)
    tel.close()
    assert len(MetricsStream.replay(str(tmp_path / "s.jsonl"))) == 3
