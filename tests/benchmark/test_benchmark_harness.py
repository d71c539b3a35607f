"""The benchmark's own code, checked on the CPU in seconds: the trace
reduction against hand-computed answers, the arithmetic against hand
counts, the generator's determinism, the contract of ``BENCHMARK.json``,
and a rehearsal of every cell's data path at a toy width, which may not
print a device metric.  The rehearsal cells live in ``cells/`` and were
added the way a later PR adds a cell: new files and one entry each."""
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
REHEARSAL = os.path.join(HERE, "cells")
sys.path.insert(0, BENCH_DIR)

from harness import cells, traffic, trace_reduce          # noqa: E402
from harness.stats import median, percentile              # noqa: E402


def _benchmark():
    return cells.load_benchmark()


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def hand_trace():
    with open(os.path.join(BENCH_DIR, "testdata", "hand_trace.json")) as f:
        return json.load(f)


def test_trace_reduction_busy_and_idle(hand_trace):
    want = hand_trace["answers"]
    got = trace_reduce.reduce_events(hand_trace)
    assert got["window_s"] == pytest.approx(want["window_ns"] / 1e9)
    assert got["busy_s"] == pytest.approx(
        np.mean(list(want["busy_ns"].values())) / 1e9)
    assert got["idle_pct"] == pytest.approx(want["idle_pct"])
    assert got["chips"] == 2
    assert got["longest_idle_gap_s"] == pytest.approx(
        want["longest_idle_gap_ns"] / 1e9)


def test_trace_reduction_collectives_in_flight_and_exposed(hand_trace):
    want = hand_trace["answers"]
    got = trace_reduce.reduce_events(hand_trace)
    assert got["collective_pct"] == pytest.approx(want["collective_pct"])
    assert got["collective_exposed_pct"] == pytest.approx(
        want["collective_exposed_pct"])
    # one chip alone: the asynchronous pair is in flight from its start op
    # to its done op, exposed only outside fusion.2
    chip0 = trace_reduce.reduce_events(
        {"chips": {"0": hand_trace["chips"]["0"]},
         "host": hand_trace["host"]})
    assert chip0["collective_pct"] == pytest.approx(
        100 * want["collective_in_flight_ns"]["0"] / want["window_ns"])
    assert chip0["collective_exposed_pct"] == pytest.approx(
        100 * want["collective_exposed_ns"]["0"] / want["window_ns"])


def test_trace_reduction_groups_by_innermost_op(hand_trace):
    want = hand_trace["answers"]
    got = trace_reduce.reduce_events(hand_trace)
    assert got["group_pct_of_busy"]["pallas"] == pytest.approx(
        want["pallas_pct_of_busy"])
    ops = dict(got["device_ops"])
    # the while op keeps only the time none of its body's ops covers
    assert ops["while.1"] == pytest.approx(100 / 2 / 1e9)
    assert ops["fusion.1"] == pytest.approx((200 + 400) / 2 / 1e9)
    assert len(got["device_ops"]) <= 10


def test_trace_reduction_names_idle_gaps_by_host_span(hand_trace):
    want = hand_trace["answers"]["idle_gaps_ns"]
    got = dict(trace_reduce.reduce_events(hand_trace)["idle_gaps"])
    assert got == {k: pytest.approx(v / 1e9) for k, v in want.items()}


def test_trace_reduction_of_nothing_is_nothing():
    assert trace_reduce.reduce_events({"chips": {}, "host": []}) is None
    assert trace_reduce.reduce_events(
        {"chips": {"0": []}, "host": [["bench:window", 0, 10]]}) is None


@pytest.mark.parametrize("name,group", [
    ("all-reduce.12", "collective"), ("all-gather-start.3", "collective"),
    ("reduce-scatter.1", "collective"), ("%collective-permute-done.2",
                                         "collective"),
    ("custom-call.7", "pallas"), ("fusion.120", "other"),
    ("while.3", "other"), ("copy.4", "other")])
def test_op_groups(name, group):
    assert trace_reduce.op_group(name) == group


def test_recorded_tpu_trace_is_read():
    """The small trace recorded on the chip (tools/record_small_trace.py):
    three steps of a toy program with a 2 ms host pause after each."""
    path = os.path.join(BENCH_DIR, "testdata", "small_tpu.xplane.pb")
    events = trace_reduce.read_xplane(path)
    assert list(events["chips"]) == ["0"]
    assert len(events["chips"]["0"]) > 10
    spans = [n for n, _, _ in events["host"]]
    assert spans.count("bench:window") == 1
    assert spans.count("bench:train_batch") == 3
    got = trace_reduce.reduce_events(events)
    assert 0 < got["busy_s"] < got["window_s"]
    # the host slept 3 x 2 ms inside the window: the device was idle then
    gaps = dict(got["idle_gaps"])
    assert gaps["bench:wait_arrival"] >= 0.006
    assert got["idle_pct"] > 50
    assert got["group_pct_of_window"]["collective"] == 0


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def gpt2():
    return cells.load_module(os.path.join(BENCH_DIR, "architectures",
                                          "gpt2.py"), "bench_arch_gpt2_t")


@pytest.mark.parametrize("name,params,gflop_per_token", [
    # 24 x (12 x 1024^2 + 13 x 1024) + (50257 + 1024) x 1024 + 2 x 1024
    ("gpt2-350m", 354_823_168, 2.2716),
    # 48 x (12 x 1600^2 + 13 x 1600) + (50257 + 1024) x 1600 + 2 x 1600
    ("gpt2-xl", 1_557_611_200, 9.8016)])
def test_operations_per_token_against_hand_counts(gpt2, name, params,
                                                  gflop_per_token):
    config = _config(name)
    assert gpt2.n_params(config) == params
    E, L, V, S = config["n_embd"], config["n_layer"], \
        config["vocab_size"], 1024
    # by hand: per layer QKV 6E^2 + out 2E^2 + MLP 16E^2, attention
    # 2 x 2 x (S/2) x E; head 2EV; backward twice the forward
    by_hand = 3 * (L * (6 * E * E + 2 * E * E + 16 * E * E + 2 * S * E)
                   + 2 * E * V)
    assert gpt2.train_flops_per_token(config, S) == by_hand
    assert by_hand / 1e9 == pytest.approx(gflop_per_token, abs=1e-3)


@pytest.mark.parametrize("name,gb", [("gpt2-350m", 2.627), ("gpt2-xl", 10.005)])
def test_decode_bytes_against_hand_counts(gpt2, name, gb):
    config = _config(name)
    # 48 lanes each reading 256 positions of bf16 keys and values in every
    # layer, and every weight once as float32
    got = gpt2.decode_step_bytes(config, lanes=48, context_positions=256,
                                 weight_bytes=4, kv_bytes=2)
    by_hand = gpt2.n_params(config) * 4 + 48 * 256 * config["n_layer"] \
        * 2 * config["n_embd"] * 2
    assert got == by_hand
    assert got / 1e9 == pytest.approx(gb, abs=0.01)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, .95) == 95 and median(xs) == 50
    assert percentile([3.0], .95) == 3.0 and percentile([], .5) is None


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------
CHAT = {"arrivals": {"process": "poisson", "rate_per_s": 10.0},
        "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                       "min": 32, "max": 768},
        "new_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                       "min": 16, "max": 256}}


def test_same_seed_same_schedule_and_lengths():
    a = traffic.requests(CHAT, 50257, seed=7, horizon_s=30)
    b = traffic.requests(CHAT, 50257, seed=7, horizon_s=30)
    c = traffic.requests(CHAT, 50257, seed=8, horizon_s=30)
    assert (a["due"] == b["due"]).all()
    assert (a["new_tokens"] == b["new_tokens"]).all()
    assert all((p == q).all() for p, q in zip(a["prompts"], b["prompts"]))
    assert len(a["due"]) != len(c["due"]) or (a["due"] != c["due"]).any()


def test_poisson_schedule_and_clipped_lengths():
    load = traffic.requests(CHAT, 50257, seed=3, horizon_s=200)
    due = load["due"]
    assert (np.diff(due) >= 0).all() and due[-1] < 200
    assert len(due) == pytest.approx(2000, rel=0.1)
    lens = np.array([len(p) for p in load["prompts"]])
    assert lens.min() >= 32 and lens.max() <= 768
    assert np.median(lens) == pytest.approx(192, rel=0.1)
    assert load["new_tokens"].min() >= 16 and load["new_tokens"].max() <= 256
    assert all(p.dtype == np.int32 and p.max() < 50257
               for p in load["prompts"])


def test_backlog_bursts_and_shared_prefix_are_data_only():
    backlog = traffic.requests(
        dict(CHAT, arrivals={"process": "backlog", "requests": 50}),
        1000, seed=1, horizon_s=10)
    assert len(backlog["due"]) == 50 and not backlog["due"].any()
    bursts = traffic.requests(
        dict(CHAT, arrivals={"process": "bursts", "burst_size": 16,
                             "every_s": 2.0}), 1000, seed=1, horizon_s=10)
    assert len(bursts["due"]) == 5 * 16
    assert sorted(set(bursts["due"])) == [0.0, 2.0, 4.0, 6.0, 8.0]
    shared = traffic.requests(
        dict(CHAT, shared_prefix={"tokens": 64, "sessions": 2}),
        1000, seed=1, horizon_s=10)
    heads = {tuple(p[:64]) for p in shared["prompts"]}
    assert len(heads) == 2
    with pytest.raises(ValueError):
        traffic.requests(dict(CHAT, arrivals={"process": "nope"}), 10, 0, 1)


def test_stratified_lengths_and_paced_arrivals_carry_the_same_work():
    """Every stretch of a few blocks holds the same spread of lengths under
    every seed, in another order, and there is one arrival in every 1/rate
    seconds: what lets some tens of requests repeat."""
    mix = {"arrivals": {"process": "paced", "rate_per_s": 2.0},
           "prompt_len": dict(CHAT["prompt_len"], stratified=16),
           "new_tokens": {"dist": "uniform", "min": 8, "max": 32,
                          "stratified": 16}}
    a = traffic.requests(mix, 50257, seed=1, horizon_s=40)
    b = traffic.requests(mix, 50257, seed=2, horizon_s=40)
    assert len(a["due"]) == len(b["due"]) == 80
    assert (a["due"] != b["due"]).any()
    assert (np.floor(a["due"] * 2.0) == np.arange(80)).all()
    lens_a = np.array([len(p) for p in a["prompts"]])
    lens_b = np.array([len(p) for p in b["prompts"]])
    assert (lens_a != lens_b).any()
    assert lens_a.min() >= 32 and lens_a.max() <= 768
    for lens in (lens_a, lens_b):
        # each block of 16 holds one length from each sixteenth of the
        # distribution: its median sits between the 8th and 9th sixteenth
        for block in lens.reshape(5, 16):
            assert 160 <= np.median(block) <= 230
            assert block.max() >= 512 and block.min() <= 80
    # the same work under both seeds, to a few percent; independent draws
    # of 80 log-normal lengths differ by 10 % and more
    assert lens_a.sum() == pytest.approx(lens_b.sum(), rel=0.05)
    assert set(a["new_tokens"]) <= set(range(8, 33))
    for block in a["new_tokens"].reshape(5, 16):
        assert np.mean(block) == pytest.approx(20, abs=1.0)


def _lens(load):
    return np.array([len(p) for p in load["prompts"]])


def test_lengths_seed_gives_every_run_the_same_lengths_in_the_same_order():
    """A mix that states ``lengths_seed`` draws its lengths from that and
    the token ids from the run's seed; one that does not draws both from
    the run's, to the draw as before the key was there."""
    mix = {"arrivals": {"process": "backlog", "requests": 96},
           "prompt_len": {"dist": "uniform", "min": 64, "max": 512,
                          "stratified": 16},
           "new_tokens": {"dist": "uniform", "min": 8, "max": 32,
                          "stratified": 16}}
    fixed = dict(mix, lengths_seed=0)
    a, b = (traffic.requests(fixed, 1000, seed=s, horizon_s=10)
            for s in (1, 2**31 + 7))
    assert (_lens(a) == _lens(b)).all()
    assert (a["new_tokens"] == b["new_tokens"]).all()
    assert any((p != q).any() for p, q in zip(a["prompts"], b["prompts"]))
    # still the stratified draw: a sixteenth of the range a request a block
    for block in _lens(a).reshape(6, 16):
        assert sorted((block - 64) * 16 // 449) == list(range(16))
    other = traffic.requests(dict(mix, lengths_seed=1), 1000, 1, 10)
    assert (_lens(other) != _lens(a)).any()
    # without the key: the run's seed draws the lengths, as it always did
    c, d = (traffic.requests(mix, 1000, seed=s, horizon_s=10) for s in (1, 2))
    assert (_lens(c) != _lens(d)).any()
    rng = np.random.default_rng(1)
    assert (_lens(c) == traffic._lengths(mix["prompt_len"], rng, 96)).all()


@pytest.mark.parametrize("name", [w["name"] for w in _benchmark()["workloads"]
                                  if w["traffic"].startswith("serve-")])
def test_a_backlog_a_window_does_not_empty_is_taken_up_in_one_order(name):
    """Where a window takes up only part of a backlog of long requests,
    WHICH requests it takes up may not hang on the run's seed (the
    long-document cell read 1.8 % from seed to seed by that, and 0.4 % with
    the order fixed; PERF.md section 6, PR 45): the two cells the check
    refused state ``lengths_seed``.  The other two are as the check measured
    them: the offline cell's window counts 1,040 short requests, 65 blocks;
    the repository cell waits for a machine to show the same on."""
    cell = cells.Cell(_benchmark(), name)
    mix = cell.traffic
    assert mix["arrivals"]["process"] == "backlog"
    a, b = (traffic.requests(mix, 1000, seed=s, horizon_s=33)
            for s in (11, 2**31 + 12))
    same = (_lens(a) == _lens(b)).all() \
        and (a["new_tokens"] == b["new_tokens"]).all()
    assert same == ("lengths_seed" in mix)
    assert same == (name in ("mistral-small-4-ep4.serve-long-docs",
                             "longcat-flash-chat-ep32.serve-long-answers"))
    assert any(len(p) != len(q) or (p != q).any()
               for p, q in zip(a["prompts"], b["prompts"]))


def test_train_batches_are_seeded_and_distinct():
    job = {"gradient_accumulation": 2, "micro_batch_per_chip": 3,
           "seq_len": 16, "distinct_batches": 4}
    a, tokens = traffic.train_batches(job, 100, seed=5, chips=4)
    b, _ = traffic.train_batches(job, 100, seed=5, chips=4)
    assert tokens == 2 * 12 * 16 and len(a) == 4
    assert a[0]["input_ids"].shape == (2, 12, 16)
    assert all((x["input_ids"] == y["input_ids"]).all()
               for x, y in zip(a, b))
    assert (a[0]["input_ids"] != a[1]["input_ids"]).any()
    assert (a[0]["labels"] == a[0]["input_ids"]).all()


# ---------------------------------------------------------------------------
# BENCHMARK.json holds to the contract
# ---------------------------------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# what ``reduced`` may never name: a hidden, intermediate, latent, state or
# projection size, a head size, an expansion factor, experts per token
WIDTHS = ("hidden_size", "intermediate_size", "n_embd", "n_inner", "_dim",
          "_rank", "head_size", "expansion", "experts_per_tok")


def test_benchmark_json_contract():
    b = _benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    cells_ = b["workloads"]
    assert 2 <= len(cells_) <= 24
    assert sum(w["chips"] == 4 for w in cells_) <= max(1, len(cells_) // 4)
    names = [w["name"] for w in cells_]
    assert len(set(names)) == len(names)
    assert len({(w["config"], w["traffic"]) for w in cells_}) == len(cells_)
    configs = {c["name"]: c for c in b["configs"]}
    assert {w["config"] for w in cells_} == set(configs)
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        # a configuration may be cut (depth, the experts or vocabulary
        # held): the file and the entry agree on what, and no cut is a width
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"]
                    if any(w in k for w in WIDTHS)], c["name"]
    for w in cells_:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    metric_names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", names)) <= set(names)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
        # reported only where the metric it moves is
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", names)) \
            <= set(moved.get("workloads", names)), m["name"]
    for name in names:
        cell = cells.Cell(b, name)
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
        assert cell.driver().run and cell.architecture().build_model


def test_every_file_of_the_benchmark_is_named_as_the_contract_allows():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in _benchmark()["paths"]:
        for folder, _, files in os.walk(os.path.join(CHECKOUT, root)):
            if "__pycache__" in folder:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), CHECKOUT)
                assert ok.match(rel), rel


# ---------------------------------------------------------------------------
# per-layer readers: nothing to read gives nothing
# ---------------------------------------------------------------------------
def test_readers_return_nothing_when_there_is_nothing_to_read():
    b = _benchmark()
    for name in [w["name"] for w in b["workloads"]]:
        cell = cells.Cell(b, name)
        for m in cell.per_layer:
            assert cell.reader(m["name"])({}) is None, m["name"]


def test_readers_on_hand_made_observations():
    cell = cells.Cell(_benchmark(), "gpt2-350m.train-pack1024")
    observed = {"tokens_per_s": 30000.0, "flops_per_token": 2.0e9,
                "chips": 1, "peaks": {"bf16_flops_per_s": 200e12},
                "dispatch_ms": [1.0, 3.0, 2.0],
                "memory_peak_bytes": 10_000_000_000,
                "compiles_in_window": 0,
                "trace": {"idle_pct": 2.5,
                          "group_pct_of_busy": {"pallas": 17.0}}}
    read = {m["name"]: cell.reader(m["name"])(observed)
            for m in cell.per_layer}
    assert read == {"train_dispatch_ms": 2.0, "train_mfu_pct": 30.0,
                    "pallas_pct": 17.0, "device_idle_pct": 2.5,
                    "peak_hbm_gb": 10.0, "compiles_in_window": 0}
    serve = cells.Cell(_benchmark(), "gpt2-350m.serve-offline")
    observed = {"counters": {"slot_steps": 200, "active_slot_steps": 50,
                             "kv_occupancy_mean": 0.25, "steps": 10},
                "queue_wait_s": [1.0, 2.0, 3.0],
                "spans": {"decode_step": [{"ms": 30.0, "a0": 4},
                                          {"ms": 0.01, "a0": 0},
                                          {"ms": 40.0, "a0": 5}],
                          "prefill_tick": [{"ms": 0.01, "a0": -1},
                                           {"ms": 5.0, "a0": -1}]}}
    read = {m["name"]: serve.reader(m["name"])(observed)
            for m in serve.per_layer}
    assert read["offline_slot_util_pct"] == 25.0
    assert read["offline_queue_wait_p50_s"] == 2.0
    assert read["kv_occupancy_pct"] == 25.0
    assert read["offline_decode_step_ms"] == 30.0
    assert read["offline_prefill_tick_ms"] == 5.0
    assert read["offline_device_idle_pct"] is None


# ---------------------------------------------------------------------------
# open loop: latency from the due time, lateness reported
# ---------------------------------------------------------------------------
def test_latency_is_taken_from_the_due_time():
    serve = cells.load_module(os.path.join(BENCH_DIR, "harness",
                                           "drive_serve.py"), "drive_serve_t")
    # request 0: due 1.0, submitted late at 1.5, first token 2.0, 5 tokens
    # by 3.0; request 1: due 2.0, never finished; the load ended at 10.0
    got = serve.latencies(
        counted=[0, 1], due=[1.0, 2.0], finished={0},
        first_token={0: 2.0}, last_token={0: 3.0}, n_tokens={0: 5},
        load_end=10.0)
    assert got["ttft"] == [1.0, 8.0]        # from due, not from submit
    assert got["tpot"] == [0.25, 0.25]      # the unfinished one: the largest
    assert serve.lateness([1.5, 2.0], [1.0, 2.0]) == {"median": 0.0,
                                                      "max": 0.5}


def test_completed_tokens_count_when_they_were_processed():
    serve = cells.load_module(os.path.join(BENCH_DIR, "harness",
                                           "drive_serve.py"), "drive_serve_u")
    window = (5.0, 10.0)
    requests = [
        # wholly inside: 600 + 21 tokens
        (6.0, 6.5, 9.0, 600, 21),
        # admitted before the window, prefill half inside (4.5-5.5): half of
        # 400 + 1; decode 5.5-7.5 inside: 10
        (4.5, 5.5, 7.5, 400, 11),
        # decode straddles the end: prefill 8-9 inside (801), 40 tokens over
        # 9-13 of which a quarter inside
        (8.0, 9.0, 13.0, 800, 41),
        # wholly outside
        (11.0, 12.0, 13.0, 500, 9)]
    by_hand = (600 + 21) + (401 / 2 + 10) + (801 + 40 / 4)
    assert serve.completed_tokens_per_s(requests, window) \
        == pytest.approx(by_hand / 5.0)
    assert serve.completed_tokens_per_s([], window) == 0.0


# ---------------------------------------------------------------------------
# rehearsal: every cell's data path at a toy width, on the CPU
# ---------------------------------------------------------------------------
def _rehearse(name, devices, seconds, trace):
    benchmark = cells.load_benchmark(os.path.join(REHEARSAL,
                                                  "BENCHMARK.json"))
    cell = cells.Cell(benchmark, name, root=REHEARSAL)
    logged = {}
    run = cell.driver().run(cell, devices, seed=3, seconds=seconds,
                            trace=trace, process_start=time.perf_counter(),
                            log=logged.update)
    return cell, run, logged


def _refuses_device_metrics(cell, run, devices):
    runner = cells.load_module(os.path.join(BENCH_DIR, "run.py"),
                               "bench_run_t")
    from harness import device as device_lib

    with pytest.raises(device_lib.NoDevice):
        device_lib.require_tpu(1)
    with pytest.raises(device_lib.NoDevice):
        runner.result_line(cell, run, device_lib.describe(devices, 0), 0)


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_training_cell(devices, chips):
    """The data path of both training cells: one chip, and mesh data=4
    with ZeRO-2 (virtual CPU devices)."""
    cell, run, logged = _rehearse("gpt2-tiny.train-tiny", devices[:chips],
                                  seconds=1.0, trace=False)
    assert run["correct"], logged["checks"]
    assert run["attempted"] >= 4 and run["failed"] == 0
    assert logged["loss_rel_err"] <= logged["loss_rtol"]
    assert run["observed"]["chips"] == chips
    assert run["observed"]["compiles_in_window"] == 0
    assert run["observed"]["peaks"] is None        # no peak of a CPU
    read = {m["name"]: cell.reader(m["name"])(run["observed"])
            for m in cell.per_layer}
    assert read["train_mfu_pct"] is None and read["device_idle_pct"] is None
    assert read["train_dispatch_ms"] > 0
    _refuses_device_metrics(cell, run, devices[:chips])


@pytest.mark.parametrize("name,trace", [
    ("gpt2-tiny.serve-tiny-open", False),
    ("gpt2-tiny.serve-tiny-backlog", True)])
def test_rehearsal_serving_cell(devices, name, trace):
    cell, run, logged = _rehearse(name, devices[:1], seconds=1.5,
                                  trace=trace)
    assert run["correct"], logged
    assert run["attempted"] > 5 and run["failed"] == 0
    # the seeded sample of four, and the longest where the draw left it out
    assert logged["reference"]["requests_checked"] in (4, 5)
    assert logged["generator_lateness_s"]["max"] >= 0
    read = {m["name"]: cell.reader(m["name"])(run["observed"])
            for m in cell.per_layer}
    assert read["compiles_in_window"] == 0
    if trace:       # the engine's own tracer was armed, the device's is a CPU
        assert read["offline_decode_step_ms"] > 0
        assert read["offline_device_idle_pct"] is None
        # the per-layer metric the rehearsal cell added with a file and an
        # entry of its own
        assert read["prefill_tokens_per_step"] > 0
    _refuses_device_metrics(cell, run, devices[:1])


def test_command_prints_no_result_without_a_tpu():
    b = _benchmark()
    out = subprocess.run(
        [sys.executable] + b["command"][1:]
        + ["--workload", b["workloads"][0]["name"], "--seed", "0",
           "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode not in (0, 1, 2), out.stderr[-2000:]
    assert "no device to measure on" in out.stderr
    assert out.stdout.strip() == ""
