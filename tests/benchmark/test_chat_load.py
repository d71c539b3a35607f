"""What PR 45 added so that ``gpt2-350m.serve-chat`` loads the engine it
measures and its tail can be judged: the traffic file at 0.8 x a swept
knee, a judged percentile held to its sample count, the sweep's backlog
rule, and the GPT-2 roofline reader's count of the weights at the width
the engine holds.  CPU, hand-made numbers, seconds."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import cells, traffic                                # noqa: E402
from harness.stats import (judged_percentile, least_samples,      # noqa: E402
                           percentile)

CELL = "gpt2-350m.serve-chat"
# the mix as PR 22 defined it and as it stays: the same users
PROMPT_LEN = {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32,
              "max": 768, "stratified": 16}
NEW_TOKENS = {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16,
              "max": 256, "stratified": 16}
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
       "memory_peak_bytes": 1}


def _load(folder, name, tag):
    return cells.load_module(os.path.join(BENCH_DIR, folder, name + ".py"),
                             f"bench_{tag}_t")


@pytest.fixture(scope="module")
def cell():
    return cells.Cell(cells.load_benchmark(withheld=True), CELL)


# ---------------------------------------------------------------------------
# the traffic file
# ---------------------------------------------------------------------------
def test_the_cell_runs_at_a_stated_share_of_a_swept_knee(cell):
    mix = cell.traffic
    knee, rate = mix["knee"], mix["arrivals"]["rate_per_s"]
    assert mix["arrivals"]["process"] == "paced"
    assert isinstance(rate, (int, float)) and not isinstance(rate, bool)
    # 0.8 x the knee, or 0.6 x with both readings recorded
    share = rate / knee["rate_per_s"]
    assert share == pytest.approx(0.8, abs=0.005) or (
        share == pytest.approx(0.6, abs=0.005) and "0.8" in knee["note"])
    assert len(knee["found_on_commit"]) == 40
    assert "/s" in knee["note"] and "backlog" in knee["note"]   # the table
    assert mix["engine"]["max_slots"] in (64, 48)
    assert mix["engine"]["max_slots"] == 64 or "48" in knee["note"]
    # everything else as it was: the same users' mix
    assert mix["prompt_len"] == PROMPT_LEN
    assert mix["new_tokens"] == NEW_TOKENS
    assert {k: v for k, v in mix["engine"].items() if k != "max_slots"} \
        == {"kv_block_size": 16, "prefill_chunk": 256,
            "max_blocks_per_seq": 64}
    assert (mix["ramp_s"], mix["drain_s"], mix["trace_s"],
            mix["check_requests"]) == (5, 30, 3, 8)
    assert mix["model_overrides"] == {"scan_layers": True}
    assert cells.load_benchmark()["run_seconds"] == 30
    why = cell.entry["why"]
    assert len(why) <= 200 and f"{rate:g}/s" in why \
        and f"{mix['engine']['max_slots']} slots" in why
    assert "pool copies" not in why         # gone with PR 27


@pytest.mark.parametrize("seed", [0, 7, 2391000031])
def test_a_window_counts_hundreds_of_requests_of_the_same_lengths(cell,
                                                                  seed):
    mix = cell.traffic
    ramp, seconds = float(mix["ramp_s"]), 30.0
    load = traffic.requests(mix, cell.config["vocab_size"], seed,
                            ramp + seconds)
    due = load["due"]
    counted = int(((due >= ramp) & (due < ramp + seconds)).sum())
    assert counted >= 2 * least_samples(.95) and least_samples(.95) == 200
    assert counted == int(round(mix["arrivals"]["rate_per_s"] * seconds))
    # the lengths are drawn as they always were: from the generator's state
    # after the arrivals, the specs above give these very lengths
    rng = np.random.default_rng(seed)
    rng.uniform(size=len(due))
    lens = np.array([len(p) for p in load["prompts"]])
    k = len(due) // 16
    assert k >= 30
    assert (lens == traffic._lengths(PROMPT_LEN, rng, len(due))).all()
    assert (load["new_tokens"]
            == traffic._lengths(NEW_TOKENS, rng, len(due))).all()
    # and every block of 16 still holds one length of each sixteenth of its
    # distribution: the same work and the same tail under every seed
    for lengths, lo, hi in ((lens, 80, 512), (load["new_tokens"], 40, 210)):
        blocks = lengths[:16 * k].reshape(k, 16)
        assert (blocks.min(axis=1) <= lo).all() \
            and (blocks.max(axis=1) >= hi).all()
        # (a block's one shift moves its sixteen lengths together: ~5 %)
        sums = blocks.sum(axis=1)
        assert sums.std() / sums.mean() < 0.08


# ---------------------------------------------------------------------------
# a judged percentile is held to its sample count
# ---------------------------------------------------------------------------
def test_a_p95_needs_ten_values_beyond_its_rank():
    assert least_samples(.95) == 200 and least_samples(.5) == 20
    assert least_samples(.99) == 1000 and least_samples(.9) == 100
    values = [float(i) for i in range(1, 201)]
    assert judged_percentile(values, .95) == percentile(values, .95) == 190.0
    assert sum(v > 190.0 for v in values) == 10
    assert judged_percentile(values[:199], .95) is None
    assert judged_percentile([], .95) is None


def _toy_run(n):
    """A run's result as ``drive_serve.run`` builds it, from hand-made
    timings of ``n`` requests: due every 0.1 s, first token 10 ms later,
    19 more tokens at a gap of 2 ms + 10 us x the request's number."""
    serve = _load("harness", "drive_serve", "drive_serve_chat")
    due = [0.1 * i for i in range(n)]
    first = {i: due[i] + 0.010 for i in range(n)}
    last = {i: first[i] + 19 * (0.002 + 1e-5 * i) for i in range(n)}
    timing = serve.latencies(
        counted=list(range(n)), due=due, finished=set(range(n)),
        first_token=first, last_token=last,
        n_tokens={i: 20 for i in range(n)}, load_end=due[-1] + 1.0)
    return {"correct": True, "attempted": n, "failed": 0,
            "compared": {"requests_failed": [0, 0]},
            "requests_counted": n,
            "end_to_end": {
                "tpot_p95_s": serve.judged_percentile(timing["tpot"], .95),
                "serve_tokens_per_s": 1.0, "setup_s": 1.0},
            "observed": {}}


def test_the_run_refuses_a_judged_percentile_over_too_few_requests(cell):
    runner = _load("", "run", "run_chat")
    # a cell that judges a p95 (the rehearsal's open-loop cell does): 200
    rehearsal = os.path.join(HERE, "cells")
    toy = cells.Cell(cells.load_benchmark(os.path.join(
        rehearsal, "BENCHMARK.json")), "gpt2-tiny.serve-tiny-open",
        root=rehearsal)
    assert [m["name"] for m in toy.end_to_end] == ["tpot_p95_s", "setup_s"]
    with pytest.raises(RuntimeError) as refused:
        runner.result_line(toy, _toy_run(199), dict(TPU), 0)
    assert "tpot_p95_s" in str(refused.value) \
        and "199 requests counted" in str(refused.value)
    line = runner.result_line(toy, _toy_run(200), dict(TPU), 0)
    # nearest rank: the 190th of 200 gaps
    assert line["metrics"]["tpot_p95_s"]["value"] \
        == pytest.approx(0.002 + 1e-5 * 189)
    assert line["requests_counted"] == 200
    assert list(line)[-1] == "compared"          # the contract: it comes last
    # the chat cell judges the same tail, under the same name, by that rule
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p95_s", "setup_s"}
    with pytest.raises(RuntimeError) as refused:
        runner.result_line(cell, _toy_run(199), dict(TPU), 0)
    assert "tpot_p95_s" in str(refused.value)
    line = runner.result_line(cell, _toy_run(200), dict(TPU), 0)
    assert line["metrics"]["tpot_p95_s"]["value"] \
        == pytest.approx(0.002 + 1e-5 * 189)
    # a cell that judges a rate is not held to the count
    offline = cells.Cell(cells.load_benchmark(), "gpt2-350m.serve-offline")
    line = runner.result_line(offline, _toy_run(60), dict(TPU), 0)
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_median_and_the_stalls_are_read_beside_the_judged_tail(cell):
    """``tpot_p50_s`` is the middle of what ``tpot_p95_s`` is the tail of,
    ``chat_step_stall_ms`` the time in steps of over three medians; both
    are the chat cell's per-layer entries and move its judged metric."""
    entries = {m["name"]: m for m in cell.per_layer}
    assert "tpot_p95_s" not in entries          # judged, from ONE place
    for name in ("tpot_p50_s", "chat_step_stall_ms",
                 "generator_lateness_p50_ms", "ttft_p95_s"):
        assert entries[name]["moves"] == "tpot_p95_s", name
        assert entries[name]["workloads"] == [CELL]
        assert cell.reader(name)({}) is None
    gaps = [0.002 + 1e-5 * i for i in range(200)]
    assert cell.reader("tpot_p50_s")({"tpot_s": gaps}) \
        == pytest.approx(0.002 + 1e-5 * 99)
    steps = [0.008] * 97 + [0.010, 0.025, 0.130]
    assert cell.reader("chat_step_stall_ms")({"step_s": steps}) \
        == pytest.approx(155.0)
    assert cell.reader("chat_step_stall_ms")({"step_s": [0.008] * 9}) == 0.0
    assert cell.reader("generator_lateness_p50_ms")(
        {"generator_lateness_s": [0.001, 0.004, 0.009]}) \
        == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# the sweep's rule: whether a backlog grew
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sweep():
    return _load("tools", "knee_sweep", "knee_sweep")


def _served(due, every_s, start=0.0):
    """First-token times of a server that takes requests in order, one
    every ``every_s`` seconds, none before it is due."""
    out, free = [], start
    for d in due:
        free = max(free, d) + every_s
        out.append(free)
    return out


def test_backlog_rule_on_hand_made_completions(sweep):
    window = (5.0, 25.0)
    due = [0.02 * i for i in range(1500)]              # 50/s for 30 s
    # a server of 60/s keeps up: everyone waits one service time
    grew, growth, offered = sweep.grows(due, _served(due, 1 / 60), window)
    assert not grew and growth == pytest.approx(0.0, abs=0.1)
    assert offered == pytest.approx(50.0)
    assert sweep.backlog(due, _served(due, 1 / 60), 10.0) in (0, 1)
    # a server of 40/s falls behind by 10 requests a second
    behind = _served(due, 1 / 40)
    grew, growth, _ = sweep.grows(due, behind, window)
    assert grew and growth == pytest.approx(10.0, rel=0.05)
    assert sweep.backlog(due, behind, 25.0) == pytest.approx(250, abs=3)
    # 49.95/s: behind by 2/3 of a request between the thirds' middles
    # (13.3 s apart), under the rule's one request; 49.9/s: by 1.3
    assert not sweep.grows(due, _served(due, 1 / 49.95), window)[0]
    assert sweep.grows(due, _served(due, 1 / 49.9), window)[0]
    # a stall before the window that is worked off inside it is no growth
    late = _served(due, 1 / 60, start=8.0)
    grew, growth, _ = sweep.grows(due, late, window)
    assert not grew and growth < 0
    # requests that were never served wait for ever
    never = [None if d > 20.0 else f
             for d, f in zip(due, _served(due, 1 / 60))]
    assert sweep.grows(due, never, window)[0]
    assert sweep.backlog(due, never, 25.0) == pytest.approx(250, abs=2)


def test_the_knee_is_the_highest_rate_that_held_under_every_seed(sweep):
    rows = [(30.0, False), (40.0, False), (50.0, False), (60.0, True),
            (30.0, False), (40.0, False), (50.0, True), (60.0, True)]
    assert sweep.knee(rows) == 40.0
    assert sweep.knee([(20.0, True), (30.0, True)]) is None
    assert sweep.knee([(20.0, False), (30.0, False)]) == 30.0
    # a rate that held above one that grew does not count
    assert sweep.knee([(30.0, False), (40.0, True), (50.0, False)]) == 30.0


# ---------------------------------------------------------------------------
# the GPT-2 roofline reader counts the width the engine holds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["decode_program_hbm_roofline_pct",
                                  "offline_decode_program_hbm_roofline_pct"])
def test_decode_roofline_counts_bf16_weights(name):
    read = _load("layer_metrics", name, name).read
    with open(os.path.join(BENCH_DIR, "configs", "gpt2-350m.json")) as f:
        config = json.load(f)
    assert config["assumed"]["served_weight_dtype"] == "bfloat16"
    keys = [3000, 5000]      # positions the live lanes attended, a program
    spans = {"clock_ms_decode": [{"ms": 0.0, "a0": 10.0},
                                 {"ms": 0.0, "a0": 20.0}],
             "attn_keys_decode": [{"ms": 0.0, "a0": k} for k in keys],
             "run_decode": [{"ms": 4.0, "a0": 40}, {"ms": 6.0, "a0": 41},
                            {"ms": 5.0, "a0": 42}, {"ms": 0.1, "a0": 0}]}
    # by hand: 354,823,168 parameters x 2 bytes, and a key and a value of
    # 1024 x 2 bytes in each of 24 layers a position attended; 819 GB/s;
    # the median decode program 5 ms
    moved = 354_823_168 * 2 + np.mean(keys) * 24 * 2 * 1024 * 2
    assert read({"spans": spans}) \
        == pytest.approx(100 * moved / 819e9 / 5e-3)
    assert 100 * 354_823_168 * 2 / 819e9 / 5e-3 == pytest.approx(17.3, 0.01)
    # counted at 4 bytes, as until PR 45, the same spans read 17.3 points
    # more: with few keys a program nearly double (the ledger's 53.7)
    assert 100 * (354_823_168 * 4 + np.mean(keys) * 98_304) / 819e9 / 5e-3 \
        - read({"spans": spans}) == pytest.approx(17.33, abs=0.01)
    assert read({}) is None and read({"spans": {}}) is None
    # no decode program alone in the window: nothing, not 0
    assert read({"spans": dict(spans, run_decode=[])}) is None


# ---------------------------------------------------------------------------
# set-up's objects are out of the collector's sight while the load runs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stated", [True, False])
def test_the_window_runs_with_set_ups_objects_frozen(devices, monkeypatch,
                                                     stated):
    """A full collection over everything set-up built takes 0.1 s and more
    and lands in some windows and not in others; for a mix that STATES
    ``freeze_setup_objects`` ``drive_serve`` freezes what is there when the
    warm-up ends, counts that as set-up, leaves the collector on, and thaws
    it all again before the engine is released.  A mix that does not state
    it runs as it always did: of the real cells the chat cell alone does."""
    import gc
    import time

    everything = cells.load_benchmark(withheld=True)
    stating = [w["name"] for w in everything["workloads"]
               if cells.Cell(everything, w["name"]).traffic.get(
                   "freeze_setup_objects")]
    assert stating == [CELL]
    rehearsal = os.path.join(HERE, "cells")
    toy = cells.Cell(cells.load_benchmark(os.path.join(
        rehearsal, "BENCHMARK.json")), "gpt2-tiny.serve-tiny-open",
        root=rehearsal)
    assert toy.traffic["freeze_setup_objects"] is True
    if not stated:
        del toy.traffic["freeze_setup_objects"]
    serve = toy.driver()
    seen = {"frozen_in_steps": [], "enabled_in_steps": []}
    step = serve.InferenceEngine.step

    def watched(engine):
        seen["frozen_in_steps"].append(gc.get_freeze_count())
        seen["enabled_in_steps"].append(gc.isenabled())
        return step(engine)

    monkeypatch.setattr(serve.InferenceEngine, "step", watched)
    before = gc.get_freeze_count()
    run = serve.run(toy, devices[:1], seed=3, seconds=1.0, trace=False,
                    process_start=time.perf_counter(),
                    log=lambda record: None)
    # (whether the toy's second of wall clock served everyone on a loaded
    # machine is other tests' business: this one watches the collector)
    assert run["requests_counted"] > 5
    assert gc.get_freeze_count() == before
    in_window = seen["frozen_in_steps"][-run["observed"]["counters"]["steps"]:]
    if stated:
        # the warm-up's own steps ran before the freeze, the load's after it
        assert min(in_window) > before + 10_000
    else:
        assert set(seen["frozen_in_steps"]) == {before}
    assert all(seen["enabled_in_steps"])


# ---------------------------------------------------------------------------
# a cell that BENCHMARK.json does not enter yet is whole, and apart
# ---------------------------------------------------------------------------
def test_withheld_cells_are_whole_and_apart():
    """``benchmark/withheld/<cell>.json`` holds, under the keys of
    ``BENCHMARK.json``, the entries of a cell that is built and tested but
    not judged yet, and says why.  ``BENCHMARK.json`` names nothing of it,
    and with its entries put back the cell resolves: its configuration,
    its traffic file, a reader for every per-layer entry, and an end-to-end
    metric of its own that every one of them moves."""
    entered = cells.load_benchmark()
    names = {w["name"] for w in entered["workloads"]}
    held_dir = os.path.join(BENCH_DIR, "withheld")
    files = sorted(f for f in os.listdir(held_dir) if f.endswith(".json")) \
        if os.path.isdir(held_dir) else []
    everything = cells.load_benchmark(withheld=True)
    assert len(everything["workloads"]) == len(names) + sum(
        len(json.load(open(os.path.join(held_dir, f)))["workloads"])
        for f in files)
    for file in files:
        with open(os.path.join(held_dir, file)) as f:
            held = json.load(f)
        assert set(held) == {"why", "workloads", "end_to_end", "per_layer"}
        assert len(held["why"]) > 100           # the fault and the readings
        own = {w["name"] for w in held["workloads"]}
        assert own and not own & names and file[:-5] in own
        for metrics in (entered["end_to_end"], entered["per_layer"]):
            assert not any(own & set(m.get("workloads", []))
                           for m in metrics)
        judged = {m["name"] for m in held["end_to_end"]}
        assert judged and all(0.01 <= m["bound"] <= 0.1
                              for m in held["end_to_end"])
        for name in own:
            cell = cells.Cell(everything, name)
            assert {m["name"] for m in cell.end_to_end} == judged | {"setup_s"}
            assert cell.traffic["driver"] and cell.config["architecture"]
            for m in cell.per_layer:
                assert cell.reader(m["name"])({}) is None, m["name"]
        for m in held["per_layer"]:
            assert set(m["workloads"]) <= own
            assert m["moves"] in judged | {"setup_s"}
