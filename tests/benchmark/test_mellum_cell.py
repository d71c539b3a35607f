"""The ``mellum`` architecture's benchmark files, checked on the CPU in
seconds: its configuration against the catalog's row, its entries and its
traffic letter for letter, its arithmetic against hand counts, its plain
reference against itself (rows of the full call, blocks against whole), its
rule against the two controls at a toy width (4 bits; the window taken out
of the sliding layers), the new per-layer readers with and without something
to read, and a rehearsal of the cell's data path at a toy width.  The toy
cell lives in ``cells/mellum/`` and was added as a PR adds a cell: new files
only.  Nothing here asserts the cell's POSITION in ``workloads`` or a count
of cells."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
REHEARSAL = os.path.join(HERE, "cells", "mellum")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

CONFIG = "mellum2-12b-a2.5b-8of28"
CELL = CONFIG + ".serve-repo-context"
TOY_CELL = "mellum-tiny.serve-tiny-repo"
ALIASES = tuple("repoctx_" + name for name in (
    "chunk_step_ms", "decode_program_ms", "prefill_program_ms",
    "host_gap_pct", "slot_util_pct", "queue_wait_p50_s", "kv_occupancy_pct",
    "device_idle_pct", "peak_hbm_gb"))
POOL_METRICS = ("swa_window_group_occupancy_pct", "swa_cache_bytes_kept_pct")
COUNTER_METRICS = ("mellum_expert_load_ratio", "mellum_experts_touched_pct")
ROOFLINES = ("mellum_grouped_matmul_roofline_pct",
             "gqa_prefill_attn_roofline_pct",
             "mellum_decode_program_hbm_roofline_pct")
# a reader that is there and by hand reads what it should, and is NOT
# entered: the harness's reduction keeps the ten largest operations, and on
# the chip this cell's two decode attention kernels are not among them
# (PERF.md section 7: which file would need which edit)
NOT_ENTERED = ("gqa_paged_decode_attn_roofline_pct",)
STATS = ("moe_routed_rows", "moe_held_rows", "moe_busiest_scaled_rows",
         "moe_experts_touched", "moe_expert_slots")
# the catalog's ``config`` for Mellum2-12B-A2.5B-Instruct (model-configs
# guide, architectures.jsonl)
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "mellum.py"), "bench_arch_mellum_t")


@pytest.fixture(scope="module")
def serve():
    return cells.load_module(os.path.join(
        BENCH_DIR, "harness", "drive_serve.py"), "bench_drive_serve_ml_t")


def _config():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _tiny(**changes):
    with open(os.path.join(REHEARSAL, "configs", "mellum-tiny.json")) as f:
        return dict(json.load(f), **changes)


def _reader(name):
    return cells.load_module(
        os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
        f"bench_metric_ml_t_{name}").read


# ---------------------------------------------------------------------------
# the configuration file and the entries
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_names_every_cut():
    config = _config()
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "mlp_layer_types"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # depth only, two whole periods: every width, all 64 experts, 8 a
    # token, the whole vocabulary
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == PERIOD * 2
    assert config["mlp_layer_types"] == ["sparse"] * 8
    assert (config["num_experts"], config["num_experts_per_tok"],
            config["vocab_size"]) == (64, 8, 98304)
    assert config["architecture"] == "mellum" \
        and config["source"].endswith(
            "JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    for key in ("qk_norm", "rope_layout", "yarn", "router", "window",
                "max_window_layers", "mtp_head", "initializer",
                "compute_dtype", "served_weight_dtype"):
        assert config["assumed"][key], key
    assert "pipeline" in config["deployment"] \
        and "no exchange" in config["deployment"]


def test_the_byte_counts_of_the_file_by_hand(arch):
    config = _config()
    E, D, H, Hkv, I, n, V = 2304, 128, 32, 4, 896, 64, 98304
    attention = E * H * D + 2 * E * Hkv * D + H * D * E
    assert round(attention / 1e6, 3) == 21.234
    expert = 3 * E * I
    assert round(expert / 1e6, 3) == 6.193
    layer = attention + E * n + 2 * E + n * expert
    assert round(layer / 1e6, 2) == 417.75 \
        and round((layer - n * expert) / 1e6, 2) == 21.39
    total = 8 * layer + 2 * V * E + E
    assert round(total / 1e6, 1) == 3795.0 and round(2 * total / 1e9, 2) \
        == 7.59
    assert arch.n_params(config) == total == config["bytes"]["parameters"]
    assert config["bytes"] == {
        "parameters": total, "parameters_a_layer": layer,
        "parameters_a_layer_outside_experts": layer - n * expert,
        "parameters_an_expert": expert, "embedding_or_head": V * E,
        "served_weight_gb": 7.59,
        "cache_bytes_a_token_and_layer": 2 * Hkv * D * 2}
    # the whole model: 12.15 B parameters, which one chip cannot hold
    assert round((28 * layer + 2 * V * E + E) / 1e9, 2) == 12.15
    # the pool at the cell's engine settings: pages of 64 rows of 512
    # values, keys and values, bf16
    page = 2 * 64 * 512 * 2
    assert page == 131072
    assert round(2 * (1 + 32 * 518) * page / 1e9, 2) == 4.35
    assert round(6 * (1 + 32 * 17 + 32) * page / 1e9, 2) == 0.45
    assert round(8 * (1 + 32 * 518) * page / 1e9, 1) == 17.4   # one shape
    assert arch.kv_row_bytes(config) == 2048
    assert arch.layers_of(config) == (2, 6)


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    b = cells.load_benchmark()
    entry = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert entry["reduced"] == _config()["reduced"]
    assert entry["source"] == _config()["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    workload = {w["name"]: w for w in b["workloads"]}[CELL]
    assert len(workload["why"]) <= 200 and len(entry["why"]) <= 200
    cell = cells.Cell(b, CELL)
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    # the names this file knows are there; a later PR may enter more
    assert {"compiles_in_window", *ALIASES, *POOL_METRICS, *COUNTER_METRICS,
            *ROOFLINES} <= set(names)
    assert all(_reader(name)({}) is None for name in NOT_ENTERED)
    for m in cell.per_layer:
        assert m["moves"] in ("serve_tokens_per_s", "setup_s")
        assert callable(cell.reader(m["name"]))
        assert cell.reader(m["name"])({}) is None, m["name"]
        assert CELL in m["workloads"]
    # the traffic the issue gives, letter for letter
    traffic = cell.traffic
    assert traffic["driver"] == "serve" and traffic["what"]
    assert traffic["model_overrides"] == {}
    assert traffic["arrivals"] == {"process": "backlog", "requests": 512}
    # sigma 0.7, the issue's letter: the one change it allows (0.5) was
    # measured on the chip and steadied nothing (two sets of six: 1.69 and
    # 0.98 % against 1.33 and 1.17 % at 0.7; PERF.md section 6, PR 37)
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.7, "min": 1024,
        "max": 32768, "stratified": 16}
    assert traffic["new_tokens"] == {"dist": "uniform", "min": 64,
                                     "max": 192, "stratified": 16}
    assert traffic["engine"] == {"max_slots": 32, "kv_block_size": 64,
                                 "prefill_chunk": 2048,
                                 "max_blocks_per_seq": 518}
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["trace_s"],
            traffic["check_requests"]) == (8, 30, 3, 4)
    engine = traffic["engine"]
    assert engine["max_blocks_per_seq"] * engine["kv_block_size"] \
        >= traffic["prompt_len"]["max"] + traffic["new_tokens"]["max"]
    assert set(traffic) == {"driver", "what", "arrivals", "prompt_len",
                            "new_tokens", "ramp_s", "drain_s", "trace_s",
                            "check_requests", "model_overrides", "engine"}


def test_the_traffic_is_short_and_long_in_one_queue():
    """The generator's own draw of the mix: mostly a few thousand tokens,
    one in sixteen over 18 k (the top stratum of sixteen), mean ~7.9 k."""
    from harness import traffic as traffic_lib

    mix = cells.Cell(cells.load_benchmark(), CELL).traffic
    load = traffic_lib.requests(mix, 98304, 2147483659, 38.0)
    lengths = np.array([len(p) for p in load["prompts"]])
    assert len(lengths) == 512 and lengths.min() >= 1024 \
        and lengths.max() <= 32768
    assert 5500 < np.median(lengths) < 6800
    assert 7400 < lengths.mean() < 8400
    assert 0.05 < np.mean(lengths > 18000) < 0.08
    assert 120 < load["new_tokens"].mean() < 136
    assert max(p.max() for p in load["prompts"]) > 98000    # whole vocabulary


# ---------------------------------------------------------------------------
# arithmetic against hand counts
# ---------------------------------------------------------------------------
def test_costs_against_hand_counts(arch):
    config = _config()
    E, I, H, D = 2304, 896, 32, 128
    outside = 8 * 21385728 + 98304 * E + E
    # 30 lanes at a mean context of 8 k: every full layer reads them all,
    # every sliding layer a window a lane
    got = arch.decode_step_bytes(
        config, keys_full=30 * 8000, keys_window=30 * 1024, weight_bytes=2,
        kv_bytes=2, experts_touched=62)
    rows = (2 * 30 * 8000 + 6 * 30 * 1024) * 2048
    assert got == outside * 2 + 8 * 62 * 3 * E * I * 2 + rows
    assert arch.decode_step_bytes(
        config, keys_full=0, keys_window=0, weight_bytes=2, kv_bytes=2) \
        == outside * 2 + 8 * 64 * 3 * E * I * 2
    assert 7.0e9 < outside * 2 + 8 * 64 * 3 * E * I * 2 < 7.3e9  # ~7.1 GB
    assert 1.2e9 < rows < 1.5e9                                  # ~1.4 GB
    # if all eight layers kept every position they would read 3.9 GB
    assert round(8 * 30 * 8000 * 2048 / 1e9, 1) == 3.9
    up = arch.grouped_matmul_cost(config, held_rows=2048 * 8 * 8,
                                  experts_touched=512, call="up")
    down = arch.grouped_matmul_cost(config, held_rows=2048 * 8 * 8,
                                    experts_touched=512, call="down")
    assert up[0] + down[0] == 2 * 2048 * 8 * 8 * 3 * E * I
    assert round((up[0] + down[0]) / 1e12, 1) == 1.6        # of a chunk
    assert up[1] + down[1] == 512 * arch.expert_bytes(config) \
        + 2048 * 64 * (E + 2 * I + I + E) * 2
    # 256 rows an expert in a chunk of 2,048: the experts' bytes (6.5 ms)
    # still outlast the operations (5.5 ms); a decode step's by far
    assert 1.0 < (up[1] / 819e9) / (up[0] / 197e12) < 1.3
    few = arch.grouped_matmul_cost(config, held_rows=30 * 8 * 8,
                                   experts_touched=500, call="up")
    assert few[0] / 197e12 < 0.02 * few[1] / 819e9
    flops, moved = arch.prefill_attn_cost(config, pairs=2048 * 8192,
                                          queries=2048)
    assert flops == 2 * 2048 * 8192 * H * 2 * D
    assert moved == (2048 * 8192 // 1024 * H * 2 * D
                     + 2048 * H * 2 * D) * 2
    flops, moved = arch.decode_attn_cost(config, keys=30 * 8000)
    assert (flops, moved) == (2 * 240000 * H * 2 * D, 240000 * 2048)
    assert moved / 819e9 > flops / 197e12       # bound by the rows' bytes


def test_counters_are_of_this_configuration_alone(arch):
    config = _config()
    assert arch.counters_are_of(config, {"moe_expert_slots": 512,
                                         "attn_keys_full": 5})
    assert arch.counters_are_of(config, {"moe_expert_slots": 512,
                                         "attn_pairs_full": 5})
    # a model of one group, and another depth
    assert not arch.counters_are_of(config, {"moe_expert_slots": 512,
                                             "attn_keys": 5})
    assert not arch.counters_are_of(config, {"moe_expert_slots": 64,
                                             "attn_keys_full": 5})


# ---------------------------------------------------------------------------
# the plain reference against itself, and the rule against its controls
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_float(arch):
    config = _tiny(assumed={"compute_dtype": "float32",
                            "initializer_range": 0.2})
    return config, arch.init_params(arch.build_model(config, {}), 7)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "architectures", "mellum.py")) as f:
        lines = [l for l in f.read().splitlines() if "deepspeed_tpu" in l
                 and ("import " in l)]
    # the one import is ``build_model``'s, of the program's model itself
    assert lines == ["    from deepspeed_tpu.models.mellum import "
                     "MellumConfig, MellumModel"]


def test_reference_in_blocks_is_the_reference_whole(tiny_float):
    """At the real size a request is many blocks of query rows long, a
    sliding layer's keys come as the blocks that hold the windows, and an
    expert's rows come as padded tiles: the same paths at toy block sizes (modules of their own, so that nothing compiled at the
    real sizes is met again)."""
    small = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "mellum.py"), "bench_arch_ml_blocks")
    small._Q_ROWS, small._LENGTH_STEP, small._TILE_ROWS, small._HEAD_ROWS \
        = 16, 32, 8, 8
    whole = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "mellum.py"), "bench_arch_ml_whole")
    whole._Q_ROWS, whole._LENGTH_STEP = 208, 208
    config, params = tiny_float
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            (1, 200), dtype=np.int32)
    full = np.asarray(whole.reference_logits(
        whole.reference_weights(params, config), config, ids))
    assert full.shape == (1, 200, config["vocab_size"])
    weights = small.reference_weights(params, config)
    np.testing.assert_allclose(
        np.asarray(small.reference_logits(weights, config, ids)), full,
        rtol=0, atol=3e-5)
    for rows in (np.arange(70, 90), np.arange(180, 199)):
        some = np.asarray(small.reference_logits(weights, config, ids, rows))
        np.testing.assert_allclose(some, full[:, rows], rtol=0, atol=3e-5)
    # causal: what follows a row does not move it
    head = np.asarray(small.reference_logits(weights, config, ids[:, :90]))
    np.testing.assert_allclose(head, full[:, :90], rtol=0, atol=3e-5)


def test_served_check_states_a_routed_rule_with_its_reasons(arch):
    check = arch.served_check(_config())
    rule = check["rule"]
    assert set(rule) == set(check["why"]) \
        == {"near_best_spacings", "share", "every_row_sigma"}
    assert rule == {"near_best_spacings": 4.0, "share": 0.95,
                    "every_row_sigma": 3.0}
    # the longest checked request rounded up to a block of query rows
    assert check["width"](32960) == 33792 and check["width"](900) == 1024


@pytest.mark.parametrize("control,held", [
    ("as_bf16", True), ("as_fp8", False), ("no_window", False)])
def test_rule_refuses_its_two_controls_at_a_toy_width(arch, serve,
                                                      tiny_float, control,
                                                      held):
    """The controls of the rule at the toy width: the reference with every
    matmul's inputs and result in 4 significand bits, and the reference
    with the window taken out of the sliding layers, each one's best token
    of every row taken as the served one; against the same in 8 bits
    (bf16's), which the rule holds.  On the chip at the cell's own size:
    ``benchmark/tools/served_controls.py`` (PERF.md section 6)."""
    config, params = tiny_float
    rule = arch.served_check(config)["rule"]
    weights = arch.reference_weights(params, config)
    for seed in range(2):
        ids = np.random.default_rng(seed).integers(
            0, config["vocab_size"], (1, 128), dtype=np.int32)
        reference = np.asarray(arch.reference_logits(weights, config, ids))
        if control == "no_window":
            low = arch.reference_logits(
                weights, dict(config, sliding_window=config[
                    "max_position_embeddings"]), ids)
        else:
            low = arch.reference_logits(
                weights, config, ids,
                control_bits={"as_bf16": 8, "as_fp8": 4}[control])
        # the rows past the first window: before it the window hides nothing
        rows = slice(config["sliding_window"] + 8, None)
        got, seen = serve.judge_rows(reference[0][rows],
                                     np.asarray(low)[0][rows].argmax(-1),
                                     rule)
        assert got is held, (seed, seen)


# ---------------------------------------------------------------------------
# rehearsal: the cell's data path at a toy width, on the CPU
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced(devices):
    logged = {}
    benchmark = cells.load_benchmark(os.path.join(REHEARSAL,
                                                  "BENCHMARK.json"))
    cell = cells.Cell(benchmark, TOY_CELL, root=REHEARSAL)
    run = cell.driver().run(
        cell, devices[:1], seed=2147483999, seconds=1.5, trace=True,
        process_start=time.perf_counter(), log=logged.update)
    return cell, run, logged


def test_the_toy_cell_names_the_real_cells_metrics():
    real = cells.Cell(cells.load_benchmark(), CELL)
    toy = cells.Cell(cells.load_benchmark(os.path.join(
        REHEARSAL, "BENCHMARK.json")), TOY_CELL, root=REHEARSAL)
    # every name the toy cell rehearses is the real cell's; a later PR may
    # enter more in the real one
    assert {m["name"] for m in toy.per_layer} \
        <= {m["name"] for m in real.per_layer}


def test_rehearsal_cell_is_correct_with_no_compilation(traced):
    cell, run, logged = traced
    assert run["correct"], logged
    assert run["attempted"] > 5 and run["failed"] == 0
    # eight and the longest: with four, the ~45 judged rows of this toy
    # routed model left the 0.95 share to luck (three rows where bf16 and
    # f32 chose another expert failed a sound run, about every second one)
    assert logged["reference"]["requests_checked"] in (8, 9)
    assert run["observed"]["compiles_in_window"] == 0
    assert run["end_to_end"]["serve_tokens_per_s"] > 0


def test_the_counters_ride_the_ring(traced):
    spans = traced[1]["observed"]["spans"]
    for group in ("prefill_16", "decode"):
        for name in STATS + ("clock_ms",):
            assert spans.get(f"{name}_{group}"), (name, group)
    for name in ("attn_pairs_full_prefill_16", "attn_pairs_window_prefill_16",
                 "attn_keys_full_decode", "attn_keys_window_decode",
                 "kv_pages_full", "kv_pages_window", "kv_pool_pages_full",
                 "kv_pool_pages_window", "kv_window_pages_freed"):
        assert spans.get(name), name
        assert all(e["ms"] == 0.0 and e["a0"] >= 0 for e in spans[name])
    assert sum(e["a0"] for e in spans["kv_window_pages_freed"]) > 0
    from harness import roofline

    progs = roofline.programs(spans)
    decodes = [p for p in progs if p["group"] == "decode"]
    chunks = [p for p in progs if p["group"].startswith("prefill_")]
    assert decodes and chunks
    config = _tiny()
    assert {p["moe_expert_slots"] for p in decodes} \
        == {config["num_experts"] * config["num_hidden_layers"]}
    # a window lane attends at most its window, a full lane its context
    assert all(0 < p["attn_keys_window"] <= p["attn_keys_full"]
               for p in decodes)
    assert all(0 < p["attn_pairs_window"] <= p["attn_pairs_full"]
               for p in chunks)
    assert any(p["attn_pairs_window"] < p["attn_pairs_full"] for p in chunks)


@pytest.mark.parametrize("name", COUNTER_METRICS + ROOFLINES[2:])
def test_readers_entered_for_the_real_sizes_give_nothing_on_the_toys(
        traced, name):
    """The toy records the same counters at ITS sizes (8 experts x 4
    layers): a reader that divides by the real configuration's gives
    nothing there (``counters_are_of``), as on any other model's run."""
    cell, run, _ = traced
    assert cell.reader(name)(run["observed"]) is None


@pytest.mark.parametrize("name", POOL_METRICS + ALIASES[:7])
def test_reader_gives_a_number_on_the_run_itself(traced, name):
    cell, run, _ = traced
    value = cell.reader(name)(run["observed"])
    assert value is not None and value > 0, name
    if name == "swa_cache_bytes_kept_pct":
        assert value < 100          # a window of 12 under prompts of ~30
    if name.endswith("occupancy_pct"):
        assert value <= 100


@pytest.mark.parametrize("name", ROOFLINES[:2] + NOT_ENTERED + ALIASES[7:8])
def test_device_metrics_are_left_out_on_a_cpu(traced, name):
    """No device trace on the CPU: a reader that needs one gives nothing
    and does not raise."""
    cell, run, _ = traced
    assert run["observed"]["trace"] is None
    assert _reader(name)(run["observed"]) is None


@pytest.mark.parametrize("name", COUNTER_METRICS + POOL_METRICS + ROOFLINES
                         + NOT_ENTERED)
def test_reader_gives_nothing_on_another_models_counters(name):
    """The parent's program, and a model of one group (``mistral4``'s
    counters): nothing, and no error."""
    def series(group, n, **counters):
        out = {f"clock_ms_{group}": [{"ms": 0.0, "a0": 100 * (i + 1)}
                                     for i in range(n)]}
        for key, value in counters.items():
            out[f"{key}_{group}"] = [{"ms": 0.0, "a0": value}] * n
        return out

    other = {"spans": {
        "run_decode": [{"ms": 25.0, "a0": 16}] * 4,
        **series("prefill_2048", 4, moe_held_rows=2048, moe_routed_rows=8192,
                 moe_busiest_scaled_rows=4096, moe_experts_touched=160,
                 moe_expert_slots=160, attn_pairs=2048 * 1025),
        **series("decode", 4, moe_held_rows=16, moe_routed_rows=64,
                 moe_busiest_scaled_rows=64, moe_experts_touched=50,
                 moe_expert_slots=160, attn_keys=16000, attn_pages=250)},
        "trace": {"window_s": 0.5, "idle_pct": 5.0, "device_ops": [
            ["moe_grouped_matmul_prefill_up.2 = bf16[2048,4096] custom-call",
             0.012]]}}
    assert _reader(name)(other) is None
    assert _reader(name)({"spans": {"run_decode": [{"ms": 1.0, "a0": 1}]},
                          "trace": None}) is None


def _hand_observed():
    """Two seconds of serving, a chunk of 2,048 and a decode program every
    0.1 s, the last half second traced: 5 chunks and 5 decode programs in
    it."""
    def series(group, n, **counters):
        out = {f"clock_ms_{group}": [{"ms": 0.0, "a0": 100 * (i + 1)}
                                     for i in range(n)]}
        for key, value in counters.items():
            out[f"{key}_{group}"] = [{"ms": 0.0, "a0": value}] * n
        return out

    return {
        "spans": {
            "run_decode": [{"ms": 14.0, "a0": 30}] * 4,
            "kv_pages_full": [{"ms": 0.0, "a0": 4000}] * 20,
            "kv_pages_window": [{"ms": 0.0, "a0": 500}] * 20,
            "kv_pool_pages_full": [{"ms": 0.0, "a0": 16576}] * 20,
            "kv_pool_pages_window": [{"ms": 0.0, "a0": 576}] * 20,
            **series("prefill_2048", 20, moe_routed_rows=2048 * 64,
                     moe_held_rows=2048 * 64,
                     moe_busiest_scaled_rows=300 * 64 * 8,
                     moe_experts_touched=512, moe_expert_slots=512,
                     attn_pairs_full=2048 * 8192,
                     attn_pairs_window=2048 * 1024),
            **series("decode", 20, moe_routed_rows=30 * 64,
                     moe_held_rows=30 * 64, moe_busiest_scaled_rows=9 * 512,
                     moe_experts_touched=496, moe_expert_slots=512,
                     attn_keys_full=30 * 8000, attn_keys_window=30 * 1024),
        },
        "trace": {"window_s": 0.5, "idle_pct": 5.0, "device_ops": [
            ["moe_grouped_matmul_prefill_up.2 = bf16[20480,1792] "
             "custom-call", 0.06],
            ["moe_grouped_matmul_prefill_up.3 = bf16[20480,1792] "
             "custom-call", 0.02],
            ["moe_grouped_matmul_prefill_down.4 = bf16[20480,2304] "
             "custom-call", 0.03],
            ["gqa_prefill_attn_full.7 = bf16[32,2048,128] custom-call",
             0.02],
            ["gqa_prefill_attn_window.8 = bf16[32,2048,128] custom-call",
             0.012],
            ["gqa_paged_decode_attn_full.9 = bf16[32,32,128] custom-call",
             0.008],
            ["fusion.9 = bf16[2048,2304] fusion", 0.2]]},
    }


def test_readers_by_hand(arch):
    observed = _hand_observed()
    config = _config()
    assert _reader("mellum_expert_load_ratio")(observed) \
        == 300 * 64 * 8 / (2048 * 64)
    assert _reader("mellum_experts_touched_pct")(observed) \
        == 100.0 * 496 / 512
    assert _reader("swa_window_group_occupancy_pct")(observed) \
        == pytest.approx(100.0 * 500 / 576)
    # 2 layers keep 4,000 pages and 6 keep 500, where 8 would keep 4,000
    assert _reader("swa_cache_bytes_kept_pct")(observed) == pytest.approx(
        100.0 * (2 * 4000 + 6 * 500) / (8 * 4000))
    # the stretch holds 5 chunks and 5 decode programs.  Grouped matmuls:
    # both call sites of the chunk's up call were kept (all 8 layers), one
    # of its down call (the larger share: the 6 sliding layers), none of
    # the decode program's
    least = 0.0
    for call, share in (("up", 1.0), ("down", 6 / 8)):
        flops, moved = arch.grouped_matmul_cost(
            config, held_rows=2048 * 64, experts_touched=512, call=call)
        least += share * 5 * max(flops / 197e12, moved / 819e9)
    got = _reader("mellum_grouped_matmul_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * least / 0.11) and 0 < got < 100
    # rectangle attention: 2 full layers over causal pairs, 6 sliding ones
    # over the pairs inside the window
    least = 0.0
    for layers, pairs in ((2, 2048 * 8192), (6, 2048 * 1024)):
        flops, moved = arch.prefill_attn_cost(config, pairs=pairs,
                                              queries=2048)
        least += 5 * layers * max(flops / 197e12, moved / 819e9)
    got = _reader("gqa_prefill_attn_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * least / 0.032) and 0 < got < 100
    # paged decode: the window kernel's operation was not kept, so it is
    # left out on both sides
    flops, moved = arch.decode_attn_cost(config, keys=30 * 8000)
    got = _reader("gqa_paged_decode_attn_roofline_pct")(observed)
    assert got == pytest.approx(
        100.0 * 5 * 2 * max(flops / 197e12, moved / 819e9) / 0.008)
    assert 0 < got < 100
    got = _reader("mellum_decode_program_hbm_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * arch.decode_step_bytes(
        config, keys_full=30 * 8000, keys_window=30 * 1024, weight_bytes=2,
        kv_bytes=2, experts_touched=62) / 819e9 / 0.014)
    assert 0 < got < 100
