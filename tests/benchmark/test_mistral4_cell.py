"""The ``mistral4`` architecture's benchmark files, checked on the CPU in
seconds: its configuration against the published keys, its arithmetic
against hand counts, its plain reference against itself (rows of the full
call, the four quarters of the experts adding up to the uncut layer), its
rule against the 4-bit control and against a program built wrongly, the new
per-layer readers with and without something to read, and a rehearsal of
the cell's data path at a toy width.  The toy cells live in
``cells/mistral4/`` and were added as a PR adds a cell: new files only."""
import copy
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
REHEARSAL = os.path.join(HERE, "cells", "mistral4")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

CELL = "mistral-small-4-ep4.serve-long-docs"
STATS = ("moe_routed_rows", "moe_held_rows", "moe_busiest_scaled_rows",
         "moe_experts_touched", "moe_expert_slots")
# the toy cell's chunks of 16 run in bucket 16; every program has a clock
COUNTERS = tuple(f"{name}_{group}" for group in ("prefill_16", "decode")
                 for name in STATS + ("clock_ms",)) \
    + ("attn_pairs_prefill_16", "attn_keys_decode")
COUNTER_METRICS = ("moe_held_rows_share_pct", "moe_expert_load_ratio",
                   "moe_experts_touched_pct")
# the last reads no device trace (spans and counters), but like the two
# kernels' it is entered for the real configuration's sizes
ROOFLINES = ("moe_grouped_matmul_roofline_pct",
             "mla_prefill_attn_roofline_pct",
             "mla_decode_program_hbm_roofline_pct")
ALIASES = tuple("longdocs_" + name for name in (
    "chunk_step_ms", "prefill_program_ms", "decode_program_ms",
    "host_gap_pct", "slot_util_pct", "kv_occupancy_pct", "device_idle_pct",
    "peak_hbm_gb", "queue_wait_p50_s", "decode_step_ms", "prefill_tick_ms"))


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "mistral4.py"), "bench_arch_m4_t")


@pytest.fixture(scope="module")
def serve():
    return cells.load_module(os.path.join(
        BENCH_DIR, "harness", "drive_serve.py"), "bench_drive_serve_m4_t")


def _config():
    with open(os.path.join(BENCH_DIR, "configs",
                           "mistral-small-4-ep4.json")) as f:
        return json.load(f)


def _tiny(**changes):
    with open(os.path.join(REHEARSAL, "configs", "mistral4-tiny.json")) as f:
        return dict(json.load(f), **changes)


def _reader(name):
    return cells.load_module(
        os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
        f"bench_metric_m4_t_{name}").read


# ---------------------------------------------------------------------------
# the configuration file and the entries
# ---------------------------------------------------------------------------
# the catalog's ``config`` for Mistral-Small-4-119B-2603 (model-configs
# guide, architectures.jsonl), numbers and flags at the top level
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_size": 4096, "intermediate_size": 12288, "kv_lora_rank": 256,
    "max_position_embeddings": 1048576, "mlp_bias": False,
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 36, "num_key_value_heads": 32, "q_lora_rank": 1024,
    "qk_head_dim": 128, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True,
    "routed_scaling_factor": 1, "tie_word_embeddings": False,
    "topk_group": 1, "v_head_dim": 128, "vocab_size": 131072}
PUBLISHED_ROPE = {
    "beta_fast": 32, "beta_slow": 1, "factor": 128,
    "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
    "original_max_position_embeddings": 8192, "rope_theta": 10000,
    "rope_type": "yarn", "type": "yarn"}


def test_configuration_holds_the_published_keys_and_names_every_cut():
    config = _config()
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts_held",
                       "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["rope_parameters"] == PUBLISHED_ROPE
    assert (config["num_hidden_layers"], config["n_routed_experts_held"],
            config["vocab_size"]) == (5, 32, 32768)
    # the guide's floors: over four layers, an eighth of the experts and of
    # the vocabulary at least
    assert config["n_routed_experts_held"] * 8 >= config["n_routed_experts"]
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for key in ("router_scoring", "llama_4_scaling", "yarn_attention_factor",
                "initializer", "vision_tower"):
        assert config["assumed"][key]
    assert "expert parallel" in config["deployment"].lower() \
        or "expert parallelism" in config["deployment"]


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    b = cells.load_benchmark()
    entry = {c["name"]: c for c in b["configs"]}["mistral-small-4-ep4"]
    assert entry["reduced"] == _config()["reduced"]
    assert entry["source"] == _config()["source"]
    cell = cells.Cell(b, CELL)
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    # the names this file knows are there; a later PR may enter more
    assert {"compiles_in_window", *ALIASES, *COUNTER_METRICS,
            *ROOFLINES} <= set(names)
    for m in cell.per_layer:
        assert m["moves"] in ("serve_tokens_per_s", "setup_s")
        assert callable(cell.reader(m["name"]))
    traffic = cell.traffic
    assert traffic["arrivals"] == {"process": "backlog", "requests": 256}
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 8192,
                                     "max": 24576, "stratified": 16}
    engine = traffic["engine"]
    assert engine["max_blocks_per_seq"] * engine["kv_block_size"] \
        >= traffic["prompt_len"]["max"] + traffic["new_tokens"]["max"]


def test_benchmark_json_contract_with_a_configuration_that_is_cut():
    """``test_benchmark_harness.py::test_benchmark_json_contract`` asserts
    ``reduced == []`` for every configuration, which held while every
    configuration ran as published; that file is the benchmark's and a PR
    of this kind may not edit it.  What it holds besides, held here with
    cuts allowed: a cut names no width, and the file and the entry agree."""
    b = cells.load_benchmark()
    names = [w["name"] for w in b["workloads"]]
    assert len(names) == len(set(names))    # how many is a later PR's
    assert sum(w["chips"] == 4 for w in b["workloads"]) \
        <= max(1, len(names) // 4)
    assert {w["config"] for w in b["workloads"]} \
        == {c["name"] for c in b["configs"]}
    width = ("hidden_size", "intermediate_size", "_dim", "_rank",
             "experts_per_tok")
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if any(w in k for w in width)]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert set(m.get("workloads", names)) <= set(names)
        assert set(m.get("workloads", names)) \
            <= set(e2e[m["moves"]].get("workloads", names)), m["name"]
    for name in names:
        cell = cells.Cell(b, name)
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
            assert cell.reader(m["name"])({}) is None, m["name"]
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        assert len(f.read()) <= 64 * 1024


# ---------------------------------------------------------------------------
# arithmetic against hand counts
# ---------------------------------------------------------------------------
def test_parameters_and_bytes_against_hand_counts(arch):
    config = _config()
    E, I, H = 4096, 2048, 32
    outside = E * 1024 + 1024 * H * 128 + E * 320 + 256 * H * 192 \
        + H * 128 * E + E * 128 + 3 * E * I + 2 * E + 1024 + 256
    assert round(outside / 1e6, 1) == 53.7      # the issue says 53.8
    layer = outside + 32 * 3 * E * I
    assert round(layer / 1e6) == 859
    total = 5 * layer + 2 * 32768 * E + E
    assert arch.n_params(config) == total
    assert round(total / 1e6) == 4564 and round(2 * total / 1e9, 2) == 9.13
    assert arch.expert_bytes(config) == 3 * E * I * 2
    # a decode step over 10 lanes at 16k: dense weights, every held expert
    # or the 12 a layer a counter says were touched, 640 B a token and layer
    dense = (total - 5 * 32 * 3 * E * I) * 2
    rows = 10 * 16384 * 5 * 320 * 2
    assert arch.decode_step_bytes(
        config, lanes=10, context_positions=16384, weight_bytes=2,
        kv_bytes=2) == dense + 5 * 32 * 3 * E * I * 2 + rows
    assert arch.decode_step_bytes(
        config, lanes=10, context_positions=16384, weight_bytes=2,
        kv_bytes=2, experts_touched=12) == dense + 5 * 12 * 3 * E * I * 2 \
        + rows


def test_kernel_costs_against_hand_counts(arch):
    config = _config()
    E, I = 4096, 2048
    # 2,048 rows on held experts over 5 layers x 30 experts touched
    up = arch.grouped_matmul_cost(config, held_rows=2048,
                                  experts_touched=150, call="up")
    down = arch.grouped_matmul_cost(config, held_rows=2048,
                                    experts_touched=150, call="down")
    assert up[0] + down[0] == 2 * 2048 * 3 * E * I
    assert up[1] + down[1] == 150 * arch.expert_bytes(config) \
        + 2048 * (E + 2 * I + I + E) * 2
    # bound by the touched experts' bytes, not by the operations
    assert all(moved / 819e9 > flops / 197e12 for flops, moved in (up, down))
    # one 2,048-query chunk from position 8,192: the causal part only
    pairs = 2048 * 8192 + 2048 * 2049 // 2
    flops, moved = arch.prefill_attn_cost(config, pairs=pairs, queries=2048)
    assert flops == 2 * pairs * 32 * (128 + 128)
    assert flops < 2 * 2048 * (8192 + 2048) * 32 * 256   # not the rectangle
    assert flops / 197e12 > moved / 819e9                 # compute-bound


# ---------------------------------------------------------------------------
# the plain reference against itself
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_float(arch):
    """The toy configuration in f32 with every expert held, its seeded
    weights and the reference's reading of them."""
    config = _tiny(n_routed_experts_held=8, first_routed_expert_held=0,
                   assumed={"compute_dtype": "float32",
                            "initializer_range": 0.2})
    params = arch.init_params(arch.build_model(config, {}), 7)
    return config, params


def test_reference_logits_of_rows_are_the_rows_of_the_full_call(
        arch, tiny_float):
    config, params = tiny_float
    weights = arch.reference_weights(params, config)
    ids = np.random.default_rng(1).integers(0, config["vocab_size"], (1, 45),
                                            dtype=np.int32)
    full = np.asarray(arch.reference_logits(weights, config, ids))
    assert full.shape == (1, 45, config["vocab_size"])
    rows = np.arange(20, 43)
    some = np.asarray(arch.reference_logits(weights, config, ids, rows))
    np.testing.assert_allclose(some, full[:, rows], rtol=0, atol=1e-5)
    # causal: what follows a row does not move it
    head = np.asarray(arch.reference_logits(weights, config, ids[:, :30]))
    np.testing.assert_allclose(head, full[:, :30], rtol=0, atol=1e-4)


def test_reference_in_blocks_is_the_reference_whole(tiny_float):
    """At the real size a request is longer than a block of query rows, a
    bucket of keys and a step of lengths, and its judged rows lie in the last
    blocks of a padded sequence: the same paths at toy block sizes (a module
    of its own, so that nothing compiled at the real sizes is met again)."""
    small = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "mistral4.py"), "bench_arch_m4_blocks")
    small._Q_ROWS, small._ROW_BLOCK, small._KEY_BUCKET = 8, 16, 32
    small._LENGTH_STEP, small._EXPERT_ROWS, small._HEAD_ROWS = 64, 8, 8
    config, params = tiny_float
    weights = small.reference_weights(params, config)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            (1, 200), dtype=np.int32)
    full = np.asarray(small.reference_logits(weights, config, ids))
    for rows in (np.arange(70, 90),         # held at 128 rows, live to 96
                 np.arange(30, 41),         # held at 64, two kept blocks
                 np.arange(180, 199)):      # the harness's width bounds it
        some = np.asarray(small.reference_logits(weights, config, ids, rows))
        np.testing.assert_allclose(some, full[:, rows], rtol=0, atol=2e-5)


def test_the_four_quarters_and_the_shared_expert_once_make_the_layer(
        arch, tiny_float):
    """A layer's routed sum is linear in the experts: what the reference
    adds for each quarter of them, plus everything that is not routed
    (the reference holding no expert) counted once, is the uncut layer."""
    import jax.numpy as jnp

    config, params = tiny_float
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(128, config["hidden_size"])), jnp.float32)

    def layer(first, count):
        cut = dict(config, first_routed_expert_held=first,
                   n_routed_experts_held=count)
        # the program's tree holds all layers' experts in one tensor
        held = dict(params, experts={
            k: v.reshape(-1, 8, *v.shape[1:])[:, first:first + count]
            .reshape(-1, *v.shape[1:]) for k, v in params["experts"].items()})
        weights = arch.reference_weights(held, cut)
        return np.asarray(arch._ref_layer(x, weights["layer"](0), cut, None))

    whole = layer(0, 8)
    unrouted = layer(0, 0)          # attention, residual, the shared expert
    routed = [layer(first, 2) - unrouted for first in (0, 2, 4, 6)]
    assert all(np.abs(r).max() > 1e-3 for r in routed)     # each adds a part
    np.testing.assert_allclose(unrouted + sum(routed), whole, rtol=0,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# the rule: holds bf16, refuses the control
# ---------------------------------------------------------------------------
def test_served_check_states_a_routed_rule_with_its_reasons(arch):
    check = arch.served_check(_config())
    rule = check["rule"]
    assert set(rule) == set(check["why"]) \
        == {"near_best_spacings", "share", "every_row_sigma"}
    assert 0.5 < rule["share"] < 1.0 and rule["every_row_sigma"] == 3.0
    # the longest checked request rounded up to 1,024, not the cap
    assert check["width"](24700) == 25600 and check["width"](1024) == 1024


@pytest.mark.parametrize("bits,held", [(8, True), (4, False)],
                         ids=["as_bf16_held", "control_as_fp8_refused"])
def test_rule_refuses_its_control_at_a_toy_width(arch, serve, tiny_float,
                                                 bits, held):
    """The control of the rule at the toy width: the reference with every
    matmul's inputs and result in 4 significand bits, its best token of
    every row taken as the served one, against the same in 8 bits
    (bf16's).  On the chip at the cell's own size:
    ``benchmark/tools/served_control.py`` (PERF.md section 6)."""
    config, params = tiny_float
    rule = arch.served_check(config)["rule"]
    weights = arch.reference_weights(params, config)
    for seed in range(2):
        ids = np.random.default_rng(seed).integers(
            0, config["vocab_size"], (1, 128), dtype=np.int32)
        reference = np.asarray(arch.reference_logits(weights, config, ids))
        low = np.asarray(arch.reference_logits(weights, config, ids,
                                               control_bits=bits))
        got, seen = serve.judge_rows(reference[0], low[0].argmax(-1), rule)
        assert got is held, (seed, seen)


# ---------------------------------------------------------------------------
# rehearsal: the cell's data path at a toy width, on the CPU
# ---------------------------------------------------------------------------
def _rehearse(name, devices, trace, log):
    benchmark = cells.load_benchmark(os.path.join(REHEARSAL,
                                                  "BENCHMARK.json"))
    cell = cells.Cell(benchmark, name, root=REHEARSAL)
    return cell, cell.driver().run(
        cell, devices, seed=2147483999, seconds=1.5, trace=trace,
        process_start=time.perf_counter(), log=log)


@pytest.fixture(scope="module")
def traced(devices):
    logged = {}
    cell, run = _rehearse("mistral4-tiny.serve-tiny-docs", devices[:1], True,
                          logged.update)
    return cell, run, logged


def test_rehearsal_cell_is_correct_with_no_compilation(traced):
    cell, run, logged = traced
    assert run["correct"], logged
    assert run["attempted"] > 5 and run["failed"] == 0
    assert logged["reference"]["requests_checked"] in (4, 5)
    assert run["observed"]["compiles_in_window"] == 0
    assert run["end_to_end"]["serve_tokens_per_s"] > 0


def test_the_counters_ride_the_ring(traced):
    spans = traced[1]["observed"]["spans"]
    for name in COUNTERS:
        assert spans.get(name), name
        assert all(e["ms"] == 0.0 and e["a0"] >= 0 for e in spans[name])
    config = _tiny()
    layers, top_k = config["num_hidden_layers"], config["num_experts_per_tok"]
    held = config["n_routed_experts_held"]
    from harness import roofline

    progs = roofline.programs(spans)
    decodes = [p for p in progs if p["group"] == "decode"]
    chunks = [p for p in progs if p["group"].startswith("prefill_")]
    assert decodes and chunks and all("clock_ms" in p for p in progs)
    # a decode program offers every held expert of every layer
    assert {p["moe_expert_slots"] for p in decodes} == {held * layers}
    # what a chunk routes is its tokens x experts a token x layers
    assert all(p["moe_routed_rows"] % (top_k * layers) == 0
               and 0 < p["moe_held_rows"] <= p["moe_routed_rows"]
               and p["attn_pairs"] > 0 for p in chunks)
    assert all(p["attn_keys"] > 0 for p in decodes)
    # the stretch is the last seconds before the last fetch
    late = roofline.in_stretch(progs, {"window_s": 0.3})
    assert 0 < len(late) < len(progs)
    assert min(p["clock_ms"] for p in late) \
        > max(p["clock_ms"] for p in progs) - 300


@pytest.mark.parametrize("name", COUNTER_METRICS + ALIASES[:6] + ALIASES[8:])
def test_reader_gives_a_number_on_the_run_itself(traced, name):
    cell, run, _ = traced
    value = cell.reader(name)(run["observed"])
    assert value is not None and value > 0, name
    if name == "moe_held_rows_share_pct":
        assert 20 < value < 80          # half the experts are held here
    if name == "moe_expert_load_ratio":
        assert 1.0 <= value <= _tiny()["n_routed_experts_held"]
    if name == "moe_experts_touched_pct":
        assert value <= 100


@pytest.mark.parametrize("name", ROOFLINES + ALIASES[6:7])
def test_device_metrics_are_left_out_on_a_cpu(traced, name):
    """No trace on a CPU; and the toy configuration's counters are not
    those of the configuration the roofline readers are entered for."""
    cell, run, _ = traced
    assert run["observed"]["trace"] is None
    assert cell.reader(name)(run["observed"]) is None


@pytest.mark.parametrize("name", ROOFLINES)
def test_roofline_reader_gives_nothing_for_another_configuration(name):
    """A run whose counters say another number of held experts and layers
    is not divided by this configuration's sizes."""
    observed = _hand_observed()
    assert _reader(name)(observed) is not None
    for series, events in observed["spans"].items():
        if series.startswith("moe_expert_slots_"):
            observed["spans"][series] = [dict(e, a0=4 * 3) for e in events]
    assert _reader(name)(observed) is None


@pytest.mark.parametrize("name", COUNTER_METRICS + ROOFLINES + ALIASES)
def test_reader_returns_nothing_on_the_parents_program(traced, name):
    """The driver lays this PR's benchmark files over the PARENT's program,
    whose traced run has spans and a trace but none of the new counters and
    no kernel of the new names; with nothing at all likewise."""
    observed = copy.deepcopy(traced[1]["observed"])
    for counter in [name for name in observed["spans"] if name.startswith(
            ("moe_", "attn_", "clock_ms_"))]:
        observed["spans"].pop(counter)
    assert observed["spans"]            # the four run_* / host_gap spans stay
    observed["trace"] = {"window_s": 3.0, "idle_pct": 8.0, "device_ops": [
        ["fusion.3 = bf16[28,16,64] fusion", 0.3],
        ["custom-call.7 = bf16[1024,1024] custom-call", 0.2]]}
    value = _reader(name)(observed)
    if name in COUNTER_METRICS + ROOFLINES:
        assert value is None
    assert _reader(name)({}) is None
    assert _reader(name)({"spans": {}, "trace": None, "counters": {}}) is None


def _hand_observed():
    """Two seconds of serving, a chunk and a decode program every 0.1 s,
    the last half second traced: 5 chunks of 2,048 and 5 decode programs
    in it, in which the two prefill kernels ran 0.1 and 0.05 s."""
    layers, held = 5, 32

    def series(group, n, **counters):
        out = {f"clock_ms_{group}": [{"ms": 0.0, "a0": 100 * (i + 1)}
                                     for i in range(n)]}
        for name, value in counters.items():
            out[f"{name}_{group}"] = [{"ms": 0.0, "a0": value}] * n
        return out

    return {
        "spans": {
            "run_prefill_decode": [{"ms": 90.0, "a0": 8}] * 20,
            "run_decode": [{"ms": 18.0, "a0": 8}] * 4,
            "host_gap": [{"ms": 10.0, "a0": 1}] * 20,
            **series("prefill_2048", 20,
                     moe_routed_rows=2048 * 4 * layers,
                     moe_held_rows=2048 * layers,
                     moe_busiest_scaled_rows=96 * held * layers,
                     moe_experts_touched=30 * layers,
                     moe_expert_slots=held * layers,
                     attn_pairs=2048 * 8192 + 2048 * 2049 // 2),
            **series("prefill_64", 2, moe_routed_rows=64 * 4 * layers,
                     moe_held_rows=16 * layers,
                     moe_busiest_scaled_rows=3 * held * layers,
                     moe_experts_touched=12 * layers,
                     moe_expert_slots=held * layers,
                     attn_pairs=64 * 8192 + 64 * 65 // 2),
            **series("decode", 20, moe_routed_rows=8 * 4 * layers,
                     moe_held_rows=8 * layers,
                     moe_busiest_scaled_rows=2 * held * layers,
                     moe_experts_touched=6 * layers,
                     moe_expert_slots=held * layers, attn_keys=8 * 16384),
        },
        "trace": {"window_s": 0.5, "idle_pct": 5.0, "device_ops": [
            ["moe_grouped_matmul_prefill_up.2 = bf16[12288,4096] custom-call",
             0.06],
            ["moe_grouped_matmul_prefill_down.3 = bf16[12288,4096] "
             "custom-call", 0.04],
            ["moe_grouped_matmul_decode_up.5 = bf16[576,4096] custom-call",
             0.01],
            ["mla_prefill_attn.1 = bf16[32,2048,128] custom-call", 0.05],
            ["fusion.9 = bf16[2048,4096] fusion", 0.2]]},
    }


def test_readers_by_hand(arch):
    observed = _hand_observed()
    held = 20 * 2048 * 5 + 2 * 16 * 5
    assert _reader("moe_held_rows_share_pct")(observed) \
        == 100.0 * held / (20 * 2048 * 4 * 5 + 2 * 64 * 4 * 5)
    assert _reader("moe_expert_load_ratio")(observed) \
        == (20 * 96 + 2 * 3) * 32 * 5 / held
    assert _reader("moe_experts_touched_pct")(observed) == 100.0 * 6 / 32
    config = _config()
    # the stretch: clocks 1,600 .. 2,000 of the 2,048 bucket (5 chunks) and
    # of decode (5 programs); the decode program's down call and the 64
    # bucket's operations were not kept, so their least time is left out too
    least = 0.0
    for rows, touched, calls in ((2048 * 5, 30 * 5, ("up", "down")),
                                 (8 * 5, 6 * 5, ("up",))):
        for call in calls:
            flops, moved = arch.grouped_matmul_cost(
                config, held_rows=rows, experts_touched=touched, call=call)
            least += 5 * max(flops / 197e12, moved / 819e9)
    got = _reader("moe_grouped_matmul_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * least / 0.11)
    assert 0 < got < 100
    # the decode program: dense weights, 6 touched experts a layer and the
    # latent rows of 8 x 16,384 keys, over its 18 ms
    got = _reader("mla_decode_program_hbm_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * arch.decode_step_bytes(
        config, lanes=8, context_positions=16384, weight_bytes=2,
        kv_bytes=2, experts_touched=6) / 819e9 / 0.018)
    assert 0 < got < 100
    flops, moved = arch.prefill_attn_cost(
        config, pairs=2048 * 8192 + 2048 * 2049 // 2)
    got = _reader("mla_prefill_attn_roofline_pct")(observed)
    assert got == pytest.approx(
        100.0 * 5 * 5 * max(flops / 197e12, moved / 819e9) / 0.05)
    assert 0 < got < 100


def test_a_program_built_with_another_scale_is_not_correct(devices):
    """``mistral4-wrong-scale`` is found under the toy cell's own root: the
    program's model built without the query scale and YaRN's softmax
    factor that the reference reads from the configuration."""
    logged = {}
    _, run = _rehearse("mistral4-tiny-wrong-scale.serve-tiny-docs",
                       devices[:1], False, logged.update)
    assert run["failed"] == 0
    assert not logged["checks"]["served_tokens_hold_to_reference"]
    assert not run["correct"]
