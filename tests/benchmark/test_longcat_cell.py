"""The ``longcat_flash`` architecture's benchmark files, checked on the CPU
in seconds: its configuration against the published keys, its arithmetic
against hand counts, its plain reference against itself (rows of the full
call, the shares of the experts adding up to the uncut layer with the
identity term counted once), its rule against the 4-bit control and against
a program built without the scale correction, the new per-layer readers with
and without something to read (and with ``mistral4``'s counters, as its
readers with these), and a rehearsal of the cell's data path at a toy width.
The toy cells live in ``cells/longcat/`` and were added as a PR adds a cell:
new files only."""
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
REHEARSAL = os.path.join(HERE, "cells", "longcat")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

CELL = "longcat-flash-chat-ep32.serve-long-answers"
STATS = ("moe_routed_rows", "moe_held_rows", "moe_busiest_scaled_rows",
         "moe_experts_touched", "moe_expert_slots", "moe_zero_rows")
# the toy cell's chunks of 16 run in bucket 16; every program has a clock
COUNTERS = tuple(f"{name}_{group}" for group in ("prefill_16", "decode")
                 for name in STATS + ("clock_ms",)) \
    + ("attn_pairs_prefill_16", "attn_keys_decode")
COUNTER_METRICS = ("scmoe_zero_choice_share_pct",
                   "scmoe_held_rows_share_pct", "scmoe_expert_load_ratio",
                   "scmoe_experts_touched_pct")
# the second reads no device trace (spans and counters), but like the
# kernel's it is entered for the real configuration's sizes
ROOFLINES = ("scmoe_grouped_matmul_roofline_pct",
             "scmoe_decode_program_hbm_roofline_pct",
             "paged_latent_decode_attn_roofline_pct")
# no ``longans_chunk_step_ms``: a prompt of this cell is one chunk at the
# most, always a final one, so no chunk ever runs under a decode program
ALIASES = tuple("longans_" + name for name in (
    "prefill_program_ms", "decode_program_ms", "host_gap_pct",
    "slot_util_pct", "kv_occupancy_pct", "queue_wait_p50_s",
    "device_idle_pct", "peak_hbm_gb"))
MISTRAL4_ROOFLINES = ("moe_grouped_matmul_roofline_pct",
                      "mla_decode_program_hbm_roofline_pct")


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "longcat_flash.py"), "bench_arch_lc_t")


@pytest.fixture(scope="module")
def serve():
    return cells.load_module(os.path.join(
        BENCH_DIR, "harness", "drive_serve.py"), "bench_drive_serve_lc_t")


def _config():
    with open(os.path.join(BENCH_DIR, "configs",
                           "longcat-flash-chat-ep32.json")) as f:
        return json.load(f)


def _tiny(**changes):
    with open(os.path.join(REHEARSAL, "configs", "longcat-tiny.json")) as f:
        return dict(json.load(f), **changes)


def _reader(name):
    return cells.load_module(
        os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
        f"bench_metric_lc_t_{name}").read


# ---------------------------------------------------------------------------
# the configuration file and the entries
# ---------------------------------------------------------------------------
# the catalog's ``config`` for LongCat-Flash-Chat (model-configs guide,
# architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def test_configuration_holds_the_published_keys_and_names_every_cut():
    config = _config()
    reduced = set(config["reduced"])
    assert reduced == {"num_layers", "n_routed_experts_held", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_layers"], config["n_routed_experts_held"],
            config["vocab_size"]) == (4, 16, 16384)
    # the guide's floors: four blocks, 8 routed experts and an eighth of
    # the vocabulary at least; the router keeps its width and its top-k
    assert config["num_layers"] >= 4
    assert config["n_routed_experts_held"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert config["published"]["n_routed_experts"] \
        == config["n_routed_experts"] == 512
    # every item the issue's layer marks as assumed
    for key in ("hidden_act", "block_order", "mla_scale_factors", "rope",
                "router_scoring", "router_bias", "norm_topk_prob",
                "zero_expert", "shared_expert", "tie_word_embeddings",
                "initializer", "compute_dtype", "served_weight_dtype"):
        assert config["assumed"][key], key
    assert "expert parallelism" in config["deployment"] \
        and "32 chips" in config["deployment"] \
        and "exchange" in config["deployment"]


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    b = cells.load_benchmark()
    entry = {c["name"]: c for c in b["configs"]}["longcat-flash-chat-ep32"]
    assert entry["reduced"] == _config()["reduced"]
    assert entry["source"] == _config()["source"]
    workload = {w["name"]: w for w in b["workloads"]}[CELL]    # by name
    assert len(workload["why"]) <= 200 and len(entry["why"]) <= 200
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
        assert cell.reader(m["name"])({}) is None, m["name"]
    # the traffic the issue gives, letter for letter
    traffic = cell.traffic
    assert traffic["arrivals"] == {"process": "backlog", "requests": 768}
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 256,
                                     "max": 1024, "stratified": 16}
    assert traffic["new_tokens"] == {"dist": "uniform", "min": 320,
                                     "max": 640, "stratified": 16}
    assert traffic["engine"] == {"max_slots": 64, "kv_block_size": 64,
                                 "prefill_chunk": 1024,
                                 "max_blocks_per_seq": 26}
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["trace_s"],
            traffic["check_requests"]) == (6, 30, 3, 4)
    engine = traffic["engine"]
    assert engine["max_blocks_per_seq"] * engine["kv_block_size"] \
        >= traffic["prompt_len"]["max"] + traffic["new_tokens"]["max"]


def test_benchmark_json_contract_with_two_configurations_that_are_cut():
    """``test_benchmark_harness.py::test_benchmark_json_contract`` asserts
    ``reduced == []`` for every configuration, and
    ``test_mistral4_cell.py``'s copy of it that the benchmark has FIVE
    cells: both are the benchmark's files, which a PR of this kind may not
    edit, and stay red until a ``benchmark`` PR (ROADMAP D6a).  What they
    hold besides, held here with cuts allowed and the cells not counted (so
    that the next cell does not turn this one red too)."""
    b = cells.load_benchmark()
    names = [w["name"] for w in b["workloads"]]
    assert len(names) == len(set(names)) <= 24 and CELL in names
    assert sum(w["chips"] == 4 for w in b["workloads"]) \
        <= max(1, len(names) // 4)
    assert {w["config"] for w in b["workloads"]} \
        == {c["name"] for c in b["configs"]}
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    width = ("hidden_size", "intermediate_size", "_dim", "_rank",
             "experts_per_tok", "topk")
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if any(w in k for w in width)]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m.get("workloads", names)) <= set(names)
        assert set(m.get("workloads", names)) \
            <= set(e2e[m["moves"]].get("workloads", names)), m["name"]
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        assert len(f.read()) <= 64 * 1024


# ---------------------------------------------------------------------------
# arithmetic against hand counts
# ---------------------------------------------------------------------------
def test_parameters_and_bytes_against_hand_counts(arch):
    config = _config()
    E, F, I, H = 6144, 12288, 2048, 64
    attention = E * 1536 + 1536 * H * 192 + E * 576 + 512 * H * 256 \
        + H * 128 * E
    assert round(attention / 1e6, 1) == 90.6
    dense = 3 * E * F
    assert round(dense / 1e6, 1) == 226.5
    outside = 2 * (attention + dense + 2 * E + 1536 + 512) + (E + 1) * 768
    assert round(outside / 1e6, 1) == 638.9
    expert = 3 * E * I
    assert round(expert / 1e6, 2) == 37.75
    block = outside + 16 * expert
    assert round(block / 1e6) == 1243
    total = 4 * block + 2 * 16384 * E + E
    assert arch.n_params(config) == total
    assert round(total / 1e6) == 5173 and round(2 * total / 1e9, 2) == 10.35
    assert arch.expert_bytes(config) == expert * 2
    # a decode step over 64 lanes at 1,000 positions: the blocks' dense
    # weights, the head (not the embedding), every held expert or the 10 a
    # block a counter says were touched, and BOTH attentions' latent rows
    dense_bytes = (4 * outside + 16384 * E + E) * 2
    rows = 64 * 1000 * 4 * 2 * 576 * 2
    assert arch.decode_step_bytes(
        config, lanes=64, context_positions=1000, weight_bytes=2,
        kv_bytes=2) == dense_bytes + 4 * 16 * expert * 2 + rows
    got = arch.decode_step_bytes(
        config, lanes=64, context_positions=1000, weight_bytes=2,
        kv_bytes=2, experts_touched=10)
    assert got == dense_bytes + 4 * 10 * expert * 2 + rows
    assert 8.5e9 < got < 9.5e9          # the issue's 9.1 GB, 11 ms at 819 GB/s
    assert 0.30 < 4 * 10 * expert * 2 / got < 0.36      # routed: a third


def test_grouped_matmul_cost_against_hand_counts(arch):
    config = _config()
    E, I = 6144, 2048
    # 64 rows on held experts over 4 blocks x 10 experts touched
    up = arch.grouped_matmul_cost(config, held_rows=64, experts_touched=40,
                                  call="up")
    down = arch.grouped_matmul_cost(config, held_rows=64,
                                    experts_touched=40, call="down")
    assert up[0] + down[0] == 2 * 64 * 3 * E * I
    assert up[1] + down[1] == 40 * arch.expert_bytes(config) \
        + 64 * (E + 2 * I + I + E) * 2
    # bound by the touched experts' bytes, not by the operations
    assert all(moved / 819e9 > flops / 197e12 for flops, moved in (up, down))


def test_counters_are_of_this_configuration_alone(arch):
    config = _config()
    assert arch.counters_are_of(config, {"moe_expert_slots": 64,
                                         "moe_zero_rows": 5})
    assert not arch.counters_are_of(config, {"moe_expert_slots": 64})
    assert not arch.counters_are_of(config, {"moe_expert_slots": 160,
                                             "moe_zero_rows": 0})


# ---------------------------------------------------------------------------
# the plain reference against itself
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_float(arch):
    """The toy configuration in f32 with every expert held, its seeded
    weights with a correction bias that moves choices."""
    import jax.numpy as jnp

    config = _tiny(n_routed_experts_held=8, first_routed_expert_held=0,
                   assumed={"compute_dtype": "float32",
                            "initializer_range": 0.2})
    params = arch.init_params(arch.build_model(config, {}), 7)
    bias = np.random.default_rng(7).normal(
        0, 0.05, params["layers"]["router_bias"].shape)
    params["layers"]["router_bias"] = jnp.asarray(bias, jnp.float32)
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
    """At the real size a request is longer than a block of query rows and
    an expert's rows come padded: the same paths at toy block sizes (a
    module of its own, so that nothing compiled at the real sizes is met
    again)."""
    small = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "longcat_flash.py"),
        "bench_arch_lc_blocks")
    small._Q_ROWS, small._EXPERT_ROWS, small._HEAD_ROWS = 8, 8, 8
    whole = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "longcat_flash.py"),
        "bench_arch_lc_whole")
    whole._Q_ROWS = 200
    config, params = tiny_float
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            (1, 200), dtype=np.int32)
    full = np.asarray(whole.reference_logits(
        whole.reference_weights(params, config), config, ids))
    weights = small.reference_weights(params, config)
    np.testing.assert_allclose(
        np.asarray(small.reference_logits(weights, config, ids)), full,
        rtol=0, atol=2e-5)
    for rows in (np.arange(70, 90), np.arange(180, 199)):
        some = np.asarray(small.reference_logits(weights, config, ids, rows))
        np.testing.assert_allclose(some, full[:, rows], rtol=0, atol=2e-5)


def test_the_shares_and_the_identity_term_once_make_the_layer(
        arch, tiny_float):
    """A block's routed sum is linear in the experts, and the identity term
    is computed whole by every share: what the reference adds for each
    quarter of the experts, plus everything every chip computes alike (the
    reference holding no expert: the attentions, the dense feed-forwards,
    the identity term) counted once, is the uncut block."""
    import jax.numpy as jnp

    config, params = tiny_float
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(128, config["hidden_size"])), jnp.float32)

    def block(first, count):
        cut = dict(config, first_routed_expert_held=first,
                   n_routed_experts_held=count)
        # the program's tree holds all blocks' experts in one tensor
        held = dict(params, experts={
            k: v.reshape(-1, 8, *v.shape[1:])[:, first:first + count]
            .reshape(-1, *v.shape[1:]) for k, v in params["experts"].items()})
        weights = arch.reference_weights(held, cut)
        return np.asarray(arch._ref_block(x, weights["block"](0), cut, None))

    whole = block(0, 8)
    alike = block(0, 0)
    shares = [block(first, 2) - alike for first in (0, 2, 4, 6)]
    assert all(np.abs(s).max() > 1e-3 for s in shares)      # each adds a part
    np.testing.assert_allclose(alike + sum(shares), whole, rtol=0,
                               atol=2e-5)
    # and the identity term is in what every chip computes alike
    no_zero = dict(config, zero_expert_num=0, n_routed_experts=12,
                   first_routed_expert_held=0, n_routed_experts_held=0)
    weights = arch.reference_weights(params, no_zero)
    without = np.asarray(arch._ref_block(x, weights["block"](0), no_zero,
                                         None))
    assert np.abs(alike - without).max() > 1e-2


# ---------------------------------------------------------------------------
# the rule: holds bf16, refuses the control
# ---------------------------------------------------------------------------
def test_served_check_states_a_routed_rule_with_its_reasons(arch):
    check = arch.served_check(_config())
    rule = check["rule"]
    assert set(rule) == set(check["why"]) \
        == {"near_best_spacings", "share", "every_row_sigma"}
    assert 0.5 < rule["share"] < 1.0 and rule["every_row_sigma"] == 3.0
    # the longest checked request rounded up to 256, not the cap
    assert check["width"](1664) == 1792 and check["width"](900) == 1024


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
    cell, run = _rehearse("longcat-tiny.serve-tiny-answers", devices[:1],
                          True, logged.update)
    return cell, run, logged


def test_the_toy_cell_names_the_real_cells_metrics():
    real = cells.Cell(cells.load_benchmark(), CELL)
    toy = cells.Cell(cells.load_benchmark(os.path.join(
        REHEARSAL, "BENCHMARK.json")), "longcat-tiny.serve-tiny-answers",
        root=REHEARSAL)
    # every name the toy cell rehearses is the real cell's; a later PR may
    # enter more in the real one
    assert {m["name"] for m in toy.per_layer} \
        <= {m["name"] for m in real.per_layer}


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
    blocks, top_k = config["num_layers"], config["moe_topk"]
    held = config["n_routed_experts_held"]
    from harness import roofline

    progs = roofline.programs(spans)
    decodes = [p for p in progs if p["group"] == "decode"]
    chunks = [p for p in progs if p["group"].startswith("prefill_")]
    assert decodes and chunks and all("clock_ms" in p for p in progs)
    # a decode program offers every held expert of every block
    assert {p["moe_expert_slots"] for p in decodes} == {held * blocks}
    # what a program routes is its tokens x choices a token x blocks, and
    # a pair is on a zero-compute expert, a held one or another chip's
    assert all(p["moe_routed_rows"] % (top_k * blocks) == 0
               and p["moe_held_rows"] + p["moe_zero_rows"]
               <= p["moe_routed_rows"] for p in progs)
    assert sum(p["moe_zero_rows"] for p in progs) > 0
    assert all(p["attn_pairs"] > 0 for p in chunks)
    assert all(p["attn_keys"] > 0 for p in decodes)


@pytest.mark.parametrize("name", COUNTER_METRICS + ALIASES[:6])
def test_reader_gives_a_number_on_the_run_itself(traced, name):
    cell, run, _ = traced
    value = cell.reader(name)(run["observed"])
    assert value is not None and value > 0, name
    if name == "scmoe_zero_choice_share_pct":
        assert 15 < value < 55          # 4 of 12 choices are zero-compute
    if name == "scmoe_held_rows_share_pct":
        assert 25 < value < 75          # half the routed experts are held
    if name == "scmoe_expert_load_ratio":
        assert 1.0 <= value <= _tiny()["n_routed_experts_held"]
    if name == "scmoe_experts_touched_pct":
        assert value <= 100


@pytest.mark.parametrize("name", ROOFLINES + ALIASES[6:7])
def test_device_metrics_are_left_out_on_a_cpu(traced, name):
    """No trace on a CPU; and the toy configuration's counters are not
    those of the configuration the roofline readers are entered for."""
    cell, run, _ = traced
    assert run["observed"]["trace"] is None
    assert cell.reader(name)(run["observed"]) is None


@pytest.mark.parametrize("name", COUNTER_METRICS + ROOFLINES + ALIASES)
def test_reader_returns_nothing_on_the_parents_program(traced, name):
    """The driver lays this PR's benchmark files over the PARENT's program,
    which cannot run this configuration at all; what a reader meets there at
    the most is a traced run with spans and a trace but none of these
    counters and no kernel of these names; with nothing at all likewise."""
    observed = copy.deepcopy(traced[1]["observed"])
    for counter in [name for name in observed["spans"] if name.startswith(
            ("moe_", "attn_", "clock_ms_"))]:
        observed["spans"].pop(counter)
    assert observed["spans"]            # the four run_* / host_gap spans stay
    observed["trace"] = {"window_s": 3.0, "idle_pct": 8.0, "device_ops": [
        ["fusion.3 = bf16[64,16,64] fusion", 0.3],
        ["custom-call.7 = bf16[1024,1024] custom-call", 0.2]]}
    value = _reader(name)(observed)
    if name in COUNTER_METRICS + ROOFLINES:
        assert value is None
    assert _reader(name)({}) is None
    assert _reader(name)({"spans": {}, "trace": None, "counters": {}}) is None


def _hand_observed(blocks=4, held=16, zero=True):
    """Two seconds of serving, a chunk and a decode program every 0.1 s,
    the last half second traced: 5 chunks of 1,024 and 5 decode programs in
    it, in which the prefill kernels ran 0.012 and 0.008 s and the decode
    program's up call 0.03 s.  ``zero=False``, 5 blocks, 32 held: the
    counters ``mistral4`` records."""
    def series(group, n, **counters):
        out = {f"clock_ms_{group}": [{"ms": 0.0, "a0": 100 * (i + 1)}
                                     for i in range(n)]}
        for name, value in counters.items():
            if zero or name != "moe_zero_rows":
                out[f"{name}_{group}"] = [{"ms": 0.0, "a0": value}] * n
        return out

    return {
        "spans": {
            "run_prefill_decode": [{"ms": 60.0, "a0": 60}] * 20,
            "run_decode": [{"ms": 25.0, "a0": 60}] * 4,
            "host_gap": [{"ms": 2.0, "a0": 1}] * 20,
            **series("prefill_1024", 20,
                     moe_routed_rows=1024 * 12 * blocks,
                     moe_zero_rows=1024 * 4 * blocks,
                     moe_held_rows=256 * blocks,
                     moe_busiest_scaled_rows=24 * held * blocks,
                     moe_experts_touched=held * blocks,
                     moe_expert_slots=held * blocks,
                     attn_pairs=1024 * 1025 // 2),
            **series("decode", 20, moe_routed_rows=60 * 12 * blocks,
                     moe_zero_rows=60 * 4 * blocks,
                     moe_held_rows=15 * blocks,
                     moe_busiest_scaled_rows=3 * held * blocks,
                     moe_experts_touched=10 * blocks,
                     moe_expert_slots=held * blocks, attn_keys=60 * 1000),
        },
        "trace": {"window_s": 0.5, "idle_pct": 5.0, "device_ops": [
            ["moe_grouped_matmul_prefill_up.2 = bf16[2048,4096] custom-call",
             0.012],
            ["moe_grouped_matmul_prefill_down.3 = bf16[2048,6144] "
             "custom-call", 0.008],
            ["moe_grouped_matmul_decode_up.5 = bf16[256,4096] custom-call",
             0.03],
            ["paged_latent_decode_attn.12 = bf16[64,64,512] custom-call",
             0.004],
            ["fusion.9 = bf16[1024,6144] fusion", 0.2]]},
    }


def test_readers_by_hand(arch):
    observed = _hand_observed()
    assert _reader("scmoe_zero_choice_share_pct")(observed) \
        == pytest.approx(100.0 / 3)
    assert _reader("scmoe_held_rows_share_pct")(observed) == pytest.approx(
        100.0 * (256 + 15) / (1024 * 8 + 60 * 8))
    assert _reader("scmoe_expert_load_ratio")(observed) == 24 * 16 / 256
    assert _reader("scmoe_experts_touched_pct")(observed) == 100.0 * 10 / 16
    config = _config()
    # the stretch: clocks 1,600 .. 2,000 of the 1,024 bucket (5 chunks) and
    # of decode (5 programs); the decode program's down call was not kept,
    # so its least time is left out too
    least = 0.0
    for rows, touched, calls in ((256 * 4, 16 * 4, ("up", "down")),
                                 (15 * 4, 10 * 4, ("up",))):
        for call in calls:
            flops, moved = arch.grouped_matmul_cost(
                config, held_rows=rows, experts_touched=touched, call=call)
            least += 5 * max(flops / 197e12, moved / 819e9)
    got = _reader("scmoe_grouped_matmul_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * least / 0.05)
    assert 0 < got < 100
    # the decode program: the blocks' dense weights and the head, 10 touched
    # experts a block and both attentions' latent rows of 60 x 1,000 keys,
    # over its 25 ms
    got = _reader("scmoe_decode_program_hbm_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * arch.decode_step_bytes(
        config, lanes=60, context_positions=1000, weight_bytes=2,
        kv_bytes=2, experts_touched=10) / 819e9 / 0.025)
    assert 0 < got < 100
    # the paged latent kernel: ONE of a block's two call sites was kept, so
    # one call site's least time (5 programs of 60 x 1,000 keys over the 4
    # blocks) stands over its seconds; with both kept, both
    flops, moved = arch.latent_decode_attn_cost(config, keys=60 * 1000)
    assert (flops, moved) == (2 * 4 * 60000 * 64 * (576 + 512),
                              4 * 60000 * 576 * 2)
    one = 5 * max(flops / 197e12, moved / 819e9)
    got = _reader("paged_latent_decode_attn_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * one / 0.004)
    assert 0 < got < 100
    observed["trace"]["device_ops"].append(
        ["paged_latent_decode_attn.13 = bf16[64,64,512] custom-call", 0.005])
    assert _reader("paged_latent_decode_attn_roofline_pct")(observed) \
        == pytest.approx(100.0 * 2 * one / 0.009)


@pytest.mark.parametrize("name", COUNTER_METRICS + ROOFLINES)
def test_new_readers_give_nothing_on_mistral4s_counters(name):
    """``mistral4`` records the five routing counters and no zero-compute
    choices, at other sizes: nothing of it is divided by these."""
    assert _reader(name)(_hand_observed()) is not None
    assert _reader(name)(_hand_observed(blocks=5, held=32, zero=False)) \
        is None


@pytest.mark.parametrize("name", MISTRAL4_ROOFLINES)
def test_mistral4s_readers_give_nothing_on_these_counters(name):
    """And the other way round: 16 held x 4 blocks are not 32 x 5."""
    assert _reader(name)(_hand_observed(blocks=5, held=32, zero=False)) \
        is not None
    assert _reader(name)(_hand_observed()) is None


def test_a_program_built_without_the_scale_correction_is_not_correct(
        devices):
    """``longcat_flash-no-scale`` is found under the toy cell's own root:
    the program's model built without the scale correction of the two
    low-rank paths that the reference reads from the configuration."""
    logged = {}
    _, run = _rehearse("longcat-tiny-no-scale.serve-tiny-answers",
                       devices[:1], False, logged.update)
    assert run["failed"] == 0
    assert not logged["checks"]["served_tokens_hold_to_reference"]
    assert not run["correct"]
