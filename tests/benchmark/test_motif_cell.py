"""The ``motif`` architecture's benchmark files, checked on the CPU in
seconds: its configuration against the catalog's row, its entries and its
traffic letter for letter, its arithmetic against hand counts and against the
configuration's ``bytes``, its plain reference against itself (rows of the
full call, blocks against whole), its rule against the four controls at a toy
width (4 bits; lambda at zero; no window; ``H_res`` without Sinkhorn), the new
per-layer readers with and without something to read, and a rehearsal of the
cell's data path at a toy width.  The toy cell lives in ``cells/motif/`` and
was added as a PR adds a cell: new files only.  Nothing here asserts the
cell's POSITION in ``workloads`` or a count of cells."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
REHEARSAL = os.path.join(HERE, "cells", "motif")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

CONFIG = "motif-3-beta-ep8"
CELL = CONFIG + ".serve-agent-turns"
TOY_CELL = "motif-tiny.serve-tiny-agent"
ALIASES = tuple("agentturns_" + name for name in (
    "chunk_step_ms", "decode_program_ms", "prefill_program_ms",
    "host_gap_pct", "slot_util_pct", "queue_wait_p50_s", "kv_occupancy_pct",
    "device_idle_pct", "peak_hbm_gb"))
POOL_METRICS = ("latent_window_group_occupancy_pct",
                "latent_cache_bytes_kept_pct")
COUNTER_METRICS = ("motif_expert_load_ratio", "motif_experts_touched_pct",
                   "motif_held_rows_share_pct", "mhc_sinkhorn_err_ppm")
ROOFLINES = ("motif_grouped_matmul_roofline_pct",
             "gdla_prefill_attn_roofline_pct",
             "motif_decode_program_hbm_roofline_pct")
# a reader that is there and by hand reads what it should, and is NOT
# entered: the harness's reduction keeps the ten largest operations, and on
# the chip this cell's two decode attention kernels are not among them (the
# full one is the twelfth: PERF.md section 5 and section 7, PR 46)
NOT_ENTERED = ("gdla_paged_decode_attn_roofline_pct",)
STATS = ("moe_routed_rows", "moe_held_rows", "moe_busiest_scaled_rows",
         "moe_experts_touched", "moe_expert_slots", "mhc_sinkhorn_err_ppm")
# the catalog's ``config`` for Motif-3-Beta (model-configs guide,
# architectures.jsonl), every key
PUBLISHED = {
    "attention_cls": "gdla", "diff_v2": True,
    "elementwise_attn_output_gate": True, "experts_top_k": 8,
    "head_dim": 192, "headwise_attn_output_gate": False,
    "hidden_act": "poly_norm", "hidden_size": 4096,
    "interleave_moe_layer_step": 1, "intermediate_size": 12288, "k_ratio": 1,
    "kv_lora_rank": 512, "load_balance_coeff": 0.0001,
    "max_position_embeddings": 262144, "max_window_layers": 9,
    "mhc_enabled": True, "mhc_expansion_rate": 4, "mhc_identity_init": False,
    "mhc_sinkhorn_iters": 20, "model_type": "Motif",
    "moe_intermediate_size": 1280, "mscale": 1, "n_dense_first_layers": 2,
    "num_attention_heads": 80, "num_experts": 384, "num_hidden_layers": 53,
    "num_key_value_heads": 16, "num_noise_heads": 16,
    "num_shared_experts": 1, "q_lora_rank": 1024, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2, "score_before_experts": False, "score_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "interleave",
    "sliding_window_period": 4, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "use_sliding_window": True,
    "v_head_dim": 128, "vocab_size": 220160, "rope_factor": 64,
    "original_seq_len": 4096,
    "rope_scaling": {"original_max_position_embeddings": 4096, "factor": 64,
                     "mscale": 1, "rope_type": "yarn", "rope_theta": 10000,
                     "beta_fast": 32, "beta_slow": 1,
                     "apply_yarn_scaling": False},
    "polynorm_output_scale": 0.5, "polynorm_output_scale_per_layer": {},
    "polynorm_bias_clamp": 0.5, "hidden_clamp": 1000000,
    "num_nextn_predict_layers": 1}


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "motif.py"), "bench_arch_motif_t")


@pytest.fixture(scope="module")
def small_arch():
    """The same file with the reference's blocks at a toy's size (a module
    of its own): two blocks of query rows in 128 tokens."""
    small = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "motif.py"), "bench_arch_motif_small_t")
    small._ROWS, small._Q_ROWS, small._KEY_BUCKET, small._TILE_ROWS, \
        small._HEAD_ROWS = 64, 32, 128, 8, 64
    return small


@pytest.fixture(scope="module")
def serve():
    return cells.load_module(os.path.join(
        BENCH_DIR, "harness", "drive_serve.py"), "bench_drive_serve_mo_t")


def _config():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _tiny(**changes):
    with open(os.path.join(REHEARSAL, "configs", "motif-tiny.json")) as f:
        return dict(json.load(f), **changes)


def _reader(name):
    return cells.load_module(
        os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
        f"bench_metric_mo_t_{name}").read


# ---------------------------------------------------------------------------
# the configuration file and the entries
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_names_every_cut():
    config = _config()
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts_held", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # depth, the experts held and the vocabulary: no width, 8 a token, the
    # router 384 wide, the window and the streams as published
    assert config["published"] == {"num_hidden_layers": 53,
                                   "num_experts": 384, "vocab_size": 220160}
    assert (config["num_hidden_layers"], config["layers_held"]) \
        == (5, [1, 4, 5, 6, 7])
    assert (config["num_experts_held"], config["first_expert_held"],
            config["vocab_size"]) == (48, 0, 27520)
    # the guide's floors: a whole period and four layers behind the dense
    # ones, at least 8 experts, at least an eighth of the vocabulary
    assert config["vocab_size"] * 8 >= 220160
    assert config["architecture"] == "motif" and config["source"].endswith(
        "Motif-Technologies/Motif-3-Beta/blob/main/config.json")
    for key in ("num_key_value_heads", "head_order", "diff_v2",
                "elementwise_attn_output_gate", "layer_pattern", "rope",
                "mhc", "polynorm", "hidden_clamp", "router", "mtp_head",
                "norms", "initializer", "compute_dtype",
                "served_weight_dtype", "layers_held"):
        assert config["assumed"][key], key
    assert "EIGHT chips" in config["deployment"] \
        and "pipeline" in config["deployment"] \
        and "not run" in config["deployment"]


def test_the_byte_counts_of_the_file_by_hand(arch):
    config = _config()
    E, H, Hkv, D, Dv, Q, R, Dr = 4096, 80, 16, 192, 128, 1024, 512, 64
    attention = E * Q + Q + Q * H * D + E * (R + Dr) + R \
        + R * Hkv * 256 + E * 64 + E * 8192 + 8192 * E
    assert round(attention / 1e6, 2) == 91.75
    mix = 4 * E + 4 * E * 24 + 24 + 3
    assert round(mix / 1e6, 2) == 0.41
    expert = 3 * E * 1280 + 4
    assert round(expert / 1e6, 2) == 15.73
    outside = attention + 2 * mix + 2 * E
    routed_outside = outside + E * 384 + expert
    assert round(routed_outside / 1e6, 1) == 109.9
    routed = routed_outside + 48 * expert
    assert round(routed / 1e6, 1) == 864.9 and round(2 * routed / 1e9, 3) \
        == 1.730
    dense = outside + 3 * E * 12288 + 4
    assert round(dense / 1e6, 1) == 243.6
    total = dense + 4 * routed + 2 * 27520 * E + E
    # the issue's table: 3,928 M, 7.86 GB; the exact leaves are in
    assert round(total / 1e6) == 3928 and round(2 * total / 1e9, 2) == 7.86
    assert arch.n_params(config) == total == config["bytes"]["parameters"]
    assert config["bytes"] == {
        "parameters": total, "attention": attention, "mhc_a_sublayer": mix,
        "an_expert": expert,
        "routed_layer_outside_routed_experts": routed_outside,
        "routed_layer_held": routed, "dense_layer": dense,
        "embedding_or_head": 27520 * E, "served_weight_gb": 7.86,
        "cache_bytes_a_token_and_layer": 1280}
    # the whole model by the same reading: "314B-A13.2B" in the catalog
    whole = dict(config, num_hidden_layers=53, layers_held=list(range(53)),
                 num_experts_held=384, vocab_size=220160)
    assert round(arch.n_params(whole) / 1e9, 1) == 315.9
    active = 2 * dense + 51 * (routed_outside + 8 * expert) + 220160 * E
    assert round(active / 1e9, 1) == 13.4
    # the pool at the cell's engine settings: pages of 64 rows stored as 640
    page = 64 * 640 * 2
    assert round((1 + 48 * 776) * page / 1e9, 2) == 3.05
    assert round(4 * (1 + 48 * 3 + 32) * page / 1e9, 3) == 0.058
    assert arch.kv_row_bytes(config) == 1152
    assert arch.layers_of(config) == (1, 4, 1, 4)
    assert arch.attention_call_sites(config) == {"full": [1],
                                                 "window": [1, 3]}
    assert arch.routed_call_sites(config) == [3, 1]
    assert arch.routed_call_sites(config, final=False) == [3]


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
    assert traffic["arrivals"] == {"process": "backlog", "requests": 384}
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 16384, "sigma": 0.6, "min": 4096,
        "max": 49152, "stratified": 16}
    assert traffic["new_tokens"] == {"dist": "uniform", "min": 192,
                                     "max": 448, "stratified": 16}
    assert traffic["lengths_seed"] == 0
    assert traffic["engine"] == {"max_slots": 48, "kv_block_size": 64,
                                 "prefill_chunk": 2048,
                                 "max_blocks_per_seq": 776}
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["trace_s"],
            traffic["check_requests"]) == (6, 40, 3, 4)
    engine = traffic["engine"]
    assert engine["max_blocks_per_seq"] * engine["kv_block_size"] \
        >= traffic["prompt_len"]["max"] + traffic["new_tokens"]["max"]
    assert set(traffic) == {"driver", "what", "arrivals", "prompt_len",
                            "new_tokens", "lengths_seed", "ramp_s",
                            "drain_s", "trace_s", "check_requests",
                            "model_overrides", "engine"}


def test_the_traffic_is_long_contexts_in_and_a_few_hundred_tokens_out():
    """The generator's own draw of the mix: the same lengths under every
    seed (``lengths_seed``), median ~16k, one in sixteen over 40k, mean
    ~19k, and ids over the whole held vocabulary."""
    from harness import traffic as traffic_lib

    mix = cells.Cell(cells.load_benchmark(), CELL).traffic
    load = traffic_lib.requests(mix, 27520, 2147483659, 36.0)
    again = traffic_lib.requests(mix, 27520, 7, 36.0)
    lengths = np.array([len(p) for p in load["prompts"]])
    assert (lengths == [len(p) for p in again["prompts"]]).all()
    assert (load["new_tokens"] == again["new_tokens"]).all()
    assert len(lengths) == 384 and lengths.min() >= 4096 \
        and lengths.max() <= 49152
    assert 15000 < np.median(lengths) < 17500
    assert 18000 < lengths.mean() < 20500
    assert 0.04 < np.mean(lengths > 40000) < 0.09
    assert 310 < load["new_tokens"].mean() < 330
    assert max(p.max() for p in load["prompts"]) > 27400


# ---------------------------------------------------------------------------
# arithmetic against hand counts
# ---------------------------------------------------------------------------
def test_costs_against_hand_counts(arch):
    config = _config()
    E, I, H = 4096, 1280, 80
    expert = 3 * E * I + 4
    outside = config["bytes"]["parameters"] - 27520 * E - 4 * 48 * expert
    # 30 lanes at a mean context of 19k: the full layer reads them all, the
    # four sliding layers a window a lane
    got = arch.decode_step_bytes(
        config, keys_full=30 * 19000, keys_window=30 * 128, weight_bytes=2,
        kv_bytes=2, experts_touched=40)
    rows = (30 * 19000 + 4 * 30 * 128) * 1152
    assert got == outside * 2 + 4 * 40 * 3 * E * I * 2 + rows
    assert 1.5e9 < outside * 2 < 1.7e9          # ~1.6 GB outside the experts
    assert 0.6e9 < rows < 0.7e9                 # ~0.67 GB of latent rows
    # if all five layers kept every position they would read 3.3 GB
    assert round(5 * 30 * 19000 * 1152 / 1e9, 1) == 3.3
    up = arch.grouped_matmul_cost(config, held_rows=2048 * 4,
                                  experts_touched=192, call="up")
    down = arch.grouped_matmul_cost(config, held_rows=2048 * 4,
                                    experts_touched=192, call="down")
    assert up[0] + down[0] == 2 * 2048 * 4 * 3 * E * I
    assert up[1] + down[1] == 192 * arch.expert_bytes(config) \
        + 2048 * 4 * (E + 2 * I + I + E) * 2
    # ~43 rows an expert in a chunk of 2,048 (8 of 384, an eighth held): the
    # experts' bytes outlast the operations, in a chunk as in a decode step
    assert (up[1] / 819e9) > 5 * (up[0] / 197e12)
    flops, moved = arch.prefill_attn_cost(config, pairs=2048 * 19000,
                                          queries=2048)
    assert flops == 2 * 2048 * 19000 * H * (192 + 128)
    assert moved == (2048 * 19000 // 1024 * H * 320 + 2048 * H * 320) * 2
    assert flops / 197e12 > moved / 819e9       # bound by the operations
    flops, moved = arch.decode_attn_cost(config, keys=30 * 19000)
    assert (flops, moved) == (2 * 570000 * H * (1024 + 64), 570000 * 1152)
    # 80 heads read ONE latent row: bound by its bytes, but the absorbed
    # scores and products are over half of that time (five heads a key/value
    # head over 1,088 values each)
    assert moved / 819e9 > flops / 197e12 > 0.5 * moved / 819e9


def test_counters_are_of_this_configuration_alone(arch):
    config = _config()
    ours = {"moe_expert_slots": 192, "mhc_sinkhorn_err_ppm": 3}
    assert arch.counters_are_of(config, dict(ours, attn_keys_full=5))
    assert arch.counters_are_of(config, dict(ours, attn_pairs_full=5))
    # a model of one group, another model of two groups, another depth
    assert not arch.counters_are_of(config, dict(ours, attn_keys=5))
    assert not arch.counters_are_of(config, {"moe_expert_slots": 192,
                                             "attn_keys_full": 5})
    assert not arch.counters_are_of(config, dict(
        ours, attn_keys_full=5, moe_expert_slots=512))


# ---------------------------------------------------------------------------
# the plain reference against itself, and the rule against its controls
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_float(arch):
    config = _tiny(assumed={"compute_dtype": "float32",
                            "initializer_range": 0.2,
                            "mhc_alpha_init": 0.2})
    return config, arch.init_params(arch.build_model(config, {}), 7)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "architectures", "motif.py")) as f:
        lines = [l for l in f.read().splitlines() if "deepspeed_tpu" in l
                 and ("import " in l)]
    # the one import is ``build_model``'s, of the program's model itself
    assert lines == ["    from deepspeed_tpu.models.motif import "
                     "MotifConfig, MotifModel"]


def test_reference_in_blocks_is_the_reference_whole(tiny_float):
    """At the real size a request is many blocks of query rows long, a
    sliding layer's keys come from the block before, the full layer's are
    padded to a bucket and an expert's rows come as padded tiles: the same
    paths at toy block sizes (modules of their own, so that nothing compiled
    at the real sizes is met again)."""
    small = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "motif.py"), "bench_arch_mo_blocks")
    small._ROWS, small._Q_ROWS, small._KEY_BUCKET, small._TILE_ROWS, \
        small._HEAD_ROWS = 16, 8, 48, 4, 8
    whole = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "motif.py"), "bench_arch_mo_whole")
    whole._ROWS, whole._Q_ROWS, whole._KEY_BUCKET = 208, 208, 208
    config, params = tiny_float
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            (1, 200), dtype=np.int32)
    full = np.asarray(whole.reference_logits(
        whole.reference_weights(params, config), config, ids))
    assert full.shape == (1, 200, config["vocab_size"])
    weights = small.reference_weights(params, config)
    np.testing.assert_allclose(
        np.asarray(small.reference_logits(weights, config, ids)), full,
        rtol=0, atol=5e-5)
    for rows in (np.arange(70, 90), np.arange(180, 199)):
        some = np.asarray(small.reference_logits(weights, config, ids, rows))
        np.testing.assert_allclose(some, full[:, rows], rtol=0, atol=5e-5)
    # causal: what follows a row does not move it
    head = np.asarray(small.reference_logits(weights, config, ids[:, :90]))
    np.testing.assert_allclose(head, full[:, :90], rtol=0, atol=5e-5)


def test_served_check_states_a_routed_rule_with_its_reasons(arch):
    check = arch.served_check(_config())
    rule = check["rule"]
    assert set(rule) == set(check["why"]) \
        == {"near_best_spacings", "share", "every_row_sigma"}
    assert all(len(why) > 40 and "PLACEHOLDER" not in why
               for why in check["why"].values())
    assert rule["near_best_spacings"] == 4.0 and 0.5 < rule["share"] < 1.0
    # the longest checked request rounded up to a block of query rows
    assert check["width"](49600) == 50176 and check["width"](900) == 1024


@pytest.mark.parametrize("control,held", [
    ("as_bf16", True), ("bits4", False), ("lambda_zero", False),
    ("no_window", False), ("no_sinkhorn", False)])
def test_rule_refuses_its_four_controls_at_a_toy_width(small_arch, serve,
                                                       tiny_float, control,
                                                       held):
    """The controls of the rule at the toy width: the reference with every
    matmul's inputs and result in 4 significand bits, and the reference with
    one mechanism taken out (lambda at zero; the window; Sinkhorn), each
    one's best token of every row taken as the served one; against the same
    in 8 bits (bf16's), which the rule holds.  On the chip at the cell's own
    size: ``benchmark/tools/served_controls_of.py`` (PERF.md section 6)."""
    arch = small_arch
    config, params = tiny_float
    rule = arch.served_check(config)["rule"]
    weights = arch.reference_weights(params, config)
    controls = dict(arch.controls_of(config), as_bf16=(config, 8))
    held_config, bits = controls[control]
    for seed in range(2):
        ids = np.random.default_rng(seed).integers(
            0, config["vocab_size"], (1, 128), dtype=np.int32)
        reference = np.asarray(arch.reference_logits(weights, config, ids))
        low = arch.reference_logits(weights, held_config, ids,
                                    control_bits=bits)
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
        == {config["num_experts_held"] * 4}
    # a window lane attends at most its window, a full lane its context
    assert all(0 < p["attn_keys_window"] <= p["attn_keys_full"]
               for p in decodes)
    assert all(0 < p["attn_pairs_window"] <= p["attn_pairs_full"]
               for p in chunks)
    assert any(p["attn_pairs_window"] < p["attn_pairs_full"] for p in chunks)


@pytest.mark.parametrize("name", COUNTER_METRICS + ROOFLINES[2:])
def test_readers_entered_for_the_real_sizes_give_nothing_on_the_toys(
        traced, name):
    """The toy records the same counters at ITS sizes (8 held experts x 4
    routed layers): a reader that divides by the real configuration's gives
    nothing there (``counters_are_of``), as on any other model's run."""
    cell, run, _ = traced
    assert cell.reader(name)(run["observed"]) is None


@pytest.mark.parametrize("name", POOL_METRICS + ALIASES[:7])
def test_reader_gives_a_number_on_the_run_itself(traced, name):
    cell, run, _ = traced
    value = cell.reader(name)(run["observed"])
    assert value is not None and value > 0, name
    if name == "latent_cache_bytes_kept_pct":
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


def _series(group, n, **counters):
    out = {f"clock_ms_{group}": [{"ms": 0.0, "a0": 100 * (i + 1)}
                                 for i in range(n)]}
    for key, value in counters.items():
        out[f"{key}_{group}"] = [{"ms": 0.0, "a0": value}] * n
    return out


@pytest.mark.parametrize("name", COUNTER_METRICS + POOL_METRICS[1:]
                         + ROOFLINES + NOT_ENTERED)
def test_reader_gives_nothing_on_another_models_counters(name):
    """The parent's program, a model of one group (``mistral4``'s counters)
    and another model of two groups (``mellum``'s): nothing, and no error."""
    one_group = {"spans": {
        "run_decode": [{"ms": 25.0, "a0": 16}] * 4,
        **_series("prefill_2048", 4, moe_held_rows=2048,
                  moe_routed_rows=8192, moe_busiest_scaled_rows=4096,
                  moe_experts_touched=160, moe_expert_slots=160,
                  attn_pairs=2048 * 1025),
        **_series("decode", 4, moe_held_rows=16, moe_routed_rows=64,
                  moe_busiest_scaled_rows=64, moe_experts_touched=50,
                  moe_expert_slots=160, attn_keys=16000, attn_pages=250)},
        "trace": {"window_s": 0.5, "idle_pct": 5.0, "device_ops": [
            ["moe_grouped_matmul_prefill_up.2 = bf16[2048,4096] custom-call",
             0.012]]}}
    two_groups = {"spans": {
        "run_decode": [{"ms": 14.0, "a0": 30}] * 4,
        "kv_pages_full": [{"ms": 0.0, "a0": 4000}] * 4,
        "kv_pages_window": [{"ms": 0.0, "a0": 500}] * 4,
        **_series("prefill_2048", 4, moe_held_rows=2048 * 64,
                  moe_routed_rows=2048 * 64, moe_busiest_scaled_rows=300,
                  moe_experts_touched=512, moe_expert_slots=512,
                  attn_pairs_full=2048 * 8192, attn_pairs_window=2048 * 1024),
        **_series("decode", 4, moe_held_rows=30 * 64, moe_routed_rows=30 * 64,
                  moe_busiest_scaled_rows=9 * 512, moe_experts_touched=496,
                  moe_expert_slots=512, attn_keys_full=30 * 8000,
                  attn_keys_window=30 * 1024)},
        "trace": {"window_s": 0.5, "idle_pct": 5.0, "device_ops": [
            ["gqa_prefill_attn_full.7 = bf16[32,2048,128] custom-call",
             0.02]]}}
    for other in (one_group, two_groups):
        assert _reader(name)(other) is None
    assert _reader(name)({"spans": {"run_decode": [{"ms": 1.0, "a0": 1}]},
                          "trace": None}) is None


def _hand_observed():
    """Two seconds of serving, a chunk of 2,048 and a decode program every
    0.1 s, the last half second traced: 5 chunks and 5 decode programs in
    it."""
    ours = dict(moe_expert_slots=192, mhc_sinkhorn_err_ppm=20)
    return {
        "spans": {
            "run_decode": [{"ms": 12.0, "a0": 30}] * 4,
            "kv_pages_full": [{"ms": 0.0, "a0": 9000}] * 20,
            "kv_pages_window": [{"ms": 0.0, "a0": 120}] * 20,
            "kv_pool_pages_full": [{"ms": 0.0, "a0": 37248}] * 20,
            "kv_pool_pages_window": [{"ms": 0.0, "a0": 176}] * 20,
            **_series("prefill_2048", 20, moe_routed_rows=2048 * 8 * 4,
                      moe_held_rows=2048 * 4,
                      moe_busiest_scaled_rows=60 * 48 * 4,
                      moe_experts_touched=192, attn_pairs_full=2048 * 19000,
                      attn_pairs_window=2048 * 128, **ours),
            **_series("decode", 20, moe_routed_rows=30 * 8 * 4,
                      moe_held_rows=30 * 4, moe_busiest_scaled_rows=5 * 192,
                      moe_experts_touched=96, attn_keys_full=30 * 19000,
                      attn_keys_window=30 * 128, **ours),
        },
        "trace": {"window_s": 0.5, "idle_pct": 5.0, "device_ops": [
            # the chunk's calls: ONE site each (the scan of three routed
            # sliding layers; the last layer's experts are not in a chunk
            # program that is not a prompt's last)
            ["moe_grouped_matmul_prefill_up.2 = bf16[22528,2560] "
             "custom-call", 0.03],
            ["moe_grouped_matmul_prefill_down.4 = bf16[22528,4096] "
             "custom-call", 0.015],
            # the decode program's up call: both sites kept; its down call:
            # the larger (3 of 4 layers)
            ["moe_grouped_matmul_decode_up.5 = bf16[1152,2560] "
             "custom-call", 0.009],
            ["moe_grouped_matmul_decode_up.6 = bf16[1152,2560] "
             "custom-call", 0.003],
            ["moe_grouped_matmul_decode_down.7 = bf16[1152,4096] "
             "custom-call", 0.005],
            ["gdla_prefill_attn_full.7 = bf16[80,2048,128] custom-call",
             0.08],
            # the window's rectangle call: the scan of three kept, the dense
            # layer's single call not
            ["gdla_prefill_attn_window.8 = bf16[80,2048,128] custom-call",
             0.006],
            ["gdla_paged_decode_attn_full.9 = bf16[48,80,512] custom-call",
             0.01],
            ["fusion.9 = bf16[2048,16384] fusion", 0.2]]},
    }


def test_readers_by_hand(arch):
    observed = _hand_observed()
    config = _config()
    assert _reader("motif_expert_load_ratio")(observed) \
        == 60 * 48 * 4 / (2048 * 4)
    assert _reader("motif_experts_touched_pct")(observed) == 100.0 * 96 / 192
    assert _reader("motif_held_rows_share_pct")(observed) == 12.5
    assert _reader("mhc_sinkhorn_err_ppm")(observed) == 2.0
    assert _reader("latent_window_group_occupancy_pct")(observed) \
        == pytest.approx(100.0 * 120 / 176)
    # 1 layer keeps 9,000 pages and 4 keep 120, where 5 would keep 9,000
    assert _reader("latent_cache_bytes_kept_pct")(observed) == pytest.approx(
        100.0 * (9000 + 4 * 120) / (5 * 9000))
    # the stretch holds 5 chunks and 5 decode programs.  Grouped matmuls: a
    # chunk's run in 3 of the 4 routed layers its counters count; the decode
    # program's up call in all four, its down call's larger site (3 of 4)
    least = 0.0
    for kind, rows, touched, shares in (
            ("prefill", 2048 * 4, 192, (3 / 4, 3 / 4)),
            ("decode", 30 * 4, 96, (1.0, 3 / 4))):
        for call, share in zip(("up", "down"), shares):
            flops, moved = arch.grouped_matmul_cost(
                config, held_rows=rows, experts_touched=touched, call=call)
            least += share * 5 * max(flops / 197e12, moved / 819e9)
    got = _reader("motif_grouped_matmul_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * least / 0.062) and 0 < got < 100
    # rectangle attention: the full layer over causal pairs, the sliding
    # layers over the pairs inside the window, 3 of their 4 layers kept
    least = 0.0
    for layers, pairs in ((1, 2048 * 19000), (3, 2048 * 128)):
        flops, moved = arch.prefill_attn_cost(config, pairs=pairs,
                                              queries=2048)
        least += 5 * layers * max(flops / 197e12, moved / 819e9)
    got = _reader("gdla_prefill_attn_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * least / 0.086) and 0 < got < 100
    # paged decode: the window kernel's operations were not kept, so it is
    # left out on both sides
    flops, moved = arch.decode_attn_cost(config, keys=30 * 19000)
    got = _reader("gdla_paged_decode_attn_roofline_pct")(observed)
    assert got == pytest.approx(
        100.0 * 5 * max(flops / 197e12, moved / 819e9) / 0.01)
    assert 0 < got < 100
    got = _reader("motif_decode_program_hbm_roofline_pct")(observed)
    assert got == pytest.approx(100.0 * arch.decode_step_bytes(
        config, keys_full=30 * 19000, keys_window=30 * 128, weight_bytes=2,
        kv_bytes=2, experts_touched=24) / 819e9 / 0.012)
    assert 0 < got < 100


def test_sites_kept_share():
    from harness import sites

    trace = {"device_ops": [["k_a.1 = x", 0.3], ["k_a.2 = x", 0.1],
                            ["k_ab.3 = x", 0.1], ["other", 1.0]]}
    assert sites.kept_share(trace, "k_a", [1, 3]) == 1.0
    assert sites.kept_share(trace, "k_ab", [1, 3]) == 0.75
    assert sites.kept_share(trace, "k_none", [1, 3]) == 0.0
    assert sites.kept_share(None, "k_a", [1, 3]) == 0.0
