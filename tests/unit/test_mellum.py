"""Mellum 2 (``models/mellum.py``: grouped-query attention, sliding and full
layers over two cache groups, a routed feed-forward over all experts) served
through the engine's decoder-block contract, at toy sizes on the CPU,
against the plain float32 reference the benchmark keeps
(``benchmark/architectures/mellum.py``, which imports nothing of the
program).

Tolerance, and why: both sides compute in float32 here, so the reference's
logit of every served token lies within 1e-4 of its row's best (the two sum
in different orders, nothing else).  The same reference WITHOUT the window
(every sliding layer sees everything) has to fail that tolerance on every
prompt longer than the window: a check that cannot tell a window from none
guards nothing.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import rotary
from deepspeed_tpu.models.mellum import (FULL, SLIDING, MellumConfig,
                                         MellumDecoder, MellumModel,
                                         rope_inv_freq)
from deepspeed_tpu.models.mistral4 import Mistral4Config, yarn_inv_freq
from deepspeed_tpu.moe.dropless import STAT_NAMES
from deepspeed_tpu.serving import CompilationCounter, InferenceEngine
from deepspeed_tpu.serving import kv_cache

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

WINDOW, CHUNK, PAGE = 12, 8, 4
ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 8,
           "original_max_position_embeddings": 32, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2079},
    SLIDING: {"rope_type": "default", "rope_theta": 10000}}
TOY = {"name": "toy", "architecture": "mellum", "attention_bias": False,
       "hidden_act": "silu", "tie_word_embeddings": False,
       "use_sliding_window": True, "vocab_size": 97, "hidden_size": 32,
       "num_hidden_layers": 8, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 8, "num_experts": 8,
       "num_experts_per_tok": 2, "moe_intermediate_size": 16,
       "norm_topk_prob": True, "rms_norm_eps": 1e-6,
       "max_position_embeddings": 512, "sliding_window": WINDOW,
       "layer_types": ([SLIDING] * 3 + [FULL]) * 2,
       "mlp_layer_types": ["sparse"] * 8, "rope_parameters": ROPE,
       "assumed": {"compute_dtype": "float32", "initializer_range": 0.2}}
TILES = {"moe_tile_rows": 8, "moe_tile_rows_decode": 8}
# a chunk of 8 over pages of 4, prompts that end off both
ENGINE = dict(max_slots=3, kv_block_size=PAGE, max_blocks_per_seq=40,
              prefill_chunk=CHUNK)
TOLERANCE = 1e-4


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "mellum.py"), "bench_arch_mellum_unit")


@pytest.fixture(scope="module")
def toy(arch):
    model = arch.build_model(TOY, TILES)
    return model, arch.init_params(model, 3)


def _engine(toy, **kwargs):
    model, params = toy
    return InferenceEngine(model, params, **dict(ENGINE, **kwargs))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _gaps(arch, weights, config, prompt, tokens):
    """How far the reference's logit of each served token lies under its
    row's best."""
    rows = np.arange(len(prompt) - 1, len(tokens) - 1)
    logits = np.asarray(arch.reference_logits(weights, config, tokens[None],
                                              rows)[0])
    return logits.max(-1) - logits[np.arange(len(rows)), tokens[rows + 1]]


# prompts longer than 3 x (window + chunk) = 60, chunk boundaries off the
# page size (a chunk is two pages; 71 and 103 end mid-page, 23 mid-chunk),
# lanes of different length decoding side by side
LENGTHS, NEW = (71, 23, 5, 103), (9, 14, 3, 6)


@pytest.fixture(scope="module")
def served(toy):
    engine = _engine(toy)
    engine.warmup()
    prompts = _prompts(LENGTHS)
    rids = [engine.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, NEW)]
    engine.serve()
    assert engine.pool.window_frees > 0         # window pages were recycled
    return prompts, [np.asarray(engine.result(r)) for r in rids]


def test_engine_serves_what_the_reference_computes(arch, toy, served):
    """Prefill in chunks, then decode, through the paged cache of two groups
    = the reference's full forward, on logits."""
    weights = arch.reference_weights(toy[1], TOY)
    for prompt, tokens in zip(*served):
        assert (tokens[:len(prompt)] == prompt).all()
        assert _gaps(arch, weights, TOY, prompt, tokens).max() <= TOLERANCE


def test_the_reference_without_the_window_fails_the_tolerance(arch, toy,
                                                              served):
    weights = arch.reference_weights(toy[1], TOY)
    no_window = dict(TOY, sliding_window=TOY["max_position_embeddings"])
    for prompt, tokens in zip(*served):
        worst = _gaps(arch, weights, no_window, prompt, tokens).max()
        if len(prompt) > WINDOW:
            assert worst > 1000 * TOLERANCE, (len(prompt), worst)
        else:       # nothing lies outside a window it has not filled
            assert worst <= TOLERANCE


def test_the_four_bit_control_fails_the_tolerance(arch, toy, served):
    weights = arch.reference_weights(toy[1], TOY)
    prompt, tokens = served[0][0], served[1][0]
    rows = np.arange(len(prompt) - 1, len(tokens) - 1)
    exact = np.asarray(arch.reference_logits(weights, TOY, tokens[None],
                                             rows)[0])
    low = np.asarray(arch.reference_logits(weights, TOY, tokens[None], rows,
                                           control_bits=4)[0])
    gap = exact.max(-1) - exact[np.arange(len(rows)), low.argmax(-1)]
    assert gap.max() > 1000 * TOLERANCE


def test_chunked_prefill_serves_what_unchunked_prefill_serves(toy):
    prompts = _prompts((13, 29, 50), seed=4)

    def serve(engine):
        rids = [engine.submit(p, max_new_tokens=4) for p in prompts]
        engine.serve()
        return [np.asarray(engine.result(r)) for r in rids]

    for a, b in zip(serve(_engine(toy)), serve(_engine(toy,
                                                       prefill_chunk=64))):
        assert (a == b).all()


def test_staggered_arrivals_compile_nothing_after_warmup(toy):
    engine = _engine(toy)
    engine.warmup()
    with CompilationCounter() as compiles:
        rids = []
        for prompt in _prompts((3, 37, 8, 61, 12, 5), seed=6):
            rids.append(engine.submit(prompt, max_new_tokens=5))
            engine.step()
            engine.step()
        engine.serve()
    assert compiles.count == 0
    assert all(engine.results[r]["status"] == "finished" for r in rids)


def test_counters_by_cache_group_ride_the_steps_one_fetch(toy):
    engine = _engine(toy, telemetry={"trace": True, "mfu": False})
    engine.warmup()
    engine.telemetry.tracer.reset()
    freed_before = engine.pool.window_frees
    rid = engine.submit(_prompts((21,), seed=7)[0], max_new_tokens=4)
    engine.serve()
    assert engine.results[rid]["status"] == "finished"
    events = {}
    for e in engine.telemetry.tracer.events():
        events.setdefault(e["name"], []).append(e["a0"])
    # 21 tokens in chunks of 8: causal pairs 36, 100, 95 in the full group,
    # in the window group at most 12 a query
    assert events["attn_pairs_full_prefill_8"] == [36, 100, 95]
    assert events["attn_pairs_window_prefill_8"] == [
        sum(min(start + i + 1, WINDOW) for i in range(n))
        for start, n in ((0, 8), (8, 8), (16, 5))] == [36, 90, 60]
    # three decode steps at positions 21, 22, 23
    assert events["attn_keys_full_decode"] == [22, 23, 24]
    assert events["attn_keys_window_decode"] == [WINDOW] * 3
    assert len(events["moe_held_rows_decode"]) == 3
    # the pool by group after every step, and what the step returned
    steps = len(events["kv_window_pages_freed"])
    assert len(events["kv_pages_full"]) == len(events["kv_pages_window"]) \
        == len(events["kv_pool_pages_window"]) == steps
    assert sum(events["kv_window_pages_freed"]) \
        == engine.pool.window_frees - freed_before > 0
    assert max(events["kv_pages_window"]) <= kv_cache.window_table_width(
        WINDOW, PAGE, CHUNK)
    # a model of one group records none of these (test_mistral4 holds its
    # names as they were)
    assert "attn_keys_decode" not in events


def test_config_under_the_published_names():
    cfg = MellumConfig()            # the published sizes
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) \
        == (28, 2304, 98304)
    assert cfg.layer_types == ((SLIDING,) * 3 + (FULL,)) * 7
    assert cfg.period == 4 and MellumDecoder(cfg).n_layer == 7
    assert cfg.rope(FULL)["attention_factor"] == 1.2772588722239782
    assert cfg.cache_rows == (512, 512)
    assert cfg.cache_groups == (("full", 7, None), ("window", 21, 1024))
    assert MellumDecoder.stat_names == STAT_NAMES
    hash(cfg)       # the programs are cached by configuration
    with pytest.raises(AssertionError, match="layer_types"):
        MellumConfig(num_hidden_layers=4, layer_types=(FULL, SLIDING) * 2)


def test_rotary_tables_follow_the_two_sections(arch):
    cfg = MellumConfig()
    plain, one = rope_inv_freq(cfg, SLIDING)
    np.testing.assert_allclose(
        plain, 500000.0 ** (-np.arange(0, 128, 2) / 128), rtol=1e-12)
    assert one == 1.0
    blended, factor = rope_inv_freq(cfg, FULL)
    assert factor == pytest.approx(0.1 * np.log(16) + 1, rel=1e-6)
    # fast dimensions keep their frequency, slow ones are divided by 16
    np.testing.assert_allclose(blended[:8], plain[:8], rtol=1e-12)
    np.testing.assert_allclose(blended[-8:], plain[-8:] / 16, rtol=1e-12)
    # the reference computes its own, from the published keys alone
    published = {"head_dim": 128,
                 "rope_parameters": {k: cfg.rope(k) for k in (FULL, SLIDING)}}
    for kind, (table, scale) in ((SLIDING, (plain, one)),
                                 (FULL, (blended, factor))):
        ref_table, ref_scale = arch.rope_table(published, kind)
        np.testing.assert_allclose(ref_table, table, rtol=1e-12)
        assert ref_scale == scale
    # ONE function for the two models that blend frequencies
    m4 = Mistral4Config()
    np.testing.assert_array_equal(yarn_inv_freq(m4), rotary.yarn_inv_freq(
        m4.qk_rope_head_dim, m4.rope_theta, m4.rope_factor,
        m4.rope_original_max_position_embeddings, m4.rope_beta_fast,
        m4.rope_beta_slow))


def test_n_params_by_hand_is_the_tree(arch, toy):
    model, params = toy
    leaves = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
    E, D, H, Hkv, n, I, V, L = 32, 8, 4, 2, 8, 16, 97, 8
    by_hand = L * (E * H * D + 2 * E * Hkv * D + H * D * E + E * n + 2 * E
                   + n * 3 * E * I) + 2 * V * E + E
    assert arch.n_params(TOY) == leaves == by_hand
    # the published layer, by hand: 417.75 M, 21.39 M outside its experts
    real = {"hidden_size": 2304, "head_dim": 128, "num_attention_heads": 32,
            "num_key_value_heads": 4, "num_experts": 64,
            "moe_intermediate_size": 896, "num_hidden_layers": 28,
            "vocab_size": 98304}
    assert arch._layer_params(real, 64) == 417747456
    assert arch._layer_params(real, 0) == 21385728
    assert arch.n_params(real) == 28 * 417747456 + 2 * 226492416 + 2304


def test_served_weights_are_held_in_the_dtype_the_model_states(toy):
    model, params = toy
    bf16 = MellumModel(dataclasses.replace(model.config,
                                           dtype=jnp.bfloat16))
    engine = InferenceEngine(bf16, params, **ENGINE)
    assert {l.dtype for l in jax.tree_util.tree_leaves(engine.params)} \
        == {jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in engine.pool.all_arrays} \
        == {jnp.dtype(jnp.bfloat16)}
    assert InferenceEngine(bf16, engine.params, **ENGINE).params \
        is engine.params
