"""Cache groups in the one paged pool (``serving/kv_cache.py``,
``serving/engine.py``): layers that keep every position beside layers that
keep a window, each group with its own tensors, free list and page table a
request, behind one ``alloc`` / ``free``.

- a window group's freed pages are given to a second request while the
  first still decodes, and both still serve what the reference computes;
- ``alloc`` that fails changes no group, ``free`` returns every group's
  pages, ``release_expired`` slides a window group's table;
- a model that states no groups has ONE, and its ``pool_shapes``, tables and
  served tokens are what they were (GPT-2, a latent model);
- the variants that know one group refuse a model of two by name.
"""
import os
import sys

import jax
import numpy as np
import pytest

from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.mellum import FULL, SLIDING
from deepspeed_tpu.serving import InferenceEngine, kv_cache
from deepspeed_tpu.serving import engine as serving
from deepspeed_tpu.serving.decoder import UnsupportedForModel
from deepspeed_tpu.serving.kv_cache import (CacheGroup, PagedKVPool,
                                            cache_groups, pool_shapes,
                                            window_table_width)

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

WINDOW, CHUNK, PAGE = 8, 8, 4
TOY = {"name": "toy", "architecture": "mellum", "attention_bias": False,
       "hidden_act": "silu", "tie_word_embeddings": False,
       "use_sliding_window": True, "vocab_size": 97, "hidden_size": 32,
       "num_hidden_layers": 4, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 8, "num_experts": 8,
       "num_experts_per_tok": 2, "moe_intermediate_size": 16,
       "norm_topk_prob": True, "rms_norm_eps": 1e-6,
       "max_position_embeddings": 256, "sliding_window": WINDOW,
       "layer_types": [SLIDING] * 3 + [FULL],
       "mlp_layer_types": ["sparse"] * 4,
       "rope_parameters": {
           FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 8,
                  "original_max_position_embeddings": 32, "beta_fast": 32,
                  "beta_slow": 1, "attention_factor": 1.2079},
           SLIDING: {"rope_type": "default", "rope_theta": 10000}},
       "assumed": {"compute_dtype": "float32", "initializer_range": 0.2}}
ENGINE = dict(max_slots=2, kv_block_size=PAGE, max_blocks_per_seq=24,
              prefill_chunk=CHUNK)


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "mellum.py"), "bench_arch_mellum_groups")


@pytest.fixture(scope="module")
def toy(arch):
    model = arch.build_model(TOY, {"moe_tile_rows": 8,
                                   "moe_tile_rows_decode": 8})
    return model, arch.init_params(model, 5)


def _engine(toy, **kwargs):
    model, params = toy
    return InferenceEngine(model, params, **dict(ENGINE, **kwargs))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 97, n).astype(np.int32)


# ---------------------------------------------------------------------------
# the groups a configuration states, and the pool's shapes by group
# ---------------------------------------------------------------------------
def test_groups_and_shapes_by_group(toy):
    cfg = toy[0].config
    assert cache_groups(cfg) == (CacheGroup("full", 1, None),
                                 CacheGroup("window", 3, WINDOW))
    assert pool_shapes(cfg, 9, PAGE, False, 0)[:2] == ((1, 9, PAGE, 16),) * 2
    assert pool_shapes(cfg, 7, PAGE, False, 1)[:2] == ((3, 7, PAGE, 16),) * 2
    # a table is as wide as a window can need, not as the context
    assert window_table_width(1024, 64) == 17
    assert window_table_width(1024, 64, 2048) == 49
    assert serving.group_table_widths(cfg, 24, PAGE, CHUNK) \
        == [(24, 24), (3, 5)]
    # the default pool never evicts: the full group a context a lane, the
    # window group a window a lane and the one chunk in flight
    assert serving.default_pool_blocks(cfg, 1, 2, 24, PAGE, CHUNK) \
        == [1 + 2 * 24, 1 + 2 * 3 + 2]
    real = toy[0].config.__class__()        # the published sizes
    assert serving.default_pool_blocks(real, 1, 32, 518, 64, 2048) \
        == [1 + 32 * 518, 1 + 32 * 17 + 32]
    assert pool_shapes(real, 577, 64, False, 1)[0] == (21, 577, 64, 512)


def test_a_model_that_states_no_groups_has_one_as_before():
    gpt2 = GPT2Config(vocab_size=97, n_positions=32, n_embd=32, n_layer=2,
                      n_head=2)
    assert cache_groups(gpt2) == (CacheGroup("full", 2, None),)
    assert pool_shapes(gpt2, 9, 4, False) == pool_shapes(gpt2, 9, 4, False,
                                                         0) \
        == ((2, 9, 4, 32), (2, 9, 4, 32), None, None)
    from deepspeed_tpu.models.mistral4 import Mistral4Config

    latent = Mistral4Config(num_hidden_layers=5)
    assert cache_groups(latent) == (CacheGroup("full", 5, None),)
    assert pool_shapes(latent, 6209, 64, False)[:2] \
        == ((5, 6209, 64, 384), None)
    assert serving.default_pool_blocks(gpt2, 2, 4, 8, 4, 8) == [2 + 4 * 8]
    assert serving.group_table_widths(gpt2, 8, 4, 8) == [(8, 8)]


# ---------------------------------------------------------------------------
# the allocator over groups
# ---------------------------------------------------------------------------
def _pool(toy, blocks=(9, 6)):
    return PagedKVPool(toy[0].config, num_blocks=list(blocks),
                       block_size=PAGE)


def _free_counts(pool):
    return [len(pool._free[0])] + [len(g.free) for g in pool._further]


def test_alloc_covers_every_group_or_changes_none(toy):
    pool = _pool(toy)
    assert _free_counts(pool) == [8, 5]
    assert pool.alloc(1, 0, 3 * PAGE)               # three pages a group
    assert _free_counts(pool) == [5, 2]
    # the window group cannot cover three more: NOTHING changes, in either
    before = (_free_counts(pool), pool.table_row(1, 8).tolist(),
              pool.table_row(1, 5, group=1).tolist())
    assert not pool.alloc(1, 0, 6 * PAGE)
    assert not pool.alloc(2, 0, 3 * PAGE)           # a newcomer neither
    assert (_free_counts(pool), pool.table_row(1, 8).tolist(),
            pool.table_row(1, 5, group=1).tolist()) == before
    assert 2 not in pool._blocks and 2 not in pool._further[0].blocks
    # the full group cannot cover: the window group keeps its pages too
    tight = _pool(toy, blocks=(4, 9))
    assert tight.alloc(1, 0, 3 * PAGE) and not tight.alloc(1, 0, 4 * PAGE)
    assert _free_counts(tight) == [0, 5]


def test_release_expired_slides_the_window_table_and_free_returns_all(toy):
    pool = _pool(toy, blocks=(12, 6))
    assert pool.alloc(1, 0, 4 * PAGE)
    full = pool.table_row(1, 8).tolist()
    assert pool.table_row(1, 5, group=1).tolist()[:4] == [1, 2, 3, 4]
    assert pool.table_base(1, 1) == 0 and pool.table_base(1, 0) == 0
    # the next query stands at 16: it sees 9..16, page 2 on; pages 0 and 1
    # go back to the window group's list, and to nobody else's
    assert pool.release_expired(1, 4 * PAGE) == 2
    assert pool.table_base(1, 1) == 2 * PAGE
    assert pool.table_row(1, 5, group=1).tolist() == [3, 4, 0, 0, 0]
    assert pool.table_row(1, 8).tolist() == full    # keeps every position
    assert _free_counts(pool) == [7, 3] and pool.window_frees == 2
    assert pool.release_expired(1, 4 * PAGE) == 0   # nothing twice
    # a second request is given the pages the first returned
    assert pool.alloc(2, 0, 2 * PAGE)
    assert pool.table_row(2, 5, group=1).tolist()[:2] == [1, 2]
    # growth continues behind the slid table
    assert pool.alloc(1, 0, 5 * PAGE)
    assert pool.table_row(1, 5, group=1).tolist() == [3, 4, 5, 0, 0]
    stats = pool.group_stats()
    assert [g["blocks_in_use"] for g in stats] == [7, 5]
    assert pool.occupancy(1) == 1.0
    # bytes over all groups: a window page costs three layers' rows
    assert pool.occupancy() == pytest.approx(
        (7 * 1 + 5 * 3) / (11 * 1 + 5 * 3))
    pool.free(1)
    pool.free(2)
    assert _free_counts(pool) == [11, 5]
    assert pool.occupancy() == 0.0 and pool.stats()["groups"][1][
        "blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# the engine over groups
# ---------------------------------------------------------------------------
def test_freed_window_pages_serve_a_second_request_while_the_first_decodes(
        arch, toy):
    """The first request decodes (its window pages expire one by one and go
    back); the second is admitted meanwhile and its prompt's chunks are
    given pages the first held.  Both serve what the reference computes."""
    engine = _engine(toy)
    engine.warmup()
    window = engine.pool._further[0]
    first = engine.submit(_prompt(30, 1), max_new_tokens=40)
    held = set()
    while not engine.scheduler.running:
        engine.step()
        held |= set(window.blocks.get(first, ()))
    for _ in range(12):
        engine.step()
        held |= set(window.blocks.get(first, ()))
    assert engine.results.get(first) is None        # still decoding
    second = engine.submit(_prompt(45, 2), max_new_tokens=12)
    reused = set()
    while engine.scheduler.has_work():
        engine.step()
        reused |= set(window.blocks.get(second, ())) & held
        held |= set(window.blocks.get(first, ()))
    assert reused, "no page of the first request reached the second"
    # the window group never held more than its lanes can need
    assert window.num_blocks - 1 == 2 * 3 + 2
    weights = arch.reference_weights(toy[1], TOY)
    for rid, n in ((first, 30), (second, 45)):
        tokens = np.asarray(engine.result(rid))
        rows = np.arange(n - 1, len(tokens) - 1)
        logits = np.asarray(arch.reference_logits(weights, TOY, tokens[None],
                                                  rows)[0])
        gap = logits.max(-1) - logits[np.arange(len(rows)), tokens[rows + 1]]
        assert gap.max() <= 1e-4
    assert all(g["blocks_in_use"] == 0 for g in engine.pool.group_stats())


def test_eviction_returns_every_groups_pages(toy):
    """A pool whose full group is too small for two long lanes: the
    scheduler preempts one, whose pages of BOTH groups go back, and both
    requests still finish."""
    engine = _engine(toy, kv_blocks=1 + 14)
    rids = [engine.submit(_prompt(n, s), max_new_tokens=30)
            for n, s in ((10, 3), (12, 4))]
    engine.serve()
    assert all(engine.results[r]["status"] == "finished" for r in rids)
    assert engine.metrics.evictions > 0
    assert all(g["blocks_in_use"] == 0 for g in engine.pool.group_stats())


def test_gpt2_tables_and_tokens_are_what_they_were():
    """One group: the engine's tables, pool and served tokens are the
    contiguous cache's (``generate``), bit for bit, as before the groups."""
    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                     n_head=2)
    model = GPT2Model(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids,
                                                "labels": ids})
    engine = InferenceEngine(model, params, max_slots=2, kv_block_size=4,
                             prefill_chunk=8)
    assert engine.groups == (CacheGroup("full", 2, None),)
    assert not engine._gtables and not engine.pool._further
    assert engine.pool.all_arrays == engine.pool.tensors.arrays
    assert tuple(a.shape for a in engine.pool.all_arrays) \
        == pool_shapes(cfg, 2 * 16 + 1, 4, False)[:2]
    assert not isinstance(engine._decode_args()[3], tuple)
    prompts = [_prompt(n, n) for n in (5, 19)]
    rids = [engine.submit(p, max_new_tokens=6) for p in prompts]
    engine.serve()
    for rid, prompt in zip(rids, prompts):
        want = np.asarray(generate(model, params, prompt[None], 6))[0]
        assert (np.asarray(engine.result(rid)) == want).all()
    assert engine.pool.stats()["groups"][0]["name"] == "full"


@pytest.mark.parametrize("variant,kwargs", [
    ("quantize_kv", {"quantize_kv": True}),
    ("prefix_cache", {"prefix_cache": True}),
    ("speculative", {"speculative": 2}),
    ("sparse_context", {"sparse_context": {"num_sliding_window_blocks": 2}}),
    ("export_request", None), ("import_request", None)])
def test_variants_that_know_one_group_refuse_two_by_name(toy, variant,
                                                         kwargs):
    with pytest.raises(UnsupportedForModel, match=variant) as refused:
        if kwargs is None:
            getattr(_engine(toy), variant)(0)
        else:
            _engine(toy, **kwargs)
    assert "ONE cache group" in str(refused.value) \
        and "window of 8" in str(refused.value)


def test_shards_refuse_two_groups_by_name(toy):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    with pytest.raises(UnsupportedForModel, match="shards"):
        _engine(toy, shards=2, mesh=mesh)


# ---------------------------------------------------------------------------
# a window group of RAW ROWS (latent rows under a window: models/motif.py)
# ---------------------------------------------------------------------------
ROWS_TOY = {
    "name": "toy-rows", "architecture": "motif", "attention_cls": "gdla",
    "diff_v2": True, "elementwise_attn_output_gate": True,
    "headwise_attn_output_gate": False, "hidden_act": "poly_norm",
    "mhc_enabled": True, "score_before_experts": False,
    "interleave_moe_layer_step": 1, "sliding_window_pattern": "interleave",
    "rope_scaling": {"apply_yarn_scaling": False}, "swa_rope_theta": 10000,
    "rope_theta": 10000, "tie_word_embeddings": False, "k_ratio": 1,
    "polynorm_output_scale_per_layer": {}, "vocab_size": 97,
    "hidden_size": 32, "num_hidden_layers": 5, "layers_held": [1, 4, 5, 6, 7],
    "num_attention_heads": 10, "num_key_value_heads": 2,
    "num_noise_heads": 2, "head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "q_lora_rank": 16, "kv_lora_rank": 16,
    "intermediate_size": 48, "moe_intermediate_size": 16, "num_experts": 16,
    "experts_top_k": 2, "num_shared_experts": 1, "n_dense_first_layers": 2,
    "first_expert_held": 0, "num_experts_held": 16, "route_norm": True,
    "route_scale": 2, "score_func": "sigmoid", "sliding_window": WINDOW,
    "sliding_window_period": 4, "mhc_expansion_rate": 4,
    "mhc_sinkhorn_iters": 20, "rms_norm_eps": 1e-5,
    "polynorm_output_scale": 0.5, "polynorm_bias_clamp": 0.5,
    "hidden_clamp": 1000000, "max_position_embeddings": 256,
    "assumed": {"compute_dtype": "float32", "initializer_range": 0.2,
                "mhc_alpha_init": 0.2}}


@pytest.fixture(scope="module")
def rows_arch():
    arch = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "motif.py"), "bench_arch_motif_groups")
    # the reference's blocks at a toy's size (a module of its own)
    arch._ROWS, arch._Q_ROWS, arch._KEY_BUCKET, arch._TILE_ROWS, \
        arch._HEAD_ROWS = 32, 16, 64, 8, 8
    return arch


@pytest.fixture(scope="module")
def rows_toy(rows_arch):
    model = rows_arch.build_model(ROWS_TOY, {"moe_tile_rows": 8,
                                             "moe_tile_rows_decode": 8})
    return model, rows_arch.init_params(model, 6)


def _gap(arch, params, tokens, n):
    rows = np.arange(n - 1, len(tokens) - 1)
    logits = np.asarray(arch.reference_logits(
        arch.reference_weights(params, ROWS_TOY), ROWS_TOY, tokens[None],
        rows)[0])
    return (logits.max(-1)
            - logits[np.arange(len(rows)), tokens[rows + 1]]).max()


def test_a_window_group_of_raw_rows_has_one_padded_tensor_a_group(rows_toy):
    cfg = rows_toy[0].config
    assert kv_cache.cache_kind(cfg) == kv_cache.RAW_ROWS
    assert cache_groups(cfg) == (CacheGroup("full", 1, None),
                                 CacheGroup("window", 4, WINDOW))
    # one raw row a token (16 | 8 stored as 128: whole lanes), no values
    assert pool_shapes(cfg, 9, PAGE, False, 0)[:2] == ((1, 9, PAGE, 128),
                                                       None)
    assert pool_shapes(cfg, 7, PAGE, False, 1)[:2] == ((4, 7, PAGE, 128),
                                                       None)
    assert serving.group_table_widths(cfg, 24, PAGE, CHUNK) \
        == [(24, 24), (3, 5)]
    real = cfg.__class__()                  # the published sizes
    assert cache_groups(real) == (CacheGroup("full", 13, None),
                                  CacheGroup("window", 40, 128))
    assert pool_shapes(real, 177, 64, False, 1)[:2] == ((40, 177, 64, 640),
                                                        None)


def test_rows_window_group_allocates_slides_and_frees_as_any_other(rows_toy):
    pool = _pool(rows_toy, blocks=(12, 6))
    assert len(pool.all_arrays) == 2        # one tensor a group
    assert pool.alloc(1, 0, 4 * PAGE)
    full = pool.table_row(1, 8).tolist()
    assert pool.table_row(1, 5, group=1).tolist()[:4] == [1, 2, 3, 4]
    assert pool.release_expired(1, 4 * PAGE) == 2
    assert pool.table_base(1, 1) == 2 * PAGE
    assert pool.table_row(1, 5, group=1).tolist() == [3, 4, 0, 0, 0]
    assert pool.table_row(1, 8).tolist() == full
    assert _free_counts(pool) == [7, 3]
    assert not pool.alloc(2, 0, 4 * PAGE)   # the window group cannot cover
    assert _free_counts(pool) == [7, 3]
    pool.free(1)
    assert _free_counts(pool) == [11, 5]


def test_rows_window_pages_are_reused_and_both_requests_hold_to_reference(
        rows_arch, rows_toy):
    """Raw latent rows in a window group: the first request decodes while
    its window pages expire and go back, the second's chunks are given
    them, and both serve what the reference computes."""
    engine = _engine(rows_toy)
    engine.warmup()
    window = engine.pool._further[0]
    first = engine.submit(_prompt(30, 1), max_new_tokens=40)
    held = set()
    while not engine.scheduler.running:
        engine.step()
        held |= set(window.blocks.get(first, ()))
    for _ in range(12):
        engine.step()
        held |= set(window.blocks.get(first, ()))
    second = engine.submit(_prompt(45, 2), max_new_tokens=12)
    reused = set()
    while engine.scheduler.has_work():
        engine.step()
        reused |= set(window.blocks.get(second, ())) & held
        held |= set(window.blocks.get(first, ()))
    assert reused and engine.pool.window_frees > 0
    for rid, n in ((first, 30), (second, 45)):
        assert _gap(rows_arch, rows_toy[1], np.asarray(engine.result(rid)),
                    n) <= 1e-4
    assert all(g["blocks_in_use"] == 0 for g in engine.pool.group_stats())


def test_rows_window_group_survives_preemption_and_resume(rows_arch,
                                                          rows_toy):
    """A full group too small for two long lanes: one is preempted, both
    groups' pages go back, it is resumed (its prompt and what it had
    generated prefilled again into fresh pages of both groups) and still
    serves what the reference computes."""
    engine = _engine(rows_toy, kv_blocks=1 + 14)
    prompts = [_prompt(n, s) for n, s in ((10, 3), (12, 4))]
    rids = [engine.submit(p, max_new_tokens=30) for p in prompts]
    engine.serve()
    assert all(engine.results[r]["status"] == "finished" for r in rids)
    assert engine.metrics.evictions > 0
    for rid, p in zip(rids, prompts):
        assert _gap(rows_arch, rows_toy[1], np.asarray(engine.result(rid)),
                    len(p)) <= 1e-4
    assert all(g["blocks_in_use"] == 0 for g in engine.pool.group_stats())
