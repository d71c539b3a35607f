"""LongCat-Flash on the serving engine's decoder-block contract, at a toy
width on the CPU (hidden 64, 4 heads of (8 | 8), latent 16, dense width 96,
8 routed experts of which 4 held and 4 zero-compute ones, top-3, 2 double
blocks): the router's bias, identity experts and weights against plain
``jax.numpy``, the shares adding up, the live-tile ladder against the
worst-case buffer, the engine against the benchmark's plain reference
through TWO latent rows a block (with a non-zero choice bias, and failing
when the scale correction is left out), staggered arrivals without a
compilation, the new counter on the step's one fetch, and the pool and the
engine variants telling raw rows from keys and values by the stated kind.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.longcat_flash import (LongCatFlashConfig,
                                                LongCatFlashModel)
from deepspeed_tpu.moe.dropless import (STAT_NAMES, ZERO_STAT_NAME,
                                        dropless_moe, route_top_k)
from deepspeed_tpu.serving import CompilationCounter, InferenceEngine
from deepspeed_tpu.serving import kv_cache
from deepspeed_tpu.serving.decoder import UnsupportedForModel

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

TOY = {"name": "toy", "architecture": "longcat_flash", "vocab_size": 97,
       "hidden_size": 64, "ffn_hidden_size": 96,
       "expert_ffn_hidden_size": 32, "num_layers": 2,
       "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
       "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
       "n_routed_experts": 8, "zero_expert_num": 4, "moe_topk": 3,
       "n_routed_experts_held": 4, "first_routed_expert_held": 2,
       "routed_scaling_factor": 6, "rms_norm_eps": 1e-5,
       "max_position_embeddings": 4096, "rope_theta": 10000000,
       "attention_method": "MLA", "attention_bias": False,
       "zero_expert_type": "identity",
       "assumed": {"compute_dtype": "float32", "initializer_range": 0.2}}
TILES = {"moe_tile_rows": 8, "moe_tile_rows_decode": 8}
ENGINE = dict(max_slots=3, kv_block_size=4, max_blocks_per_seq=16)


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "longcat_flash.py"),
        "bench_arch_longcat_unit")


def _with_bias(params, seed=11, spread=0.05):
    """The seeded tree with a correction bias that is not zero: of the size
    of a probability (1/12 here), so that it moves choices."""
    bias = np.random.default_rng(seed).normal(
        0, spread, params["layers"]["router_bias"].shape)
    return dict(params, layers=dict(
        params["layers"], router_bias=jnp.asarray(bias, jnp.float32)))


@pytest.fixture(scope="module")
def toy(arch):
    model = arch.build_model(TOY, TILES)
    return model, _with_bias(arch.init_params(model, 3))


def _engine(toy, **kwargs):
    model, params = toy
    return InferenceEngine(model, params, **dict(ENGINE, **kwargs))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _serve(engine, prompts, new):
    rids = [engine.submit(p, max_new_tokens=new) for p in prompts]
    engine.serve()
    return [np.asarray(engine.result(r)) for r in rids]


# ---------------------------------------------------------------------------
# the router: bias, identity experts, weights
# ---------------------------------------------------------------------------
def _moe_inputs(T=24, E=32, I=16, routed=8, zero=4, seed=3):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    return (f32(T, E), f32(E, routed + zero) * 0.5,
            {"gate_up": f32(routed, E, 2 * I) * 0.2,
             "down": f32(routed, I, E) * 0.2})


def _plain_layer(x, router, experts, top_k, first, count, zero, bias=None,
                 scaling=6.0):
    """``sum_{e chosen, held} w_e SwiGLU_e(x) + (sum_{e chosen, zero} w_e)
    x``, chosen by p + bias, weighed by scaling * p: plain numpy loops."""
    x, router = np.asarray(x, np.float64), np.asarray(router, np.float64)
    logits = x @ router
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    score = p if bias is None else p + np.asarray(bias, np.float64)
    routed = router.shape[1] - zero
    out = np.zeros_like(x)
    for t in range(len(x)):
        for e in np.argsort(-score[t])[:top_k]:
            w = scaling * p[t, e]
            if e >= routed:
                out[t] += w * x[t]
            elif first <= e < first + count:
                gu = x[t] @ np.asarray(experts["gate_up"][e], np.float64)
                inner = gu.shape[0] // 2
                hidden = gu[:inner] / (1 + np.exp(-gu[:inner])) * gu[inner:]
                out[t] += w * (hidden @ np.asarray(experts["down"][e],
                                                   np.float64))
    return out


def _held(experts, first, count):
    return {k: v[first:first + count] for k, v in experts.items()}


def _layer(x, router, experts, first, count, **kwargs):
    kwargs = dict(dict(top_k=3, tile_m=8, norm_topk_prob=False, scaling=6.0,
                       zero_experts=4), **kwargs)
    return dropless_moe(x, router, _held(experts, first, count),
                        experts_held=(first, count), **kwargs)


def test_choice_is_by_score_plus_bias_and_weight_by_score_alone():
    x, router, _ = _moe_inputs()
    bias = jnp.asarray(np.random.default_rng(5).normal(0, 0.1, 12),
                       jnp.float32)
    plain_w, plain_ids = route_top_k(x, router, 3, norm_topk_prob=False,
                                     scaling=6.0)
    weights, ids = route_top_k(x, router, 3, norm_topk_prob=False,
                               scaling=6.0, choice_bias=bias)
    probs = np.asarray(jax.nn.softmax(x @ router, axis=-1))
    assert (np.asarray(ids) != np.asarray(plain_ids)).any()     # it moves
    np.testing.assert_array_equal(
        np.asarray(ids), np.argsort(-(probs + np.asarray(bias)), -1)[:, :3])
    # the weights are the probabilities of what was chosen, times 6: the
    # bias is not in them and they are not renormalised
    np.testing.assert_allclose(
        np.asarray(weights),
        6.0 * np.take_along_axis(probs, np.asarray(ids), -1), rtol=1e-6)
    assert not np.allclose(np.asarray(weights).sum(-1), 6.0)
    assert np.asarray(plain_w).sum(-1).max() < 6.0


@pytest.mark.parametrize("first,count", [(0, 8), (2, 4), (6, 2)])
@pytest.mark.parametrize("biased", [False, True])
def test_layer_is_the_held_part_plus_the_identity_term(first, count,
                                                       biased):
    x, router, experts = _moe_inputs()
    bias = jnp.asarray(np.random.default_rng(6).normal(0, 0.1, 12),
                       jnp.float32) if biased else None
    y, stats = _layer(x, router, experts, first, count, choice_bias=bias)
    np.testing.assert_allclose(
        np.asarray(y), _plain_layer(x, router, experts, 3, first, count, 4,
                                    bias), atol=2e-5)
    stats = dict(zip(STAT_NAMES + (ZERO_STAT_NAME,), map(int, stats)))
    assert stats["moe_routed_rows"] == 24 * 3
    assert 0 < stats["moe_zero_rows"] < 24 * 3
    assert stats["moe_held_rows"] + stats["moe_zero_rows"] <= 24 * 3
    if count == 8:
        assert stats["moe_held_rows"] + stats["moe_zero_rows"] == 24 * 3


def test_a_token_on_zero_compute_experts_alone_costs_no_expert_row():
    """A bias that lifts the four identity experts over every routed one:
    every choice is zero-compute, the layer is (sum of weights) x and the
    grouped matmul is given no live tile.  The other way round, every choice
    a held expert: no identity term."""
    x, router, experts = _moe_inputs()
    lift = np.zeros(12, np.float32)
    lift[8:] = 10.0
    y, stats = _layer(x, router, experts, 0, 8, choice_bias=jnp.asarray(lift))
    probs = np.asarray(jax.nn.softmax(x @ router, axis=-1))
    weight = 6.0 * np.sort(probs[:, 8:], -1)[:, -3:].sum(-1)
    np.testing.assert_allclose(np.asarray(y), weight[:, None] * np.asarray(x),
                               rtol=1e-5, atol=1e-6)
    stats = dict(zip(STAT_NAMES + (ZERO_STAT_NAME,), map(int, stats)))
    assert stats["moe_held_rows"] == 0 and stats["moe_experts_touched"] == 0
    assert stats["moe_zero_rows"] == stats["moe_routed_rows"] == 24 * 3
    y, stats = _layer(x, router, experts, 0, 8,
                      choice_bias=jnp.asarray(-lift))
    stats = dict(zip(STAT_NAMES + (ZERO_STAT_NAME,), map(int, stats)))
    assert stats["moe_zero_rows"] == 0 and stats["moe_held_rows"] == 24 * 3
    np.testing.assert_allclose(
        np.asarray(y), _plain_layer(x, router, experts, 3, 0, 8, 4, -lift),
        atol=2e-5)


def test_padding_rows_route_nowhere_and_count_no_identity_choice():
    x, router, experts = _moe_inputs()
    valid = jnp.arange(24) < 10
    y, stats = _layer(x, router, experts, 0, 8, valid=valid)
    stats = dict(zip(STAT_NAMES + (ZERO_STAT_NAME,), map(int, stats)))
    assert stats["moe_routed_rows"] == 10 * 3
    assert stats["moe_held_rows"] + stats["moe_zero_rows"] == 10 * 3
    whole, _ = _layer(x, router, experts, 0, 8)
    np.testing.assert_allclose(np.asarray(y[:10]), np.asarray(whole[:10]),
                               atol=1e-6)
    assert not np.asarray(y[10:]).any()


def test_the_32_shares_and_the_identity_term_once_make_the_layer():
    """32 chips of one routed expert each: every share computes its own
    expert's part AND the identity term; the held parts of all, plus the
    identity term counted once, are the uncut layer."""
    x, router, experts = _moe_inputs(T=40, routed=32, zero=16, seed=8)
    kwargs = dict(top_k=6, zero_experts=16)
    whole, _ = _layer(x, router, experts, 0, 32, **kwargs)
    # a chip that holds an expert no token chose computes the identity alone
    identity = _plain_layer(x, router, experts, 6, 0, 0, 16)
    shares = [np.asarray(_layer(x, router, experts, e, 1, **kwargs)[0])
              - identity for e in range(32)]
    assert sum(np.abs(s).max() > 1e-3 for s in shares) > 16
    np.testing.assert_allclose(identity + sum(shares), np.asarray(whole),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(whole), _plain_layer(x, router, experts, 6, 0, 32, 16),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", ["even", "one_expert_takes_all", "none"])
def test_live_tiles_give_what_the_worst_case_buffer_gives(case):
    """The ladder of buffers runs the same rows through the same kernels:
    the same numbers (to f32's rounding: one is fused inside a branch),
    whichever rung the step's live tiles reach, the longest (every choice
    on one held expert) and none."""
    x, router, experts = _moe_inputs(T=40, routed=8, zero=4)
    bias = np.zeros(12, np.float32)
    if case == "one_expert_takes_all":
        bias[2] = 10.0
    if case == "none":
        bias[8:] = 10.0
    kwargs = dict(top_k=3, tile_m=8, choice_bias=jnp.asarray(bias))
    worst, stats = _layer(x, router, experts, 2, 4, **kwargs)
    ladder, ladder_stats = jax.jit(lambda x: _layer(
        x, router, experts, 2, 4, live_tiles=True, **kwargs))(x)
    np.testing.assert_allclose(np.asarray(ladder), np.asarray(worst),
                               rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ladder_stats),
                                  np.asarray(stats))
    if case == "one_expert_takes_all":      # 40 rows on one expert: 5 tiles
        assert int(stats[2]) == 40 * 4


# ---------------------------------------------------------------------------
# the engine: two latent rows a block, the contract, the counters
# ---------------------------------------------------------------------------
def test_two_raw_rows_are_two_pool_tensors_of_whole_lanes(toy):
    model, _ = toy
    assert kv_cache.cache_rows(model.config) == (24, 24)
    assert kv_cache.cache_kind(model.config) == kv_cache.RAW_ROWS
    k, v, ks, vs = kv_cache.pool_shapes(model.config, 9, 4, False)
    assert k == v == (2, 9, 4, 128) and ks is None and vs is None
    # the published widths: two rows of 576, each stored as 640 lanes
    real = LongCatFlashConfig(num_layers=4)
    assert kv_cache.cache_rows(real) == (576, 576)
    assert kv_cache.pool_shapes(real, 1665, 64, False)[:2] \
        == ((4, 1665, 64, 640),) * 2
    engine = _engine(toy, prefill_chunk=8)
    assert engine.n_pool_tensors() == 2
    # the pool's bytes are what was allocated, not keys and values of heads
    assert engine.pool.device_bytes() == 2 * 2 * (1 + 3 * 16) * 4 * 128 * 4 \
        == sum(t.size * t.dtype.itemsize for t in engine.pool.tensors.arrays)


@pytest.mark.parametrize("variant,kwargs", [
    ("quantize_kv", {"quantize_kv": True}),
    ("prefix_cache", {"prefix_cache": True}),
    ("speculative", {"speculative": 2}),
    ("sparse_context", {"sparse_context": {"num_sliding_window_blocks": 2}}),
    ("shards", {"shards": 2}),
    ("export_request", None),
    ("import_request", None)])
def test_variants_that_know_keys_and_values_only_refuse_by_name(
        toy, variant, kwargs):
    """Two cached rows are not keys and values: the variants ask the kind
    the configuration states, not the number of rows."""
    with pytest.raises(UnsupportedForModel, match=variant):
        if kwargs is None:
            getattr(_engine(toy, prefill_chunk=8), variant)(
                0 if variant == "export_request" else {"rid": 0})
        else:
            _engine(toy, prefill_chunk=8, **kwargs)


def test_int8_pool_refuses_raw_rows_whatever_their_number(toy):
    model, _ = toy
    with pytest.raises(ValueError, match="no head in them"):
        kv_cache.PagedKVPool(model.config, num_blocks=9, block_size=4,
                             quantize_kv=True)


@pytest.mark.parametrize("biased", [True, False],
                         ids=["choice_bias", "bias_zero"])
def test_engine_serves_what_the_reference_computes(arch, toy, biased):
    """Prefill in chunks of 8 and decode through the two latent rows of the
    paged cache: every served token is the reference's best of its row (f32
    on both sides), with the correction bias moving choices and without."""
    model, params = toy
    if not biased:
        params = dict(params, layers=dict(
            params["layers"], router_bias=jnp.zeros_like(
                params["layers"]["router_bias"])))
    engine = _engine((model, params), prefill_chunk=8)
    engine.warmup()
    prompts = _prompts((5, 21, 30, 9))
    weights = arch.reference_weights(params, TOY)
    for prompt, tokens in zip(prompts, _serve(engine, prompts, 6)):
        assert (tokens[:len(prompt)] == prompt).all()
        rows = np.arange(len(prompt) - 1, len(tokens) - 1)
        logits = np.asarray(arch.reference_logits(
            weights, TOY, tokens[None], rows)[0])
        best = logits.max(-1)
        served = logits[np.arange(len(rows)), tokens[rows + 1]]
        np.testing.assert_allclose(served, best, rtol=0, atol=1e-4)


def test_the_bias_moves_what_is_served(arch, toy):
    """The case above is not idle: the same weights with the bias at zero
    route otherwise, and the reference's logits move."""
    model, params = toy
    weights = arch.reference_weights(params, TOY)
    flat = arch.reference_weights(dict(params, layers=dict(
        params["layers"], router_bias=jnp.zeros_like(
            params["layers"]["router_bias"]))), TOY)
    ids = _prompts((40,), seed=2)[0][None]
    a = np.asarray(arch.reference_logits(weights, TOY, ids))
    b = np.asarray(arch.reference_logits(flat, TOY, ids))
    assert np.abs(a - b).max() > 1e-2


@pytest.mark.parametrize("left_out", ["mla_scale_q_lora",
                                      "mla_scale_kv_lora"])
def test_a_program_without_the_scale_correction_is_not_the_reference(
        arch, toy, left_out):
    """Leave one of the two corrections out of the PROGRAM and its tokens'
    logits no longer lie at the reference's best."""
    model, params = toy
    wrong = LongCatFlashModel(dataclasses.replace(model.config,
                                                  **{left_out: False}))
    engine = _engine((wrong, params), prefill_chunk=8)
    weights = arch.reference_weights(params, TOY)
    gaps = []
    prompts = _prompts((21, 30, 17, 26), seed=1)
    for prompt, tokens in zip(prompts, _serve(engine, prompts, 8)):
        rows = np.arange(len(prompt) - 1, len(tokens) - 1)
        logits = np.asarray(arch.reference_logits(
            weights, TOY, tokens[None], rows)[0])
        gaps.append(logits.max(-1)
                    - logits[np.arange(len(rows)), tokens[rows + 1]])
    assert np.concatenate(gaps).max() > 1e-2


def test_chunked_prefill_serves_what_unchunked_prefill_serves(toy):
    prompts = _prompts((13, 29, 40), seed=4)
    chunked = _serve(_engine(toy, prefill_chunk=8), prompts, 4)
    whole = _serve(_engine(toy, prefill_chunk=64), prompts, 4)
    for a, b in zip(chunked, whole):
        assert (a == b).all()


def test_absorbed_decode_serves_what_expanded_prefill_serves(toy):
    """The second token of a request comes from the decode program
    (absorbed, over both latent rows); fed back as part of the prompt, the
    same position is scored by a prefill chunk (expanded)."""
    engine = _engine(toy, prefill_chunk=8)
    for prompt in _prompts((6, 19, 33), seed=5):
        first, second = _serve(engine, [prompt], 2)[0][-2:]
        again = _serve(engine, [np.append(prompt, first).astype(np.int32)],
                       1)[0]
        assert again[-1] == second


def test_engine_decodes_through_the_paged_latent_kernel(arch, monkeypatch):
    """The decode program on the branch a TPU takes (the kernel over the
    pages of BOTH latent rows, in interpret mode here) serves what the
    gathered view serves: staggered arrivals, mixed lengths, idle lanes.
    The smallest pool the compiled kernel would take: a latent of 128, pages
    of 8 rows (``latent_reads_in_place``)."""
    from deepspeed_tpu.ops.transformer.paged_attention import \
        paged_latent_decode_attention
    from deepspeed_tpu.serving import engine as serving

    wide = dict(TOY, kv_lora_rank=128)
    model = arch.build_model(wide, TILES)
    params = _with_bias(arch.init_params(model, 5))
    prompts, new = _prompts((5, 11, 3, 26), seed=8), (6, 9, 12, 5)

    def served():
        # a program traced on the other branch must not be found again
        serving._make_decode_step.cache_clear()
        engine = InferenceEngine(model, params, max_slots=3, kv_block_size=8,
                                 max_blocks_per_seq=8, prefill_chunk=8)
        rids = []
        for prompt, n in zip(prompts, new):
            rids.append(engine.submit(prompt, max_new_tokens=n))
            engine.step()
            engine.step()
        engine.serve()
        return [np.asarray(engine.result(r)) for r in rids]

    over_view = served()
    calls = []

    def on_the_kernel(*args, **kw):
        calls.append(args[2].shape)
        return paged_latent_decode_attention(
            *args, **{**kw, "interpret": True})

    monkeypatch.setattr(serving, "paged_latent_decode_attention",
                        on_the_kernel)
    monkeypatch.setattr(serving.jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    try:
        over_pages = served()
    finally:
        serving._make_decode_step.cache_clear()
    assert len(calls) == 2 and len(set(calls)) == 1 \
        and calls[0][2:] == (8, 256), \
        "one traced block, two attentions, each over its own pool tensor"
    for a, b in zip(over_pages, over_view):
        np.testing.assert_array_equal(a, b)


def test_staggered_arrivals_compile_nothing_after_warmup(toy):
    engine = _engine(toy, prefill_chunk=8)
    engine.warmup()
    prompts = _prompts((3, 17, 8, 31, 12, 5), seed=6)
    with CompilationCounter() as compiles:
        rids = []
        for prompt in prompts:
            rids.append(engine.submit(prompt, max_new_tokens=5))
            engine.step()
            engine.step()
        engine.serve()
    assert compiles.count == 0
    assert all(engine.results[r]["status"] == "finished" for r in rids)


def test_the_decoders_own_counter_rides_the_steps_one_fetch(toy):
    engine = _engine(toy, prefill_chunk=8, telemetry={"trace": True,
                                                      "mfu": False})
    assert engine.dec.stat_names == STAT_NAMES + ("moe_zero_rows",)
    engine.warmup()
    engine.telemetry.tracer.reset()
    _serve(engine, _prompts((21, 9), seed=7), 4)
    events = {}
    for e in engine.telemetry.tracer.events():
        events.setdefault(e["name"], []).append(e)
    cfg = engine.cfg

    def of(counter, kind):
        return [e["a0"] for name in events
                if name.startswith(f"{counter}_{kind}")
                for e in events[name]]
    # 21 + 9 prompt tokens went through prefill chunks, no padding counted;
    # a pair is on a zero-compute expert, a held one, or another chip's
    pairs = 30 * cfg.moe_topk * cfg.num_layers
    assert sum(of("moe_routed_rows", "prefill")) == pairs
    assert 0 < sum(of("moe_zero_rows", "prefill")) < pairs
    assert sum(of("moe_zero_rows", "prefill")) \
        + sum(of("moe_held_rows", "prefill")) < pairs
    assert len(of("moe_zero_rows", "decode")) \
        == len(of("attn_keys", "decode")) == len(of("clock_ms", "decode")) > 0
    assert all(e["ph"] == "X" and e["dur"] == 0.0
               for name in events if name.startswith("moe_zero_rows_")
               for e in events[name])


def test_served_weights_are_held_in_bf16_and_the_bias_in_f32(toy):
    model, params = toy
    bf16 = LongCatFlashModel(dataclasses.replace(model.config,
                                                 dtype=jnp.bfloat16))
    engine = InferenceEngine(bf16, params, **ENGINE, prefill_chunk=8)
    held = engine.params
    assert held["layers"]["router_bias"].dtype == jnp.float32
    assert {l.dtype for path, l in
            jax.tree_util.tree_flatten_with_path(held)[0]
            if "router_bias" not in jax.tree_util.keystr(path)} \
        == {jnp.dtype(jnp.bfloat16)}
    assert engine.pool.tensors.k.dtype == engine.pool.tensors.v.dtype \
        == jnp.bfloat16
    assert InferenceEngine(bf16, held, **ENGINE,
                           prefill_chunk=8).params is held
