"""Mistral 4 on the serving engine's decoder-block contract, at a toy width
on the CPU (hidden 64, 4 heads, latent 16, 8 experts of which 2-4 held,
top-2, 2 layers, original rotary context 16 so that YaRN and the query scale
are live on both sides of it): the two new kernels against plain
``jax.numpy``, the dropless layer's shares adding up, the engine against
the benchmark's plain reference through latent pages, chunked against
unchunked prefill, absorbed decode against the expanded form, staggered
arrivals without a compilation, the counters on the step's one fetch, and
the engine variants that refuse a model whose cache is not (keys, values).
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.mistral4 import (Mistral4Config, Mistral4Model,
                                           softmax_scale, yarn_inv_freq)
from deepspeed_tpu.moe.dropless import (STAT_NAMES, dropless_moe,
                                        held_layout, route_top_k)
from deepspeed_tpu.moe.grouped_matmul import grouped_matmul
from deepspeed_tpu.ops.transformer.rect_attention import (
    _blocks, mla_decode_attention, rect_flash_attention)
from deepspeed_tpu.serving import CompilationCounter, InferenceEngine
from deepspeed_tpu.serving import kv_cache
from deepspeed_tpu.serving.decoder import UnsupportedForModel

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 8,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
TOY = {"name": "toy", "architecture": "mistral4", "vocab_size": 97,
       "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
       "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
       "n_routed_experts_held": 4, "first_routed_expert_held": 2,
       "num_experts_per_tok": 2, "n_shared_experts": 1,
       "moe_intermediate_size": 32, "norm_topk_prob": True,
       "routed_scaling_factor": 1, "rms_norm_eps": 1e-6,
       "max_position_embeddings": 4096, "rope_interleave": True,
       "first_k_dense_replace": 0, "n_group": 1, "topk_group": 1,
       "hidden_act": "silu", "rope_parameters": ROPE,
       "assumed": {"compute_dtype": "float32", "initializer_range": 0.2}}
TILES = {"moe_tile_rows": 8, "moe_tile_rows_decode": 8}
ENGINE = dict(max_slots=3, kv_block_size=4, max_blocks_per_seq=16)


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "mistral4.py"), "bench_arch_m4_unit")


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


def _serve(engine, prompts, new):
    rids = [engine.submit(p, max_new_tokens=new) for p in prompts]
    engine.serve()
    return [np.asarray(engine.result(r)) for r in rids]


# ---------------------------------------------------------------------------
# the kernels against plain jax.numpy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_active", [0, 3, 6])
def test_grouped_matmul_computes_the_active_tiles_and_no_others(n_active):
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(48, 32)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 32, 16)), jnp.float32)
    tile_expert = jnp.asarray([0, 0, 2, 3, 3, 3], jnp.int32)
    out = np.asarray(grouped_matmul(lhs, rhs, tile_expert,
                                    jnp.int32(n_active), tile_m=8))
    want = np.einsum("tmk,tkn->tmn", np.asarray(lhs).reshape(6, 8, 32),
                     np.asarray(rhs)[np.asarray(tile_expert)])
    np.testing.assert_allclose(out.reshape(6, 8, 16)[:n_active],
                               want[:n_active], rtol=1e-5, atol=1e-5)


def _rect_case(q_start, C, S=64, block_k=16, widths=(16, 8, 16),
               dtype=jnp.float32, tol=1e-5):
    return pytest.param(q_start, C, S, block_k, widths, dtype, tol,
                        id=f"{q_start}+{C}of{S}-k{block_k}-"
                           f"{'|'.join(map(str, widths))}-{dtype.__name__}")


@pytest.mark.parametrize("shared", [False, True], ids=["plain", "shared"])
@pytest.mark.parametrize("q_start,C,S,block_k,widths,dtype,tol", [
    # four key blocks: whole ones, the diagonal, skipped ones
    _rect_case(0, 8), _rect_case(5, 8), _rect_case(24, 16),
    _rect_case(37, 3),
    # S is no multiple of the key block: the last block is ragged, and the
    # last query stands inside it ...
    _rect_case(60, 10, S=72), _rect_case(90, 12, S=104, block_k=32),
    _rect_case(61, 11, S=72, block_k=32), _rect_case(97, 7, S=104),
    # ... or before it, so that it is never read
    _rect_case(40, 16, S=72), _rect_case(50, 8, S=104, block_k=32),
    # fewer keys than one block
    _rect_case(3, 9, S=12),
    # the chunk, padded to its bucket, runs past the view's end: into a
    # ragged last block, with a whole query block past it, past a whole one
    _rect_case(60, 16, S=72), _rect_case(66, 16, S=72, block_k=32),
    _rect_case(100, 16, S=104, block_k=32), _rect_case(52, 24),
    # both models' head widths in bf16, held to the f32 result: scores,
    # softmax and accumulator are f32, only p is rounded for its product
    _rect_case(130, 40, S=200, block_k=64, widths=(64, 64, 128),
               dtype=jnp.bfloat16, tol=6e-3),
    _rect_case(130, 40, S=200, block_k=64, widths=(128, 64, 128),
               dtype=jnp.bfloat16, tol=6e-3),
])
def test_rectangle_attention_is_causal_attention_with_an_offset(
        q_start, C, S, block_k, widths, dtype, tol, shared):
    rng = np.random.default_rng(1)
    H, (D, Ds, Dv) = 2, widths
    q, k, v, qs, ks = (jnp.asarray(rng.normal(size=shape), dtype)
                       for shape in ((H, C, D), (H, S, D), (H, S, Dv),
                                     (H, C, Ds), (S, Ds)))
    q, qs = (a * jnp.asarray((D + Ds) ** -0.25, dtype) for a in (q, qs))
    # nothing after the last query's position is anybody's to see: blocks
    # wholly after it are never read, the rest of its own block is masked
    # in the scores and zeroed in the values (what a ragged block holds
    # past the view's end is NaN too, in interpret mode)
    k, v, ks = (a.at[..., q_start + C:, :].set(jnp.nan) for a in (k, v, ks))
    out = np.asarray(rect_flash_attention(
        q, k, v, q_start, *((qs, ks) if shared else ()), block_q=8,
        block_k=block_k), np.float32)
    assert np.isfinite(out).all()
    q, k, v, qs, ks = (np.nan_to_num(np.asarray(a, np.float32))
                       for a in (q, k, v, qs, ks))
    scores = np.einsum("hqd,hkd->hqk", q, k)
    if shared:
        scores = scores + np.einsum("hqd,kd->hqk", qs, ks)
    seen = (q_start + np.arange(C))[:, None] >= np.arange(S)[None, :]
    probs = np.asarray(jax.nn.softmax(jnp.where(seen, scores, -np.inf), -1))
    np.testing.assert_allclose(out, np.einsum("hqk,hkd->hqd", probs, v),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("C,S", [(2048, 24832), (1024, 1664), (128, 24832),
                                 (4, 1664)])
def test_rectangle_attention_walks_the_blocks_it_was_asked_for(C, S):
    """No divisor of the view's length shrinks a tile: 24,832 = 97 x 256
    and 1,664 = 13 x 128 once made the key block 256 and 128."""
    rows, block_q, block_k = _blocks(C, S)
    assert rows == max(C, 128) and rows % block_q == 0
    assert block_q == min(rows, 1024) and block_k == 1024


def test_absorbed_decode_attention_is_attention_over_expanded_keys():
    rng = np.random.default_rng(2)
    B, H, S, R, Dr, Dn, Dv = 3, 4, 24, 16, 8, 8, 16
    latent = jnp.asarray(rng.normal(size=(B, S, 128)), jnp.float32) \
        .at[..., R + Dr:].set(0)                # stored padded to the lanes
    w = rng.normal(size=(R, H, Dn + Dv)).astype(np.float32)
    q_nope, q_rope = (rng.normal(size=(B, H, d)).astype(np.float32)
                      for d in (Dn, Dr))
    n_keys = np.array([24, 7, 1])
    o_lat = mla_decode_attention(
        jnp.einsum("bhd,chd->bhc", q_nope, w[..., :Dn]), q_rope, latent,
        jnp.asarray(n_keys), R)
    got = np.einsum("bhc,chv->bhv", o_lat, w[..., Dn:])
    c, k_rope = np.asarray(latent[..., :R]), np.asarray(latent[..., R:R + Dr])
    expanded = np.einsum("bsc,chd->bshd", c, w)
    s = np.einsum("bhd,bshd->bhs", q_nope, expanded[..., :Dn]) \
        + np.einsum("bhd,bsd->bhs", q_rope, k_rope)
    s = np.where(np.arange(S)[None, None] < n_keys[:, None, None], s,
                 -np.inf)
    want = np.einsum("bhs,bshv->bhv", np.asarray(jax.nn.softmax(s, -1)),
                     expanded[..., Dn:])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the dropless layer
# ---------------------------------------------------------------------------
def _moe_inputs(T=24, E=32, I=16, n=8, seed=3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(E, n)), jnp.float32)
    experts = {"gate_up": jnp.asarray(rng.normal(size=(n, E, 2 * I)) * 0.2,
                                      jnp.float32),
               "down": jnp.asarray(rng.normal(size=(n, I, E)) * 0.2,
                                   jnp.float32)}
    return x, router, experts


def _plain_moe(x, router, experts, top_k, first, count):
    weights, ids = route_top_k(x, router, top_k)
    weights, ids = np.asarray(weights), np.asarray(ids)
    inner = experts["down"].shape[1]
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for w, e in zip(weights[t], ids[t]):
            if first <= e < first + count:
                gu = np.asarray(x[t] @ experts["gate_up"][e])
                h = np.asarray(jax.nn.silu(gu[:inner])) * gu[inner:]
                out[t] += w * np.asarray(h @ experts["down"][e])
    return out


@pytest.mark.parametrize("first,count", [(0, 8), (2, 4), (6, 2)])
def test_dropless_layer_is_the_held_part_of_the_routed_sum(first, count):
    x, router, experts = _moe_inputs()
    held = {k: v[first:first + count] for k, v in experts.items()}
    y, stats = dropless_moe(x, router, held, top_k=2,
                            experts_held=(first, count), tile_m=8)
    np.testing.assert_allclose(
        np.asarray(y), _plain_moe(x, router, experts, 2, first, count),
        rtol=1e-4, atol=1e-5)
    stats = dict(zip(STAT_NAMES, np.asarray(stats)))
    ids = np.asarray(route_top_k(x, router, 2)[1])
    on_held = (ids >= first) & (ids < first + count)
    sizes = np.bincount(ids[on_held] - first, minlength=count)
    assert stats == {"moe_routed_rows": 48, "moe_held_rows": on_held.sum(),
                     "moe_busiest_scaled_rows": sizes.max() * count,
                     "moe_experts_touched": (sizes > 0).sum(),
                     "moe_expert_slots": count}


def test_the_four_quarters_add_up_to_the_uncut_layer():
    x, router, experts = _moe_inputs()
    whole, _ = dropless_moe(x, router, experts, top_k=2, experts_held=(0, 8),
                            tile_m=8)
    parts = [dropless_moe(x, router,
                          {k: v[f:f + 2] for k, v in experts.items()},
                          top_k=2, experts_held=(f, 2), tile_m=8)[0]
             for f in (0, 2, 4, 6)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_no_token_is_dropped_when_every_choice_lands_on_one_expert():
    x, router, experts = _moe_inputs()
    router = jnp.zeros((32, 8)).at[0, 5].set(50.0).at[0, 6].set(25.0)
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)      # everyone picks 5 and 6
    held = {k: v[4:8] for k, v in experts.items()}
    y, stats = dropless_moe(x, router, held, top_k=2, experts_held=(4, 4),
                            tile_m=8)
    np.testing.assert_allclose(np.asarray(y),
                               _plain_moe(x, router, experts, 2, 4, 4),
                               rtol=1e-4, atol=1e-5)
    assert int(stats[1]) == 2 * x.shape[0]          # every pair was held


def test_padding_rows_route_nowhere():
    x, router, experts = _moe_inputs()
    valid = jnp.arange(24) < 10
    y, stats = dropless_moe(x, router, experts, top_k=2, experts_held=(0, 8),
                            tile_m=8, valid=valid)
    assert np.abs(np.asarray(y)[10:]).max() == 0.0
    assert int(stats[0]) == 20 and int(stats[1]) == 20
    lay = held_layout(route_top_k(x, router, 2)[1], valid, (0, 8), 8)
    assert int(lay["sizes"].sum()) == 20 \
        and int(lay["n_tiles"]) <= lay["tile_expert"].shape[0]


# ---------------------------------------------------------------------------
# the model's positions
# ---------------------------------------------------------------------------
def test_yarn_frequencies_and_scale_follow_the_family(arch):
    cfg = Mistral4Config()          # the published sizes
    inv = yarn_inv_freq(cfg)
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, 64, 2) / 64)
    # fast dimensions keep their frequency, slow ones are divided by factor
    np.testing.assert_allclose(inv[:8], plain[:8], rtol=1e-12)
    np.testing.assert_allclose(inv[-4:], plain[-4:] / 128, rtol=1e-12)
    assert np.all(np.diff(inv) < 0)
    np.testing.assert_allclose(
        inv, arch._yarn_inv_freq(dict(ROPE, factor=128,
                                      original_max_position_embeddings=8192),
                                 64), rtol=1e-12)
    assert softmax_scale(cfg) == pytest.approx(
        128 ** -0.5 * (0.1 * np.log(128) + 1) ** 2)


# ---------------------------------------------------------------------------
# the engine: latent pages, the contract, the counters
# ---------------------------------------------------------------------------
def test_latent_pages_are_one_tensor_of_whole_lanes(toy):
    model, _ = toy
    assert kv_cache.cache_rows(model.config) == (24,)
    k, v, ks, vs = kv_cache.pool_shapes(model.config, 9, 4, False)
    assert k == (2, 9, 4, 128) and v is None and ks is None and vs is None
    assert kv_cache.pool_shapes(Mistral4Config(num_hidden_layers=5), 6209,
                                64, False)[0] == (5, 6209, 64, 384)
    engine = _engine(toy, prefill_chunk=8)
    assert engine.n_pool_tensors() == 1
    assert engine.pool.device_bytes() == 2 * (1 + 3 * 16) * 4 * 128 * 4
    gpt2 = GPT2Config(vocab_size=97, n_positions=32, n_embd=32, n_layer=2,
                      n_head=2)
    assert kv_cache.pool_shapes(gpt2, 9, 4, False)[:2] \
        == ((2, 9, 4, 32), (2, 9, 4, 32))
    assert kv_cache.cache_rows(gpt2) == (32, 32)


def _assert_best_of_the_reference(arch, params, config, prompts, served):
    """Every served token is the reference's best of its row (f32 on both
    sides)."""
    weights = arch.reference_weights(params, config)
    for prompt, tokens in zip(prompts, served):
        assert (tokens[:len(prompt)] == prompt).all()
        rows = np.arange(len(prompt) - 1, len(tokens) - 1)
        logits = np.asarray(arch.reference_logits(
            weights, config, tokens[None], rows)[0])
        best = logits.max(-1)
        served_logits = logits[np.arange(len(rows)), tokens[rows + 1]]
        np.testing.assert_allclose(served_logits, best, rtol=0, atol=1e-4)


def test_engine_serves_what_the_reference_computes(arch, toy):
    """Prefill in chunks of 8 and decode through latent pages, prompts on
    both sides of the original rotary context (16): every served token is
    the reference's best of its row (f32 on both sides)."""
    model, params = toy
    engine = _engine(toy, prefill_chunk=8)
    engine.warmup()
    prompts = _prompts((5, 21, 30, 9))
    _assert_best_of_the_reference(arch, params, TOY, prompts,
                                  _serve(engine, prompts, 6))


def test_engine_decodes_through_the_paged_latent_kernel(arch, monkeypatch):
    """The decode program on the branch a TPU takes (the kernel over the
    latent pages where they lie, in interpret mode here) serves what the
    float32 reference computes, as it does over the gathered view:
    staggered arrivals, mixed lengths, idle lanes.  The smallest pool the
    compiled kernel would take: a latent of 128, pages of 8 rows
    (``latent_reads_in_place``)."""
    from deepspeed_tpu.ops.transformer.paged_attention import \
        paged_latent_decode_attention
    from deepspeed_tpu.serving import engine as serving

    wide = dict(TOY, kv_lora_rank=128)
    model = arch.build_model(wide, TILES)
    params = arch.init_params(model, 5)
    calls = []

    def on_the_kernel(*args, **kw):
        calls.append(args[2].shape)
        return paged_latent_decode_attention(
            *args, **{**kw, "interpret": True})

    monkeypatch.setattr(serving, "paged_latent_decode_attention",
                        on_the_kernel)
    monkeypatch.setattr(serving.jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    # a program traced by an earlier test holds the other branch, and this
    # one must not be left for a later test
    serving._make_decode_step.cache_clear()
    try:
        engine = InferenceEngine(model, params, max_slots=3, kv_block_size=8,
                                 max_blocks_per_seq=8, prefill_chunk=8)
        prompts, rids = _prompts((5, 21, 30, 9), seed=8), []
        for prompt, n in zip(prompts, (6, 9, 12, 5)):
            rids.append(engine.submit(prompt, max_new_tokens=n))
            engine.step()
            engine.step()
        engine.serve()
    finally:
        serving._make_decode_step.cache_clear()
    assert calls == [(2, 1 + 3 * 8, 8, 256)], \
        "one traced block: the decode program alone takes the kernel, once"
    _assert_best_of_the_reference(
        arch, params, wide, prompts,
        [np.asarray(engine.result(rid)) for rid in rids])


def test_chunked_prefill_serves_what_unchunked_prefill_serves(toy):
    prompts = _prompts((13, 29, 40), seed=4)
    chunked = _serve(_engine(toy, prefill_chunk=8), prompts, 4)
    whole = _serve(_engine(toy, prefill_chunk=64), prompts, 4)
    for a, b in zip(chunked, whole):
        assert (a == b).all()


def test_absorbed_decode_serves_what_expanded_prefill_serves(toy):
    """The second token of a request comes from the decode program
    (absorbed, over latent rows); fed back as part of the prompt, the same
    position is scored by a prefill chunk (expanded keys and values)."""
    engine = _engine(toy, prefill_chunk=8)
    for prompt in _prompts((6, 19, 33), seed=5):
        first, second = _serve(engine, [prompt], 2)[0][-2:]
        again = _serve(engine, [np.append(prompt, first).astype(np.int32)],
                       1)[0]
        assert again[-1] == second


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


def test_counters_come_on_the_steps_one_fetch(toy, monkeypatch):
    from deepspeed_tpu.serving import engine as serving

    engine = _engine(toy, prefill_chunk=8, telemetry={"trace": True,
                                                      "mfu": False})
    engine.warmup()
    engine.telemetry.tracer.reset()
    fetches, gets = [], []
    fetch, get = InferenceEngine._fetch, jax.device_get
    monkeypatch.setattr(InferenceEngine, "_fetch",
                        lambda self, *a, **k: (fetches.append(1),
                                               fetch(self, *a, **k))[1])
    monkeypatch.setattr(serving.jax, "device_get",
                        lambda x: (gets.append(1), get(x))[1])
    _serve(engine, _prompts((21, 9), seed=7), 4)
    assert len(gets) == len(fetches) > 0        # no sync of their own
    assert not engine._stats_pending
    events = {}
    for e in engine.telemetry.tracer.events():
        events.setdefault(e["name"], []).append(e)
    cfg = engine.cfg
    per_token = cfg.num_experts_per_tok * cfg.num_hidden_layers

    def of(counter, kind):
        return [e["a0"] for name in events
                if name.startswith(f"{counter}_{kind}")
                for e in events[name]]
    # 21 + 9 prompt tokens went through prefill chunks, no padding counted
    assert sum(of("moe_routed_rows", "prefill")) == 30 * per_token
    assert all(e["ph"] == "X" and e["dur"] == 0.0
               for name in events if name.startswith(("moe_", "clock_ms_"))
               for e in events[name])
    # causal pairs of 21 tokens in chunks of 8 (36, 100, 95) and of 9
    assert sorted(of("attn_pairs", "prefill")) == [9, 36, 36, 95, 100]
    assert [e["a0"] for e in events["attn_pairs_prefill_4"]] == [9]
    # one row of counters and one clock a program
    assert len(of("moe_held_rows", "decode")) \
        == len(of("attn_keys", "decode")) == len(of("clock_ms", "decode")) > 0
    assert len(of("clock_ms", "prefill")) == 5


def test_served_weights_are_held_in_the_dtype_the_model_states(toy):
    model, params = toy
    bf16 = Mistral4Model(Mistral4Config(**{
        **{f.name: getattr(model.config, f.name)
           for f in model.config.__dataclass_fields__.values()},
        "dtype": jnp.bfloat16}))
    engine = InferenceEngine(bf16, params, **ENGINE, prefill_chunk=8)
    assert {l.dtype for l in jax.tree_util.tree_leaves(engine.params)} \
        == {jnp.dtype(jnp.bfloat16)}
    assert engine.pool.tensors.k.dtype == jnp.bfloat16
    # a tree already held is the tree the engine reads: no program run
    assert InferenceEngine(bf16, engine.params, **ENGINE,
                           prefill_chunk=8).params is engine.params
    # GPT-2 states its own: what its block casts, its LayerNorms as given
    gpt2 = GPT2Model(GPT2Config(vocab_size=97, n_positions=32, n_embd=32,
                                n_layer=2, n_head=2))
    ids = np.zeros((1, 8), np.int32)
    given = gpt2.init(jax.random.PRNGKey(0), {"input_ids": ids,
                                              "labels": ids})
    held = InferenceEngine(gpt2, given, max_slots=2).params
    assert held["ln_f"]["scale"] is given["ln_f"]["scale"]
    for path, leaf in jax.tree_util.tree_flatten_with_path(held)[0]:
        assert leaf.dtype == (jnp.float32 if "['ln_" in
                              jax.tree_util.keystr(path) else jnp.bfloat16)


@pytest.mark.parametrize("variant,kwargs", [
    ("quantize_kv", {"quantize_kv": True}),
    ("prefix_cache", {"prefix_cache": True}),
    ("speculative", {"speculative": 2}),
    ("sparse_context", {"sparse_context": {"num_sliding_window_blocks": 2}}),
    ("export_request", None)])
def test_variants_that_know_keys_and_values_only_refuse_by_name(
        toy, variant, kwargs):
    with pytest.raises(UnsupportedForModel, match=variant):
        if kwargs is None:
            _engine(toy, prefill_chunk=8).export_request(0)
        else:
            _engine(toy, prefill_chunk=8, **kwargs)


# ---------------------------------------------------------------------------
# the kernels at the published widths, for the described chip
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever this installation raises without libtpu
        pytest.skip(f"no TPU compiler here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(fn, v5e, *shapes):
    return jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
        for shape, dtype in shapes)).compile().as_text()


@pytest.mark.parametrize("tile_m,rows,K,N", [(128, 12288, 4096, 4096),
                                             (128, 12288, 2048, 4096),
                                             (16, 576, 4096, 4096)])
def test_grouped_matmul_compiles_for_v5e_under_its_own_name(v5e, tile_m,
                                                            rows, K, N):
    text = _compiled(
        lambda lhs, rhs, te, n: grouped_matmul(lhs, rhs, te, n,
                                               tile_m=tile_m,
                                               interpret=False),
        v5e, ((rows, K), jnp.bfloat16), ((32, K, N), jnp.bfloat16),
        ((rows // tile_m,), jnp.int32), ((), jnp.int32))
    assert "%moe_grouped_matmul" in text and "tpu_custom_call" in text
    # the experts' matrices go to the kernel where they lie
    assert re.search(r"bf16\[[\d,]*\]\S* copy\(", text) is None


@pytest.mark.parametrize("H,S,D,C,dtype", [
    (32, 24832, 64, 2048, jnp.bfloat16), (32, 24832, 64, 128, jnp.bfloat16),
    (32, 24832, 64, 4, jnp.bfloat16), (64, 1664, 128, 1024, jnp.bfloat16),
    (64, 1664, 128, 512, jnp.bfloat16), (32, 24832, 64, 2048, jnp.float32),
    (64, 1664, 128, 1024, jnp.float32)])
def test_rectangle_attention_compiles_for_v5e_under_its_own_name(v5e, H, S,
                                                                 D, C, dtype):
    """At the published widths: Mistral's 32 heads of 64 (+ 64 shared
    rotary) / 128 over 24,832 cached positions, LongCat's 64 heads of 128
    (+ 64) / 128 over 1,664: the blocks chosen fit the chip's VMEM, in the
    bf16 both serve in and in f32."""
    text = _compiled(
        lambda q, k, v, s, qs, ks: rect_flash_attention(
            q, k, v, s, qs, ks, interpret=False),
        v5e, ((H, C, D), dtype), ((H, S, D), dtype), ((H, S, 128), dtype),
        ((), jnp.int32), ((H, C, 64), dtype), ((S, 64), dtype))
    assert "%mla_prefill_attn" in text and "tpu_custom_call" in text
