"""Motif 3 (``models/motif.py``: grouped differential attention over raw
latent rows in two cache groups, a four-stream mHC residual, PolyNorm
feed-forwards, a sigmoid router over a held share of the experts) served
through the engine's decoder-block contract, at toy sizes on the CPU,
against the plain float32 reference the benchmark keeps
(``benchmark/architectures/motif.py``, which imports nothing of the
program).

Tolerance, and why: both sides compute in float32 here, so the reference's
logit of every served token lies within 1e-4 of its row's best (the two sum
in different orders, the program absorbs ``kv_b`` in decode and folds the
mixes' norm into ``Phi``, nothing else).  The same reference with every
matmul in bf16's 8 significand bits has to fail that tolerance by a factor
of ten, and each of the three MECHANISM controls (lambda at zero, no window,
``H_res`` without Sinkhorn) by a hundred: a check that cannot tell the model
from one of them guards nothing.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import motif
from deepspeed_tpu.models.motif import (MotifConfig, MotifDecoder,
                                        MotifModel, SINKHORN_STAT)
from deepspeed_tpu.moe.dropless import STAT_NAMES, route_top_k
from deepspeed_tpu.serving import CompilationCounter, InferenceEngine
from deepspeed_tpu.serving import kv_cache

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

WINDOW, CHUNK, PAGE = 6, 8, 4
TOY = {
    "name": "toy", "architecture": "motif", "attention_cls": "gdla",
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
    "experts_top_k": 4, "num_shared_experts": 1, "n_dense_first_layers": 2,
    "first_expert_held": 4, "num_experts_held": 8, "route_norm": True,
    "route_scale": 2, "score_func": "sigmoid", "sliding_window": WINDOW,
    "sliding_window_period": 4, "mhc_expansion_rate": 4,
    "mhc_sinkhorn_iters": 20, "rms_norm_eps": 1e-5,
    "polynorm_output_scale": 0.5, "polynorm_bias_clamp": 0.5,
    "hidden_clamp": 1000000, "max_position_embeddings": 256,
    "assumed": {"compute_dtype": "float32", "initializer_range": 0.2,
                "mhc_alpha_init": 0.2}}
TILES = {"moe_tile_rows": 8, "moe_tile_rows_decode": 8}
ENGINE = dict(max_slots=3, kv_block_size=PAGE, max_blocks_per_seq=40,
              prefill_chunk=CHUNK)
TOLERANCE = 1e-4


@pytest.fixture(scope="module")
def arch():
    arch = cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "motif.py"), "bench_arch_motif_unit")
    # the reference's blocks at a toy's size (a module of its own): several
    # blocks of query rows a prompt, a window that reaches into the block
    # before, tiles of a few rows an expert
    arch._ROWS, arch._Q_ROWS, arch._KEY_BUCKET, arch._TILE_ROWS, \
        arch._HEAD_ROWS = 32, 16, 64, 8, 8
    return arch


def _perturbed(params, seed=11):
    """The seeded tree with the leaves that start at a constant (PolyNorm's
    four, the mixes' beta) moved off it, so that a wrong reading of one of
    them shows."""
    key = jax.random.PRNGKey(seed)
    out = jax.tree_util.tree_map(lambda a: a, params)
    for group, name, scale in (("dense", "poly", 0.3),
                               ("routed", "shared_poly", 0.3),
                               ("experts", "poly", 0.3),
                               ("layers", "mhc_beta", 0.3)):
        key, k = jax.random.split(key)
        leaf = out[group][name]
        out[group][name] = leaf + scale * jax.random.normal(k, leaf.shape,
                                                            leaf.dtype)
    return out


@pytest.fixture(scope="module")
def toy(arch):
    model = arch.build_model(TOY, TILES)
    return model, _perturbed(arch.init_params(model, 3))


def _engine(toy, **kwargs):
    model, params = toy
    return InferenceEngine(model, params, **dict(ENGINE, **kwargs))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _gaps(arch, weights, config, prompt, tokens, **control):
    """How far the reference's logit of each served token lies under its
    row's best; with a control, of the token the CONTROL puts first."""
    rows = np.arange(len(prompt) - 1, len(tokens) - 1)
    logits = np.asarray(arch.reference_logits(
        weights, TOY, tokens[None], rows)[0])
    served = tokens[rows + 1]
    if control or config is not TOY:
        served = np.asarray(arch.reference_logits(
            weights, config, tokens[None], rows, **control)[0]).argmax(-1)
    return logits.max(-1) - logits[np.arange(len(rows)), served]


# prompts longer than 3 x (window + chunk), chunk boundaries off the page
# size (71 and 103 end mid-page, 23 mid-chunk), lanes of different length
# decoding side by side
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
    of raw rows = the reference's full forward, on logits."""
    weights = arch.reference_weights(toy[1], TOY)
    for prompt, tokens in zip(*served):
        assert (tokens[:len(prompt)] == prompt).all()
        assert _gaps(arch, weights, TOY, prompt, tokens).max() <= TOLERANCE


@pytest.mark.parametrize("control,factor", [
    ("bits8", 10), ("lambda_zero", 100), ("no_window", 100),
    ("no_sinkhorn", 100)])
def test_every_control_fails_the_tolerance(arch, toy, served, control,
                                           factor):
    """bf16's significand in place of f32, and each mechanism taken out:
    the token the control puts first lies far under the reference's best on
    some served row."""
    weights = arch.reference_weights(toy[1], TOY)
    controls = dict(arch.controls_of(TOY), bits8=(TOY, 8))
    config, bits = controls[control]
    worst = max(_gaps(arch, weights, config, prompt, tokens,
                      **({} if bits is None else {"control_bits": bits}))
                .max() for prompt, tokens in zip(*served))
    assert worst > factor * TOLERANCE, (control, worst)


def test_controls_are_the_four_the_cell_runs(arch):
    assert set(arch.controls_of(TOY)) == {"bits4", "lambda_zero",
                                          "no_window", "no_sinkhorn"}
    assert arch.controls_of(TOY)["bits4"][1] == 4


def test_chunked_prefill_serves_what_unchunked_prefill_serves(toy):
    prompts = _prompts((13, 29, 50), seed=4)

    def serve(engine):
        rids = [engine.submit(p, max_new_tokens=4) for p in prompts]
        engine.serve()
        return [np.asarray(engine.result(r)) for r in rids]

    for a, b in zip(serve(_engine(toy)), serve(_engine(toy,
                                                       prefill_chunk=64))):
        assert (a == b).all()


def test_counters_by_cache_group_and_the_models_own_ride_the_fetch(toy):
    engine = _engine(toy, telemetry={"trace": True, "mfu": False})
    engine.warmup()
    engine.telemetry.tracer.reset()
    with CompilationCounter() as compiles:
        rid = engine.submit(_prompts((21,), seed=7)[0], max_new_tokens=4)
        engine.serve()
    assert compiles.count == 0
    assert engine.results[rid]["status"] == "finished"
    events = {}
    for e in engine.telemetry.tracer.events():
        events.setdefault(e["name"], []).append(e["a0"])
    # a group of RAW ROWS records by group as one of keys and values does
    assert events["attn_pairs_full_prefill_8"] == [36, 100, 95]
    assert events["attn_pairs_window_prefill_8"] == [
        sum(min(start + i + 1, WINDOW) for i in range(n))
        for start, n in ((0, 8), (8, 8), (16, 5))]
    assert events["attn_keys_full_decode"] == [22, 23, 24]
    assert events["attn_keys_window_decode"] == [WINDOW] * 3
    # four routed layers of eight held experts; the dense layer counts none
    assert set(events["moe_expert_slots_decode"]) == {4 * 8}
    assert events["moe_routed_rows_decode"] == [4 * 4] * 3
    # the Sinkhorn error: parts per million summed over ten sublayers
    for group in ("decode", "prefill_8"):
        errs = events[f"{SINKHORN_STAT}_{group}"]
        assert all(0 <= e < 10 * 100 for e in errs), errs
    assert MotifDecoder.stat_names == STAT_NAMES + (SINKHORN_STAT,)
    assert sum(events["kv_window_pages_freed"]) > 0


def test_engine_decodes_through_the_paged_latent_kernel(arch, toy,
                                                        monkeypatch):
    """The decode program on the branch a TPU takes (the latent kernel in
    interpret mode here, with ``starts`` in the window group and the name
    the model gives it) serves what the reference computes."""
    from deepspeed_tpu.serving import engine as serving

    calls = []

    def on_the_kernel(*args, **kw):
        calls.append((kw.get("name"), kw.get("starts") is not None))
        return serving_kernel(*args, **{**kw, "interpret": True})

    serving_kernel = serving.paged_latent_decode_attention
    monkeypatch.setattr(serving, "paged_latent_decode_attention",
                        on_the_kernel)
    monkeypatch.setattr(serving, "latent_reads_in_place",
                        lambda shape, rank: True)
    monkeypatch.setattr(serving, "reads_in_place", lambda shape: True)
    monkeypatch.setattr(serving.jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    serving._make_decode_step.cache_clear()
    try:
        engine = _engine(toy)
        prompts = _prompts((19, 33), seed=9)
        rids = [engine.submit(p, max_new_tokens=8) for p in prompts]
        engine.serve()
    finally:
        serving._make_decode_step.cache_clear()
    assert set(calls) == {("gdla_paged_decode_attn_full", False),
                          ("gdla_paged_decode_attn_window", True)}
    weights = arch.reference_weights(toy[1], TOY)
    for rid, prompt in zip(rids, prompts):
        tokens = np.asarray(engine.result(rid))
        assert _gaps(arch, weights, TOY, prompt, tokens).max() <= TOLERANCE


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------
def test_the_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer(arch,
                                                                      toy):
    """One chip of eight computes its two experts' part and the shared
    expert; the eight parts, the shared expert counted once, are the uncut
    layer: the program's with all sixteen held, and the reference's."""
    model, params = toy
    cfg = model.config
    held = cfg.experts_held[1]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 24, 32)),
                    jnp.float32)
    k = 1                                       # the second routed layer
    own = {name: leaf[k * held:(k + 1) * held]
           for name, leaf in params["experts"].items()}
    # this toy holds experts 4 .. 11; give the layer all sixteen, the
    # others seeded
    rng = jax.random.PRNGKey(5)
    every = {name: jnp.concatenate([
        0.2 * jax.random.normal(jax.random.fold_in(rng, i), (4,)
                                + leaf.shape[1:], leaf.dtype)
        if name != "poly" else leaf[:4], leaf,
        0.2 * jax.random.normal(jax.random.fold_in(rng, 7 + i), (4,)
                                + leaf.shape[1:], leaf.dtype)
        if name != "poly" else leaf[:4]])
        for i, (name, leaf) in enumerate(own.items())}

    def layer(first, count):
        share = dataclasses.replace(cfg, experts_held=(first, count))
        tree = dict(params, routed={n: l[k:k + 1]
                                    for n, l in params["routed"].items()},
                    experts={n: l[first:first + count]
                             for n, l in every.items()})
        return MotifDecoder(share)._ffn(tree, 0, False, x, None)[0]

    rp = {name: leaf[k] for name, leaf in params["routed"].items()}
    shared = motif._poly_ffn(cfg, x[0], rp["shared_gate_up"],
                             rp["shared_down"], rp["shared_poly"])[None]
    parts = sum(layer(2 * i, 2) - shared for i in range(8)) + shared
    uncut = layer(0, 16)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(uncut),
                               rtol=0, atol=2e-5)
    # and the reference's uncut layer, from the same leaves
    uncut_config = dict(TOY, first_expert_held=0, num_experts_held=16)
    static = arch._static(uncut_config)
    mm = arch._matmul(arch._kept(None))
    c = dict(static)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x[0] @ rp["router"])
        weights, ids = jax.lax.top_k(scores, 4)
        weights = 2 * weights / weights.sum(-1, keepdims=True)
        want = arch._poly_ffn(x[0], {"gate_up": rp["shared_gate_up"],
                                     "down": rp["shared_down"],
                                     "poly": rp["shared_poly"]}, c, mm)
        want = arch._ref_routed(
            want, x[0], (weights, ids),
            (every["gate_up"], every["down"], every["poly"], 0),
            uncut_config, static, None)
    np.testing.assert_allclose(np.asarray(uncut[0]), np.asarray(want),
                               rtol=0, atol=2e-5)


def _mix_of(params, l=1, s=0):
    return {name[4:]: params["layers"][name][l, s] for name in
            ("mhc_norm", "mhc_phi", "mhc_beta", "mhc_alpha")}


@pytest.mark.parametrize("iters,converged", [(20, True), (2, False)])
def test_h_res_is_doubly_stochastic_after_twenty_iterations_not_two(
        toy, iters, converged):
    model, params = toy
    cfg = dataclasses.replace(model.config, mhc_sinkhorn_iters=iters)
    X = jnp.asarray(np.random.default_rng(1).standard_normal((256, 4 * 32)),
                    jnp.float32)
    _, _, h_res, err = motif.mhc_pre(cfg, _mix_of(params), X)
    h = np.asarray(h_res).reshape(256, 4, 4)    # (token, row, column)
    worst = max(np.abs(h.sum(2) - 1).max(), np.abs(h.sum(1) - 1).max())
    assert bool(worst <= 1e-4) is converged, worst
    # the counter reads what is there
    assert float(err.max()) == pytest.approx(worst, abs=1e-6)
    assert (h > 0).all()


def test_the_mixes_are_the_equations(arch, toy):
    """``mhc_pre`` / ``mhc_post`` (the norm folded into Phi, the sums
    written out) against the reference's, which follow the equations
    letter for letter."""
    model, params = toy
    cfg, mix = model.config, _mix_of(params, 2, 1)
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.standard_normal((40, 4, 32)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    u, h_post, h_res, _ = motif.mhc_pre(cfg, mix, X.reshape(40, -1))
    out = motif.mhc_post(cfg, X.reshape(40, -1), y, h_post, h_res) \
        .reshape(X.shape)
    c = dict(arch._static(TOY))
    with jax.default_matmul_precision("highest"):
        u_ref, post_ref, res_ref = arch._mhc_pre(
            X, mix, c, arch._matmul(arch._kept(None)))
        out_ref = arch._mhc_post(X, y, post_ref, res_ref, c)
    np.testing.assert_allclose(np.asarray(u), np.asarray(u_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_res).reshape(40, 4, 4),
                               np.asarray(res_ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5)


class _Hook:
    """The least of the engine's cache hook that one chunk of ONE sequence
    needs: the rows it writes are the view, from position 0."""

    def __init__(self, T, window):
        self.positions = jnp.arange(T)[None]
        self.maxpos = jnp.asarray([T - 1])
        self.k_start = jnp.zeros(1, jnp.int32)
        self.window, self.row_valid = window, None

    def write_rows(self, i, rows):
        self.rows = jnp.pad(rows, ((0, 0), (0, 128 - rows.shape[1])))

    def view_rows(self, i):
        return self.rows[None]


def _attend(toy, x, window, **leaves):
    model, params = toy
    dec = MotifDecoder(model.config)
    lp = {name: leaf[1] for name, leaf in params["layers"].items()}
    lp.update(leaves)
    hook = _Hook(x.shape[1], window)
    return np.asarray(dec._attention(
        lp, x, hook, motif._rope_cos_sin(model.config, hook.positions))[0])


@pytest.mark.parametrize("back,moves", [(WINDOW - 1, True), (WINDOW, False),
                                        (WINDOW + 5, False)])
def test_a_sliding_layer_sees_its_window_and_nothing_behind_it(toy, back,
                                                               moves):
    """A key ``window`` or more positions back changes nothing of a
    sliding layer's output at a position; one ``window - 1`` back does."""
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 40, 32)),
                    jnp.float32)
    p = 30
    before = _attend(toy, x, WINDOW)
    after = _attend(toy, x.at[0, p - back].add(1.0), WINDOW)
    changed = np.abs(after[p] - before[p]).max()
    assert (changed > 1e-3) if moves else (changed == 0.0), changed
    # a full layer sees it at any distance
    assert np.abs(_attend(toy, x.at[0, p - back].add(1.0), None)[p]
                  - _attend(toy, x, None)[p]).max() > 1e-3


@pytest.mark.parametrize("push,lam", [(-1e4, 0.0), (0.0, 0.5)])
def test_lambda_at_zero_gives_the_signal_heads_alone(toy, push, lam):
    """The differential step by hand: with lambda's projection pushed far
    down lambda is 0 and the output is the gated SIGNAL heads through
    ``o``; at a projection of zero it is 1/2 and half of each group's noise
    head is gone from its four signal heads."""
    model, params = toy
    cfg = model.config
    x = jnp.asarray(np.random.default_rng(4).standard_normal((1, 24, 32)),
                    jnp.float32).at[..., 0].set(1.0)
    lp = {name: leaf[1] for name, leaf in params["layers"].items()}
    got = _attend(toy, x, None,
                  lam=jnp.zeros_like(lp["lam"]).at[0].set(push))
    hook = _Hook(24, None)
    cos, sin = motif._rope_cos_sin(cfg, hook.positions)
    heads = motif.latent_attention(
        cfg, {n: lp[n] for n in ("q_a", "q_a_norm", "q_b", "kv_a",
                                 "kv_a_norm", "kv_b")}, x, hook,
        q_scale=cfg.head_dim ** -0.5, cos=cos, sin=sin, kv_heads=2) \
        .reshape(1, 24, 2, 5, 8)
    signal, noise = heads[:, :, :, :4], heads[:, :, :, 4:]
    want = (jax.nn.sigmoid(x @ lp["gate"])
            * (signal - lam * noise).reshape(1, 24, -1)) @ lp["o"]
    np.testing.assert_allclose(got, np.asarray(want[0]), atol=2e-5)


@pytest.mark.parametrize("norm,scaling", [(True, 2.0), (False, 1.0)])
def test_route_top_k_sigmoid_against_numpy(norm, scaling):
    rng = np.random.default_rng(5)
    x, router = rng.standard_normal((12, 16)), rng.standard_normal((16, 10))
    weights, ids = route_top_k(jnp.asarray(x, jnp.float32),
                               jnp.asarray(router, jnp.float32), 3,
                               norm_topk_prob=norm, scaling=scaling,
                               score="sigmoid")
    scores = 1 / (1 + np.exp(-(x @ router)))
    want_ids = np.argsort(-scores, axis=-1)[:, :3]
    want = np.take_along_axis(scores, want_ids, -1)
    if norm:
        want = want / want.sum(-1, keepdims=True)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(weights), want * scaling,
                               rtol=1e-5)
    # the default is the softmax it always was
    soft, _ = route_top_k(jnp.asarray(x, jnp.float32),
                          jnp.asarray(router, jnp.float32), 3)
    assert np.asarray(soft).sum(-1) == pytest.approx(1.0, abs=1e-5)


@pytest.mark.parametrize("bias", [0.2, 3.0, -3.0])
def test_poly_norm_against_numpy(toy, arch, bias):
    cfg = toy[0].config
    rng = np.random.default_rng(6)
    z = rng.standard_normal((7, 16)) * 2
    w = np.array([0.5, -0.25, 0.125, bias])
    want = 0.5 * (sum(w[i] * z ** (i + 1)
                      / np.sqrt((z ** (2 * i + 2)).mean(-1, keepdims=True)
                                + 1e-5) for i in range(3))
                  + np.clip(bias, -0.5, 0.5))
    got = motif.poly_norm(jnp.asarray(z, jnp.float32),
                          jnp.asarray(w, jnp.float32), cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    ref = arch.poly_norm(jnp.asarray(z, jnp.float32),
                         jnp.asarray(w, jnp.float32), 0.5, 0.5, 1e-5)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5, atol=2e-6)


def test_config_under_the_published_names_and_its_groups():
    cfg = MotifConfig()             # the published sizes
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) \
        == (53, 4096, 220160)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.num_noise_heads, cfg.head_dim, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) \
        == (80, 16, 16, 192, 128, 64, 128)
    assert cfg.cache_rows == (576,) and cfg.cache_kind == "rows"
    assert kv_cache.cache_groups(cfg) == (
        kv_cache.CacheGroup("full", 13, None),
        kv_cache.CacheGroup("window", 40, 128))
    assert [cfg.is_full(l) for l in range(8)] == [False] * 3 + [True] \
        + [False] * 3 + [True]
    assert [cfg.is_dense(l) for l in range(4)] == [True, True, False, False]
    cut = MotifConfig(num_hidden_layers=5, layers_held=(1, 4, 5, 6, 7),
                      experts_held=(0, 48), vocab_size=27520)
    assert kv_cache.cache_groups(cut) == (
        kv_cache.CacheGroup("full", 1, None),
        kv_cache.CacheGroup("window", 4, 128))
    # the stage's runs: the dense sliding layer, three routed sliding layers
    # as one scan, the routed full layer
    assert [(r[0], r[1], r[3]) for r in motif._runs(cut)] \
        == [(True, False, 1), (False, False, 3), (False, True, 1)]
    assert MotifDecoder(cut).n_layer == 1


def test_n_params_by_hand_is_the_tree(arch, toy):
    model, params = toy
    leaves = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
    assert arch.n_params(TOY) == leaves
    E, V = 32, 97
    attention = E * 16 + 16 + 16 * 10 * 16 + E * 24 + 16 + 16 * 2 * 16 \
        + E * 8 + E * 64 + 64 * E
    mix = 4 * E + 4 * E * 24 + 24 + 3
    outside = attention + 2 * mix + 2 * E
    by_hand = outside + 3 * E * 48 + 4 \
        + 4 * (outside + E * 16 + 9 * (3 * E * 16 + 4)) + 2 * V * E + E
    assert leaves == by_hand


def test_served_weights_are_held_in_the_dtype_the_model_states(toy):
    model, params = toy
    bf16 = MotifModel(dataclasses.replace(model.config, dtype=jnp.bfloat16))
    engine = InferenceEngine(bf16, params, **ENGINE)
    assert {l.dtype for l in jax.tree_util.tree_leaves(engine.params)} \
        == {jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in engine.pool.all_arrays} \
        == {jnp.dtype(jnp.bfloat16)}
