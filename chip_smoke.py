"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, in ONE process, at the full width and depth
of gpt2-350m (24 layers, hidden 1024, 16 heads x 64, vocabulary 50257 padded
to 50304, sequence 1024, bf16, random weights from --seed):

    device   jax.devices() must be TPUs — no CPU continuation
    kernels  every Pallas variant the models dispatch, compiled on the chip,
             forward and jax.grad against the jnp / XLA reference path
    train    deepspeed_tpu.initialize -> engine.train_batch, ZeRO-2 + Adam
    serve    InferenceEngine.warmup/submit/serve against generate()

    python chip_smoke.py              # one chip; what the driver runs
    python chip_smoke.py --chips 4    # only the legs that need four chips

Each phase prints one JSON line when it ends (phase, ok, seconds, what it
checked, the numbers it saw).  Every phase runs even after one failed, so a
single chip call reports all of them; any failure makes the exit code 1 and
the last line is then not the ``ok`` line.  The times printed are
information, not claims: the benchmark defines the metrics.
"""
import argparse
import functools
import gc
import json
import sys
import time
import traceback

import numpy as np

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.generation import _attn_core, generate
from deepspeed_tpu.models.gpt2 import GPT2Model, gpt2_config
from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
from deepspeed_tpu.ops.sparse_attention import (FixedSparsityConfig,
                                                block_sparse_attention)
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
from deepspeed_tpu.ops.transformer.functional import (
    scaled_dot_product_attention)
from deepspeed_tpu.ops.transformer.mhc_mix import (fold_phi, mhc_post_mix,
                                                   mhc_pre_mix)
from deepspeed_tpu.ops.transformer.paged_attention import \
    paged_decode_attention
from deepspeed_tpu.ops.transformer.rect_attention import rect_flash_attention
from deepspeed_tpu.serving import (CompilationCounter, FleetRouter,
                                   InferenceEngine)
from deepspeed_tpu.serving.engine import _pool_view
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

# Tolerances, bf16-sized.  A kernel output or gradient may differ from its
# reference by this fraction of the reference's largest magnitude: the
# reference rounds its probabilities to bf16 (8 significand bits, 2^-8 per
# rounding) where the kernels keep them in f32, so a few roundings apart.
KERNEL_TOL = 3e-2
# The residual mixes' gates and H_res are f32 from end to end: f32's
# rounding through a 16,384-term product and twenty Sinkhorn iterations.
# One bf16 rounding of Phi (which XLA's excess precision once made of the
# three-piece split, on the chip only) reads a hundred times this.
MIX_TOL = 1e-5
# <out, cot> == <v, dL/dv> holds exactly for attention-with-dropout when
# forward and backward drew the same keep mask (out is linear in v)
DROPOUT_IDENTITY_TOL = 2e-2
# losses of the same global batch on two meshes (bf16 compute, f32 loss)
LOSS_RTOL = 2e-2
# Greedy tokens are bit-identical to generate()'s in the CPU tests (f32).
# In bf16 on the chip two logits one spacing apart (2^-6 near 2.0: bf16
# keeps 8 significand bits) come out in either order, and a random model's
# top two often are.  Equality is kept where it can hold, and the two
# allowances are bf16 steps at the magnitude of the row's best logit:
# the first token that differs from the reference's must tie with it, one
# step apart at most; and since the sequences share no prefix after that,
# every served token must stay within two steps of the best logit under
# the training forward of the served prefix — one rounding for the served
# path's logit, one for the referee's.
TIE_STEPS = 1
NEAR_BEST_STEPS = 2

SEQ = 1024
MICRO_BATCH = 8     # fits one v5e with full remat (the benchmark's cell runs 16)
# full remat, scanned layers, chunked loss head: what a 16 GB chip trains with
TRAINED = dict(remat=True, remat_policy="nothing", scan_layers=True,
               loss_chunk_tokens=8192)
SERVED = dict(scan_layers=True)


def gpt2_350m(**overrides):
    return gpt2_config("gpt2-350m", n_positions=SEQ, dtype=jnp.bfloat16,
                       **overrides)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(got).all(), "non-finite values in kernel result")
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-6))


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def phase_device(chips):
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"jax.devices() reports platform {d0.platform!r}, not 'tpu'")
    check(len(devs) >= chips, f"need {chips} chips, jax.devices() has "
                              f"{len(devs)}")
    cache_dir = enable_compile_cache()
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "jax": jax.__version__,
            "compile_cache": cache_dir}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _qkv(rng, shape):
    return [jnp.asarray(rng.standard_normal(shape) * 0.5, jnp.bfloat16)
            for _ in range(3)]


def _fwd_and_grads(attn, q, k, v, cot):
    """(out, (dq, dk, dv)) of ``sum(attn(q, k, v) * cot)`` under one jit."""
    def loss(q, k, v):
        out = attn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * cot), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return out, grads


def _compare(kernel, reference, q, k, v, cot, rows=None):
    """Largest relative error of the kernel's output and gradients against
    the reference's; ``rows`` (B, S) bool restricts the output comparison
    to valid query rows (the cotangent is already zero elsewhere)."""
    out_k, g_k = _fwd_and_grads(kernel, q, k, v, cot)
    out_r, g_r = _fwd_and_grads(reference, q, k, v, cot)
    if rows is not None:
        keep = rows[:, None, :, None]
        out_k, out_r = jnp.where(keep, out_k, 0), jnp.where(keep, out_r, 0)
    errs = {"out": _rel_err(out_k, out_r)}
    for name, a, b in zip(("dq", "dk", "dv"), g_k, g_r):
        errs[name] = _rel_err(a, b)
    worst = max(errs.values())
    check(worst <= KERNEL_TOL,
          f"kernel disagrees with its reference: {errs} > {KERNEL_TOL}")
    return {k_: round(v_, 5) for k_, v_ in errs.items()}


def _padding(rng, batch, seq):
    """(B, S) bool: each row keeps a prefix of between S/2 and S keys."""
    lengths = rng.integers(seq // 2, seq, size=batch)
    lengths[0] = seq                       # one unpadded row
    return np.arange(seq)[None, :] < lengths[:, None]


def _paged_decode(rng, lanes, n_head, head_dim, block, pages, layers=2):
    """The serving decode kernel against the shared core over the gathered
    view (the path every other platform runs), on a pool whose pages are
    scattered, with idle lanes, and with NaN in every row no query may see:
    the trash block, the pages past a lane's length, its last page's tail."""
    HD, rows = n_head * head_dim, pages * block
    n_blocks = 1 + lanes * pages
    lengths = rng.integers(1, rows + 1, size=lanes)
    lengths[:3] = (rows, 0, block + 1)     # full, idle, one row into a page
    tables = 1 + rng.permutation(lanes * pages).reshape(lanes, pages)
    seen = np.arange(rows)[None, :] < lengths[:, None]           # (B, S)
    filled = np.zeros((n_blocks, block), bool)
    filled[tables] = seen.reshape(lanes, pages, block)
    k, v = (np.where(filled[None, :, :, None], rng.standard_normal(
        (layers, n_blocks, block, HD)) * 0.5, np.nan) for _ in range(2))
    k, v = jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((lanes, HD)) * 0.5, jnp.bfloat16)
    tables, lengths = jnp.asarray(tables, jnp.int32), jnp.asarray(
        lengths, jnp.int32)
    layer = layers - 1
    got = jax.jit(functools.partial(paged_decode_attention, n_head=n_head))(
        q, k, v, layer, tables, lengths)

    @jax.jit
    def reference(q, k, v):
        keep = jnp.asarray(seen)[:, None, :, None]
        views = [jnp.where(keep, _pool_view(t, None, layer, tables, n_head,
                                            False, q.dtype), 0)
                 for t in (k, v)]
        unprojected = {"c_proj": {"kernel": jnp.eye(HD, dtype=q.dtype),
                                  "bias": jnp.zeros(HD, q.dtype)}}
        return _attn_core(q.reshape(lanes, n_head, 1, head_dim), *views,
                          jnp.asarray(seen)[:, None, None, :], unprojected,
                          q.dtype)[:, 0]

    live = np.asarray(lengths) > 0
    check(not np.asarray(got, np.float32)[~live].any(),
          "paged decode: an idle lane's output is not zero")
    err = _rel_err(np.asarray(got, np.float32)[live],
                   np.asarray(reference(q, k, v), np.float32)[live])
    check(err <= KERNEL_TOL, f"paged decode kernel disagrees with the core "
                             f"over the view: {err} > {KERNEL_TOL}")
    return {"out": round(err, 5)}


def _window_prefill(rng, H, Hkv, C, S, D, Ds, window, ahead):
    """A sliding layer's chunk (``rect_flash_attention`` with a window: the
    band kernel), bf16 as served, against the definition in float64 on the
    host: H query heads over Hkv key heads, C queries whose first stands
    ``ahead`` rows into a view of S rows, Ds further columns of scores whose
    keys all heads share (0: none), and a NaN in every row of the view
    that no query sees."""
    f64 = np.float64
    bf16 = lambda *shape: jnp.asarray(                       # noqa: E731
        rng.standard_normal(shape), jnp.bfloat16)
    q, k, v = bf16(H, C, D) * D ** -0.5, bf16(Hkv, S, D), bf16(Hkv, S, D)
    shared = (bf16(H, C, Ds) * D ** -0.5, bf16(S, Ds)) if Ds else ()
    q_start = 6144
    k_start = q_start - ahead
    kpos, qpos = k_start + np.arange(S), q_start + np.arange(C)
    dead = ((kpos > qpos[-1]) | (kpos <= qpos[0] - window))[:, None]
    k, v = (jnp.where(dead, jnp.nan, t) for t in (k, v))
    if Ds:
        shared = (shared[0], jnp.where(dead, jnp.nan, shared[1]))
    out = np.asarray(rect_flash_attention(
        q, k, v, jnp.int32(q_start), *shared, k_start=jnp.int32(k_start),
        window=window), f64)
    check(np.isfinite(out).all(), "window prefill: non-finite result")
    seen = (kpos[None] <= qpos[:, None]) \
        & (kpos[None] > qpos[:, None] - window)
    G = H // Hkv
    live = lambda t: np.where(dead, 0.0, np.asarray(t, f64))  # noqa: E731
    worst = 0.0
    for h in range(Hkv):
        heads = slice(h * G, (h + 1) * G)
        s = np.asarray(q[heads], f64) @ live(k[h]).T
        if Ds:
            s += np.asarray(shared[0][heads], f64) @ live(shared[1]).T
        s = np.where(seen[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ live(v[h])
        worst = max(worst, _rel_err(out[heads], want))
    check(worst <= KERNEL_TOL,
          f"window prefill: the band leaves float64's by {worst}")
    return {"out": round(worst, 5)}


def _mhc_mixes(rng, rows, n, width, iters=20, eps=1e-5):
    """The two kernels of the residual mixes (bf16 streams and weights, as
    served) against the equations in float64 on the host: the gates and
    H_res to f32's rounding, what is written in bf16 to a bf16 spacing."""
    f64 = np.float64
    S = 2 * n + n * n
    bf16 = lambda a: jnp.asarray(a, jnp.bfloat16)            # noqa: E731
    X = bf16(1.5 * rng.standard_normal((rows, n * width)))
    y = bf16(rng.standard_normal((rows, width)))
    norm = bf16(1 + 0.1 * rng.standard_normal(n * width))
    phi = bf16(rng.standard_normal((n * width, S)) / (n * width) ** 0.5)
    alpha, beta = bf16([0.7, 0.9, 1.1]), bf16(0.3 * rng.standard_normal(S))

    @jax.jit
    def kernels(X, y, norm, phi, alpha, beta):
        folded, consts = fold_phi(norm, phi, alpha, beta, n)
        u, h_post, h_res, err = mhc_pre_mix(X, folded, consts, n=n,
                                            iters=iters, eps=eps)
        return u, h_post, h_res, err, mhc_post_mix(X, y, h_post, h_res,
                                                   clamp=1e6)

    u, h_post, h_res, err, out = (np.asarray(a, f64) for a in kernels(
        X, y, norm, phi, alpha, beta))
    X, y, norm, phi, alpha, beta = (np.asarray(a, f64) for a in (
        X, y, norm, phi, alpha, beta))
    abc = X / np.sqrt((X * X).mean(-1, keepdims=True) + eps) * norm @ phi
    sigmoid = lambda z: 1 / (1 + np.exp(-z))                 # noqa: E731
    pre = sigmoid(alpha[0] * abc[:, :n] + beta[:n])
    post = 2 * sigmoid(alpha[1] * abc[:, n:2 * n] + beta[n:2 * n])
    res = np.exp((alpha[2] * abc[:, 2 * n:] + beta[2 * n:])
                 .reshape(rows, n, n))
    for _ in range(iters):
        res = res / res.sum(-1, keepdims=True)
        res = res / res.sum(-2, keepdims=True)
    streams = X.reshape(rows, n, width)
    seen = {"h_post": np.abs(h_post - post).max(),
            "h_res": np.abs(h_res - res.reshape(rows, -1)).max(),
            "u": _rel_err(u, np.einsum("si,sie->se", pre, streams)),
            "out": _rel_err(out, (np.einsum("sij,sje->sie", res, streams)
                                  + post[:, :, None] * y[:, None])
                            .reshape(rows, -1))}
    check(max(seen["h_post"], seen["h_res"]) <= MIX_TOL,
          f"mhc mixes: the gates or H_res leave float64's by {seen}")
    check(max(seen["u"], seen["out"]) <= 2.0 ** -7,
          f"mhc mixes: u or the streams leave float64's by {seen}")
    left = np.maximum(np.abs(res.sum(2) - 1).max(1),
                      np.abs(res.sum(1) - 1).max(1))
    check(np.abs(err - left).max() <= MIX_TOL,
          f"mhc mixes: the kernel counts {err.max()} left by Sinkhorn, "
          f"float64 {left.max()}")
    return {name: float(f"{value:.3g}") for name, value in seen.items()}


def phase_kernels(seed=0, *, causal_shape=(8, 16, 1024, 64),
                  bias_shape=(8, 16, 512, 64),
                  sparse_shape=(2, 12, 4096, 64), sparse_block=64,
                  paged_shape=(28, 16, 64, 16, 64),
                  mhc_shape=(2048, 4, 4096),
                  window_shapes=((80, 16, 2048, 2240, 128, 64, 128, 128),
                                 (32, 4, 2048, 3136, 128, 0, 1024, 1024))):
    rng = np.random.default_rng(seed)
    seen = {}

    # a sliding layer's chunk, Motif's and Mellum's: query and key heads,
    # queries, rows of the view, head size, shared columns, window, and how
    # far into the view the first query stands
    for shape in window_shapes:
        seen[f"window_prefill_w{shape[6]}"] = _window_prefill(rng, *shape)

    # Motif's residual mixes: rows, streams, width of a stream (a chunk of
    # the agent-turns cell)
    seen["mhc_mixes"] = _mhc_mixes(rng, *mhc_shape)

    # serving decode over the paged pool: lanes, heads, head size, rows a
    # page, pages a lane (the chat cell's)
    seen["paged_decode_attn"] = _paged_decode(rng, *paged_shape)

    # flash, causal: the GPT-2 path
    q, k, v = _qkv(rng, causal_shape)
    cot = jnp.asarray(rng.standard_normal(causal_shape), jnp.float32)
    seen["flash_causal"] = _compare(
        lambda q, k, v: scaled_dot_product_attention(
            q, k, v, causal=True, use_pallas=True),
        lambda q, k, v: scaled_dot_product_attention(
            q, k, v, causal=True, use_pallas=False),
        q, k, v, cot)

    # flash, in-kernel dropout: no reference draws the same mask, so the
    # checks are determinism per seed and the linear-in-v identity, which
    # fails unless both backward sweeps regenerate the forward's mask
    def dropped(seed_):
        return _fwd_and_grads(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, dropout_rate=0.1, dropout_seed=seed_),
            q, k, v, cot)

    out_a, g_a = dropped(7)
    out_b, g_b = dropped(7)
    out_c, _ = dropped(8)
    check(all(bool(jnp.isfinite(t.astype(jnp.float32)).all())
              for t in (out_a, *g_a)), "dropout: non-finite result")
    check(all(bool((a == b).all()) for a, b in
              zip((out_a, *g_a), (out_b, *g_b))),
          "dropout: two runs with one seed differ")
    check(not bool((out_a == out_c).all()),
          "dropout: another seed gave the same output")
    lhs = float(jnp.sum(out_a.astype(jnp.float32) * cot))
    rhs = float(jnp.sum(v.astype(jnp.float32) * g_a[2].astype(jnp.float32)))
    scale = float(jnp.linalg.norm(out_a.astype(jnp.float32))
                  * jnp.linalg.norm(cot)) ** 0.5
    ident = abs(lhs - rhs) / max(abs(lhs), scale)
    check(ident <= DROPOUT_IDENTITY_TOL,
          f"dropout: <out,cot>={lhs} but <v,dv>={rhs}: forward and "
          f"backward masks disagree")
    seen["flash_dropout"] = {"identity_rel_err": round(ident, 5),
                             "deterministic": True}

    # flash, key-padding bias: the BERT path, real padding in the mask
    B, _, S, _ = bias_shape
    q, k, v = _qkv(rng, bias_shape)
    valid = _padding(rng, B, S)
    mask = jnp.asarray(valid)[:, None, None, :]
    cot = jnp.asarray(rng.standard_normal(bias_shape)
                      * valid[:, None, :, None], jnp.float32)
    seen["flash_key_bias"] = _compare(
        lambda q, k, v: scaled_dot_product_attention(
            q, k, v, mask=mask, use_pallas=True),
        lambda q, k, v: scaled_dot_product_attention(
            q, k, v, mask=mask, use_pallas=False),
        q, k, v, cot, rows=jnp.asarray(valid))

    # block-sparse, with and without key padding, against the XLA path
    B, H, S, _ = sparse_shape
    layout = np.asarray(FixedSparsityConfig(
        num_heads=H, block=sparse_block).make_layout(S))
    q, k, v = _qkv(rng, sparse_shape)
    valid = _padding(rng, B, S)
    for name, kpm, rows in (("block_sparse", None, None),
                            ("block_sparse_key_bias",
                             jnp.asarray(valid, jnp.float32),
                             jnp.asarray(valid))):
        cot = rng.standard_normal(sparse_shape)
        if rows is not None:
            cot = cot * valid[:, None, :, None]
        cot = jnp.asarray(cot, jnp.float32)

        def sparse(use_pallas):
            return lambda q, k, v: block_sparse_attention(
                q, k, v, layout, sparse_block, key_padding_mask=kpm,
                key_padding_mask_mode="mul", use_pallas=use_pallas)

        seen[name] = _compare(sparse(True), sparse(False), q, k, v, cot,
                              rows=rows)
    return {"tolerance": KERNEL_TOL, "rel_err": seen,
            "shapes": {"flash_causal": list(causal_shape),
                       "flash_key_bias": list(bias_shape),
                       "block_sparse": list(sparse_shape)
                       + [f"block {sparse_block}"],
                       "paged_decode_attn": list(paged_shape),
                       "mhc_mixes": list(mhc_shape),
                       "window_prefill": [list(s) for s in window_shapes]}}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def _train_config(micro_batch, gas, mesh):
    n_data = mesh["data"]
    return {
        "train_batch_size": micro_batch * gas * n_data,
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "mesh": dict(mesh, allow_partial=True),
        "steps_per_print": 10 ** 9,
    }


def _lm_batch(seed, vocab, gas, rows, seq):
    ids = np.random.default_rng(seed).integers(0, vocab, (gas, rows, seq))
    return {"input_ids": ids, "labels": ids.copy()}


def _memory(dev):
    stats = dev.memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit") if k in stats}


def phase_train(seed=0, *, cfg=None, micro_batch=MICRO_BATCH, seq=SEQ,
                steps=5, expect_flash=True):
    cfg = cfg or gpt2_350m(**TRAINED)
    model = GPT2Model(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config_params=_train_config(
            micro_batch, 1, {"data": 1, "model": 1, "pipe": 1}))
    batch = _lm_batch(seed, cfg.vocab_size, 1, micro_batch, seq)

    t0 = time.perf_counter()
    first = engine.train_batch(batch=batch)
    first.block_until_ready()
    compile_s = time.perf_counter() - t0

    losses, step_s = [float(first)], []
    with CompilationCounter() as recompiles:
        for _ in range(steps - 2):
            t0 = time.perf_counter()
            loss = engine.train_batch(batch=batch)
            loss.block_until_ready()
            step_s.append(time.perf_counter() - t0)
            losses.append(float(loss))
        # the same step, closed by the transfer instead: a driver that
        # reads the loss back times this way, so the two must agree
        t0 = time.perf_counter()
        last = float(jax.device_get(engine.train_batch(batch=batch)))
        device_get_step_s = time.perf_counter() - t0
        losses.append(last)
    check(np.isfinite(losses).all(), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(recompiles.count == 0,
          f"{recompiles.count} compilations after the first step")

    # a silent fall to the jnp attention path must fail the phase: the
    # compiled fused step has to hold the flash kernels
    hlo = engine.program_registry.get("fused_train_step").hlo()
    kernels = hlo.count("tpu_custom_call")
    if expect_flash:
        check(kernels >= 3, f"the fused step's HLO holds {kernels} "
                            f"tpu_custom_call(s): flash fwd + 2 bwd missing")
    n_params = model.num_params(engine.state.params)
    mem = _memory(jax.devices()[0])
    del engine
    gc.collect()
    return {"model": f"{cfg.n_layer}L/{cfg.n_embd}h/{cfg.n_head}heads "
                     f"vocab {cfg.padded_vocab_size}",
            "params_m": round(n_params / 1e6, 1),
            "micro_batch": micro_batch, "seq": seq, "losses": losses,
            "compilations_after_first_step": recompiles.count,
            "tpu_custom_calls_in_fused_step": kernels,
            "memory": mem,
            "info_compile_s": round(compile_s, 2),
            "info_step_s_block_until_ready": [round(s, 4) for s in step_s],
            "info_step_s_device_get": round(device_get_step_s, 4)}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _serve_setup(seed, cfg, n_requests, prompt_range):
    """Seeded weights and prompts.  Prompt lengths come in pairs, so the
    reference compiles one generate() program per pair, not per request."""
    model = GPT2Model(cfg)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (1, 8))
    params = model.init(jax.random.PRNGKey(seed),
                        {"input_ids": ids, "labels": ids})
    lengths = rng.integers(prompt_range[0], prompt_range[1] + 1,
                           size=(n_requests + 1) // 2)
    lengths = np.tile(lengths, 2)[:n_requests]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    return model, params, prompts


def _serve_requests(engine, prompts, new_tokens):
    """Submit staggered (one arrival per engine step), serve to the end;
    returns the token arrays in submission order."""
    rids = []
    for p in prompts:
        rids.append(engine.submit(p, max_new_tokens=new_tokens))
        engine.step()
    engine.serve()
    for rid in rids:
        status = engine.results[rid]["status"]
        check(status == "finished", f"request {rid} ended {status!r}")
    return [np.asarray(engine.result(rid)) for rid in rids]


def _reference_tokens(model, params, prompts, new_tokens):
    by_len = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(len(p), []).append(i)
    out = [None] * len(prompts)
    for idx in by_len.values():
        rows = generate(model, params, np.stack([prompts[i] for i in idx]),
                        new_tokens)
        for i, row in zip(idx, rows):
            out[i] = np.asarray(row)
    return out


def _bf16_step(x):
    """Spacing of bf16 values at the magnitude of ``x``."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 2.0 ** -100))) - 7)


def _token_agreement(model, params, served, reference, prompts):
    """Holds the served tokens to the reference's (see TIE_STEPS above).
    The referee is the training forward of the served sequences, teacher
    forced, its logits rounded to bf16 (one padded batch: causal attention
    keeps the padding out of what counts).  Up to a request's first
    divergence that is the common prefix of both sequences."""
    for i, (got, ref) in enumerate(zip(served, reference)):
        check(got.shape == ref.shape, f"request {i}: {got.shape} tokens "
                                      f"served, {ref.shape} expected")
        check((got[:len(prompts[i])] == prompts[i]).all(),
              f"request {i}: prompt echoed wrongly")
    ids = np.zeros((2, len(served), max(map(len, served))), np.int32)
    for i, (got, ref) in enumerate(zip(served, reference)):
        ids[0, i, :len(got)] = got
        ids[1, i, :len(ref)] = ref

    @jax.jit
    def referee(params, ids, other):
        logits = jax.lax.reduce_precision(
            model.module.apply({"params": params}, ids[:, :-1],
                               train=False).astype(jnp.float32),
            exponent_bits=8, mantissa_bits=7)

        def logit_of(tokens):                # row j scores token j + 1
            return jnp.take_along_axis(
                logits, tokens[:, 1:, None], axis=-1)[..., 0]

        return jnp.max(logits, axis=-1), logit_of(ids), logit_of(other)

    best, of_served, of_reference = map(np.asarray,
                                        referee(params, ids[0], ids[1]))
    steps = _bf16_step(best)
    below_best = (best - of_served) / steps
    apart = np.abs(of_served - of_reference) / steps
    divergences, failures, worst = [], [], 0.0
    for i, (got, ref) in enumerate(zip(served, reference)):
        rows = slice(len(prompts[i]) - 1, len(got) - 1)
        own = below_best[i, rows]
        worst = max(worst, float(own.max()))
        if own.max() > NEAR_BEST_STEPS:
            failures.append(
                f"request {i}: served token {rows.start + 1 + own.argmax()}"
                f" lies {own.max():.2f} bf16 steps below the best logit of "
                f"the training forward (allowed {NEAR_BEST_STEPS})")
        differ = np.flatnonzero(got != ref)
        if differ.size:
            d = int(differ[0])
            divergences.append({"request": i, "token": d,
                                "steps_apart": float(apart[i, d - 1])})
            if apart[i, d - 1] > TIE_STEPS:
                failures.append(
                    f"request {i}: first differs from the reference at "
                    f"token {d}, where the two candidates lie "
                    f"{apart[i, d - 1]:.2f} bf16 steps apart (a tie is "
                    f"{TIE_STEPS})")
    seen = {"identical_to_reference": len(served) - len(divergences),
            "of": len(served), "first_divergences": divergences,
            "worst_steps_below_best": worst,
            "allowed_steps": {"tie_at_divergence": TIE_STEPS,
                              "below_best": NEAR_BEST_STEPS}}
    check(not failures, f"{failures}; saw {seen}")
    return seen


def _engine_kwargs(prompt_range, new_tokens, kv_block_size, prefill_chunk):
    """InferenceEngine(max_slots=8) with a pool that holds every request at
    its longest, so nothing is evicted."""
    return dict(max_slots=8, kv_block_size=kv_block_size,
                prefill_chunk=prefill_chunk,
                max_blocks_per_seq=-(-(prompt_range[1] + new_tokens)
                                     // kv_block_size))


def phase_serve(seed=0, *, cfg=None, n_requests=8, prompt_range=(128, 512),
                new_tokens=32, kv_block_size=16, prefill_chunk=128):
    before = _memory(jax.devices()[0])
    model, params, prompts = _serve_setup(
        seed, cfg or gpt2_350m(**SERVED), n_requests, prompt_range)
    engine = InferenceEngine(model, params, **_engine_kwargs(
        prompt_range, new_tokens, kv_block_size, prefill_chunk))
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    with CompilationCounter() as recompiles:
        served = _serve_requests(engine, prompts, new_tokens)
    serve_s = time.perf_counter() - t0
    check(recompiles.count == 0,
          f"{recompiles.count} compilations after warmup()")
    report = engine.serving_report()

    t0 = time.perf_counter()
    reference = _reference_tokens(model, params, prompts, new_tokens)
    reference_s = time.perf_counter() - t0
    agreement = _token_agreement(model, params, served, reference, prompts)
    return {"requests": n_requests,
            "prompt_lengths": [len(p) for p in prompts],
            "new_tokens": new_tokens, "finished": len(served),
            "tokens_vs_generate": agreement,
            "compilations_after_warmup": recompiles.count,
            "kv_blocks": report["config"]["kv_blocks"],
            "prefill_chunk": prefill_chunk,
            "memory_before": before,
            "memory": _memory(jax.devices()[0]),
            "info_warmup_s": round(warmup_s, 2),
            "info_serve_s": round(serve_s, 3),
            "info_generate_reference_s": round(reference_s, 2),
            "info_ttft_s": report["ttft_s"], "info_tpot_s": report["tpot_s"]}


# ---------------------------------------------------------------------------
# --chips 4: what exists only across chips
# ---------------------------------------------------------------------------
def _placement(tree):
    """Per device id, the bytes of ``tree``'s addressable shards there."""
    out = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return {str(d): out[d] for d in sorted(out)}


def leg_zero2_data4(seed=0, *, cfg=None, micro_batch=MICRO_BATCH, seq=SEQ,
                    steps=3):
    """ZeRO-2 over mesh data=4 against the same global batch on a mesh of
    jax.devices()[:1] (gradient accumulation 4), one after the other."""
    cfg = cfg or gpt2_350m(**TRAINED)
    ids = _lm_batch(seed, cfg.vocab_size, 1, 4 * micro_batch, seq)
    runs = {}
    for name, n_data, gas in (("data4", 4, 1), ("one_device", 1, 4)):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg), config_params=_train_config(
                micro_batch, gas, {"data": n_data, "model": 1, "pipe": 1}))
        batch = {k: v.reshape(gas, -1, seq) for k, v in ids.items()}
        losses = [float(jax.device_get(engine.train_batch(batch=batch)))
                  for _ in range(steps)]
        runs[name] = {"losses": losses}
        if n_data == 4:
            state = engine.state
            place = {"optimizer_state": _placement(state.opt_state),
                     "grad_accumulator": _placement(state.accum),
                     "params": _placement(state.params)}
            for what in ("optimizer_state", "grad_accumulator"):
                sizes = place[what]
                check(len(sizes) == 4, f"{what} lives on devices "
                                       f"{list(sizes)}, not on four")
                # ZeRO-2 shards, not replicas: no device holds the lot
                check(max(sizes.values()) < 0.5 * sum(sizes.values()),
                      f"{what} is not spread over the mesh: {sizes}")
            runs[name]["shard_bytes_by_device"] = place
            runs[name]["memory_by_device"] = {
                str(d.id): _memory(d) for d in jax.devices()[:4]}
        del engine
        gc.collect()
    a, b = runs["data4"]["losses"], runs["one_device"]["losses"]
    check(np.isfinite(a + b).all(), f"non-finite loss: {a} {b}")
    check(np.allclose(a, b, rtol=LOSS_RTOL),
          f"data=4 losses {a} != one-device losses {b} (rtol {LOSS_RTOL})")
    return dict(runs, loss_rtol=LOSS_RTOL)


def leg_pipeline(seed=0, *, cfg=None, micro_batch=2, seq=SEQ, gas=2,
                 expect_flash=True):
    """One 1F1B step of PipelineEngine at pipe 2 x model 2 against the
    same module, seed and batch run as one stage on one device."""
    # gpt2-350m widths; depth cut to 8 so the stage programs of both
    # engines compile inside the call
    cfg = cfg or gpt2_350m(n_layer=8, loss_chunk_tokens=0)
    batch = _lm_batch(seed, cfg.vocab_size, gas, micro_batch, seq)
    losses = {}
    for name, pipe, tp in (("pipe2_model2", 2, 2), ("one_stage", 1, 1)):
        config = _train_config(micro_batch, gas,
                               {"pipe": pipe, "data": 1, "model": tp})
        config["zero_optimization"] = {"stage": 1}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=gpt2_pipeline_module(cfg, partition_method="uniform"),
            config_params=config)
        losses[name] = float(engine.train_batch(batch=batch))
        if pipe == 2:
            # PipelineEngine compiles such stages in the process: read
            # back from the persistent cache they halt chips 2 and 3
            cache_on = bool(jax.config.jax_enable_compilation_cache
                            and jax.config.jax_compilation_cache_dir)
            check(not cache_on or jax.devices()[0].platform != "tpu",
                  "PipelineEngine left the persistent compile cache on")
            stage_devices = [sorted({
                d.id for leaf in jax.tree_util.tree_leaves(st.params)
                for d in leaf.devices()}) for st in engine.stage_states]
            check(len(set(map(tuple, stage_devices))) == 2,
                  f"both stages on the same devices: {stage_devices}")
            # heads are split over 'model' here: the kernel has to be in
            # the stage's own program, mapped over its two chips
            kernels = engine.program_registry.get("chunk0:fwd").hlo() \
                .count("tpu_custom_call")
            if expect_flash:
                check(kernels >= 1, "no flash kernel in stage 0's forward")
        del engine
        gc.collect()
    a, b = losses["pipe2_model2"], losses["one_stage"]
    check(np.isfinite([a, b]).all(), f"non-finite loss: {losses}")
    check(np.isclose(a, b, rtol=LOSS_RTOL),
          f"pipelined loss {a} != one-stage loss {b} (rtol {LOSS_RTOL})")
    return {"losses": losses, "loss_rtol": LOSS_RTOL,
            "stage_devices": stage_devices,
            "tpu_custom_calls_in_stage0_forward": kernels,
            "persistent_compile_cache_on_after_engine": cache_on,
            "layers": cfg.n_layer,
            "schedule": "1f1b", "micro_batches": gas}


def leg_serve_shards(seed=0, *, cfg=None, n_requests=8,
                     prompt_range=(128, 512), new_tokens=32,
                     kv_block_size=16, prefill_chunk=128):
    """Serving with shards=4 over a four-device mesh against shards=1, and
    where a FleetRouter's replicas put their params and pools."""
    from jax.sharding import Mesh

    model, params, prompts = _serve_setup(
        seed, cfg or gpt2_350m(**SERVED), n_requests, prompt_range)
    kw = _engine_kwargs(prompt_range, new_tokens, kv_block_size,
                        prefill_chunk)
    tokens, pools = {}, {}
    for shards in (4, 1):
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",)) \
            if shards > 1 else None
        engine = InferenceEngine(model, params, shards=shards, mesh=mesh,
                                 **kw)
        engine.warmup()
        with CompilationCounter() as recompiles:
            tokens[shards] = _serve_requests(engine, prompts, new_tokens)
        check(recompiles.count == 0, f"shards={shards}: "
              f"{recompiles.count} compilations after warmup()")
        pools[shards] = _placement(engine.pool.tensors.k)
        del engine
        gc.collect()
    check(len(pools[4]) == 4, f"shards=4 pool lives on {list(pools[4])}")
    agreement = _token_agreement(model, params, tokens[4], tokens[1],
                                 prompts)

    # finding 6, written down and not changed here: serving/fleet.py places
    # nothing, so every in-process replica lands on the default device
    router = FleetRouter(model, params, replicas=2, engine_kwargs=kw)
    fleet = [{"replica": i,
              "params_devices": sorted(_placement(r.engine.params)),
              "pool_devices": sorted(_placement(r.engine.pool.tensors.k))}
             for i, r in enumerate(router.replicas)]
    return {"tokens_shards4_vs_shards1": agreement,
            "pool_k_bytes_by_device": {str(s): p for s, p in pools.items()},
            "fleet_replica_placement": fleet}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def run_phase(name, fn, *args, **kw):
    """Run one phase, print its JSON line; returns (ok, result)."""
    t0 = time.perf_counter()
    try:
        result = fn(*args, **kw)
        line = {"phase": name, "ok": True,
                "seconds": round(time.perf_counter() - t0, 2), **result}
    except Exception as e:  # report the phase as failed, run the next
        traceback.print_exc(file=sys.stderr)
        result = None
        line = {"phase": name, "ok": False,
                "seconds": round(time.perf_counter() - t0, 2),
                "error": f"{type(e).__name__}: {e}"[:2000]}
    print(json.dumps(line), flush=True)
    return line["ok"], result


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the legs that need four chips")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    ok, device = run_phase("device", phase_device, args.chips)
    if not ok:
        return 1
    if args.chips == 4:
        # the pipeline leg last: its engine turns the persistent compile
        # cache off for the rest of the process
        phases = [("zero2_data4", leg_zero2_data4),
                  ("serve_shards4", leg_serve_shards),
                  ("pipeline_pipe2_model2", leg_pipeline)]
    else:
        phases = [("kernels", phase_kernels), ("train", phase_train),
                  ("serve", phase_serve)]
    failed = [name for name, fn in phases
              if not run_phase(name, fn, args.seed)[0]]
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
