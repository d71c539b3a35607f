"""Motif 3 (``model_type: Motif``, Motif Technologies) text decoder, for
serving.

The layer follows the published ``config.json`` keys.  Where they do not
settle a reading, ``benchmark/configs/motif-3-beta-ep8.json`` names it under
``assumed``; the equations stand in ``benchmark/architectures/motif.py``.

- Residual path (mHC, ``mhc_expansion_rate`` streams): the model carries
  ``X (B, T, n * E)``, n = 4 streams side by side, its OWN affair: the
  serving engine reads ``B, T, _ = x.shape`` and nothing else of it.  Every
  sublayer F (attention, then feed-forward) reads ``u = sum_i H_pre[i] X[i]``
  and writes ``X = H_res X + H_post (x) F(RMSNorm(u))``, the three mixes
  computed from the token's own streams, ``H_res`` (n x n) made doubly
  stochastic by ``mhc_sinkhorn_iters`` Sinkhorn iterations:
  :func:`mhc_pre`, :func:`mhc_post`, each ONE Pallas kernel over blocks of
  rows of the streams (``ops/transformer/mhc_mix.py``: f32 inside, no f32
  copy of X in HBM); the equations stand in the benchmark's reference.
- Attention (GDLA): LATENT attention (``models/latent_attention.py``, the
  family's one function) whose ``kv_b`` up-projects the latent to
  ``num_key_value_heads`` key/value heads that the ``num_attention_heads``
  query heads share, ``num_noise_heads`` of them NOISE heads: the heads come
  back unprojected, each signal head loses ``lambda`` times its group's noise
  head (``diff_v2``: after the softmax . V, lambda a sigmoid of a projection
  of the token), a sigmoid gate of the attention's width follows, then
  ``o``.  The program holds its heads GROUPED: key/value head g's four signal
  heads and then its noise head, so that query head h reads key/value head
  ``h // 5`` in both kernels and no key is repeated.  ``layers l`` with
  ``l % sliding_window_period == period - 1`` see everything, the others
  their last ``sliding_window`` positions; WHAT IS CACHED is the raw latent
  row ``[c_kv | k_r]`` in TWO cache groups (``cache_groups``): the full
  layers' keep every position, the sliding layers' the pages a window can
  still see.
- Feed-forward: ``down(PolyNorm(x W_gate) * (x W_up))``; the first
  ``n_dense_first_layers`` layers dense, the others routed
  (``moe/dropless.py``: a sigmoid's scores, the ``experts_top_k`` largest
  renormalised and scaled by ``route_scale``, over the experts this chip
  holds) beside a shared expert.  Every expert has its own PolyNorm.

The model enters the serving engine through the decoder-block contract
(``serving/decoder.py``): :class:`MotifDecoder`, whose block is the whole
held STAGE (``layers_held``): runs of layers of one kind (dense or routed,
sliding or full) are a ``lax.scan`` each, so a kernel is one operation a
KIND of layer in the compiled program.  The multi-token-prediction head
(``num_nextn_predict_layers``) is not served and its weights are not held.
There is no training path here (``moe/dropless.py`` is forward only).
"""
import dataclasses
import itertools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.latent_attention import (_rms_norm,
                                                   latent_attention)
from deepspeed_tpu.moe.dropless import STAT_NAMES, dropless_moe
from deepspeed_tpu.moe.grouped_matmul import KERNEL_NAME
from deepspeed_tpu.ops.transformer.mhc_mix import (fold_phi, mhc_post_mix,
                                                   mhc_pre_mix)

# what the two attention kernels are called in the compiled program and the
# device trace, with the cache group's name behind
PREFILL_KERNEL = "gdla_prefill_attn"
DECODE_KERNEL = "gdla_paged_decode_attn"
# the model's own counter beside the routed layer's: the largest
# |row or column sum - 1| of H_res after the last Sinkhorn iteration over a
# step's valid tokens, in parts per million, summed over the sublayers
SINKHORN_STAT = "mhc_sinkhorn_err_ppm"


@dataclasses.dataclass(frozen=True)
class MotifConfig:
    vocab_size: int = 220160
    hidden_size: int = 4096
    num_hidden_layers: int = 53
    num_attention_heads: int = 80       # signal and noise heads together
    num_key_value_heads: int = 16
    num_noise_heads: int = 16
    head_dim: int = 192                 # nope | rope
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1280
    num_experts: int = 384
    experts_top_k: int = 8
    num_shared_experts: int = 1
    n_dense_first_layers: int = 2
    route_norm: bool = True
    route_scale: float = 2.0
    score_func: str = "sigmoid"
    sliding_window: int = 128
    sliding_window_period: int = 4
    mhc_expansion_rate: int = 4
    mhc_sinkhorn_iters: int = 20
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    polynorm_output_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    hidden_clamp: float = 1e6
    max_position_embeddings: int = 262144
    # the PUBLISHED indices of the layers this stage holds, ascending (None:
    # all ``num_hidden_layers``); a layer's kind is its published index's
    layers_held: Optional[Tuple[int, ...]] = None
    # (first, count): the routed experts this chip holds
    experts_held: Tuple[int, int] = (0, 384)
    dtype: Any = jnp.bfloat16       # compute AND served-weight dtype
    initializer_range: float = 0.02
    # what the three mixes' scales (alpha) start at
    mhc_alpha_init: float = 0.2
    # rows a tile of the grouped matmul takes, prefill / decode
    moe_tile_rows: int = 128
    moe_tile_rows_decode: int = 16
    # the Pallas kernels' ``interpret=``: None is the kernels' own default
    # (the interpreter on a CPU backend); a compile-only rehearsal for a
    # described chip states False
    pallas_interpret: Optional[bool] = None

    def __post_init__(self):
        held = self.layers_held
        if held is None:
            held = range(self.num_hidden_layers)
        held = tuple(int(l) for l in held)
        object.__setattr__(self, "layers_held", held)
        assert len(held) == self.num_hidden_layers \
            and list(held) == sorted(set(held)), held
        signal = self.num_attention_heads - self.num_noise_heads
        assert self.num_noise_heads == self.num_key_value_heads \
            and signal % self.num_key_value_heads == 0, \
            "one noise head a key/value head, the signal heads shared evenly"
        assert self.num_shared_experts == 1

    def is_full(self, i):
        """Whether held layer ``i`` sees everything."""
        per = self.sliding_window_period
        return self.layers_held[i] % per == per - 1

    def is_dense(self, i):
        return self.layers_held[i] < self.n_dense_first_layers

    # what ``latent_attention`` reads
    @property
    def qk_nope_head_dim(self):
        return self.head_dim - self.qk_rope_head_dim

    # what the serving engine reads of any configuration
    @property
    def n_layer(self):
        return self.num_hidden_layers

    @property
    def n_positions(self):
        return self.max_position_embeddings

    @property
    def cache_rows(self):
        """Widths of the rows a layer caches a token: one latent row."""
        return (self.kv_lora_rank + self.qk_rope_head_dim,)

    cache_kind = "rows"     # raw rows, no (keys, values) of heads in them

    @property
    def cache_groups(self):
        """``(name, layers, window)``: the full layers keep every position,
        the sliding layers a window (the first group keeps everything)."""
        n_full = sum(self.is_full(i) for i in range(self.num_hidden_layers))
        assert 0 < n_full < self.num_hidden_layers, self.layers_held
        return (("full", n_full, None),
                ("window", self.num_hidden_layers - n_full,
                 self.sliding_window))

    def decoder(self):
        return MotifDecoder(self)


def _runs(cfg):
    """The held layers as runs of one kind: ``(dense, full, first, count,
    first_of_kind, first_in_group)``, ``first_of_kind`` counted among the
    dense or the routed layers, ``first_in_group`` within the cache
    group."""
    kinds = [(cfg.is_dense(i), cfg.is_full(i))
             for i in range(cfg.num_hidden_layers)]
    runs, first = [], 0
    for (dense, full), run in itertools.groupby(kinds):
        count = len(list(run))
        before = kinds[:first]
        runs.append((dense, full, first, count,
                     sum(d == dense for d, _ in before),
                     sum(f == full for _, f in before)))
        first += count
    return runs


# ---------------------------------------------------------------------------
# the pieces that are Motif's own
# ---------------------------------------------------------------------------
def poly_norm(z, w, cfg):
    """``scale * (sum_i w_i z^i / sqrt(mean(z^(2i)) + eps) + clip(b))`` over
    the last dim, i = 1..3; z f32 (..., I); w (..., 4) = [w1, w2, w3, b]
    broadcast against z's leading dims."""
    w = w.astype(jnp.float32)
    out, power = 0.0, 1.0
    for i in range(3):
        power = power * z
        out = out + w[..., i:i + 1] * power * jax.lax.rsqrt(
            jnp.mean(jnp.square(power), -1, keepdims=True)
            + cfg.rms_norm_eps)
    clamp = cfg.polynorm_bias_clamp
    return cfg.polynorm_output_scale * (
        out + jnp.clip(w[..., 3:4], -clamp, clamp))


def _poly_ffn(cfg, x, gate_up, down, poly):
    """``down(PolyNorm(x W_gate) * (x W_up))`` over x (N, E)."""
    z = (x @ gate_up).astype(jnp.float32)
    inner = down.shape[0]
    h = poly_norm(z[:, :inner], poly, cfg) * z[:, inner:]
    return h.astype(x.dtype) @ down


def mhc_pre(cfg, p, X):
    """The three mixes of one sublayer from the token's own streams, and
    what the sublayer reads.  X (N, n E), the streams side by side; p:
    ``norm`` (n E,), ``phi`` (n E, 2 n + n n), ``beta`` (2 n + n n,),
    ``alpha`` (3,).  Returns u (N, E) in X's dtype, H_post (N, n) f32,
    H_res (N, n n) f32 (row i, column j at ``i n + j``) and the Sinkhorn
    error (N,): ONE kernel over blocks of rows of X
    (``ops/transformer/mhc_mix.py``), f32 inside, the norm's weight folded
    into ``phi`` and its rsqrt taken out of the product."""
    n = cfg.mhc_expansion_rate
    with jax.named_scope("mhc_pre"):
        folded, consts = fold_phi(p["norm"], p["phi"], p["alpha"],
                                  p["beta"], n)
        return mhc_pre_mix(X, folded, consts, n=n,
                           iters=cfg.mhc_sinkhorn_iters,
                           eps=cfg.rms_norm_eps,
                           interpret=cfg.pallas_interpret)


def mhc_post(cfg, X, y, h_post, h_res):
    """``X = H_res X + H_post (x) y``, clipped at ``hidden_clamp``.  X
    (N, n E), y (N, E), H_post (N, n), H_res (N, n n) -> (N, n E) in X's
    dtype: the second kernel of ``ops/transformer/mhc_mix.py``."""
    with jax.named_scope("mhc_post"):
        return mhc_post_mix(X, y, h_post, h_res, clamp=cfg.hidden_clamp,
                            interpret=cfg.pallas_interpret)


def _rope_cos_sin(cfg, positions):
    """Plain rotary of base ``rope_theta`` over the rotary part."""
    dim = cfg.qk_rope_head_dim
    inv_freq = 1.0 / cfg.rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angles), jnp.sin(angles)


# one layer's matrices outside its feed-forward: name -> shape
def _layer_shapes(cfg):
    E, H, n = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.mhc_expansion_rate
    R, Dr, Dv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    signal = H - cfg.num_noise_heads
    return {
        "q_a": (E, cfg.q_lora_rank),
        "q_b": (cfg.q_lora_rank, H * cfg.head_dim),
        "kv_a": (E, R + Dr),
        "kv_b": (R, cfg.num_key_value_heads
                 * (cfg.qk_nope_head_dim + Dv)),
        "lam": (E, signal),
        "gate": (E, signal * Dv),
        "o": (signal * Dv, E),
        "mhc_phi": (2, n * E, 2 * n + n * n),
    }


def _draw(cfg, key, shape):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg.initializer_range).astype(cfg.dtype)


def _stacked(cfg, key, shapes, L):
    """Every matrix of ``shapes`` drawn for ``L`` layers, stacked (L, ...),
    in one jitted call."""
    @jax.jit
    def draw(key):
        keys = jax.random.split(key, L * len(shapes)) \
            .reshape(L, len(shapes), -1)
        return {name: jnp.stack([_draw(cfg, keys[l, i], shape)
                                 for l in range(L)])
                for i, (name, shape) in enumerate(shapes.items())}
    return draw(key) if L else {}


class MotifModel:
    """``config`` and seeded ``init``: what ``InferenceEngine`` and the
    tests need of a model.  The tree: ``embed``, ``norm``, ``head``;
    ``layers``: every matrix outside the feed-forwards STACKED by held layer
    (L, ...), ``q_b``'s heads grouped (the module docstring), the two
    sublayers' mHC leaves stacked (L, 2, ...); ``dense``: ``gate_up``,
    ``down``, ``poly`` of the dense layers; ``routed``: ``router`` and the
    shared expert of the routed layers; ``experts``: ``gate_up``
    (Lr * held, E, 2 I), ``down`` (Lr * held, I, E), ``poly`` (Lr * held, 4),
    all routed layers' held experts in ONE tensor each, which the grouped
    matmul indexes where they lie.  Weights normal(0, initializer_range) in
    ``cfg.dtype``, norms at one, PolyNorm at (1/3, 1/3, 1/3, 0), the mixes'
    alpha at ``mhc_alpha_init`` and beta at zero; the experts a layer a
    jitted call, in place."""

    def __init__(self, config: MotifConfig):
        self.config = config

    def init(self, rng, batch=None):
        cfg = self.config
        L, E, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
        n, I = cfg.mhc_expansion_rate, cfg.moe_intermediate_size
        held = cfg.experts_held[1]
        Ld = sum(cfg.is_dense(i) for i in range(L))
        Lr = L - Ld
        k_embed, k_head, k_layers, k_dense, k_routed, k_experts = \
            jax.random.split(rng, 6)
        ones = lambda *shape: jnp.ones(shape, cfg.dtype)       # noqa: E731
        poly = lambda *lead: jnp.broadcast_to(                 # noqa: E731
            jnp.asarray([1 / 3, 1 / 3, 1 / 3, 0.0], cfg.dtype), lead + (4,))

        fill = jax.jit(
            lambda buf, key, l: jax.lax.dynamic_update_slice(
                buf, _draw(cfg, key, (held,) + buf.shape[1:]),
                (l * held, 0, 0)), donate_argnums=0)

        def experts(key, shape):
            buf = jnp.zeros((Lr * held,) + shape, cfg.dtype)
            for l, k in enumerate(jax.random.split(key, max(Lr, 1))[:Lr]):
                buf = fill(buf, k, l)
            return buf

        layers = _stacked(cfg, k_layers, _layer_shapes(cfg), L)
        layers.update(
            attn_norm=ones(L, E), ffn_norm=ones(L, E),
            q_a_norm=ones(L, cfg.q_lora_rank),
            kv_a_norm=ones(L, cfg.kv_lora_rank),
            mhc_norm=ones(L, 2, n * E),
            mhc_beta=jnp.zeros((L, 2, 2 * n + n * n), cfg.dtype),
            mhc_alpha=jnp.full((L, 2, 3), cfg.mhc_alpha_init, cfg.dtype))
        dense = _stacked(cfg, k_dense, {
            "gate_up": (E, 2 * cfg.intermediate_size),
            "down": (cfg.intermediate_size, E)}, Ld)
        dense["poly"] = poly(Ld)
        routed = _stacked(cfg, k_routed, {
            "router": (E, cfg.num_experts),
            "shared_gate_up": (E, 2 * I), "shared_down": (I, E)}, Lr)
        routed["shared_poly"] = poly(Lr)
        k_gate_up, k_down = jax.random.split(k_experts)
        draw = jax.jit(lambda k, shape: _draw(cfg, k, shape),
                       static_argnums=1)
        return {"embed": draw(k_embed, (V, E)), "norm": ones(E),
                "head": draw(k_head, (E, V)), "layers": layers,
                "dense": dense, "routed": routed,
                "experts": {"gate_up": experts(k_gate_up, (E, 2 * I)),
                            "down": experts(k_down, (I, E)),
                            "poly": poly(Lr * held)}}


class MotifDecoder:
    """Motif under the serving engine's decoder-block contract: ONE block,
    the whole held stage, over ``x (B, T, n * E)``."""

    stat_names = STAT_NAMES + (SINKHORN_STAT,)
    scan_layers = False     # the stage's runs of layers scan themselves
    n_layer = 1

    def __init__(self, cfg):
        self.cfg = cfg
        self.dtype = cfg.dtype
        self.runs = _runs(cfg)

    def hold(self, params):
        from deepspeed_tpu.serving.decoder import held_as

        return held_as(params, self.dtype)

    def embed(self, params, tokens, positions):
        # the streams start as copies; positions enter by RoPE
        return jnp.tile(params["embed"][tokens],
                        (1,) * tokens.ndim + (self.cfg.mhc_expansion_rate,))

    def final_norm(self, params, x):
        n = self.cfg.mhc_expansion_rate
        x = jnp.sum(x.reshape(*x.shape[:-1], n, -1).astype(jnp.float32),
                    axis=-2).astype(x.dtype)
        return _rms_norm(x, params["norm"], self.cfg.rms_norm_eps)

    def logits(self, params, xe):
        return jnp.dot(xe, params["head"],
                       preferred_element_type=jnp.float32)

    # -- the stage ------------------------------------------------------
    def block(self, params, _, x, cache):
        cos_sin = _rope_cos_sin(self.cfg, cache.positions)
        stats = jnp.zeros(len(self.stat_names), jnp.int32)
        for run in self.runs:
            x, stats = self._run(params, run, x, stats, cache, cos_sin)
        return x, stats

    def _run(self, params, run, x, stats, cache, cos_sin):
        """A run of layers of one kind: the layer itself where it is one,
        else ONE traced layer under ``lax.scan``, the run's cache group
        carried through it."""
        dense, full, first, count, of_kind, in_group = run
        group = "full" if full else "window"

        def layer(x, stats, i):
            x, row = self._layer(params, first + i, of_kind + i, dense, x,
                                 cache.at(group, in_group + i), cos_sin)
            return x, stats + row

        if count == 1:
            return layer(x, stats, 0)

        def step(carry, i):
            x, stats, held = carry
            cache.restore(group, held)
            x, stats = layer(x, stats, i)
            return (x, stats, cache.carry(group)), None

        (x, stats, held), _ = jax.lax.scan(
            step, (x, stats, cache.carry(group)), jnp.arange(count))
        cache.restore(group, held)
        return x, stats

    def _layer(self, params, l, k, dense, x, cache, cos_sin):
        """Held layer ``l`` (the ``k``-th of its kind, dense or routed):
        attention, then the feed-forward, each between its mixes.  Returns
        x and the layer's ``stat_names`` row."""
        cfg = self.cfg
        B, T, _ = x.shape
        n, E = cfg.mhc_expansion_rate, cfg.hidden_size
        lp = {name: leaf[l] for name, leaf in params["layers"].items()}
        valid = cache.row_valid
        X = x.reshape(B * T, n * E)
        worst = jnp.float32(0.0)
        for s, norm in enumerate(("attn_norm", "ffn_norm")):
            mix = {name[4:]: lp[name][s] for name in
                   ("mhc_norm", "mhc_phi", "mhc_beta", "mhc_alpha")}
            u, h_post, h_res, err = mhc_pre(cfg, mix, X)
            h = _rms_norm(u, lp[norm], cfg.rms_norm_eps).reshape(B, T, E)
            if s == 0:
                y = self._attention(lp, h, cache, cos_sin)
            else:
                y, routed = self._ffn(params, k, dense, h, valid)
            X = mhc_post(cfg, X, y.reshape(B * T, E), h_post, h_res)
            if valid is not None:
                err = jnp.where(valid.reshape(-1), err, 0.0)
            worst = worst + jnp.max(err)
        ppm = jnp.round(worst * 1e6).astype(jnp.int32)
        return X.reshape(B, T, n * E), jnp.concatenate([routed, ppm[None]])

    def _ffn(self, params, k, dense, x, valid):
        """The ``k``-th dense or routed feed-forward over x (B, T, E), and
        the routed layer's ``STAT_NAMES`` row (zeros for a dense one)."""
        cfg = self.cfg
        B, T, E = x.shape
        rows = x.reshape(B * T, E)
        if dense:
            dp = {name: leaf[k] for name, leaf in params["dense"].items()}
            return _poly_ffn(cfg, rows, dp["gate_up"], dp["down"],
                             dp["poly"]).reshape(B, T, E), \
                jnp.zeros(len(STAT_NAMES), jnp.int32)
        rp = {name: leaf[k] for name, leaf in params["routed"].items()}
        experts = params["experts"]
        decode = T == 1
        inner = cfg.moe_intermediate_size

        def activation(gate_up, matrix):
            return poly_norm(gate_up[:, :inner], experts["poly"][matrix],
                             cfg) * gate_up[:, inner:]

        routed, stats = dropless_moe(
            rows, rp["router"], experts,
            top_k=cfg.experts_top_k, experts_held=cfg.experts_held,
            first_matrix=k * cfg.experts_held[1],
            tile_m=cfg.moe_tile_rows_decode if decode
            else cfg.moe_tile_rows,
            kernel_name=KERNEL_NAME + ("_decode" if decode else "_prefill"),
            valid=None if valid is None else valid.reshape(-1),
            norm_topk_prob=cfg.route_norm, scaling=cfg.route_scale,
            score=cfg.score_func, activation=activation,
            interpret=cfg.pallas_interpret)
        shared = _poly_ffn(cfg, rows, rp["shared_gate_up"],
                           rp["shared_down"], rp["shared_poly"])
        return (routed + shared).reshape(B, T, E), stats

    def _attention(self, lp, x, cache, cos_sin):
        cfg = self.cfg
        B, T, _ = x.shape
        Hkv, Dv = cfg.num_key_value_heads, cfg.v_head_dim
        G = cfg.num_attention_heads // Hkv      # a group: signal.., noise
        group = "full" if cache.window is None else "window"
        # without ``o``: the heads come back side by side
        latent = {name: lp[name] for name in (
            "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b")}
        heads = latent_attention(
            cfg, latent, x, cache, q_scale=cfg.head_dim ** -0.5,
            cos=cos_sin[0], sin=cos_sin[1], kv_heads=Hkv,
            prefill_name=f"{PREFILL_KERNEL}_{group}",
            decode_name=f"{DECODE_KERNEL}_{group}")
        heads = heads.reshape(B, T, Hkv, G, Dv).astype(jnp.float32)
        lam = jax.nn.sigmoid((x @ lp["lam"]).astype(jnp.float32)) \
            .reshape(B, T, Hkv, G - 1, 1)
        diff = (heads[:, :, :, :G - 1] - lam * heads[:, :, :, G - 1:]) \
            .reshape(B, T, -1)
        gate = jax.nn.sigmoid((x @ lp["gate"]).astype(jnp.float32))
        return (gate * diff).astype(x.dtype) @ lp["o"]
