"""Mistral 4 (``model_type: mistral4``) text decoder, for serving.

The layer follows the published ``config.json`` keys, which are those of the
DeepSeek-V3 family: pre-RMSNorm blocks of LATENT attention (MLA) and a
ROUTED feed-forward beside a shared expert, no biases, an untied head.

- Attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> H heads of
  (nope | rope); ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
  ``k_r = RoPE(k_r)`` shared by all heads; ``[k_nope | v] = c_kv W_kvb``.
  RoPE is interleaved with YaRN frequencies; the softmax scale carries
  YaRN's ``mscale_all_dim`` squared, and ``llama_4_scaling_beta`` scales
  the query by ``1 + beta ln(1 + floor(pos / original_max))``.  WHAT IS
  CACHED is ``c_kv`` and ``k_r``: ``kv_lora_rank + qk_rope_head_dim`` values
  a token, one raw row, no separate value (``cache_rows``,
  ``cache_kind``).  Prefill expands keys and values from the gathered
  latent rows and attends with the rectangle kernel; decode absorbs
  ``W_kvb`` into the query and the output and attends over the latent rows
  themselves.  Same mathematics, and ONE function for every latent model:
  ``models/latent_attention.py``, which this model calls with its YaRN
  table and its position-dependent query scale.
- Feed-forward: ``moe/dropless.py`` over the experts this chip holds
  (``experts_held``), plus the shared expert once.

The model enters the serving engine through the decoder-block contract
(``serving/decoder.py``): :class:`Mistral4Decoder`.  There is no training
path here: at 16 bytes a parameter the smallest honest cut does not fit a
chip (``benchmark/configs/mistral-small-4-ep4.json``).
"""
import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import rotary
from deepspeed_tpu.models.latent_attention import (_rms_norm, _swiglu,
                                                   latent_attention)
from deepspeed_tpu.moe.dropless import STAT_NAMES, dropless_moe
from deepspeed_tpu.moe.grouped_matmul import KERNEL_NAME


@dataclasses.dataclass(frozen=True)
class Mistral4Config:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    rope_original_max_position_embeddings: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    llama_4_scaling_beta: float = 0.1
    # (first, count): the routed experts this chip holds
    experts_held: Tuple[int, int] = (0, 128)
    dtype: Any = jnp.bfloat16       # compute AND served-weight dtype
    initializer_range: float = 0.02
    # rows a tile of the grouped matmul takes, prefill / decode
    moe_tile_rows: int = 128
    moe_tile_rows_decode: int = 16
    # the Pallas kernels' ``interpret=``: None is the kernels' own default
    # (the interpreter on a CPU backend); a compile-only rehearsal for a
    # described chip states False
    pallas_interpret: Optional[bool] = None

    # what the serving engine reads of any configuration
    @property
    def n_layer(self):
        return self.num_hidden_layers

    @property
    def n_positions(self):
        return self.max_position_embeddings

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_rows(self):
        """Widths of the rows a layer caches a token: one latent row."""
        return (self.kv_lora_rank + self.qk_rope_head_dim,)

    cache_kind = "rows"     # raw rows, no (keys, values) of heads in them

    def decoder(self):
        return Mistral4Decoder(self)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """YaRN's blend of the plain and the interpolated rotary frequencies,
    (qk_rope_head_dim / 2,) float64, as DeepSeek-V3's code computes it
    (``models/rotary.py``, which ``models/mellum.py`` calls too)."""
    return rotary.yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
        cfg.rope_original_max_position_embeddings, cfg.rope_beta_fast,
        cfg.rope_beta_slow)


def softmax_scale(cfg):
    return cfg.qk_head_dim ** -0.5 \
        * _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2


def _rope_cos_sin(cfg, positions):
    angles = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    scale = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


# one layer's matrices outside its routed experts: name -> shape
def _layer_shapes(cfg):
    E, H = cfg.hidden_size, cfg.num_attention_heads
    I, R, Dr = cfg.moe_intermediate_size, cfg.kv_lora_rank, \
        cfg.qk_rope_head_dim
    return {
        "q_a": (E, cfg.q_lora_rank),
        "q_b": (cfg.q_lora_rank, H * cfg.qk_head_dim),
        "kv_a": (E, R + Dr),
        "kv_b": (R, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o": (H * cfg.v_head_dim, E),
        "router": (E, cfg.n_routed_experts),
        "shared_gate_up": (E, 2 * I * cfg.n_shared_experts),
        "shared_down": (I * cfg.n_shared_experts, E),
    }


def _draw(cfg, key, shape):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg.initializer_range).astype(cfg.dtype)


class Mistral4Model:
    """``config`` and seeded ``init``: what ``InferenceEngine`` and the
    tests need of a model.  The tree: ``embed``, ``norm``, ``head``;
    ``layers``: every matrix outside the routed experts STACKED by layer
    (L, ...), so that one traced block serves all layers
    (``Mistral4Decoder.scan_layers``); ``experts``: ``gate_up``
    (L * held, E, 2 I) and ``down`` (L * held, I, E), all layers' held
    experts in ONE tensor each, which the grouped matmul indexes where
    they lie (a stacked tensor sliced by layer would be copied, 1.6 GB a
    layer, in every program).  Weights normal(0, initializer_range) in
    ``cfg.dtype``, norms at one; made a layer a jitted call, in place."""

    def __init__(self, config: Mistral4Config):
        self.config = config

    def init(self, rng, batch=None):
        cfg = self.config
        L, E, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
        held, I = cfg.experts_held[1], cfg.moe_intermediate_size
        k_embed, k_head, k_layers, k_experts = jax.random.split(rng, 4)
        ones = lambda *shape: jnp.ones(shape, cfg.dtype)       # noqa: E731
        shapes = _layer_shapes(cfg)

        @jax.jit
        def dense(key):
            keys = jax.random.split(key, L * len(shapes)) \
                .reshape(L, len(shapes), -1)
            return {name: jnp.stack([_draw(cfg, keys[l, i], shape)
                                     for l in range(L)])
                    for i, (name, shape) in enumerate(shapes.items())}

        fill = jax.jit(
            lambda buf, key, l: jax.lax.dynamic_update_slice(
                buf, _draw(cfg, key, (held,) + buf.shape[1:]),
                (l * held, 0, 0)), donate_argnums=0)

        def experts(key, shape):
            buf = jnp.zeros((L * held,) + shape, cfg.dtype)
            for l, k in enumerate(jax.random.split(key, L)):
                buf = fill(buf, k, l)
            return buf

        k_gate_up, k_down = jax.random.split(k_experts)
        layers = dense(k_layers)
        layers.update(attn_norm=ones(L, E), ffn_norm=ones(L, E),
                      q_a_norm=ones(L, cfg.q_lora_rank),
                      kv_a_norm=ones(L, cfg.kv_lora_rank))
        draw = jax.jit(lambda k, shape: _draw(cfg, k, shape),
                       static_argnums=1)
        return {"embed": draw(k_embed, (V, E)), "norm": ones(E),
                "head": draw(k_head, (E, V)), "layers": layers,
                "experts": {"gate_up": experts(k_gate_up, (E, 2 * I)),
                            "down": experts(k_down, (I, E))}}


class Mistral4Decoder:
    """Mistral 4 under the serving engine's decoder-block contract."""

    stat_names = STAT_NAMES
    scan_layers = True      # one traced block, the weights stacked by layer

    def __init__(self, cfg):
        self.cfg = cfg
        self.dtype = cfg.dtype
        self.n_layer = cfg.num_hidden_layers

    def hold(self, params):
        from deepspeed_tpu.serving.decoder import held_as

        return held_as(params, self.dtype)

    def embed(self, params, tokens, positions):
        return params["embed"][tokens]          # positions enter by RoPE

    def final_norm(self, params, x):
        return _rms_norm(x, params["norm"], self.cfg.rms_norm_eps)

    def logits(self, params, xe):
        return jnp.dot(xe, params["head"],
                       preferred_element_type=jnp.float32)

    # -- one block ------------------------------------------------------
    def block(self, params, l, x, cache):
        cfg = self.cfg
        bp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        h = x + self._attention(
            bp, _rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps), cache)
        y, stats = self._ffn(
            bp, params["experts"], l,
            _rms_norm(h, bp["ffn_norm"], cfg.rms_norm_eps), cache.row_valid)
        return h + y, stats

    def _ffn(self, bp, experts, l, x, valid):
        cfg = self.cfg
        B, T, E = x.shape
        rows = x.reshape(B * T, E)
        decode = T == 1
        routed, stats = dropless_moe(
            rows, bp["router"], experts,
            top_k=cfg.num_experts_per_tok, experts_held=cfg.experts_held,
            first_matrix=l * cfg.experts_held[1],
            tile_m=cfg.moe_tile_rows_decode if decode
            else cfg.moe_tile_rows,
            kernel_name=KERNEL_NAME + ("_decode" if decode else "_prefill"),
            valid=None if valid is None else valid.reshape(-1),
            norm_topk_prob=cfg.norm_topk_prob,
            scaling=cfg.routed_scaling_factor,
            interpret=cfg.pallas_interpret)
        shared = {"gate_up": bp["shared_gate_up"], "down": bp["shared_down"]}
        return (routed + _swiglu(rows, shared)).reshape(B, T, E), stats

    def _attention(self, bp, x, cache):
        cfg = self.cfg
        pos = cache.positions                               # (B, T)
        cos, sin = _rope_cos_sin(cfg, pos)                  # (B, T, Dr/2)
        # the softmax scale and the position-dependent query scale, once
        q_scale = softmax_scale(cfg) * (
            1.0 + cfg.llama_4_scaling_beta * jnp.log1p(jnp.floor(
                pos.astype(jnp.float32)
                / cfg.rope_original_max_position_embeddings)))
        return latent_attention(cfg, bp, x, cache, q_scale=q_scale,
                                cos=cos, sin=sin)
