"""LongCat-Flash (``meituan-longcat/LongCat-Flash-Chat``) decoder, for
serving.

One block is the SHORTCUT-CONNECTED double block (ScMoE): two sub-blocks of
latent attention (MLA) and a dense SwiGLU feed-forward, and ONE routed
layer beside them, which reads the first sub-block's normed hidden state
and joins the residual only after the second::

    for i in (0, 1):
        h = h + MLA_i(norm_in[i](h))
        u = norm_post[i](h)
        if i == 0: s = ROUTED(u)          # the shortcut: computed here ...
        h = h + SwiGLU_i(u)
        if i == 1: h = h + s              # ... joined here

so the routed layer (and, in a deployment, its exchange between chips)
depends on nothing of a whole attention and a whole dense feed-forward.
All norms are RMSNorm, no biases, the head untied.

- Attention: ``models/latent_attention.py``, the function ``mistral4``
  calls, with LongCat's arguments: plain interleaved RoPE (theta 1e7, no
  scaling), the softmax scale ``qk_head_dim**-0.5``, and the report's SCALE
  CORRECTION of the two low-rank paths (``mla_scale_q_lora``,
  ``mla_scale_kv_lora``): the query times ``(hidden / q_lora_rank)**0.5``,
  the normed latent times ``(hidden / kv_lora_rank)**0.5`` (keys and values
  both carry it, the rotary key does not).  WHAT IS CACHED, a token, a
  block and an attention: ``[c_kv * (hidden / kv_lora_rank)**0.5 | k_r]``,
  the latent AFTER its norm WITH the factor folded in and the rotary key
  after RoPE: ``cache_rows = (576, 576)``, two raw rows (``cache_kind``).
- Routed layer: ``moe/dropless.py`` over ``n_routed_experts`` experts and
  ``zero_expert_num`` zero-compute (identity) ones behind them: softmax in
  f32 over all choices, the ``moe_topk`` largest of ``p + bias`` (the
  correction bias moves the choice, never the weight), weights
  ``routed_scaling_factor * p`` NOT renormalised, no shared expert.  This
  chip computes the part of the experts it holds (``experts_held``) and the
  identity term whole.

The model enters the serving engine through the decoder-block contract
(``serving/decoder.py``): :class:`LongCatFlashDecoder`.  There is no
training path here: at 16 bytes a parameter the smallest honest cut is four
chips' whole memory (``benchmark/configs/longcat-flash-chat-ep32.json``).
"""
import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.latent_attention import (_rms_norm, _swiglu,
                                                   latent_attention)
from deepspeed_tpu.moe.dropless import (STAT_NAMES, ZERO_STAT_NAME,
                                        dropless_moe)
from deepspeed_tpu.moe.grouped_matmul import KERNEL_NAME


@dataclasses.dataclass(frozen=True)
class LongCatFlashConfig:
    # the published keys, under their names
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    rope_theta: float = 1e7
    # (first, count): the routed experts this chip holds
    experts_held: Tuple[int, int] = (0, 512)
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
        return self.num_layers

    @property
    def n_positions(self):
        return self.max_position_embeddings

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_rows(self):
        """Widths of the rows a BLOCK caches a token: one latent row for
        each of its two attentions."""
        return (self.kv_lora_rank + self.qk_rope_head_dim,) * 2

    cache_kind = "rows"     # raw rows: two of them are not keys and values

    def decoder(self):
        return LongCatFlashDecoder(self)


def query_scale(cfg):
    """What the query is multiplied by: the softmax scale and the scale
    correction of the query's low-rank path."""
    lora = (cfg.hidden_size / cfg.q_lora_rank) ** 0.5 \
        if cfg.mla_scale_q_lora else 1.0
    return cfg.qk_head_dim ** -0.5 * lora


def latent_scale(cfg):
    """The scale correction of the latent: on keys and values alike."""
    return (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5 \
        if cfg.mla_scale_kv_lora else 1.0


def _rope_cos_sin(cfg, positions):
    dim = cfg.qk_rope_head_dim
    inv_freq = 1.0 / cfg.rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angles), jnp.sin(angles)


# a block's matrices outside its routed experts, name -> shape; the first
# seven once a SUB-BLOCK (stacked (L, 2, ...)), the router once a block
def _sub_block_shapes(cfg):
    E, H, F = cfg.hidden_size, cfg.num_attention_heads, cfg.ffn_hidden_size
    R, Dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    return {
        "q_a": (E, cfg.q_lora_rank),
        "q_b": (cfg.q_lora_rank, H * cfg.qk_head_dim),
        "kv_a": (E, R + Dr),
        "kv_b": (R, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o": (H * cfg.v_head_dim, E),
        "gate_up": (E, 2 * F),
        "down": (F, E),
    }


def _draw(cfg, key, shape):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg.initializer_range).astype(cfg.dtype)


class LongCatFlashModel:
    """``config`` and seeded ``init``: what ``InferenceEngine`` and the
    tests need of a model.  The tree: ``embed``, ``norm``, ``head``;
    ``layers``: every matrix outside the routed experts STACKED by block,
    a sub-block's own with a sub-block axis of 2 behind it ((L, 2, ...):
    ``q_a`` .. ``o``, ``gate_up``, ``down``, the four norms; ``q_b``,
    ``kv_a`` and ``kv_b`` held (out, in), ``_HELD_TRANSPOSED``), the block's
    ``router`` (L, E, routed + zero) and ``router_bias`` (L, routed + zero),
    the correction bias, f32 and zero from the seed; ``experts``:
    ``gate_up`` (L * held, E, 2 I) and ``down`` (L * held, I, E), all
    blocks' held experts in ONE tensor each, which the grouped matmul
    indexes where they lie.  Weights normal(0, initializer_range) in
    ``cfg.dtype``, norms at one; made a matrix a jitted call, in place: a
    dense feed-forward's matrix is 151 MB, 604 MB as drawn in f32."""

    def __init__(self, config: LongCatFlashConfig):
        self.config = config

    def init(self, rng, batch=None):
        cfg = self.config
        L, E, V = cfg.num_layers, cfg.hidden_size, cfg.vocab_size
        held, I = cfg.experts_held[1], cfg.expert_ffn_hidden_size
        choices = cfg.n_routed_experts + cfg.zero_expert_num
        k_embed, k_head, k_layers, k_router, k_experts = \
            jax.random.split(rng, 5)
        ones = lambda *shape: jnp.ones(shape, cfg.dtype)       # noqa: E731
        def fill_at(buf, key, index):
            lead = buf.ndim - len(index)
            return jax.lax.dynamic_update_slice(
                buf, _draw(cfg, key, (1,) * len(index) + buf.shape[-lead:]),
                tuple(index) + (0,) * lead)

        fill = jax.jit(fill_at, donate_argnums=0)

        def stack(key, lead, shape):
            """(*lead, *shape), a matrix a call."""
            buf = jnp.zeros(lead + shape, cfg.dtype)
            keys = jax.random.split(key, int(np.prod(lead)))
            for k, index in zip(keys, np.ndindex(*lead)):
                buf = fill(buf, k, jnp.asarray(index, jnp.int32))
            return buf

        shapes = _sub_block_shapes(cfg)
        layers = {
            name: stack(k, (L, 2), shape[::-1] if name in _HELD_TRANSPOSED
                        else shape)
            for k, (name, shape) in zip(
                jax.random.split(k_layers, len(shapes)), shapes.items())}
        layers.update(
            router=stack(k_router, (L,), (E, choices)),
            router_bias=jnp.zeros((L, choices), jnp.float32),
            norm_in=ones(L, 2, E), norm_post=ones(L, 2, E),
            q_a_norm=ones(L, 2, cfg.q_lora_rank),
            kv_a_norm=ones(L, 2, cfg.kv_lora_rank))
        k_gate_up, k_down = jax.random.split(k_experts)
        draw = jax.jit(lambda k, shape: _draw(cfg, k, shape),
                       static_argnums=1)
        return {"embed": draw(k_embed, (V, E)), "norm": ones(E),
                "head": draw(k_head, (E, V)), "layers": layers,
                "experts": {
                    "gate_up": stack(k_gate_up, (L * held,), (E, 2 * I)),
                    "down": stack(k_down, (L * held,), (I, E))}}


# held (out, in), the transpose of what ``latent_attention`` multiplies by:
# the layout in which the compiler runs BOTH programs' products with them
# (heads of 192 values do not fill 128-lane tiles; held (in, out), every
# program copied the whole stacks of ``q_b`` and ``kv_b`` at its start, 0.44
# GB, and a sub-block's ``q_b`` twice more inside the loop)
_HELD_TRANSPOSED = ("q_b", "kv_a", "kv_b")
# the leaves of ``layers`` a sub-block owns (axis 1 is the sub-block)
_SUB_BLOCK = tuple(_sub_block_shapes(LongCatFlashConfig())) \
    + ("norm_in", "norm_post", "q_a_norm", "kv_a_norm")


class LongCatFlashDecoder:
    """LongCat-Flash under the serving engine's decoder-block contract."""

    stat_names = STAT_NAMES + (ZERO_STAT_NAME,)
    scan_layers = True      # one traced block, the weights stacked by block

    def __init__(self, cfg):
        self.cfg = cfg
        self.dtype = cfg.dtype
        self.n_layer = cfg.num_layers

    def hold(self, params):
        from deepspeed_tpu.serving.decoder import held_as

        # the correction bias is added to f32 probabilities: held as given
        return held_as(params, self.dtype, keep=lambda path: any(
            getattr(k, "key", None) == "router_bias" for k in path))

    def embed(self, params, tokens, positions):
        return params["embed"][tokens]          # positions enter by RoPE

    def final_norm(self, params, x):
        return _rms_norm(x, params["norm"], self.cfg.rms_norm_eps)

    def logits(self, params, xe):
        return jnp.dot(xe, params["head"],
                       preferred_element_type=jnp.float32)

    # -- one double block -----------------------------------------------
    def block(self, params, l, x, cache):
        cfg = self.cfg
        layers = params["layers"]
        cos, sin = _rope_cos_sin(cfg, cache.positions)      # (B, T, Dr/2)
        h = x
        for i in (0, 1):
            # ONE slice a matrix, by (block, sub-block): a block's slab
            # sliced out first has two readers, and the compiler then
            # copies it (1.2 GB a block) before either reads it
            sp = {name: layers[name][l, i].T if name in _HELD_TRANSPOSED
                  else layers[name][l, i] for name in _SUB_BLOCK}
            h = h + latent_attention(
                cfg, sp, _rms_norm(h, sp["norm_in"], cfg.rms_norm_eps),
                cache, q_scale=query_scale(cfg), cos=cos, sin=sin,
                latent_scale=latent_scale(cfg), row=i)
            u = _rms_norm(h, sp["norm_post"], cfg.rms_norm_eps)
            if i == 0:      # the shortcut: routed here, joined below
                routed, stats = self._routed(
                    layers["router"][l], layers["router_bias"][l],
                    params["experts"], l, u, cache.row_valid)
            h = h + _swiglu(u, sp)
        return h + routed, stats

    def _routed(self, router, bias, experts, l, x, valid):
        cfg = self.cfg
        B, T, E = x.shape
        decode = T == 1
        y, stats = dropless_moe(
            x.reshape(B * T, E), router, experts,
            top_k=cfg.moe_topk, experts_held=cfg.experts_held,
            first_matrix=l * cfg.experts_held[1],
            tile_m=cfg.moe_tile_rows_decode if decode
            else cfg.moe_tile_rows,
            kernel_name=KERNEL_NAME + ("_decode" if decode else "_prefill"),
            valid=None if valid is None else valid.reshape(-1),
            norm_topk_prob=False, scaling=cfg.routed_scaling_factor,
            choice_bias=bias,
            zero_experts=cfg.zero_expert_num,
            # top-12 over 768 with 16 held: the worst-case buffer is 48
            # times the mean, and walking it whole cost a full chunk 8 of
            # its 71 ms on the chip (PERF.md section 6, PR 34)
            live_tiles=True, interpret=cfg.pallas_interpret)
        return y.reshape(B, T, E), stats
