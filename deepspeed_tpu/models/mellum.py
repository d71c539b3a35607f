"""Mellum 2 (``model_type: mellum``, JetBrains) text decoder, for serving.

The layer follows the published ``config.json`` keys: pre-RMSNorm blocks of
GROUPED-QUERY attention (``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim``, no bias) and a
ROUTED SwiGLU feed-forward (``num_experts`` experts of
``moe_intermediate_size``, ``num_experts_per_tok`` a token, softmax,
renormalised, no shared expert, no dense layer), an untied head.

``layer_types`` repeats a period of SLIDING layers and one FULL layer
(published: three and one).  A sliding layer's query at position p sees the
keys at ``p - sliding_window + 1 .. p`` and turns q and k by plain RoPE
(``rope_parameters["sliding_attention"]``); a full layer sees everything and
turns them by YaRN's blended frequencies with ``attention_factor`` on cos
and sin (``rope_parameters["full_attention"]``).  Rotary is over all
``head_dim`` values in the half-split convention:
``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin]``.

WHAT IS CACHED is keys and values of the ``num_key_value_heads`` heads, in
TWO cache groups (``cache_groups``, ``serving/kv_cache.py``): the full
layers' rows keep every position, the sliding layers' only what a window can
still see, each group with its own pool tensors, pages and page table behind
one ``alloc`` / ``free`` a request.  A chunk of a prompt attends ONE gather
of the lane's pages per group through the rectangle kernel
(``rect_flash_attention``: key head ``h // G``, ``k_start``, ``window``);
one query a lane attends through ``cache.attend_heads``, which on a TPU
reads the pages where they lie.

The model enters the serving engine through the decoder-block contract
(``serving/decoder.py``): :class:`MellumDecoder`, whose traced unit is ONE
PERIOD (the sliding layers as a ``lax.scan`` of their own, then the full
layer), so that a kernel is one operation a KIND of layer in the compiled
program.  There is no training path here (``moe/dropless.py`` is forward
only).
"""
import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import rotary
from deepspeed_tpu.models.latent_attention import _rms_norm
from deepspeed_tpu.moe.dropless import STAT_NAMES, dropless_moe
from deepspeed_tpu.moe.grouped_matmul import KERNEL_NAME
from deepspeed_tpu.ops.transformer.rect_attention import \
    rect_flash_attention

SLIDING, FULL = "sliding_attention", "full_attention"
# what the two attention kernels are called in the compiled program and the
# device trace, with the cache group's name behind
PREFILL_KERNEL = "gqa_prefill_attn"
DECODE_KERNEL = "gqa_paged_decode_attn"

_PUBLISHED_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}


def _frozen(value):
    """A dict as sorted pairs, so that a configuration hashes."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    return value


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    sliding_window: int = 1024
    # one entry a layer; None: the published period, three sliding layers
    # and a full one, as often as the depth holds it
    layer_types: Optional[Tuple[str, ...]] = None
    # the two sections as published (a dict; held as sorted pairs)
    rope_parameters: Any = None
    dtype: Any = jnp.bfloat16       # compute AND served-weight dtype
    initializer_range: float = 0.02
    # rows a tile of the grouped matmul takes, prefill / decode
    moe_tile_rows: int = 128
    moe_tile_rows_decode: int = 16
    # the Pallas kernels' ``interpret=``: None is the kernels' own default
    # (the interpreter on a CPU backend); a compile-only rehearsal for a
    # described chip states False
    pallas_interpret: Optional[bool] = None

    def __post_init__(self):
        types = self.layer_types
        if types is None:
            types = ((SLIDING,) * 3 + (FULL,)) * (self.num_hidden_layers // 4)
        object.__setattr__(self, "layer_types", tuple(types))
        object.__setattr__(self, "rope_parameters", _frozen(
            _PUBLISHED_ROPE if self.rope_parameters is None
            else self.rope_parameters))
        per = self.period
        assert len(self.layer_types) == self.num_hidden_layers \
            and self.num_hidden_layers % per == 0 \
            and self.layer_types == ((SLIDING,) * (per - 1) + (FULL,)) \
            * (self.num_hidden_layers // per), \
            f"layer_types must repeat sliding layers and one full layer: " \
            f"{self.layer_types}"
        assert self.num_attention_heads % self.num_key_value_heads == 0

    def rope(self, kind):
        """The ``rope_parameters`` section of a kind of layer, a dict."""
        return dict(dict(self.rope_parameters)[kind])

    @property
    def period(self):
        """Layers from one full layer to the next, that one included."""
        return self.layer_types.index(FULL) + 1

    # what the serving engine reads of any configuration
    @property
    def n_layer(self):
        return self.num_hidden_layers

    @property
    def n_positions(self):
        return self.max_position_embeddings

    @property
    def n_head(self):
        return self.num_key_value_heads     # the CACHED heads

    @property
    def cache_rows(self):
        """Keys and values of the key/value heads, side by side in a row."""
        return (self.num_key_value_heads * self.head_dim,) * 2

    @property
    def cache_groups(self):
        """``(name, layers, window)``: the full layers keep every position,
        the sliding layers a window (the first group keeps everything)."""
        n_full = self.num_hidden_layers // self.period
        return (("full", n_full, None),
                ("window", self.num_hidden_layers - n_full,
                 self.sliding_window))

    def decoder(self):
        return MellumDecoder(self)


def rope_inv_freq(cfg, kind):
    """(head_dim / 2,) float64 rotary frequencies of a kind of layer, and
    what its cos and sin are multiplied by."""
    rope = cfg.rope(kind)
    dim, base = cfg.head_dim, float(rope["rope_theta"])
    if rope["rope_type"] == "yarn":
        return rotary.yarn_inv_freq(
            dim, base, float(rope["factor"]),
            rope["original_max_position_embeddings"],
            float(rope["beta_fast"]), float(rope["beta_slow"])), \
            float(rope["attention_factor"])
    assert rope["rope_type"] == "default", rope
    return 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim), 1.0


def _rope_cos_sin(cfg, kind, positions):
    inv_freq, factor = rope_inv_freq(cfg, kind)
    angles = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def _rope_half_split(x, cos, sin):
    """``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin]`` over the last
    dim's two halves; cos / sin broadcast against (..., d / 2).  f32."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# one layer's matrices outside its experts: name -> shape
def _layer_shapes(cfg):
    E, D = cfg.hidden_size, cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    return {"qkv": ((H + 2 * Hkv) * D, E),      # [q | k | v], (out, in)
            "o": (H * D, E),
            "router": (cfg.num_experts, E)}       # (out, in)


def _draw(cfg, key, shape):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg.initializer_range).astype(cfg.dtype)


class MellumModel:
    """``config`` and seeded ``init``: what ``InferenceEngine`` and the
    tests need of a model.  The tree: ``embed``, ``norm``, ``head``;
    ``layers``: every matrix outside the experts STACKED by layer (L, ...):
    ``qkv`` ([q | k | v], one product) and ``router``, both held (out, in)
    (held (in, out), every program copied the whole ``qkv`` stack, 189 MB at
    8 layers, into that layout at its start and relaid the router's:
    ``test_tpu_compile.py``), ``o``, the two norms;
    ``experts``: ``gate_up`` (L * experts, E, 2 I) and ``down``
    (L * experts, I, E), all layers' experts in ONE tensor each, which the
    grouped matmul indexes where they lie.  Weights normal(0,
    initializer_range) in ``cfg.dtype``, norms at one; the matrices outside
    the experts in one jitted call, the experts a layer a call, in place
    (a layer's ``gate_up`` is 528 MB, four times that as drawn in f32)."""

    def __init__(self, config: MellumConfig):
        self.config = config

    def init(self, rng, batch=None):
        cfg = self.config
        L, E, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
        n, I = cfg.num_experts, cfg.moe_intermediate_size
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
                buf, _draw(cfg, key, (n,) + buf.shape[1:]),
                (l * n, 0, 0)), donate_argnums=0)

        def experts(key, shape):
            buf = jnp.zeros((L * n,) + shape, cfg.dtype)
            for l, k in enumerate(jax.random.split(key, L)):
                buf = fill(buf, k, l)
            return buf

        k_gate_up, k_down = jax.random.split(k_experts)
        layers = dense(k_layers)
        layers.update(attn_norm=ones(L, E), ffn_norm=ones(L, E))
        draw = jax.jit(lambda k, shape: _draw(cfg, k, shape),
                       static_argnums=1)
        return {"embed": draw(k_embed, (V, E)), "norm": ones(E),
                "head": draw(k_head, (E, V)), "layers": layers,
                "experts": {"gate_up": experts(k_gate_up, (E, 2 * I)),
                            "down": experts(k_down, (I, E))}}


class MellumDecoder:
    """Mellum under the serving engine's decoder-block contract: a block is
    one PERIOD of ``layer_types``."""

    stat_names = STAT_NAMES
    scan_layers = True      # one traced period, the weights stacked by layer

    def __init__(self, cfg):
        self.cfg = cfg
        self.dtype = cfg.dtype
        self.n_layer = cfg.num_hidden_layers // cfg.period   # blocks

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

    # -- one period -----------------------------------------------------
    def block(self, params, p, x, cache):
        """Period ``p``: its sliding layers, a scan of their own over the
        window group's pool (one traced layer, so one attention kernel and
        one pair of grouped matmuls for all of them), then its full
        layer."""
        per = self.cfg.period
        tables = {kind: _rope_cos_sin(self.cfg, kind, cache.positions)
                  for kind in (SLIDING, FULL)}

        def sliding(carry, i):
            x, stats, held = carry
            cache.restore("window", held)
            x, row = self._layer(
                params, p * per + i, x,
                cache.at("window", p * (per - 1) + i), tables[SLIDING])
            return (x, stats + row, cache.carry("window")), None

        stats = jnp.zeros(len(self.stat_names), jnp.int32)
        if per > 1:
            (x, stats, held), _ = jax.lax.scan(
                sliding, (x, stats, cache.carry("window")),
                jnp.arange(per - 1))
            cache.restore("window", held)
        x, row = self._layer(params, p * per + per - 1, x,
                             cache.at("full", p), tables[FULL])
        return x, stats + row

    def _layer(self, params, l, x, cache, cos_sin):
        cfg = self.cfg
        # ONE slice a matrix, by layer
        lp = {name: leaf[l] for name, leaf in params["layers"].items()}
        h = x + self._attention(
            lp, _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps), cache,
            cos_sin)
        y, stats = self._ffn(
            lp, params["experts"], l,
            _rms_norm(h, lp["ffn_norm"], cfg.rms_norm_eps), cache.row_valid)
        return h + y, stats

    def _ffn(self, lp, experts, l, x, valid):
        cfg = self.cfg
        B, T, E = x.shape
        decode = T == 1
        y, stats = dropless_moe(
            x.reshape(B * T, E), lp["router"].T, experts,
            top_k=cfg.num_experts_per_tok,
            experts_held=(0, cfg.num_experts),
            first_matrix=l * cfg.num_experts,
            tile_m=cfg.moe_tile_rows_decode if decode
            else cfg.moe_tile_rows,
            kernel_name=KERNEL_NAME + ("_decode" if decode else "_prefill"),
            valid=None if valid is None else valid.reshape(-1),
            norm_topk_prob=cfg.norm_topk_prob,
            interpret=cfg.pallas_interpret)
        return y.reshape(B, T, E), stats

    def _attention(self, lp, x, cache, cos_sin):
        cfg = self.cfg
        B, T, _ = x.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        cos, sin = (t[:, :, None] for t in cos_sin)     # (B, T, 1, D/2)
        qkv = (x @ lp["qkv"].T).reshape(B, T, H + 2 * Hkv, D)
        q = _rope_half_split(qkv[:, :, :H].astype(jnp.float32), cos, sin)
        k = _rope_half_split(qkv[:, :, H:H + Hkv].astype(jnp.float32),
                             cos, sin).astype(x.dtype)
        cache.write_heads(0, k.reshape(B * T, Hkv, D))
        cache.write_heads(1, qkv[:, :, H + Hkv:].reshape(B * T, Hkv, D))
        group = "full" if cache.window is None else "window"
        if T == 1:
            # one query a lane: the engine's form (on a TPU the pages where
            # they lie); the head's scale is the core's and the kernel's
            out = cache.attend_heads(
                q.astype(x.dtype).transpose(0, 2, 1, 3), H, None,
                name=f"{DECODE_KERNEL}_{group}")
        else:
            # a chunk of one sequence: ONE gather of the lane's pages of
            # this group, the scale folded into the query
            assert B == 1, "chunked prefill attends one sequence a program"
            out = rect_flash_attention(
                (q[0] * D ** -0.5).astype(x.dtype).transpose(1, 0, 2),
                cache.view_heads(0, Hkv)[0], cache.view_heads(1, Hkv)[0],
                cache.positions[0, 0],
                k_start=None if cache.window is None else cache.k_start[0],
                window=cache.window, interpret=cfg.pallas_interpret,
                name=f"{PREFILL_KERNEL}_{group}")
            out = out.transpose(1, 0, 2).reshape(1, T, H * D)
        return out @ lp["o"]
