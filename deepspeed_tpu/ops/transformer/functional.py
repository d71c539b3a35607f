"""Attention + fused-elementwise functional ops: the dispatch point between the
jnp reference path and Pallas TPU kernels.

Reference analog: csrc/transformer/*.cu fused kernels (SURVEY §2.7).  Every op
here has a jnp reference implementation (always correct, XLA-fused) and may
have a Pallas fast path registered; `deepspeed_tpu.ops.registry` reports which
is active (the ds_report analog).
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import mesh as mesh_lib


def _fold_shard_index(seed):
    """The in-kernel dropout mask is a function of (seed, local batch*head
    index, position), so inside a per-shard region every shard would draw
    the same mask: fold the shard's index into the seed.  Only along the
    axes that split batch and heads — along any other mapped axis ('pipe')
    the operands are replicas and must draw one mask."""
    manual = tuple(a for a in jax.sharding.get_abstract_mesh().manual_axes
                   if a in (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS,
                            mesh_lib.SEQ_AXIS))
    if not manual:
        return seed
    # int32 multiply wraps; any odd constant spreads the indices
    return seed + jax.lax.axis_index(manual).astype(jnp.int32) \
        * jnp.int32(0x632BE5AB)


def scaled_dot_product_attention(q, k, v, *, mask=None, bias=None, causal=False,
                                 dropout_rng=None, dropout_rate=0.0,
                                 scale: Optional[float] = None,
                                 use_pallas: Optional[bool] = None):
    """Attention over [batch, heads, seq, head_dim] tensors.

    use_pallas None (the default) gives the Pallas flash-attention kernel
    (deepspeed_tpu.ops.transformer.flash_attention) to a TPU when the shapes
    allow, and the jnp reference path to everything else; True forces the
    kernel (interpret mode on the CPU backend), False the jnp path.
    """
    kw = dict(mask=mask, bias=bias, causal=causal, dropout_rng=dropout_rng,
              dropout_rate=dropout_rate, scale=scale)
    if use_pallas is None and _pallas_attention_ok(
            q, k, v, mask, bias, dropout_rate, dropout_rng):
        # which platform runs this is known only when the program is
        # lowered — a pipeline's host-side init runs the same forward on
        # the CPU while the default backend is the TPU — so the choice is
        # made there; only the chosen branch is lowered
        return jax.lax.platform_dependent(
            q, k, v,
            tpu=lambda q, k, v: _pallas_attention(q, k, v, interpret=False,
                                                  **kw),
            default=lambda q, k, v: _jnp_attention(q, k, v, **kw))
    if use_pallas:
        return _pallas_attention(q, k, v, **kw)
    return _jnp_attention(q, k, v, **kw)


def _pallas_attention(q, k, v, *, mask, bias, causal, dropout_rng,
                      dropout_rate, scale, interpret=None):
    assert dropout_rate == 0.0 or dropout_rng is not None, (
        "pallas flash attention dropout needs a dropout_rng to derive "
        "the in-kernel counter seed")
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    if mask is not None:
        # boolean keep-mask -> additive bias (the kernel's in-block
        # form); combined with any explicit bias by addition, matching
        # the jnp path's where(mask, logits+bias, -inf)
        mask_bias = jnp.where(mask, jnp.float32(0.0), jnp.float32(-1e30))
        bias = mask_bias if bias is None else bias + mask_bias
    extra = {}                  # optional operands: name -> (array, spec)
    if bias is not None:
        # a broadcast (size-1) dim of the bias has nothing to shard
        extra["bias"] = (bias, P(*(None if n == 1 else ax for n, ax in
                                   zip(bias.shape, mesh_lib.HEAD_SHARDED))))
    if dropout_rate > 0.0:
        # per-step scalar seed for the in-kernel counter-based PRNG
        extra["seed"] = (jax.random.randint(
            dropout_rng, (1,), 0, 2 ** 31 - 1, dtype=jnp.int32), P())

    def kernel(q, k, v, *rest):
        named = dict(zip(extra, rest))
        seed = named.get("seed")
        return flash_attention(
            q, k, v, bias=named.get("bias"), causal=causal, scale=scale,
            dropout_rate=dropout_rate, interpret=interpret,
            dropout_seed=None if seed is None else _fold_shard_index(seed))

    heads = mesh_lib.HEAD_SHARDED
    specs = [heads] * 3 + [spec for _, spec in extra.values()]
    return mesh_lib.per_shard(kernel, specs, heads)(
        q, k, v, *(array for array, _ in extra.values()))


def _jnp_attention(q, k, v, *, mask, bias, causal, dropout_rng, dropout_rate,
                   scale):
    head_dim = q.shape[-1]
    scale = (head_dim ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((q_len, k_len), dtype=bool),
                               k_len - q_len)
        logits = jnp.where(causal_mask, logits, jnp.float32(-1e30))
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _pallas_attention_ok(q, k, v, mask, bias, dropout_rate,
                         dropout_rng=None) -> bool:
    # Shapes the kernel takes: seq and head_dim aligned to MXU tiles;
    # causal, additive bias, boolean keep-masks, and dropout (counter-based
    # PRNG) are all handled in-kernel. Bias/mask gradients are not produced
    # (fine for constant masks — a learned bias needs use_pallas=False).
    if dropout_rate > 0.0 and dropout_rng is None:
        return False

    def key_padding_shaped(m):
        # auto-dispatch only for key-padding-shaped (B, 1, 1, S_k) masks/
        # biases — in practice always constants. A full (learned) bias
        # would silently get zero gradient through the kernel; it must opt
        # in with use_pallas=True.
        return (getattr(m, "ndim", 0) == 4 and m.shape[1] == 1
                and m.shape[2] == 1)

    if bias is not None and not key_padding_shaped(bias):
        return False
    if mask is not None and not key_padding_shaped(mask):
        return False
    b, h, s, d = q.shape
    # the kernel is mapped over the mesh (mesh_lib.per_shard): a batch or a
    # head count the mesh does not divide stays on the jnp path
    return s % 128 == 0 and d in (64, 128, 256) and k.shape == q.shape \
        and mesh_lib.shards_evenly(q.shape, mesh_lib.HEAD_SHARDED)


def gelu(x, approximate=True):
    return jax.nn.gelu(x, approximate=approximate)


def bias_gelu(x, bias):
    """Fused bias+GeLU (reference csrc/transformer/gelu_kernels.cu); XLA fuses."""
    return jax.nn.gelu(x + bias, approximate=True)


def layer_norm(x, gamma, beta, eps=1e-12):
    """LayerNorm in fp32 accumulations (reference normalize_kernels.cu)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


def bias_residual_layer_norm(x, bias, residual, gamma, beta, eps=1e-12):
    """Fused bias+residual+LayerNorm (reference: fused add+LN in
    normalize_kernels.cu)."""
    return layer_norm(x + bias + residual, gamma, beta, eps)


def dropout(x, rng, rate, deterministic=False):
    if deterministic or rate == 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)
