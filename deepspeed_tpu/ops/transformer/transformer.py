"""DeepSpeedTransformerLayer — the fused BERT-style encoder layer.

Reference behavior: deepspeed/ops/transformer/transformer.py:39-614 backed by
the CUDA fused kernel (csrc/transformer/ds_transformer_cuda.cpp:146-546:
QKV GEMM -> strided-batch attention GEMMs -> fused-bias softmax -> fused
bias+residual LayerNorm -> fused bias-GeLU, with saved dropout masks).

TPU formulation: one flax module whose whole body lives inside the jitted
train step — XLA fuses bias/dropout/residual/LayerNorm into the GEMMs the
same way the CUDA kernel hand-fuses them, and the attention core routes
through the Pallas flash kernel (ops/transformer/functional.py). The
memory-saving config flags map to rematerialization policies instead of
manual buffer reuse:
- normalize_invertible / attn_dropout_checkpoint / gelu_checkpoint ->
  jax.checkpoint over the layer body (recompute instead of save);
- stochastic_mode -> nothing to relax (TPU execution is deterministic).
"""
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.functional import \
    scaled_dot_product_attention


class TransformerConfig:
    """Base config (reference transformer.py:21-37)."""

    def __init__(self, batch_size=-1, hidden_size=-1, intermediate_size=-1,
                 heads=-1, attn_dropout_ratio=-1, hidden_dropout_ratio=-1,
                 num_hidden_layers=-1, initializer_range=-1):
        self.layer_id = -1
        self.batch_size = batch_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.heads = heads
        self.attn_dropout_ratio = attn_dropout_ratio
        self.hidden_dropout_ratio = hidden_dropout_ratio
        self.num_hidden_layers = num_hidden_layers
        self.initializer_range = initializer_range


class DeepSpeedTransformerConfig(TransformerConfig):
    """Config with the exact reference surface (transformer.py:39-140).

    TPU notes: fp16 selects the compute dtype (bf16 is the TPU-native
    choice; fp16 kept for parity); local_rank/seed/test_gemm are accepted
    for compatibility (device binding and RNG are engine concerns here).
    """

    def __init__(self, batch_size=-1, hidden_size=-1, intermediate_size=-1,
                 heads=-1, attn_dropout_ratio=-1, hidden_dropout_ratio=-1,
                 num_hidden_layers=-1, initializer_range=-1,
                 layer_norm_eps=1e-12, local_rank=-1, seed=-1, fp16=False,
                 bf16=False, pre_layer_norm=True, normalize_invertible=False,
                 gelu_checkpoint=False, adjust_init_range=True,
                 attn_dropout_checkpoint=False, stochastic_mode=False,
                 huggingface=False, training=True, sparsity_config=None):
        super().__init__(
            batch_size, hidden_size,
            intermediate_size if intermediate_size > 0 else 4 * hidden_size,
            heads, attn_dropout_ratio, hidden_dropout_ratio,
            num_hidden_layers, initializer_range)
        self.fp16 = fp16
        self.bf16 = bf16
        self.pre_layer_norm = pre_layer_norm
        self.local_rank = local_rank
        self.seed = seed
        self.normalize_invertible = normalize_invertible
        self.gelu_checkpoint = gelu_checkpoint
        self.adjust_init_range = adjust_init_range
        self.test_gemm = False
        self.layer_norm_eps = layer_norm_eps
        self.training = training
        self.is_grad_enabled = True
        self.attn_dropout_checkpoint = attn_dropout_checkpoint
        self.stochastic_mode = stochastic_mode
        self.huggingface = huggingface
        # a SparsityConfig (ops/sparse_attention) routes the attention core
        # through the block-sparse path — same params (QKV/out projections
        # untouched), different attention pattern. The reference swaps
        # whole modules (sparse_attention_utils.py:85-150); here the swap
        # is this one config field.
        self.sparsity_config = sparsity_config

    @classmethod
    def from_dict(cls, json_object):
        config = cls()
        for key, value in json_object.items():
            config.__dict__[key] = value
        return config

    @property
    def compute_dtype(self):
        if self.fp16:
            return jnp.float16
        if self.bf16:
            return jnp.bfloat16
        return jnp.float32

    @property
    def remat(self):
        """Any memory-saving flag -> rematerialize the layer body."""
        return (self.normalize_invertible or self.gelu_checkpoint
                or self.attn_dropout_checkpoint)


class _EncoderBody(nn.Module):
    """BERT encoder layer body (attention + FFN), pre- or post-LN."""
    config: DeepSpeedTransformerConfig

    @nn.compact
    def __call__(self, hidden_states, attention_mask, train: bool):
        cfg = self.config
        dtype = cfg.compute_dtype
        E = cfg.hidden_size
        H = cfg.heads
        B, S, _ = hidden_states.shape
        head_dim = E // H
        init_std = cfg.initializer_range
        out_std = init_std / math.sqrt(2.0 * max(1, cfg.num_hidden_layers)) \
            if cfg.adjust_init_range else init_std

        def dense(features, name, std):
            return nn.Dense(features, dtype=dtype, name=name,
                            kernel_init=nn.initializers.normal(std))

        x = hidden_states.astype(dtype)
        residual = x

        # --- attention -------------------------------------------------
        attn_in = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                               name="attn_ln")(x) if cfg.pre_layer_norm else x
        qkv = dense(3 * E, "qkv", init_std)(attn_in)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, S, H, head_dim).transpose(0, 2, 1, 3)

        drop_rng = self.make_rng("dropout") \
            if (train and cfg.attn_dropout_ratio > 0) else None
        # Ulysses sequence parallelism: under a nontrivial 'seq' mesh axis
        # the heads dim picks up the seq shard and the sequence dim goes
        # full (GSPMD all_to_all) — same flip as models/gpt2.py; every dim
        # names its axes so data/model sharding is preserved
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.parallel import mesh as mesh_lib

        head_sp = mesh_lib.HEAD_SHARDED
        qh = mesh_lib.constrain(heads(q), head_sp)
        kh = mesh_lib.constrain(heads(k), head_sp)
        vh = mesh_lib.constrain(heads(v), head_sp)
        if cfg.sparsity_config is not None:
            from deepspeed_tpu.ops.sparse_attention.sparse_self_attention \
                import block_sparse_attention

            assert drop_rng is None, (
                "sparsity_config does not support attention dropout "
                "(the reference's sparse path has none either); set "
                "attn_dropout_ratio=0")
            # HF extended additive mask (B,1,1,S) -> per-key additions;
            # anything with per-query structure cannot collapse to a key
            # bias and must fail loudly, not attend wrongly
            kpm = None
            if attention_mask is not None:
                assert attention_mask.shape[1] == 1 \
                    and attention_mask.shape[2] == 1, (
                        "sparsity_config supports key-padding masks "
                        "(B, 1, 1, S) only; got attention_mask shape "
                        f"{attention_mask.shape} — per-query masks need "
                        "the dense path (sparsity_config=None)")
                kpm = attention_mask[:, 0, 0, :]
            ctx = block_sparse_attention(
                qh, kh, vh,
                cfg.sparsity_config.make_layout(S),
                cfg.sparsity_config.block,
                key_padding_mask=kpm, key_padding_mask_mode="add")
        else:
            ctx = scaled_dot_product_attention(
                qh, kh, vh, causal=False, bias=attention_mask,
                dropout_rng=drop_rng,
                dropout_rate=cfg.attn_dropout_ratio if train else 0.0)
        ctx = mesh_lib.constrain(ctx, P("data", "model", "seq", None))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, E)
        attn_out = dense(E, "attn_out", out_std)(ctx)
        if train and cfg.hidden_dropout_ratio > 0:
            attn_out = nn.Dropout(cfg.hidden_dropout_ratio)(
                attn_out, deterministic=False)
        x = residual + attn_out
        if not cfg.pre_layer_norm:
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                             name="attn_ln")(x)

        # --- feed-forward ---------------------------------------------
        residual = x
        ffn_in = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                              name="ffn_ln")(x) if cfg.pre_layer_norm else x
        h = dense(cfg.intermediate_size, "ffn_inter", init_std)(ffn_in)
        h = nn.gelu(h, approximate=False)
        h = dense(E, "ffn_out", out_std)(h)
        if train and cfg.hidden_dropout_ratio > 0:
            h = nn.Dropout(cfg.hidden_dropout_ratio)(h, deterministic=False)
        x = residual + h
        if not cfg.pre_layer_norm:
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                             name="ffn_ln")(x)
        return x


class DeepSpeedTransformerLayer(nn.Module):
    """Drop-in encoder layer (reference transformer.py:470-614).

    __call__(hidden_states, attention_mask) -> hidden_states, where
    attention_mask is an additive bias broadcastable to (B, H, S, S)
    (HF-style extended mask) or None.
    """
    config: DeepSpeedTransformerConfig

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None,
                 train: Optional[bool] = None):
        cfg = self.config
        train = cfg.training if train is None else train
        body = _EncoderBody
        if cfg.remat and train:
            body = nn.remat(_EncoderBody, static_argnums=(3,))
        return body(cfg, name="body")(hidden_states, attention_mask, train)
