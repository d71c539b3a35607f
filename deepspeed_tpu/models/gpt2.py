"""GPT-2 family — the flagship LM for benchmarks.

TPU-first design: flax linen decoder with
- bf16 compute / fp32 master params (engine-managed),
- Megatron-style tensor parallelism expressed as PartitionSpecs over the
  'model' mesh axis (this build owns TP natively; the reference only consumed
  an external Megatron mpu, SURVEY §2.5),
- jax.checkpoint (remat) per block for activation checkpointing,
- attention through ops.transformer.functional (Pallas flash path on TPU).

Size table mirrors the reference perf harness configs
(tests/model/Megatron_GPT2/run_perf_test.py:18-84: 1.5B = 48L x 1600h etc.).
"""
import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.api import (chunked_lm_cross_entropy,
                                      cross_entropy_loss)
from deepspeed_tpu.ops.transformer.functional import scaled_dot_product_attention
from deepspeed_tpu.parallel import mesh as mesh_lib


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    # Pad the embedding/LM-head vocab dim to a multiple of this so the two
    # biggest matmuls in the model tile cleanly onto the MXU's 128 lanes
    # (50257 -> 50304). Purely an internal layout: ids stay < vocab_size,
    # logits are sliced/masked back to vocab_size everywhere. 0 disables.
    pad_vocab_multiple: int = 128
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16      # compute dtype
    remat: bool = False            # activation checkpointing per block
    # remat policy: what the per-block checkpoint SAVES (everything else is
    # recomputed in the backward). 'nothing' = full remat (max memory
    # saving, max recompute); 'attn_out' = save the flash-attention outputs
    # (skips recomputing the attention kernel — the most expensive fwd op —
    # while still freeing the big QK/PV intermediates); 'dots' = save every
    # matmul output (least recompute, most memory)
    remat_policy: str = "nothing"
    scan_layers: bool = False      # lax.scan over blocks: compile time O(1)
                                   # in depth, params stacked (L, ...)
    use_pallas_attention: Optional[bool] = None  # None = auto
    loss_chunk_tokens: int = 8192  # chunked LM-head xent (0 = dense logits);
                                   # keeps peak memory O(chunk*V) not O(B*S*V).
                                   # 8192 on v5e: scan overhead amortized to
                                   # parity with the dense head (round-4 sweep)
    # attention under a nontrivial 'seq' mesh axis: 'ulysses' = all_to_all
    # head/seq reshard around a full-sequence kernel (parallel/ulysses.py);
    # 'ring' = K/V rotation with O(S/N) attention memory
    # (parallel/ring_attention.py; no dropout path)
    attention_sp_mode: str = "ulysses"
    # Mixture-of-Experts (expert parallelism over the 'data' mesh axis;
    # moe/sharded_moe.py). 0 experts = dense model. Every moe_layer_freq-th
    # block (the odd ones, GShard-style alternation) swaps its MLP for MoE.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_layer_freq: int = 2
    moe_aux_loss_coef: float = 0.01

    @property
    def head_dim(self):
        return self.n_embd // self.n_head

    @property
    def padded_vocab_size(self):
        from deepspeed_tpu.models.api import pad_to_multiple

        return pad_to_multiple(self.vocab_size, self.pad_vocab_multiple)


# named configs; 1.5B mirrors the reference's 48L/1600h perf config
GPT2_SIZES = {
    "gpt2-125m": dict(n_layer=12, n_embd=768, n_head=12),
    "gpt2-350m": dict(n_layer=24, n_embd=1024, n_head=16),
    "gpt2-760m": dict(n_layer=24, n_embd=1536, n_head=16),
    "gpt2-1.5b": dict(n_layer=48, n_embd=1600, n_head=25),
    "gpt2-4b": dict(n_layer=64, n_embd=2304, n_head=24),
    "gpt2-8b": dict(n_layer=72, n_embd=3072, n_head=24),
    "gpt2-10b": dict(n_layer=50, n_embd=4096, n_head=32),
}


def gpt2_config(name: str, **overrides) -> GPT2Config:
    base = dict(GPT2_SIZES[name])
    base.update(overrides)
    return GPT2Config(**base)


def remat_policy(name: str):
    """Map a GPT2Config.remat_policy name to a jax.checkpoint policy
    (None = save nothing, i.e. classic full remat)."""
    if name in ("nothing", "", None):
        return None
    if name == "attn_out":
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    if name == "dots":
        return jax.checkpoint_policies.dots_saveable
    raise ValueError(f"unknown remat_policy {name!r} "
                     "(expected nothing|attn_out|dots)")


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.config
        B, S, E = x.shape
        # fused QKV projection: one big MXU matmul, sharded over 'model'
        qkv = nn.Dense(3 * E, dtype=cfg.dtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, S, cfg.n_head, cfg.head_dim).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        drop_rng = self.make_rng("dropout") if (train and cfg.dropout > 0) else None
        amesh = jax.sharding.get_abstract_mesh()
        ring = (cfg.attention_sp_mode == "ring" and amesh is not None
                and not amesh.empty and amesh.shape.get("seq", 1) > 1)
        if ring:
            # ring sequence parallelism: K/V shards rotate over the 'seq'
            # axis, attention memory stays O(S/N) per device
            # (parallel/ring_attention.py)
            assert drop_rng is None, \
                "attention_sp_mode='ring' has no dropout path"
            from deepspeed_tpu.parallel.ring_attention import (
                _ring_attention_local)

            spec = P("data", "model", "seq", None)
            y = jax.shard_map(
                lambda qq, kk, vv: _ring_attention_local(
                    qq, kk, vv, axis_name="seq", causal=True, scale=None,
                    vary_axes=("data", "model")),
                in_specs=(spec, spec, spec), out_specs=spec,
                axis_names={"data", "model", "seq"})(q, k, v)
        else:
            # Ulysses sequence parallelism (parallel/ulysses.py): with a
            # nontrivial 'seq' axis these constraints flip the sequence dim
            # to full and shard heads over ('model','seq') instead (GSPMD
            # all_to_all) so the attention kernel sees the whole sequence.
            # Every dim names its axes — a partial spec would pin the
            # batch's 'data' and the heads' 'model' sharding to replicated.
            head_sp = mesh_lib.HEAD_SHARDED
            q = mesh_lib.constrain(q, head_sp)
            k = mesh_lib.constrain(k, head_sp)
            v = mesh_lib.constrain(v, head_sp)
            y = scaled_dot_product_attention(
                q, k, v, causal=True, dropout_rng=drop_rng,
                dropout_rate=cfg.dropout if train else 0.0,
                use_pallas=cfg.use_pallas_attention)
            y = mesh_lib.constrain(y, P("data", "model", "seq", None))
        y = y.transpose(0, 2, 1, 3).reshape(B, S, E)
        # marker for remat_policy='attn_out': saving here means the backward
        # re-runs only the (cheap) projections/LN/GeLU, not the attention
        y = checkpoint_name(y, "attn_out")
        y = nn.Dense(E, dtype=cfg.dtype, name="c_proj")(y)
        if train and cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=False)
        return y


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.config
        h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, name="c_fc")(x)
        h = nn.gelu(h, approximate=True)
        h = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="c_proj")(h)
        if train and cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=False)
        return h


class Block(nn.Module):
    config: GPT2Config
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.config
        # pre-LN
        x = x + CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         name="ln_1")(x), train)
        h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         name="ln_2")(x)
        if self.use_moe:
            from deepspeed_tpu.moe import MoE

            ffn = MoE(num_experts=cfg.moe_num_experts, d_ff=4 * cfg.n_embd,
                      k=cfg.moe_top_k,
                      capacity_factor=cfg.moe_capacity_factor,
                      aux_loss_coef=cfg.moe_aux_loss_coef,
                      dtype=cfg.dtype, name="moe")
        else:
            ffn = MLP(cfg, name="mlp")
        x = x + ffn(h, train)
        # keep activations sharded batch-over-data (and sequence-over-seq
        # under sequence parallelism) as blocks stack
        x = mesh_lib.constrain(x, P("data", "seq", None))
        return x


class GPT2LMHead(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, train: bool = False,
                 return_hidden: bool = False):
        cfg = self.config
        B, S = input_ids.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.padded_vocab_size, cfg.n_embd), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.n_positions, cfg.n_embd), jnp.float32)
        x = wte.astype(cfg.dtype)[input_ids] + wpe.astype(cfg.dtype)[None, :S]
        if train and cfg.dropout > 0:
            x = nn.Dropout(cfg.dropout)(x, deterministic=False)
        block = Block
        if cfg.remat:
            block = nn.remat(Block, static_argnums=(2,),
                             policy=remat_policy(cfg.remat_policy))
        if cfg.moe_num_experts:
            # heterogeneous layers (dense/MoE alternation) can't share one
            # scanned body; unrolled loop only
            assert not cfg.scan_layers, \
                "moe_num_experts > 0 requires scan_layers=False"
            for i in range(cfg.n_layer):
                x = block(cfg, name=f"h_{i}",
                          use_moe=(i % cfg.moe_layer_freq
                                   == cfg.moe_layer_freq - 1))(x, train)
        elif cfg.scan_layers:
            # ONE traced block scanned over stacked (L, ...) params: the
            # compiled program is depth-independent (big HLOs from unrolled
            # deep stacks are the main TPU compile-time cost)
            class _Body(nn.Module):
                config: GPT2Config

                @nn.compact
                def __call__(self, carry, _):
                    return block(self.config, name="block")(carry, train), None

            stack = nn.scan(_Body, variable_axes={"params": 0},
                            split_rngs={"params": True, "dropout": True},
                            length=cfg.n_layer)
            x, _ = stack(cfg, name="h")(x, None)
        else:
            for i in range(cfg.n_layer):
                x = block(cfg, name=f"h_{i}")(x, train)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         name="ln_f")(x)
        if return_hidden:
            # training loss path: the chunked xent applies the tied head
            # itself without materializing full logits
            return x, wte
        # tied LM head: logits against the embedding matrix; the matmul runs
        # at the padded (MXU-aligned) width, then the pad columns drop out
        logits = jnp.einsum("bse,ve->bsv", x, wte.astype(cfg.dtype))
        return logits[..., :cfg.vocab_size]


def gpt2_tp_leaf_spec(joined: str, leaf, stacked: bool = False):
    """Megatron-style TP rule for one GPT-2 param leaf — the single source
    of truth shared by GPT2Model.param_partition_spec and the pipeline
    LayerSpecs (models/gpt2_pipe.py):
    - QKV (c_attn) and MLP-in (c_fc) kernels: shard output dim,
    - attn-out / MLP-out (c_proj) kernels: shard input dim,
    - token embedding (wte): shard vocab dim,
    - everything else replicated.

    joined: '/'-joined param path; stacked: leaf carries a leading (L,)
    scan dim.
    """
    if leaf.ndim == 0:
        return P()
    if "moe" in joined:
        from deepspeed_tpu.moe import moe_leaf_spec

        spec = moe_leaf_spec(joined, leaf)
        if spec is not None:
            return spec
    lead = (None,) if stacked else ()
    if "wte" in joined:
        return P("model", None)
    if "wpe" in joined:
        return P()
    kernel_ndim = leaf.ndim - (1 if stacked else 0)
    if "c_attn" in joined or "c_fc" in joined:
        return P(*lead, None, "model") if kernel_ndim == 2 \
            else P(*lead, "model")
    if "c_proj" in joined:
        return P(*lead, "model", None) if kernel_ndim == 2 \
            else P(*lead)
    return P(*lead) if stacked else P()


class GPT2Model:
    """Engine model contract for GPT-2 (see models/api.py)."""

    def __init__(self, config: GPT2Config):
        self.config = config
        self.module = GPT2LMHead(config)

    def init(self, rng, batch):
        return self.module.init({"params": rng, "dropout": rng},
                                batch["input_ids"], train=False)["params"]

    def loss(self, params, batch, rng, train=True):
        cfg = self.config
        chunk = cfg.loss_chunk_tokens

        def apply(**kw):
            if cfg.moe_num_experts:
                out, col = self.module.apply(
                    {"params": params}, batch["input_ids"], train=train,
                    rngs={"dropout": rng}, mutable=["losses"], **kw)
                from deepspeed_tpu.moe import sum_moe_losses

                return out, sum_moe_losses(col.get("losses", {}))
            return self.module.apply(
                {"params": params}, batch["input_ids"], train=train,
                rngs={"dropout": rng}, **kw), None

        if chunk:
            (hidden, wte), aux = apply(return_hidden=True)
            # next-token LM loss, chunked head (no full-logits residual)
            loss, metrics = chunked_lm_cross_entropy(
                hidden[:, :-1], wte, batch["labels"][:, 1:],
                chunk_tokens=chunk, ignore_index=-100,
                valid_vocab=cfg.vocab_size)
        else:
            logits, aux = apply()
            # next-token LM loss
            loss, metrics = cross_entropy_loss(
                logits[:, :-1], batch["labels"][:, 1:], ignore_index=-100)
        if aux is not None and train:
            # the load-balance regularizer only exists to shape routing
            # gradients; eval loss must stay comparable to dense models
            loss = loss + aux
            metrics = dict(metrics, moe_aux_loss=aux, loss=loss)
        return loss, metrics

    def param_partition_spec(self, params):
        """Megatron-style TP layout over the 'model' axis:
        - QKV and MLP-in kernels: shard output dim,
        - attn-out and MLP-out kernels: shard input dim,
        - token embedding: shard vocab dim,
        - LayerNorms/biases on sharded-output layers: shard to match.
        """
        scanned = self.config.scan_layers

        def spec(path, leaf):
            names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
            joined = "/".join(str(n) for n in names)
            # scan-stacked block params carry a leading (L,) dim
            stacked = scanned and joined.startswith("h/")
            return gpt2_tp_leaf_spec(joined, leaf, stacked)

        return jax.tree_util.tree_map_with_path(spec, params)

    def num_params(self, params):
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))

    def generate(self, params, input_ids, max_new_tokens, **kw):
        """KV-cache autoregressive decoding (models/generation.py)."""
        from deepspeed_tpu.models.generation import generate

        return generate(self, params, input_ids, max_new_tokens, **kw)
