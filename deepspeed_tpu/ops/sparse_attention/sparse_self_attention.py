"""Block-sparse self-attention over a SparsityConfig layout.

Reference behavior: deepspeed/ops/sparse_attention/sparse_self_attention.py:
14-164 (QKV -> SDD block matmul -> scaled masked block softmax -> DSD block
matmul, driven by a per-head block layout) with Triton kernels
(matmul.py:16-750, softmax.py:17-304).

TPU formulation: the layout expands to a block mask consumed by a fused
masked flash-style computation. Two execution paths:
- `block_sparse_attention` (default): XLA path — scores masked by the
  layout before softmax; XLA fuses mask+softmax+matmul, and masked blocks
  are skipped at the block level when the layout is head-uniform banded.
- a Pallas kernel that walks only active blocks per query-row (planned;
  tracked as the perf milestone — the API is identical, so callers are
  unaffected).

Masks follow the reference semantics: `key_padding_mask_mode`/
`attn_mask_mode` are 'add' (additive logits) or 'mul' (multiplicative 0/1)
(reference sparse_self_attention.py:27-43); `rpe` is added to the scores
(relative position embedding, reference softmax.py:17-219).
"""
from typing import Optional

import numpy as np

from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
    FixedSparsityConfig, SparsityConfig)


def layout_to_token_mask(layout, block: int):
    """(H, nb, nb) 0/1 block layout -> (H, S, S) boolean token mask."""
    import jax.numpy as jnp

    layout = jnp.asarray(layout, bool)
    return jnp.repeat(jnp.repeat(layout, block, axis=1), block, axis=2)


def block_sparse_attention(q, k, v, layout, block: int,
                           rpe=None, key_padding_mask=None, attn_mask=None,
                           key_padding_mask_mode: str = "add",
                           attn_mask_mode: str = "mul",
                           scale: Optional[float] = None,
                           use_pallas: Optional[bool] = None):
    """Masked block-sparse attention.

    q/k/v: (B, H, S, D); layout: (H, S/block, S/block) 0/1;
    rpe: (S, S) or broadcastable additive bias;
    key_padding_mask: (B, S) — 'add': float additions (-inf for pad),
        'mul': 0/1 multiplier; attn_mask: (S, S) likewise.

    On TPU with no rpe/masks, dispatches to the LUT-driven Pallas kernel
    (block_sparse_kernel.py) — O(active blocks) compute/memory; otherwise
    the XLA masked path runs (O(S^2) compute, still fused).
    """
    import jax
    import jax.numpy as jnp

    if use_pallas is None:
        # key padding rides the kernel as an in-kernel additive bias; only
        # rpe / full attn_mask (dense S x S structures) force the XLA path
        use_pallas = (rpe is None and attn_mask is None
                      and jax.default_backend() == "tpu"
                      and q.shape[2] % block == 0)
    if use_pallas:
        from deepspeed_tpu.ops.sparse_attention.block_sparse_kernel import \
            pallas_block_sparse_attention

        assert rpe is None and attn_mask is None
        key_bias = None
        if key_padding_mask is not None:
            kpm = jnp.asarray(key_padding_mask, jnp.float32)
            if key_padding_mask_mode == "mul":
                key_bias = jnp.where(kpm != 0, 0.0, -1e30)
            elif key_padding_mask_mode == "add":
                key_bias = kpm
            else:
                raise ValueError(
                    f"unknown key_padding_mask_mode "
                    f"{key_padding_mask_mode!r}")
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.parallel.mesh import per_shard

        # a Mosaic kernel must run per shard: batch over 'data' only — the
        # kernel indexes its per-head LUT by local head, so heads stay whole
        batch_sp = P("data", None, None, None)
        operands, specs = [q, k, v], [batch_sp] * 3
        if key_bias is not None:
            operands.append(key_bias)
            specs.append(P("data", None))

        def kernel(q, k, v, key_bias=None):
            return pallas_block_sparse_attention(
                q, k, v, layout, block, scale=scale, key_bias=key_bias)

        return per_shard(kernel, specs, batch_sp)(*operands)

    B, H, S, D = q.shape
    nb = S // block
    assert layout.shape[-1] == nb, \
        f"layout {layout.shape} does not match seq {S} / block {block}"
    scale = (D ** -0.5) if scale is None else scale

    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if rpe is not None:
        scores = scores + jnp.asarray(rpe, jnp.float32)
    if attn_mask is not None:
        am = jnp.asarray(attn_mask, jnp.float32)
        if attn_mask_mode == "mul":
            scores = jnp.where(am[None, None] != 0, scores, -1e30)
        elif attn_mask_mode == "add":
            scores = scores + am[None, None]
        else:
            raise ValueError(f"unknown attn_mask_mode {attn_mask_mode!r}")
    if key_padding_mask is not None:
        kpm = jnp.asarray(key_padding_mask, jnp.float32)
        if key_padding_mask_mode == "mul":
            scores = jnp.where(kpm[:, None, None, :] != 0, scores, -1e30)
        elif key_padding_mask_mode == "add":
            scores = scores + kpm[:, None, None, :]
        else:
            raise ValueError(
                f"unknown key_padding_mask_mode {key_padding_mask_mode!r}")

    tok_mask = layout_to_token_mask(layout, block)        # (H, S, S)
    scores = jnp.where(tok_mask[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (no active block) produce uniform probs over -1e30
    # logits; zero them like the reference kernel's empty-row behavior
    any_active = jnp.any(tok_mask, axis=-1)               # (H, S)
    probs = probs * any_active[None, :, :, None]
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


class SparseSelfAttention:
    """Module-style wrapper with the reference's call signature
    (reference sparse_self_attention.py:14-60, forward :110-164)."""

    def __init__(self, sparsity_config: Optional[SparsityConfig] = None,
                 key_padding_mask_mode: str = "add",
                 attn_mask_mode: str = "mul"):
        self.sparsity_config = sparsity_config or \
            FixedSparsityConfig(num_heads=4)
        assert key_padding_mask_mode in ("add", "mul")
        assert attn_mask_mode in ("add", "mul")
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode
        self._layout_cache = {}   # seq_len -> layout (reference master_layout)

    def get_layout(self, seq_len):
        if seq_len not in self._layout_cache:
            self._layout_cache[seq_len] = np.asarray(
                self.sparsity_config.make_layout(seq_len))
        return self._layout_cache[seq_len]

    def __call__(self, query, key, value, rpe=None, key_padding_mask=None,
                 attn_mask=None):
        B, H, S, D = query.shape
        assert H == self.sparsity_config.num_heads, \
            f"input has {H} heads, sparsity config has " \
            f"{self.sparsity_config.num_heads}"
        layout = self.get_layout(S)
        return block_sparse_attention(
            query, key, value, layout, self.sparsity_config.block,
            rpe=rpe, key_padding_mask=key_padding_mask, attn_mask=attn_mask,
            key_padding_mask_mode=self.key_padding_mask_mode,
            attn_mask_mode=self.attn_mask_mode)

    # torch-API alias
    forward = __call__
