"""Pallas TPU flash attention — fused memory-efficient attention kernel.

TPU-native replacement for the reference's fused CUDA attention path
(reference: csrc/transformer/softmax_kernels.cu + strided_batch_gemm.h,
dispatched from ds_transformer_cuda.cpp:146-291).  Instead of materialising
the [B,H,S,S] score matrix in HBM, the kernel streams K/V blocks through
VMEM with an online-softmax accumulator (running max / denominator), so
attention memory is O(S) and the matmuls stay on the MXU.

Forward saves only the per-row logsumexp; backward recomputes probabilities
blockwise (two sweeps: dk/dv then dq) — the flash-attention v2 scheme.

Attention dropout runs IN-KERNEL with a counter-based hash PRNG: the keep
mask for (head, q, k) is a pure function of (seed, position), so backward
regenerates the exact forward mask instead of saving an S x S byte mask to
HBM (the reference's CUDA layer saves masks — dropout_kernels.cu +
attn_dropout_checkpoint; SURVEY §2.7 maps that to counter-based PRNG on
TPU). The hash is the murmur3 finalizer over plain uint32 ops, so the same
code runs compiled on TPU and in interpreter mode on CPU.
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 512/1024 were picked at (8, 16, 1024, 64) on a v5e before PR 21; the
# kernel's own rate is not measured since PR 21 (PERF.md §5: the flash
# calls are 26.5 % of the training cell's busy time).  Blocks are clamped
# to the sequence length at call time
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
# optional overrides for the backward sweeps only (0 = inherit fwd blocks);
# settable via env DSTPU_FLASH_BWD_BLOCK_Q/K for on-chip sweeps
import os as _os
_BWD_BLOCK_Q = int(_os.environ.get("DSTPU_FLASH_BWD_BLOCK_Q", "0"))
_BWD_BLOCK_K = int(_os.environ.get("DSTPU_FLASH_BWD_BLOCK_K", "0"))
# lse/delta wire format: by default they travel 128-lane broadcast
# ((bh, s_q, 128), 127/128 of the bytes redundant — ~0.4 GB/tensor/layer at
# the gpt2-350m bench shapes). DSTPU_FLASH_LSE2D=1 switches to compact
# (bh, 1, s_q) rows with an in-kernel (1, bq) -> (bq, 1) relayout; read
# when a call is traced, and not the default until a chip measurement
# says the Mosaic relayout is cheap (chip_smoke.py checks its results).


def _lse_2d():
    return _os.environ.get("DSTPU_FLASH_LSE2D", "0") == "1"


NEG_INF = -1e30


def _col(ref):
    """Per-row statistic from its wire block: (1, 1, bq) compact row ->
    (bq, 1) column, or the legacy 128-lane block's first lane."""
    if _lse_2d():
        return ref[...].reshape(-1, 1)
    return ref[0][:, 0:1]


def _dot(a, b, dims):
    """MXU dot: native (bf16) inputs, fp32 accumulation. Casting inputs to
    fp32 first would force fp32 MXU passes at a fraction of bf16 throughput —
    the round-4 profile showed exactly that (kernel slower than the jnp
    path); inputs stay in their storage dtype and only the accumulator is
    fp32."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _fit_block(block, seq):
    """Largest lane-aligned block <= `block` that divides `seq` (whole
    `seq` if smaller); None when no 128-aligned divisor exists — degenerate
    sub-tile blocks would fail deep in Mosaic or crawl, so the caller
    raises loudly instead."""
    if seq <= block:
        return seq
    while block >= 128:
        if seq % block == 0:
            return block
        block //= 2
    return None


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def _dropout_keep(seed_ref, bh, q_start, k_start, block_q, block_k, s_k,
                  rate):
    """Keep-mask block for attention dropout: murmur3-finalizer hash of the
    global (q, k) position, pre-mixed with (seed, batch*head). Deterministic
    given the seed, so forward and both backward sweeps regenerate identical
    masks from the positions alone."""
    def mix(h):
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        return h ^ (h >> 16)

    seed = seed_ref[0].astype(jnp.uint32) \
        + jnp.uint32(0x9E3779B9) * jnp.uint32(bh)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (block_q, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (1, block_k), 1)
    # q and k positions are mixed in two rounds rather than combined into a
    # q*s_k + k linear index: the product overflows uint32 beyond ~64k seq
    # (q rows 2^32/s_k apart would alias and share keep patterns)
    rh = mix(seed ^ (jnp.uint32(q_start) + rows))           # (bq, 1)
    h = mix(rh ^ (jnp.uint32(0x27D4EB2F) *
                  (jnp.uint32(k_start) + cols)))            # (bq, bk)
    return h >= jnp.uint32(min(rate, 0.9999) * 4294967296.0)


def _apply_bias(s, bias_ref, bias_kind):
    """Additive attention bias inside a kernel block.

    bias_kind 'key': bias_ref block is (1, 1, block_k) — the HF
    extended-mask (B, 1, 1, S_k) case, broadcast over query rows; 'full':
    (1, block_q, block_k) per-(batch*head) scores bias."""
    if bias_kind == "none":
        return s
    return s + bias_ref[0]


def _bias_specs(bias, bias_kind, num_heads, block_q, block_k, qmap, kmap):
    """(operands, in_specs) for the optional bias input. qmap/kmap map grid
    ids to the bias q/k block index."""
    if bias_kind == "none":
        return [], []
    if bias_kind == "key":
        # (B, 1, S_k): the unit middle dim makes the block's last two dims
        # (1, block_k) = (whole dim, lane-aligned), which the TPU lowering
        # takes; a 2-D (B, S_k) array blocked (1, block_k) is refused
        spec = pl.BlockSpec(
            (1, 1, block_k),
            lambda b, i, j: (b // num_heads, 0, kmap(i, j)))
        return [bias], [spec]
    spec = pl.BlockSpec(
        (1, block_q, block_k),
        lambda b, i, j: (b, qmap(i, j), kmap(i, j)))
    return [bias], [spec]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, scale, causal, bias_kind, dropout_rate, s_k_total,
                block_q, block_k, num_k_blocks):
    seed_ref = None
    if dropout_rate > 0.0:
        seed_ref, *refs = refs
    if bias_kind == "none":
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        bias_ref = None
    else:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly above the diagonal
    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                # [bq, d] storage dtype
        k = k_ref[0]                                # [bk, d]
        v = v_ref[0]                                # [bk, d]
        s = _dot(q, k, ((1,), (1,))) * scale                 # [bq, bk] f32
        s = _apply_bias(s, bias_ref, bias_kind)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = (q_start + rows) >= (k_start + cols)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0:1]                               # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)           # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                      # [bq, 1]
        p = jnp.exp(s - m_new)                               # [bq, bk] f32
        # softmax denominator accumulates UNdropped p; dropout scales only
        # the value accumulation (normalize-then-drop semantics, same as
        # the reference applying dropout to softmax output)
        l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, bi, q_start,
                                 k_start, block_q, block_k, s_k_total,
                                 dropout_rate)
            p_acc = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        else:
            p_acc = p
        acc_scr[:] = acc_scr[:] * alpha + _dot(
            p_acc.astype(v.dtype), v, ((1,), (0,)))
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log(l_safe)
        if _lse_2d():
            lse_ref[...] = lse.reshape(lse_ref.shape)
        else:
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _seed_ops(seed, dropout_rate):
    """(operands, in_specs) for the dropout seed — a scalar in SMEM."""
    if dropout_rate <= 0.0:
        return [], []
    return [seed], [pl.BlockSpec(memory_space=pltpu.SMEM)]


def _flash_fwd(q, k, v, bias, seed, *, scale, causal, bias_kind, num_heads,
               dropout_rate, block_q, block_k, interpret):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    nq = pl.cdiv(s_q, block_q)
    nk = pl.cdiv(s_k, block_k)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bias_kind=bias_kind,
        dropout_rate=dropout_rate, s_k_total=s_k,
        block_q=block_q, block_k=block_k, num_k_blocks=nk)
    seed_ops, seed_specs = _seed_ops(seed, dropout_rate)
    bias_ops, bias_specs = _bias_specs(
        bias, bias_kind, num_heads, block_q, block_k,
        qmap=lambda i, j: i, kmap=lambda i, j: j)

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ] + bias_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
            if _lse_2d()
            else pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct(
                (bh, 1, s_q) if _lse_2d() else (bh, s_q, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*seed_ops, q, k, v, *bias_ops)
    return out, (lse[:, 0, :] if _lse_2d() else lse[:, :, 0])


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dkdv_kernel(*refs, scale, causal, bias_kind, dropout_rate,
                     s_k_total, block_q, block_k, num_q_blocks):
    seed_ref = None
    if dropout_rate > 0.0:
        seed_ref, *refs = refs
    if bias_kind == "none":
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        bias_ref = None
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = _col(lse_ref)                            # [bq, 1]
        delta = _col(delta_ref)                        # [bq, 1]
        s = _dot(q, k, ((1,), (1,))) * scale                  # [bq, bk] f32
        s = _apply_bias(s, bias_ref, bias_kind)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = (q_start + rows) >= (k_start + cols)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                                  # [bq, bk] f32
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, bi, q_start,
                                 k_start, block_q, block_k, s_k_total,
                                 dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
        else:
            p_drop = p
        dv_scr[:] += _dot(p_drop.astype(do.dtype), do, ((0,), (0,)))  # [bk,d]
        dp = _dot(do, v, ((1,), (1,)))                        # [bq, bk] f32
        if dropout_rate > 0.0:
            # dL/dP = keep/(1-r) * dO V^T; delta already equals
            # rowsum(P_drop o dP) = rowsum(dO o O)
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - delta) * scale
        dk_scr[:] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))   # [bk, d]

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, bias_kind, dropout_rate, s_k_total,
                   block_q, block_k, num_k_blocks):
    seed_ref = None
    if dropout_rate > 0.0:
        seed_ref, *refs = refs
    if bias_kind == "none":
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        bias_ref = None
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dq_ref, dq_scr) = refs
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = _col(lse_ref)
        delta = _col(delta_ref)
        s = _dot(q, k, ((1,), (1,))) * scale
        s = _apply_bias(s, bias_ref, bias_kind)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = (q_start + rows) >= (k_start + cols)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, ((1,), (1,)))
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, bi, q_start,
                                 k_start, block_q, block_k, s_k_total,
                                 dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta) * scale
        dq_scr[:] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd(res, g, *, scale, causal, bias_kind, num_heads, dropout_rate,
               block_q, block_k, interpret):
    q, k, v, bias, seed, out, lse = res
    do = g
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    # the backward sweeps accumulate into (block, d) fp32 scratch and run a
    # 5-matmul body — their best tile shape differs from the forward's;
    # independent env knobs let a sweep on the chip set them alone.
    # A knob with no 128-aligned divisor fails as loudly as the forward
    # does (flash_attention.py asserts in flash_attention()) — a partial
    # Pallas block would silently corrupt the gradients.
    block_q = _fit_block(min(_BWD_BLOCK_Q or block_q, s_q), s_q)
    block_k = _fit_block(min(_BWD_BLOCK_K or block_k, s_k), s_k)
    assert block_q is not None and block_k is not None, (
        f"flash backward: DSTPU_FLASH_BWD_BLOCK_Q/K={_BWD_BLOCK_Q}/"
        f"{_BWD_BLOCK_K} have no 128-aligned divisor of seq ({s_q}, {s_k})")
    nq = pl.cdiv(s_q, block_q)
    nk = pl.cdiv(s_k, block_k)

    # delta_i = rowsum(dO_i * O_i) — standard flash backward precompute
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if _lse_2d():
        lse_w = lse.astype(jnp.float32)[:, None, :]          # (bh, 1, s_q)
        delta_w = delta[:, None, :]
    else:
        lse_w = jnp.broadcast_to(
            lse[:, :, None], (bh, s_q, 128)).astype(jnp.float32)
        delta_w = jnp.broadcast_to(delta[:, :, None], (bh, s_q, 128))

    def stat_spec(index_q):
        """BlockSpec for the lse/delta operands; index_q maps grid ids to
        the q-block index."""
        if _lse_2d():
            return pl.BlockSpec((1, 1, block_q),
                                lambda b, x, y: (b, 0, index_q(x, y)))
        return pl.BlockSpec((1, block_q, 128),
                            lambda b, x, y: (b, index_q(x, y), 0))

    seed_ops, seed_specs = _seed_ops(seed, dropout_rate)
    # dkdv grid is (bh, k-block, q-block): bias maps transposed
    bias_ops, bias_specs = _bias_specs(
        bias, bias_kind, num_heads, block_q, block_k,
        qmap=lambda j, i: i, kmap=lambda j, i: j)
    dkdv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          bias_kind=bias_kind, dropout_rate=dropout_rate,
                          s_k_total=s_k,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq),
        grid=(bh, nk, nq),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            stat_spec(lambda j, i: i),
            stat_spec(lambda j, i: i),
        ] + bias_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*seed_ops, q, k, v, do, lse_w, delta_w, *bias_ops)
    dk, dv = dkdv

    bias_ops, bias_specs = _bias_specs(
        bias, bias_kind, num_heads, block_q, block_k,
        qmap=lambda i, j: i, kmap=lambda i, j: j)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bias_kind=bias_kind, dropout_rate=dropout_rate,
                          s_k_total=s_k,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk),
        grid=(bh, nq, nk),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            stat_spec(lambda i, j: i),
            stat_spec(lambda i, j: i),
        ] + bias_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*seed_ops, q, k, v, do, lse_w, delta_w, *bias_ops)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11,
                                                    12))
def _flash_attention_3d(q, k, v, bias, seed, scale, causal, bias_kind,
                        num_heads, dropout_rate, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, bias, seed, scale=scale, causal=causal,
                        bias_kind=bias_kind, num_heads=num_heads,
                        dropout_rate=dropout_rate,
                        block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def _flash_3d_fwd(q, k, v, bias, seed, scale, causal, bias_kind, num_heads,
                  dropout_rate, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, bias, seed, scale=scale, causal=causal,
                          bias_kind=bias_kind, num_heads=num_heads,
                          dropout_rate=dropout_rate,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return out, (q, k, v, bias, seed, out, lse)


def _flash_3d_bwd(scale, causal, bias_kind, num_heads, dropout_rate, block_q,
                  block_k, interpret, res, g):
    dq, dk, dv = _flash_bwd(res, g, scale=scale, causal=causal,
                            bias_kind=bias_kind, num_heads=num_heads,
                            dropout_rate=dropout_rate,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)
    # bias is a constant additive mask (HF extended mask / key padding):
    # no gradient is produced for it (zeros keep the vjp total)
    dbias = None if res[3] is None else jnp.zeros_like(res[3])
    # integer primals take float0 cotangents (JAX convention for the int32
    # seed; a zeros_like int cotangent only works by accident)
    dseed = None if res[4] is None else \
        jnp.zeros(res[4].shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


# nondiff args start at 5: scale, causal, bias_kind, num_heads,
# dropout_rate, blocks, interpret
_flash_attention_3d.defvjp(_flash_3d_fwd, _flash_3d_bwd)


def flash_attention(q, k, v, *, bias=None, causal: bool = False,
                    scale: Optional[float] = None,
                    dropout_rate: float = 0.0, dropout_seed=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None):
    """Flash attention over [batch, heads, seq, head_dim] tensors.

    bias: optional ADDITIVE attention bias — (B, 1, 1, S_k) HF extended
    mask / key-padding form, or any shape broadcastable to (B, H, S_q, S_k).
    Treated as a constant (no bias gradient). Differentiable in q/k/v
    (custom VJP with blockwise recomputation). On the CPU backend runs in
    Pallas interpreter mode (slow; tests only).

    dropout_rate/dropout_seed: in-kernel attention dropout. The seed (int
    scalar or 0-d/1-elem int32 array, typically drawn per-step from the
    engine's dropout rng) fully determines the keep mask; backward
    regenerates it from positions, nothing is stored.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    # kernel causal mask is top-left aligned (q_idx >= k_idx from 0); with
    # s_q != s_k that diverges from bottom-right-aligned decode semantics
    assert not causal or s_q == s_k, (
        f"causal flash attention requires equal q/k lengths, got ({s_q}, {s_k}); "
        f"use the jnp path for cross-length (decode) attention")
    # shrink each block to the largest 128-aligned divisor of the sequence
    # length: any s % 128 == 0 stays on the kernel (e.g. 640 uses
    # 128-blocks rather than failing the 512-default divisibility — partial
    # Pallas blocks would silently corrupt the softmax, so divisibility is
    # non-negotiable and unaligned lengths fail loudly)
    block_q = _fit_block(block_q, s_q)
    block_k = _fit_block(block_k, s_k)
    assert block_q is not None and block_k is not None, (
        f"seq lengths ({s_q}, {s_k}) have no 128-aligned block divisor; "
        f"pad the sequence to a multiple of 128 or use the jnp path")
    scale = (d ** -0.5) if scale is None else scale
    bias_kind = "none"
    bias3 = None
    if bias is not None:
        assert bias.ndim == 4, f"bias must be 4D, got shape {bias.shape}"
        if bias.shape[1] == 1 and bias.shape[2] == 1:
            # key-padding bias: one row per batch, broadcast over heads/rows
            bias_kind = "key"
            bias3 = jnp.broadcast_to(
                bias[:, 0, :, :], (b, 1, s_k)).astype(jnp.float32)
        else:
            bias_kind = "full"
            bias3 = jnp.broadcast_to(
                bias, (b, h, s_q, s_k)).astype(jnp.float32).reshape(
                    b * h, s_q, s_k)
    dropout_rate = float(dropout_rate)
    assert 0.0 <= dropout_rate < 1.0, f"bad dropout_rate {dropout_rate}"
    seed1 = None
    if dropout_rate > 0.0:
        assert dropout_seed is not None, \
            "dropout_rate > 0 requires dropout_seed"
        seed1 = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
    q3 = q.reshape(b * h, s_q, d)
    k3 = k.reshape(b * h, k.shape[2], d)
    v3 = v.reshape(b * h, v.shape[2], d)
    out = _flash_attention_3d(q3, k3, v3, bias3, seed1, scale, causal,
                              bias_kind, h, dropout_rate, block_q, block_k,
                              interpret)
    return out.reshape(b, h, s_q, d)
