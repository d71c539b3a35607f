"""Pallas TPU block-sparse attention — layout-driven flash kernel.

TPU-native replacement for the reference's Triton SDD/softmax/DSD pipeline
(reference deepspeed/ops/sparse_attention/matmul.py:16-750, softmax.py:17-304,
trsrc/*.tr): instead of three kernel launches with materialized block-sparse
score storage, ONE fused kernel walks, per (batch*head, q_block), only the
active k-blocks listed in a lookup table built from the SparsityConfig
layout (the analog of the reference's LUT construction, matmul.py:98-241),
maintaining a flash-style online softmax. Compute and memory are
O(active_blocks), giving the reference's "10x longer sequences" scaling law
on the MXU.

LUT encoding (host-built from the (H, nb, nb) layout):
  cols[h, qb, a]  = column (k-block) index of the a'th active block
  nnz[h, qb]      = number of active blocks in the row
  rows_t / nnz_t  = the transpose LUT (per k-block active q-blocks), used by
                    the dk/dv backward sweep.
Padded entries point at block 0 and are skipped via `a < nnz`.

Masking is block-granular, matching the XLA reference path
(sparse_self_attention.layout_to_token_mask).
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import (
    NEG_INF, _interpret_default)

# scalar memory per TensorCore on TPU v4 and later (jax's own
# pallas/mosaic/tpu_info.py table); the scalar-prefetched LUT lives there
SMEM_BYTES = 1 << 20


def _check_lut_fits_smem(which, block, *luts):
    """Fail at trace time when a kernel's scalar-prefetched LUT cannot fit
    SMEM — the compiler's own RESOURCE_EXHAUSTED names neither the layout
    nor the block size."""
    need = sum(4 * int(np.asarray(a).size) for a in luts)
    if need > SMEM_BYTES:
        raise ValueError(
            f"block-sparse attention ({which}): the layout's lookup table "
            f"needs {need} bytes of scalar memory (SMEM) at block size "
            f"{block}, the chip has {SMEM_BYTES}; use a larger block (the "
            f"table shrinks with the square of the block size)")


def _key_bias_blocks(key_bias, block):
    """(B, S) per-key bias -> (B * S/block, 1, block), so a (1, 1, block)
    BlockSpec spans whole trailing dims — the TPU lowering refuses a
    (1, block) block of a 2-D (B, S) array at any block size."""
    B, S = key_bias.shape
    return key_bias.reshape(B * (S // block), 1, block)


def build_luts(layout):
    """layout (H, nb, nb) 0/1 -> (cols, nnz, rows_t, nnz_t) int32 arrays.

    cols: (H, nb, max_nnz) forward LUT; rows_t: (H, nb, max_nnz_t)
    transpose LUT. Padding entries are 0 (skipped via the nnz counts)."""
    layout = np.asarray(layout) != 0
    H, nb, _ = layout.shape
    nnz = layout.sum(-1).astype(np.int32)                  # (H, nb)
    nnz_t = layout.sum(1).astype(np.int32)                 # (H, nb)
    max_nnz = max(1, int(nnz.max()))
    max_nnz_t = max(1, int(nnz_t.max()))
    cols = np.zeros((H, nb, max_nnz), np.int32)
    rows_t = np.zeros((H, nb, max_nnz_t), np.int32)
    for h in range(H):
        for qb in range(nb):
            idx = np.flatnonzero(layout[h, qb])
            cols[h, qb, :len(idx)] = idx
        for kb in range(nb):
            idx = np.flatnonzero(layout[h, :, kb])
            rows_t[h, kb, :len(idx)] = idx
    return cols, nnz, rows_t, nnz_t


# ---------------------------------------------------------------------------
# forward: grid (bh, nq, max_nnz), k/v blocks indexed through the LUT
# ---------------------------------------------------------------------------
def _fwd_kernel(cols_ref, nnz_ref, *refs, scale, heads, max_nnz, nq,
                has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, kb_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        kb_ref = None
    ai = pl.program_id(2)

    @pl.when(ai == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    b = pl.program_id(0)
    qi = pl.program_id(1)
    h = jax.lax.rem(b, heads)
    active = ai < nnz_ref[h * nq + qi]

    @pl.when(active)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if kb_ref is not None:
            # per-key additive bias (key padding): (1, block) row broadcast
            s = s + kb_ref[0]
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, 0:1] * alpha + jnp.sum(p, -1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ai == max_nnz - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # empty rows (no active block) emit zeros, like the XLA path
        o_ref[0] = jnp.where(l > 0.0, acc_scr[:] / l_safe, 0.0
                             ).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_scr[:, 0:1] + jnp.log(l_safe),
                                      lse_ref.shape[1:])


def _sparse_fwd(q, k, v, cols, nnz, *, scale, block, heads, interpret,
                key_bias=None):
    bh, S, d = q.shape
    nq = S // block
    max_nnz = cols.shape[-1]
    _check_lut_fits_smem("forward", block, cols, nnz)
    cols_flat = jnp.asarray(np.asarray(cols).reshape(-1), jnp.int32)
    nnz_flat = jnp.asarray(np.asarray(nnz).reshape(-1), jnp.int32)

    def kv_index(b, qi, ai, cols_ref, nnz_ref):
        h = jax.lax.rem(b, heads)
        kb = cols_ref[(h * nq + qi) * max_nnz + ai]
        return (b, kb, 0)

    def kb_index(b, qi, ai, cols_ref, nnz_ref):
        h = jax.lax.rem(b, heads)
        kb = cols_ref[(h * nq + qi) * max_nnz + ai]
        return ((b // heads) * nq + kb, 0, 0)

    bias_ops = [] if key_bias is None else \
        [_key_bias_blocks(key_bias, block)]
    bias_specs = [] if key_bias is None else \
        [pl.BlockSpec((1, 1, block), kb_index)]
    kernel = functools.partial(_fwd_kernel, scale=scale, heads=heads,
                               max_nnz=max_nnz, nq=nq,
                               has_bias=key_bias is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, max_nnz),
        in_specs=[
            pl.BlockSpec((1, block, d),
                         lambda b, qi, ai, cols_ref, nnz_ref: (b, qi, 0)),
            pl.BlockSpec((1, block, d), kv_index),
            pl.BlockSpec((1, block, d), kv_index),
        ] + bias_specs,
        out_specs=[
            pl.BlockSpec((1, block, d),
                         lambda b, qi, ai, cols_ref, nnz_ref: (b, qi, 0)),
            pl.BlockSpec((1, block, 128),
                         lambda b, qi, ai, cols_ref, nnz_ref: (b, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bh, S, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, S, 128), jnp.float32)],
        interpret=interpret,
    )(cols_flat, nnz_flat, q, k, v, *bias_ops)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward: dq walks the forward LUT; dk/dv walk the transpose LUT
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(cols_ref, nnz_ref, *refs, scale, heads, max_nnz, nq,
                   has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kb_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        kb_ref = None
    ai = pl.program_id(2)

    @pl.when(ai == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    b = pl.program_id(0)
    qi = pl.program_id(1)
    h = jax.lax.rem(b, heads)
    active = ai < nnz_ref[h * nq + qi]

    @pl.when(active)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if kb_ref is not None:
            s = s + kb_ref[0]
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ai == max_nnz - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(rows_ref, nnzt_ref, *refs, scale, heads, max_nnz_t, nk,
                     has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kb_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        kb_ref = None
    ai = pl.program_id(2)

    @pl.when(ai == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    b = pl.program_id(0)
    ki = pl.program_id(1)
    h = jax.lax.rem(b, heads)
    active = ai < nnzt_ref[h * nk + ki]

    @pl.when(active)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if kb_ref is not None:
            # this kernel's s is (q_rows, k_rows) with k fixed to block ki:
            # the bias row for block ki broadcasts over q rows
            s = s + kb_ref[0]
        p = jnp.exp(s - lse)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ai == max_nnz_t - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _sparse_bwd(res, do, *, scale, block, heads, interpret):
    q, k, v, key_bias, out, lse, cols, nnz, rows_t, nnz_t = res
    bh, S, d = q.shape
    nq = S // block
    max_nnz = cols.shape[-1]
    max_nnz_t = rows_t.shape[-1]
    _check_lut_fits_smem("backward dq", block, cols, nnz)
    _check_lut_fits_smem("backward dk/dv", block, rows_t, nnz_t)
    cols_flat = jnp.asarray(np.asarray(cols).reshape(-1), jnp.int32)
    nnz_flat = jnp.asarray(np.asarray(nnz).reshape(-1), jnp.int32)
    rows_flat = jnp.asarray(np.asarray(rows_t).reshape(-1), jnp.int32)
    nnzt_flat = jnp.asarray(np.asarray(nnz_t).reshape(-1), jnp.int32)

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                    # (bh, S)
    lse_w = jnp.broadcast_to(lse[:, :, None], (bh, S, 128)).astype(jnp.float32)
    delta_w = jnp.broadcast_to(delta[:, :, None], (bh, S, 128))

    def q_row(b, i, ai, *refs):
        return (b, i, 0)

    # ---- dq: forward LUT ------------------------------------------------
    def kv_from_cols(b, qi, ai, cols_ref, nnz_ref):
        h = jax.lax.rem(b, heads)
        return (b, cols_ref[(h * nq + qi) * max_nnz + ai], 0)

    def kb_from_cols(b, qi, ai, cols_ref, nnz_ref):
        h = jax.lax.rem(b, heads)
        kb = cols_ref[(h * nq + qi) * max_nnz + ai]
        return ((b // heads) * nq + kb, 0, 0)

    bias_ops = [] if key_bias is None else \
        [_key_bias_blocks(key_bias, block)]
    dq_bias_specs = [] if key_bias is None else \
        [pl.BlockSpec((1, 1, block), kb_from_cols)]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, heads=heads,
                          max_nnz=max_nnz, nq=nq,
                          has_bias=key_bias is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, max_nnz),
            in_specs=[
                pl.BlockSpec((1, block, d), q_row),
                pl.BlockSpec((1, block, d), kv_from_cols),
                pl.BlockSpec((1, block, d), kv_from_cols),
                pl.BlockSpec((1, block, d), q_row),
                pl.BlockSpec((1, block, 128), q_row),
                pl.BlockSpec((1, block, 128), q_row),
            ] + dq_bias_specs,
            out_specs=pl.BlockSpec((1, block, d), q_row),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, S, d), q.dtype),
        interpret=interpret,
    )(cols_flat, nnz_flat, q, k, v, do, lse_w, delta_w, *bias_ops)

    # ---- dk/dv: transpose LUT ------------------------------------------
    def q_from_rows(b, ki, ai, rows_ref, nnzt_ref):
        h = jax.lax.rem(b, heads)
        return (b, rows_ref[(h * nq + ki) * max_nnz_t + ai], 0)

    def k_row(b, ki, ai, *refs):
        return (b, ki, 0)

    dkdv_bias_specs = [] if key_bias is None else \
        [pl.BlockSpec((1, 1, block),
                      lambda b, ki, ai, *r: ((b // heads) * nq + ki, 0, 0))]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, heads=heads,
                          max_nnz_t=max_nnz_t, nk=nq,
                          has_bias=key_bias is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, max_nnz_t),
            in_specs=[
                pl.BlockSpec((1, block, d), q_from_rows),
                pl.BlockSpec((1, block, d), k_row),
                pl.BlockSpec((1, block, d), k_row),
                pl.BlockSpec((1, block, d), q_from_rows),
                pl.BlockSpec((1, block, 128), q_from_rows),
                pl.BlockSpec((1, block, 128), q_from_rows),
            ] + dkdv_bias_specs,
            out_specs=[pl.BlockSpec((1, block, d), k_row),
                       pl.BlockSpec((1, block, d), k_row)],
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((bh, S, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, S, d), v.dtype)],
        interpret=interpret,
    )(rows_flat, nnzt_flat, q, k, v, do, lse_w, delta_w, *bias_ops)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry: differentiable block-sparse attention over a layout
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _sparse_attention_core(q3, k3, v3, key_bias, luts, scale, heads,
                           interpret):
    out, _ = _sparse_fwd(q3, k3, v3, luts[0], luts[1], scale=scale,
                         block=q3.shape[1] // luts[1].shape[1], heads=heads,
                         interpret=interpret, key_bias=key_bias)
    return out


def _core_fwd(q3, k3, v3, key_bias, luts, scale, heads, interpret):
    block = q3.shape[1] // luts[1].shape[1]
    out, lse = _sparse_fwd(q3, k3, v3, luts[0], luts[1], scale=scale,
                           block=block, heads=heads, interpret=interpret,
                           key_bias=key_bias)
    return out, (q3, k3, v3, key_bias, out, lse)


def _core_bwd(luts, scale, heads, interpret, res, do):
    q3, k3, v3, key_bias, out, lse = res
    block = q3.shape[1] // luts[1].shape[1]
    full_res = (q3, k3, v3, key_bias, out, lse,
                luts[0], luts[1], luts[2], luts[3])
    dq, dk, dv = _sparse_bwd(full_res, do, scale=scale, block=block,
                             heads=heads, interpret=interpret)
    # key padding is a constant mask, no gradient (flash kernel convention)
    dkb = None if key_bias is None else jnp.zeros_like(key_bias)
    return dq, dk, dv, dkb


_sparse_attention_core.defvjp(_core_fwd, _core_bwd)


def pallas_block_sparse_attention(q, k, v, layout, block: int,
                                  scale: Optional[float] = None,
                                  key_bias=None,
                                  interpret: Optional[bool] = None):
    """(B, H, S, D) block-sparse attention over a (H, S/block, S/block)
    layout via the LUT-driven Pallas kernels. Differentiable in q/k/v.

    key_bias: optional (B, S) ADDITIVE per-key bias (key-padding mask,
    -inf/-1e30 for padded keys) applied inside the kernel — long-sequence
    BERT keeps its padding mask without falling back to the O(S^2) path.
    Treated as constant (no gradient)."""
    if interpret is None:
        interpret = _interpret_default()
    B, H, S, D = q.shape
    assert S % block == 0
    scale = (D ** -0.5) if scale is None else scale
    luts = build_luts(layout)
    # hashable static LUTs for custom_vjp nondiff arg
    luts = tuple(np.asarray(a) for a in luts)
    q3 = q.reshape(B * H, S, D)
    k3 = k.reshape(B * H, S, D)
    v3 = v.reshape(B * H, S, D)
    if key_bias is not None:
        assert key_bias.shape == (B, S), key_bias.shape
        key_bias = jnp.asarray(key_bias, jnp.float32)
    out = _sparse_attention_core(q3, k3, v3, key_bias, _HashableLuts(luts),
                                 scale, H, interpret)
    return out.reshape(B, H, S, D)


class _HashableLuts(tuple):
    """numpy LUTs as a hashable static arg (id-keyed hash is fine: LUTs are
    rebuilt per layout object and layouts are cached by SparseSelfAttention)."""

    def __new__(cls, arrays):
        return super().__new__(cls, arrays)

    def __hash__(self):
        return hash(tuple(a.tobytes() for a in self))

    def __eq__(self, other):
        return isinstance(other, _HashableLuts) and \
            all((a == b).all() for a, b in zip(self, other))
