"""Attention of a chunk of queries over a longer run of cached keys.

Chunked prefill attends C queries, the first at absolute position
``q_start``, to every cached key up to each query's own position: a
rectangle (C x S) with a causal offset.  ``functional.py``'s dispatch gives
the flash kernel only ``k.shape == q.shape``, and the plain path would hold
the whole (H, C, S) score tensor in f32 (6.5 GB at 32 x 2048 x 24832), so
this kernel walks the keys in blocks with the online softmax and keeps one
(block_q, block_k) tile of scores.  Forward only: serving.

``q_start`` is a traced scalar (scalar prefetch), so one compiled program
serves every chunk of a prompt.  Key blocks that lie wholly after a query
block's last position are neither fetched (their block index is clamped to
the last one needed) nor computed.  The softmax scale is the caller's: it
is folded into ``q``.

``mla_decode_attention`` is the decode side of latent (MLA) pages: one
query a lane against the gathered latent rows, with the up-projections
absorbed into the query and the output, in plain ``jax.numpy``.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import \
    _interpret_default

KERNEL_NAME = "mla_prefill_attn"
NEG_INF = -1e30
_MIN_ROWS = 128


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(q_start_ref, *refs, block_q, block_k, num_k_blocks, shared):
    if shared:
        q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, m_scr, l_scr, acc_scr = \
            refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_lo = q_start_ref[0] + qi * block_q      # position of the first query
    k_lo = ki * block_k

    def accumulate(masked):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _dot(q, k, ((1,), (1,)))                        # (bq, bk) f32
        if shared:      # the key part all heads share: one more product
            s = s + _dot(qs_ref[0], ks_ref[...], ((1,), (1,)))
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_lo + rows >= k_lo + cols, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot(p.astype(v.dtype), v,
                                               ((1,), (0,)))
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # a key block wholly before the block's first query needs no mask (the
    # softmax's elementwise passes bound this kernel, not the MXU: the
    # mask is a quarter of them); one that straddles the diagonal does;
    # one wholly after the last query is skipped
    whole = k_lo + block_k - 1 <= q_lo
    pl.when(whole)(lambda: accumulate(False))
    pl.when(jnp.logical_and(jnp.logical_not(whole),
                            k_lo <= q_lo + block_q - 1))(
        lambda: accumulate(True))

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)) \
            .astype(o_ref.dtype)


def _fit(block, n):
    """Largest block <= ``block`` that divides n, halving (n if smaller)."""
    if n <= block:
        return n
    while n % block:
        block //= 2
    return block


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def rect_flash_attention(q, k, v, q_start, q_shared=None, k_shared=None, *,
                         block_q=512, block_k=512, interpret=None):
    """q: (H, C, D), scale folded in; k: (H, S, D); v: (H, S, Dv); query i
    stands at absolute position ``q_start + i`` and sees keys 0 ..
    ``q_start + i`` (key j is position j).  ``q_shared`` (H, C, Ds) and
    ``k_shared`` (S, Ds): a further part of the scores whose keys ALL heads
    share (MLA's rotary key), ``q_shared . k_shared`` added to ``q . k``
    without the shared keys ever being copied per head.  Returns
    (H, C, Dv) in q's dtype.  Key blocks wholly after the last query's
    position are never read; inside the last block read, rows past it are
    masked in the scores but meet the zero weights in the value product,
    so they must be finite (the caller zeroes what no query may see)."""
    H, C, D = q.shape
    _, S, Dv = v.shape
    shared = q_shared is not None
    assert k.shape == (H, S, D), (q.shape, k.shape, v.shape)
    if interpret is None:
        interpret = _interpret_default()
    rows = -(-C // _MIN_ROWS) * _MIN_ROWS       # whole tiles of queries
    if rows != C:
        pad = ((0, 0), (0, rows - C), (0, 0))
        q = jnp.pad(q, pad)
        q_shared = jnp.pad(q_shared, pad) if shared else None
    bq, bk = _fit(block_q, rows), _fit(block_k, S)
    assert S % bk == 0 and rows % bq == 0, (rows, S, bq, bk)
    nk = S // bk

    def last_needed(qi, qs):
        # the last key block any query of block qi may see
        return jnp.minimum((qs[0] + (qi + 1) * bq - 1) // bk, nk - 1)

    def q_map(h, qi, ki, qs):
        return h, qi, 0

    def kv_map(h, qi, ki, qs):
        return h, jnp.minimum(ki, last_needed(qi, qs)), 0

    in_specs = [pl.BlockSpec((1, bq, D), q_map),
                pl.BlockSpec((1, bk, D), kv_map),
                pl.BlockSpec((1, bk, Dv), kv_map)]
    operands = [q, k, v]
    if shared:
        Ds = q_shared.shape[-1]
        assert q_shared.shape == (H, rows, Ds) and k_shared.shape == (S, Ds)
        in_specs += [
            pl.BlockSpec((1, bq, Ds), q_map),
            pl.BlockSpec((bk, Ds), lambda h, qi, ki, qs: (
                jnp.minimum(ki, last_needed(qi, qs)), 0))]
        operands += [q_shared, k_shared]
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=bq, block_k=bk, num_k_blocks=nk,
                          shared=shared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, rows // bq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, Dv), q_map),
            scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, Dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((H, rows, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.asarray(q_start, jnp.int32).reshape(1), *operands)
    return out[:, :C]


def mla_decode_attention(q_lat, q_rope, latent, n_keys, latent_rank):
    """One query a lane over gathered latent rows, up-projections absorbed.

    q_lat: (B, H, R) = q_nope . W_uk, scale folded in; q_rope: (B, H, Dr);
    latent: (B, S, >= R + Dr) rows ``[c_kv | k_rope | zeros]`` in position
    order (the pool stores a row padded to whole lanes, and the view is not
    cut back: the query is padded instead); n_keys: (B,) keys a lane may
    see (positions 0 .. n_keys - 1).  Returns (B, H, R):
    ``softmax(scores) . c_kv``, to be taken through W_uv by the caller.
    Scores and softmax in f32, masked keys exactly zero."""
    with jax.named_scope("mla_decode_attn"):
        S, stored = latent.shape[1:]
        seen = jnp.arange(S)[None, :] < n_keys[:, None]          # (B, S)
        # 0 * NaN = NaN: a masked row must not reach the value product
        latent = jnp.where(seen[:, :, None], latent, 0)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, stored - q.shape[-1])))
        s = jnp.einsum("bhd,bsd->bhs", q, latent,
                       preferred_element_type=jnp.float32)
        s = jnp.where(seen[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(latent.dtype)
        # over the whole stored row, then cut: cutting the rows first would
        # copy the gathered view (bound by its bytes, not by the product)
        return jnp.einsum("bhs,bsc->bhc", p, latent)[..., :latent_rank]
