"""Attention of a chunk of queries over a longer run of cached keys.

Chunked prefill attends C queries, the first at absolute position
``q_start``, to every cached key up to each query's own position: a
rectangle (C x S) with a causal offset.  ``functional.py``'s dispatch gives
the flash kernel only ``k.shape == q.shape``, and the plain path would hold
the whole (H, C, S) score tensor in f32 (6.5 GB at 32 x 2048 x 24832), so
this kernel walks the keys in blocks with the online softmax and keeps one
(block_q, block_k) tile of scores.  Forward only: serving.

The blocks are the ones asked for, whatever S is: the last key block of
the view may be ragged.  What a grid step costs beside its two products is
paid per ROW of the tile (two cross-lane reductions, the broadcasts of the
running maximum and of the rescale, the state read and written), so a wide
key block is what makes the products the larger part; the scores are ONE
product over the head's and the shared key part, put side by side.
Scores, running maximum and sum, exponentials and the accumulator are f32;
only p is rounded to the values' dtype for its product.

``q_start`` is a traced scalar (scalar prefetch), so one compiled program
serves every chunk of a prompt.  Key blocks that lie wholly after a query
block's last position are neither fetched (their block index is clamped to
the last one needed) nor computed; in the last block a query may see, the
scores past it are masked, and the value rows past the LAST query, or past
the view's end where the padded chunk runs beyond it, are zeroed in the
kernel (0 * NaN = NaN, and what a ragged block holds past the view's end
was never written), so the caller need define nothing past the last query.
The softmax scale is the caller's: it is folded into ``q``.

Grouped-query heads: ``k`` and ``v`` may carry fewer heads than ``q``
(``H = G * Hkv``); query head ``h`` then reads key head ``h // G`` through
the key blocks' index map, and no key is repeated in memory.  ``k_start``
(a traced scalar beside ``q_start``) says that key j stands at absolute
position ``k_start + j``: the view of a cache group that keeps a window
begins at the oldest page the lane still holds.  ``window`` (static) bounds
what a query sees from below: the keys at positions ``p - window + 1 .. p``
of a query at ``p``; key blocks wholly below the first query of a query
block's window are neither fetched nor computed, the edge block is masked
and its value rows below that window zeroed.  Without the three the traced
kernel is what it was (the latent models' instance, their cells' yardstick).

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


def _kernel(q_start_ref, q_ref, k_ref, v_ref, *refs, queries, keys,
            shared, offset=False, window=None):
    if shared:
        ks_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_lo = q_start_ref[0] + qi * block_q      # position of the first query
    # the last position anybody sees: of the last query that is no padding,
    # or of the view's last key where the (padded) chunk runs past it
    if offset:      # key j stands at position k_start + j
        q_last = jnp.minimum(q_start_ref[0] + queries,
                             q_start_ref[1] + keys) - 1
        k_lo = q_start_ref[1] + ki * block_k
    else:
        q_last = jnp.minimum(q_start_ref[0] + queries, keys) - 1
        k_lo = ki * block_k                   # position of the first key
    if window is not None:
        # the lowest key the block's first and last query see
        lo_first = jnp.minimum(q_lo, q_last) - (window - 1)
        lo_last = jnp.minimum(q_lo + block_q - 1, q_last) - (window - 1)

    def accumulate(masked):
        k, v = k_ref[0], v_ref[0]
        if shared:      # [k | k_shared]: ONE product over D + Ds
            k = jnp.concatenate([k, ks_ref[...]], axis=1)
        s = _dot(q_ref[0], k, ((1,), (1,)))                 # (bq, bk) f32
        if masked:
            # a query sees the keys up to its own position, and none past
            # `q_last`.  The value rows past it hold anything (past the
            # view's end they were never written): 0 * NaN = NaN, so they
            # must not reach the value product
            sees = jnp.minimum(q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0), q_last) - k_lo
            if window is None:
                s = jnp.where(jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1) <= sees, s, NEG_INF)
                row = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                v = jnp.where(row <= q_last - k_lo, v, 0)
            else:
                # and none below its window; a row that sees nothing of
                # this block is wiped by the rescale of the block in which
                # it first sees a key (its own position, at the latest)
                col = jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where((col <= sees) & (col > sees - window), s,
                              NEG_INF)
                row = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                v = jnp.where((row <= q_last - k_lo)
                              & (row >= lo_first - k_lo), v, 0)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot(p.astype(v.dtype), v,
                                               ((1,), (0,)))

    # a block wholly before the first query (and inside the view: the
    # common one of a long context) needs no mask; one that no query of
    # the block may see is not computed
    whole = k_lo + block_k - 1 <= jnp.minimum(q_lo, q_last)
    if window is None:
        pl.when(whole)(functools.partial(accumulate, False))
        pl.when(jnp.logical_not(whole) & (
            k_lo <= jnp.minimum(q_lo + block_q - 1, q_last)))(
                functools.partial(accumulate, True))
    else:
        # nor is a block wholly below the first query's window computed,
        # and only one at or above the last query's needs no mask
        whole = whole & (k_lo >= lo_last)
        pl.when(whole)(functools.partial(accumulate, False))
        pl.when(jnp.logical_not(whole)
                & (k_lo <= jnp.minimum(q_lo + block_q - 1, q_last))
                & (k_lo + block_k - 1 >= lo_first))(
                    functools.partial(accumulate, True))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        # every query sees its own position: the sum is never zero
        o_ref[0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)


def _blocks(C, S, block_q=None, block_k=None):
    """(rows, block_q, block_k): the C queries padded to ``rows`` (whole
    tiles of 128) and walked ``block_q`` at a time, the keys ``block_k`` a
    grid step.  No block is cut to a divisor of S: the last may be ragged.

    Measured on the v5e at 32 x (64 | 64 / 128) over 24,832 keys and 64 x
    (128 | 64 / 128) over 1,664, bf16: a (1024, 1024) tile is the fastest
    that fits (a key block of 512 takes 1.6 times as long, one of 2,048 or
    a query block of 512 a tenth longer).  In VMEM: the f32 scores 4 MB
    and their exponentials 2 MB in bf16, k, v and the shared keys 0.25 MB
    a block each (lanes padded to 128) twice over, q and the result 0.25
    MB twice over, the running state 1.5 MB: ~10 MB, under the chip's
    default scoped limit of 16."""
    rows = -(-C // _MIN_ROWS) * _MIN_ROWS
    bq = min(block_q or 1024, rows)
    while rows % bq:
        bq //= 2
    return rows, bq, min(block_k or 1024, S)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_q", "block_k",
                                    "interpret", "name"))
def rect_flash_attention(q, k, v, q_start, q_shared=None, k_shared=None, *,
                         k_start=None, window=None, block_q=None,
                         block_k=None, interpret=None, name=KERNEL_NAME):
    """q: (H, C, D), scale folded in; k: (Hkv, S, D); v: (Hkv, S, Dv),
    ``H`` a multiple of ``Hkv`` (query head h reads key head
    ``h // (H // Hkv)``); query i stands at absolute position
    ``q_start + i`` and sees keys 0 .. ``q_start + i`` (key j is position
    j, or ``k_start + j`` where a traced ``k_start`` is given; with a
    static ``window`` only the last ``window`` of them, its own position
    included; a query past the view's end, as the padding of a chunk may
    be, sees what the last real query sees).
    ``q_shared`` (H, C, Ds) and ``k_shared`` (S, Ds): a further part of the
    scores whose keys ALL heads share (MLA's rotary key): the scores are
    ``[q | q_shared] . [k | k_shared]``, one product, the shared keys put
    beside each head's in VMEM and never copied per head in HBM.  Returns
    (H, C, Dv) in q's dtype.  Rows of k, v and k_shared past the last
    query's position may hold anything: blocks wholly past it are never
    read, and in the last block read the scores past it are masked and the
    value rows zeroed."""
    H, C, D = q.shape
    Hkv, S, Dv = v.shape
    shared = q_shared is not None
    assert k.shape == (Hkv, S, D) and H % Hkv == 0, \
        (q.shape, k.shape, v.shape)
    G = H // Hkv
    offset = k_start is not None
    if interpret is None:
        interpret = _interpret_default()
    rows, bq, bk = _blocks(C, S, block_q, block_k)
    if shared:
        Ds = q_shared.shape[-1]
        assert q_shared.shape == (H, C, Ds) and k_shared.shape == (S, Ds)
        q = jnp.concatenate([q, q_shared], axis=-1)     # C rows: cheap
    if rows != C:
        q = jnp.pad(q, ((0, 0), (0, rows - C), (0, 0)))

    def q_map(h, qi, ki, qs):
        return h, qi, 0

    def kv_map(h, qi, ki, qs):
        # a block that no query of block qi may see is not fetched: the
        # index stays at the last one needed (and, under a window, at the
        # first)
        if not offset and window is None and G == 1:
            return h, jnp.minimum(
                ki, (qs[0] + jnp.minimum((qi + 1) * bq, C) - 1) // bk), 0
        k0 = qs[1] if offset else 0
        last = (qs[0] + jnp.minimum((qi + 1) * bq, C) - 1 - k0) // bk
        at = jnp.minimum(ki, last)
        if window is not None:
            first = jnp.maximum(
                (qs[0] + qi * bq - (window - 1) - k0) // bk, 0)
            at = jnp.maximum(at, jnp.minimum(first, last))
        return h // G, at, 0

    in_specs = [pl.BlockSpec((1, bq, q.shape[-1]), q_map),
                pl.BlockSpec((1, bk, D), kv_map),
                pl.BlockSpec((1, bk, Dv), kv_map)]
    operands = [q, k, v]
    if shared:
        in_specs.append(pl.BlockSpec((bk, Ds),
                                     lambda *at: kv_map(*at)[1:]))
        operands.append(k_shared)
    out = pl.pallas_call(
        functools.partial(_kernel, queries=C, keys=S, shared=shared,
                          offset=offset, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, rows // bq, pl.cdiv(S, bk)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, Dv), q_map),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, Dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((H, rows, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(jnp.stack([jnp.asarray(q_start, jnp.int32),
                 jnp.asarray(k_start, jnp.int32)]) if offset
      else jnp.asarray(q_start, jnp.int32).reshape(1), *operands)
    return out[:, :C]


def mla_decode_attention(q_lat, q_rope, latent, n_keys, latent_rank,
                         starts=None):
    """One query a lane over gathered latent rows, up-projections absorbed.

    q_lat: (B, H, R) = q_nope . W_uk, scale folded in; q_rope: (B, H, Dr);
    latent: (B, S, >= R + Dr) rows ``[c_kv | k_rope | zeros]`` in position
    order (the pool stores a row padded to whole lanes, and the view is not
    cut back: the query is padded instead); n_keys: (B,) keys a lane may
    see (rows 0 .. n_keys - 1 of the view; from row ``starts`` (B,) on
    where given: a window).  Returns (B, H, R):
    ``softmax(scores) . c_kv``, to be taken through W_uv by the caller.
    Scores and softmax in f32, masked keys exactly zero."""
    with jax.named_scope("mla_decode_attn"):
        S, stored = latent.shape[1:]
        seen = jnp.arange(S)[None, :] < n_keys[:, None]          # (B, S)
        if starts is not None:
            seen = seen & (jnp.arange(S)[None, :] >= starts[:, None])
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
