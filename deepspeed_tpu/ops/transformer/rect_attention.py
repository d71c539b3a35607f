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
begins at the oldest page the lane still holds.  Without the two the
traced kernel is what it was (the latent models' instance, their cells'
yardstick).

``window`` (static) bounds what a query sees from below: the keys at
positions ``p - window + 1 .. p`` of a query at ``p``.  Such a call walks a
BAND, not the rectangle, in a body of its own (``_band_kernel``): a block
of ``block_q`` queries sees ``block_q + window - 1`` keys, so one grid step
= (key head, query block) takes the ``G`` query heads of the key head as
``G * block_q`` rows against that band of the head's view, in one pass:
scores, mask, softmax and the value product, no key grid, no running
maximum, no rescale.  The view of a window group is short by construction
(the window, a chunk and a page), so a head's view stands whole in VMEM,
fetched once a head, and each step cuts its band from it at a whole tile of
rows; what the band holds beside the block's windows is masked, and its
value rows that no query of the block sees are zeroed as above.  The
rectangle's tile held eight windows of 128 (sixteen times the pairs that
count, through the online softmax): ``_blocks`` says what each costs.

``mla_decode_attention`` is the decode side of latent (MLA) pages: one
query a lane against the gathered latent rows, with the up-projections
absorbed into the query and the output, in plain ``jax.numpy``.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import (
    NEG_INF, _interpret_default)

KERNEL_NAME = "mla_prefill_attn"
_MIN_ROWS = 128
_LANES = 128
_BAND_ALIGN = 16     # rows of a packed bf16 tile: where a band may begin
_VIEW_BYTES = 8 << 20   # of a window's view in VMEM, beside a step's scores


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(q_start_ref, q_ref, k_ref, v_ref, *refs, queries, keys,
            shared, offset=False):
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
            s = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) <= sees, s, NEG_INF)
            row = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            v = jnp.where(row <= q_last - k_lo, v, 0)
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
    pl.when(whole)(functools.partial(accumulate, False))
    pl.when(jnp.logical_not(whole) & (
        k_lo <= jnp.minimum(q_lo + block_q - 1, q_last)))(
            functools.partial(accumulate, True))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        # every query sees its own position: the sum is never zero
        o_ref[0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)


def _band_kernel(q_start_ref, q_ref, k_ref, v_ref, *refs, queries, keys,
                 window, band, shared, offset):
    """One grid step = (key head, query block): the G query heads of the
    key head as G * block_q rows against the ``band`` rows of the head's
    view (whole in VMEM) that hold the block's windows.  One pass: no key
    grid, no running state."""
    if shared:
        ks_ref, o_ref = refs
    else:
        (o_ref,) = refs
    G, block_q = q_ref.shape[1:3]
    q_lo = q_start_ref[0] + pl.program_id(1) * block_q
    k0 = q_start_ref[1] if offset else 0      # key j stands at k0 + j
    # the last position anybody sees, as in the rectangle's body
    q_last = jnp.minimum(q_start_ref[0] + queries, k0 + keys) - 1
    # the lowest position the block's first query sees
    lo_first = jnp.minimum(q_lo, q_last) - (window - 1)
    # the band begins at a whole tile of rows at or below it, inside the view
    at = pl.multiple_of(jnp.minimum(
        jnp.maximum(lo_first - k0, 0) // _BAND_ALIGN * _BAND_ALIGN,
        k_ref.shape[1] - band), _BAND_ALIGN)
    k = k_ref[0, pl.ds(at, band), :]
    v = v_ref[0, pl.ds(at, band), :]
    if shared:      # [k | k_shared]: ONE product over D + Ds
        k = jnp.concatenate([k, ks_ref[pl.ds(at, band), :]], axis=1)
    s = _dot(q_ref[0].reshape(G * block_q, -1), k, ((1,), (1,)))
    # one mask for the G heads: a query sees the keys up to its own
    # position, none below its window and none past `q_last`
    k_lo = k0 + at                            # position of the band's row 0
    sees = jnp.minimum(q_lo + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0), q_last) - k_lo
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, band), 1)
    s = jnp.where((col <= sees) & (col > sees - window),
                  s.reshape(G, block_q, band), NEG_INF)
    # the value rows no query of the block sees hold anything (a page given
    # back, a row never written): 0 * NaN = NaN, so they must not reach
    # the value product
    row = jax.lax.broadcasted_iota(jnp.int32, (band, 1), 0)
    v = jnp.where((row <= q_last - k_lo) & (row >= lo_first - k_lo), v, 0)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    # every query sees its own position: the sum is never zero
    out = _dot(p.reshape(G * block_q, band).astype(v.dtype), v,
               ((1,), (0,))).reshape(G, block_q, -1)
    o_ref[0] = (out / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _blocks(C, S, block_q=None, block_k=None, window=None, G=1):
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
    default scoped limit of 16.

    With a ``window``, ``block_k`` is the BAND: the rows of the view one
    step takes for ``block_q`` queries of each of the ``G`` query heads of
    a key head, ``block_q + window - 1`` and the up to 15 rows that a start
    at a whole tile of rows adds, in whole lanes.  A step's cost beside its
    pairs is paid per step and the band's share that no query of the block
    sees grows with ``block_q``, so the blocks are small: 128 queries, and
    halved while the step's scores (``G * block_q`` rows of a band) would
    outgrow the rectangle's tile of 1024 x 1024.  Measured on the v5e, a
    call alone with the concatenation of the query's two parts (0.2 ms),
    bf16, C 2,048: 80 heads over 16 key heads (128 | 64 / 128), window 128,
    view 2,240 rows: ``block_q`` 64 / 128 / 256 = bands of 256 / 384 / 512
    = 0.67 / 0.61 / 0.65 ms (863 / 1,865 / 4,291 scheduled bundles a
    step), the rectangle's (1024, 1024) walk 2.14 ms; in the chunk program
    0.38 ms a call against 1.77.  32 heads over 4 (128 / 128), window
    1,024, view 3,136 rows: ``block_q`` 64 / 128 = bands of 1,152 / 1,280
    = 0.32 / 0.34 ms (256 does not fit VMEM), the rectangle's walk 0.64 ms
    where the view begins 1,024 rows before the chunk and 0.89 where 1,087;
    in the chunk program 0.30 ms a call against 0.50.
    In VMEM at 80 over 16: the head's view 0.57 MB each of k, v and the
    shared keys twice over, the scores 1 MB in f32 and their exponentials,
    q and the result 0.5 MB twice over: ~7 MB."""
    rows = -(-C // _MIN_ROWS) * _MIN_ROWS
    if window is not None:
        bq = min(block_q or _MIN_ROWS, rows)
        while rows % bq or (not block_q and bq > _BAND_ALIGN
                            and G * bq * _band(bq, window) > 1024 * 1024):
            bq //= 2
        return rows, bq, _band(bq, window)
    bq = min(block_q or 1024, rows)
    while rows % bq:
        bq //= 2
    return rows, bq, min(block_k or 1024, S)


def _band(bq, window):
    return -(-(bq + window - 1 + _BAND_ALIGN - 1) // _LANES) * _LANES


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
    query's position (and, under a ``window``, below the first query's
    window) may hold anything: blocks wholly past it are never read, and
    in the last block read the scores past it are masked and the value
    rows zeroed."""
    H, C, D = q.shape
    Hkv, S, Dv = v.shape
    shared = q_shared is not None
    assert k.shape == (Hkv, S, D) and H % Hkv == 0, \
        (q.shape, k.shape, v.shape)
    G = H // Hkv
    offset = k_start is not None
    if interpret is None:
        interpret = _interpret_default()
    rows, bq, bk = _blocks(C, S, block_q, block_k, window, G)
    if shared:
        Ds = q_shared.shape[-1]
        assert q_shared.shape == (H, C, Ds) and k_shared.shape == (S, Ds)
        q = jnp.concatenate([q, q_shared], axis=-1)     # C rows: cheap
    if rows != C:
        q = jnp.pad(q, ((0, 0), (0, rows - C), (0, 0)))
    starts = jnp.stack([jnp.asarray(q_start, jnp.int32),
                        jnp.asarray(k_start, jnp.int32)]) if offset \
        else jnp.asarray(q_start, jnp.int32).reshape(1)
    if window is not None:
        return _band_attention(starts, q, k, v, k_shared, C, bq, bk, window,
                               interpret, name)[:, :C]

    def q_map(h, qi, ki, qs):
        return h, qi, 0

    def kv_map(h, qi, ki, qs):
        # a block that no query of block qi may see is not fetched: the
        # index stays at the last one needed
        if not offset and G == 1:
            return h, jnp.minimum(
                ki, (qs[0] + jnp.minimum((qi + 1) * bq, C) - 1) // bk), 0
        k0 = qs[1] if offset else 0
        last = (qs[0] + jnp.minimum((qi + 1) * bq, C) - 1 - k0) // bk
        at = jnp.minimum(ki, last)
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
                          offset=offset),
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
    )(starts, *operands)
    return out[:, :C]


def _band_attention(starts, q, k, v, k_shared, C, bq, band, window,
                    interpret, name):
    """The call of a ``window``: q (H, rows, D [+ Ds]) padded to whole
    blocks, ``starts`` the scalars of the rectangle's call.  A key head's
    view stands whole in VMEM (its block index moves with the head alone,
    so it is fetched once a head, the shared keys once a call) and each
    step cuts its band from it."""
    H, rows, Dq = q.shape
    Hkv, S, Dv = v.shape
    G = H // Hkv
    shared = k_shared is not None
    # the view in whole tiles of rows, and no shorter than one band
    Sp = max(band, -(-S // _BAND_ALIGN) * _BAND_ALIGN)
    held = 2 * Sp * q.dtype.itemsize * sum(
        -(-t.shape[-1] // _LANES) * _LANES
        for t in (k, v, k_shared) if t is not None)
    assert held <= _VIEW_BYTES, \
        f"a window's view stands whole in VMEM, twice over: {held} bytes " \
        f"for {S} rows; a window group's view is the window, a chunk and " \
        f"a page"
    if Sp != S:
        k, v = (jnp.pad(t, ((0, 0), (0, Sp - S), (0, 0))) for t in (k, v))
        if shared:
            k_shared = jnp.pad(k_shared, ((0, Sp - S), (0, 0)))

    def q_map(h, qi, qs):
        return h, 0, qi, 0

    def view_map(h, qi, qs):
        return h, 0, 0

    in_specs = [pl.BlockSpec((1, G, bq, Dq), q_map),
                pl.BlockSpec((1, Sp, k.shape[-1]), view_map),
                pl.BlockSpec((1, Sp, Dv), view_map)]
    operands = [q.reshape(Hkv, G, rows, Dq), k, v]
    if shared:
        in_specs.append(pl.BlockSpec(k_shared.shape, lambda h, qi, qs: (0, 0)))
        operands.append(k_shared)
    out = pl.pallas_call(
        functools.partial(_band_kernel, queries=C, keys=S, window=window,
                          band=band, shared=shared,
                          offset=starts.shape[0] == 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Hkv, rows // bq),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, G, bq, Dv), q_map)),
        out_shape=jax.ShapeDtypeStruct((Hkv, G, rows, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(starts, *operands)
    return out.reshape(H, rows, Dv)


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
