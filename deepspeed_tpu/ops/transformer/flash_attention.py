"""Pallas TPU flash attention — fused memory-efficient attention kernel.

TPU-native replacement for the reference's fused CUDA attention path
(reference: csrc/transformer/softmax_kernels.cu + strided_batch_gemm.h,
dispatched from ds_transformer_cuda.cpp:146-291).  Instead of materialising
the [B,H,S,S] score matrix in HBM, the kernel streams K/V blocks through
VMEM with an online-softmax accumulator (running max / denominator), so
attention memory is O(S) and the matmuls stay on the MXU.

Forward saves only the per-row logsumexp; backward recomputes probabilities
blockwise (two sweeps: dk/dv then dq) — the flash-attention v2 scheme.

A grid step holds a block of up to ``_BLOCK`` queries and one of up to
``_BLOCK`` keys and walks it ``_SUB`` rows at a time, each row block over
the columns it needs and no others: under ``causal`` a tile above the
diagonal is neither fetched (its block index is clamped to the nearest one
needed) nor computed, a tile the diagonal crosses is walked as a staircase
(a row block's columns end at its own diagonal, and only that last
``_SUB`` x ``_SUB`` piece is masked), a tile below it is one unmasked
product.  ``flash_tiles`` counts the pieces.  What a row costs beside its
products (two cross-lane reductions, the broadcast of its maximum) is paid
once a row block and tile, not once a piece; with one key block a row (any
S up to ``_BLOCK``) there is no running state and no rescale at all.

The row statistics travel one value a row: the forward writes the
logsumexp as (bh, 1, s_q) f32, the backward reads it and ``delta`` in the
same form.  The dk/dv sweep computes its scores TRANSPOSED (keys down the
sublanes, queries along the lanes), so both statistics are the rows they
are stored as and all four of its products are plain or transposed-rhs;
the forward and the dq sweep turn them between row and column once a row
block.  Scores, exponentials, statistics and accumulators are f32; only p
and ds are rounded to the operands' dtype for their products.

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

# queries and keys held a grid step, and rows walked at a time inside it
_BLOCK = 1024
_SUB = 256

NEG_INF = -1e30


def _dot(a, b, dims):
    """MXU dot: native (bf16) inputs, fp32 accumulation. Casting inputs to
    fp32 first would force fp32 MXU passes at a fraction of bf16 throughput —
    the round-4 profile showed exactly that (kernel slower than the jnp
    path); inputs stay in their storage dtype and only the accumulator is
    fp32."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a . b^T
_NN = ((1,), (0,))      # a . b


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


def _tile_kind(q_lo, q_hi, k_lo, k_hi, causal):
    """What queries [q_lo, q_hi) need of keys [k_lo, k_hi): 'full' (every
    score), 'mask' (the diagonal crosses the tile) or 'skip' (none)."""
    if not causal or k_hi - 1 <= q_lo:
        return "full"
    return "skip" if k_lo > q_hi - 1 else "mask"


def flash_tiles(s_q, s_k, block_q, block_k, causal):
    """(visited, masked, skipped) tiles of (block_q, block_k) in the walk
    of s_q queries over s_k keys; the masked ones are among the visited."""
    kinds = [_tile_kind(q, q + block_q, k, k + block_k, causal)
             for q in range(0, s_q, block_q) for k in range(0, s_k, block_k)]
    skipped = kinds.count("skip")
    return len(kinds) - skipped, kinds.count("mask"), skipped


def _walk(rows, sub, width, diagonal, keys_first=False):
    """[(row_lo, pieces)]: for each block of `sub` rows of a tile the
    pieces of its columns it computes, each `(col_lo, col_hi, masked)`;
    neighbours that need no mask are one piece.  `diagonal` says the causal
    diagonal crosses the tile, whose rows and columns then start at the
    same position; `keys_first` that the tile is the transposed one of
    the dk/dv sweep."""
    out = []
    for lo in range(0, rows, sub):
        pieces = []
        for c in range(0, width, sub) if diagonal else ():
            kind = (_tile_kind(c, c + sub, lo, lo + sub, True)
                    if keys_first else
                    _tile_kind(lo, lo + sub, c, c + sub, True))
            if kind == "full" and pieces and not pieces[-1][2] \
                    and pieces[-1][1] == c:
                pieces[-1] = (pieces[-1][0], c + sub, False)
            elif kind != "skip":
                pieces.append((c, c + sub, kind == "mask"))
        out.append((lo, pieces if diagonal else [(0, width, False)]))
    return out


def _causal_mask(s, row_lo, col_lo, keys_first=False):
    """NEG_INF where key > query; row 0 of s stands at `row_lo` and column
    0 at `col_lo`, counted from a common origin."""
    a = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + row_lo
    b = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + col_lo
    return jnp.where((b >= a) if keys_first else (a >= b), s, NEG_INF)


def _to_row(col):
    """(n, 1) -> (1, n): through the transpose unit (as a reshape it costs
    a quarter of the forward's bundles)."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[0:1]


def _to_col(row):
    """(1, n) -> (n, 1)."""
    return row.reshape(row.shape[1], 1)


def _dropout_keep(seed_ref, bh, q_start, k_start, n_q, n_k, rate,
                  keys_first=False):
    """Keep-mask block for attention dropout: murmur3-finalizer hash of the
    global (q, k) position, pre-mixed with (seed, batch*head). Deterministic
    given the seed, so forward and both backward sweeps regenerate identical
    masks from the positions alone.  (n_q, n_k), or (n_k, n_q) with
    `keys_first` (the dk/dv sweep's transposed tile)."""
    def mix(h):
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        return h ^ (h >> 16)

    seed = seed_ref[0].astype(jnp.uint32) \
        + jnp.uint32(0x9E3779B9) * jnp.uint32(bh)
    if keys_first:
        qs = jax.lax.broadcasted_iota(jnp.uint32, (1, n_q), 1)
        ks = jax.lax.broadcasted_iota(jnp.uint32, (n_k, 1), 0)
    else:
        qs = jax.lax.broadcasted_iota(jnp.uint32, (n_q, 1), 0)
        ks = jax.lax.broadcasted_iota(jnp.uint32, (1, n_k), 1)
    # q and k positions are mixed in two rounds rather than combined into a
    # q*s_k + k linear index: the product overflows uint32 beyond ~64k seq
    # (q rows 2^32/s_k apart would alias and share keep patterns)
    rh = mix(seed ^ (jnp.uint32(q_start) + qs))
    h = mix(rh ^ (jnp.uint32(0x27D4EB2F) * (jnp.uint32(k_start) + ks)))
    return h >= jnp.uint32(min(rate, 0.9999) * 4294967296.0)


def _bias_block(bias_ref, bias_kind, rows, cols, keys_first=False):
    """The additive bias of a piece, `rows` and `cols` slices of the tile
    (queries, keys); transposed for the dk/dv sweep.

    bias_kind 'key': bias_ref block is (1, 1, block_k) — the HF
    extended-mask (B, 1, 1, S_k) case, broadcast over query rows; 'full':
    (1, block_q, block_k) per-(batch*head) scores bias."""
    if bias_kind == "key":
        row = bias_ref[0, :, cols]
        return _to_col(row) if keys_first else row
    full = bias_ref[0, rows, cols]
    return full.T if keys_first else full


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


def _split_refs(refs, dropout_rate, bias_kind, n_in):
    """(seed_ref, the n_in tensor inputs, bias_ref, the rest)."""
    seed_ref = None
    if dropout_rate > 0.0:
        seed_ref, *refs = refs
    ins, refs = refs[:n_in], refs[n_in:]
    bias_ref = None
    if bias_kind != "none":
        bias_ref, *refs = refs
    return seed_ref, ins, bias_ref, refs


def _by_tile(causal, qi, ki, blocks, tile):
    """Run `tile(diagonal)` for the grid step's tile of query block `qi`
    and key block `ki` of `blocks` each way: the staircase where the
    causal diagonal crosses it, the whole tile below the diagonal, nothing
    above it."""
    if not causal:
        tile(False)
    elif blocks == 1:       # the one tile is the diagonal's
        tile(True)
    else:
        # causal: s_q == s_k and both are cut alike, so tiles are square
        pl.when(ki == qi)(functools.partial(tile, True))
        pl.when(ki < qi)(functools.partial(tile, False))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, scale, causal, bias_kind, dropout_rate, sub, nk):
    seed_ref, (q_ref, k_ref, v_ref), bias_ref, refs = _split_refs(
        refs, dropout_rate, bias_kind, 3)
    o_ref, lse_ref, *scratch = refs
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    bi, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    online = nk > 1         # more key blocks than one: a running state
    if online:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def finish(rows, m, l, acc):
        o_ref[0, rows] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, :, rows] = _to_row(m + jnp.log(l))

    def tile(diagonal):
        for lo, pieces in _walk(block_q, sub, block_k, diagonal):
            rows = slice(lo, lo + sub)
            scores = []
            for k_lo, k_hi, masked in pieces:
                cols = slice(k_lo, k_hi)
                s = _dot(q_ref[0, rows], k_ref[0, cols], _NT) * scale
                if bias_kind != "none":
                    s = s + _bias_block(bias_ref, bias_kind, rows, cols)
                scores.append(_causal_mask(s, lo, k_lo) if masked else s)
            m = functools.reduce(jnp.maximum, [
                jnp.max(s, axis=-1, keepdims=True) for s in scores])
            if online:
                m_prev = m_scr[rows]
                m = jnp.maximum(m_prev, m)
                alpha = jnp.exp(m_prev - m)
            l, acc = 0.0, 0.0
            for (k_lo, k_hi, _), s in zip(pieces, scores):
                p = jnp.exp(s - m)                               # f32
                # softmax denominator accumulates UNdropped p; dropout
                # scales only the value accumulation (normalize-then-drop
                # semantics, same as the reference applying dropout to
                # softmax output)
                l = l + jnp.sum(p, axis=-1, keepdims=True)
                if dropout_rate > 0.0:
                    keep = _dropout_keep(seed_ref, bi, qi * block_q + lo,
                                         ki * block_k + k_lo, sub,
                                         k_hi - k_lo, dropout_rate)
                    p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
                v = v_ref[0, k_lo:k_hi]
                acc = acc + _dot(p.astype(v.dtype), v, _NN)
            if online:
                m_scr[rows] = m
                l_scr[rows] = l_scr[rows] * alpha + l
                acc_scr[rows] = acc_scr[rows] * alpha + acc
            else:
                finish(rows, m, l, acc)

    _by_tile(causal, qi, ki, nk, tile)

    if online:
        @pl.when(ki == nk - 1)
        def _finalize():
            # every row has seen a key by now (its own, under causal): the
            # sum is never zero
            for lo in range(0, block_q, sub):
                rows = slice(lo, lo + sub)
                finish(rows, m_scr[rows], l_scr[rows], acc_scr[rows])


def _seed_ops(seed, dropout_rate):
    """(operands, in_specs) for the dropout seed — a scalar in SMEM."""
    if dropout_rate <= 0.0:
        return [], []
    return [seed], [pl.BlockSpec(memory_space=pltpu.SMEM)]


def _up_to_diagonal(causal):
    """Key block index for step `j` of query block `i` (forward, dq sweep):
    under causal it stays at the last block the query block needs, so
    nothing is fetched for a skipped step."""
    if causal:
        return lambda i, j: jnp.minimum(i, j)
    return lambda i, j: j


def _from_diagonal(causal):
    """Query block index for step `i` of key block `j` (dk/dv sweep): under
    causal key block j is needed by the query blocks from j on, and the
    index stays at j before."""
    if causal:
        return lambda j, i: jnp.maximum(i, j)
    return lambda j, i: i


def _flash_fwd(q, k, v, bias, seed, *, scale, causal, bias_kind, num_heads,
               dropout_rate, block_q, block_k, interpret):
    """(out (bh, s_q, d), lse (bh, 1, s_q) f32)."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    nq, nk = s_q // block_q, s_k // block_k
    sub = _fit_block(_SUB, block_q)
    kj = _up_to_diagonal(causal)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bias_kind=bias_kind,
        dropout_rate=dropout_rate, sub=sub, nk=nk)
    seed_ops, seed_specs = _seed_ops(seed, dropout_rate)
    bias_ops, bias_specs = _bias_specs(
        bias, bias_kind, num_heads, block_q, block_k,
        qmap=lambda i, j: i, kmap=kj)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kj(i, j), 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kj(i, j), 0)),
        ] + bias_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ] if nk > 1 else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*seed_ops, q, k, v, *bias_ops)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dkdv_kernel(*refs, scale, causal, bias_kind, dropout_rate, sub, nq):
    """Grid (bh, key block, query block).  The tile is TRANSPOSED: keys
    down the rows, queries along the lanes."""
    seed_ref, ins, bias_ref, refs = _split_refs(
        refs, dropout_rate, bias_kind, 6)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = ins
    dk_ref, dv_ref, *scratch = refs
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    bi, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    accumulate = nq > 1
    if accumulate:
        dk_scr, dv_scr = scratch

        @pl.when(qi == 0)
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(diagonal):
        for lo, pieces in _walk(block_k, sub, block_q, diagonal,
                                keys_first=True):
            keys = slice(lo, lo + sub)
            dk, dv = 0.0, 0.0
            for q_lo, q_hi, masked in pieces:
                cols = slice(q_lo, q_hi)
                q, do = q_ref[0, cols], do_ref[0, cols]
                st = _dot(k_ref[0, keys], q, _NT) * scale  # (sub, queries)
                if bias_kind != "none":
                    st = st + _bias_block(bias_ref, bias_kind, cols, keys,
                                          keys_first=True)
                if masked:
                    st = _causal_mask(st, lo, q_lo, keys_first=True)
                pt = jnp.exp(st - lse_ref[0, :, cols])               # f32
                dpt = _dot(v_ref[0, keys], do, _NT)
                if dropout_rate > 0.0:
                    keep = _dropout_keep(seed_ref, bi, qi * block_q + q_lo,
                                         ki * block_k + lo, q_hi - q_lo, sub,
                                         dropout_rate, keys_first=True)
                    inv = 1.0 / (1.0 - dropout_rate)
                    pt_drop = jnp.where(keep, pt * inv, 0.0)
                    # dL/dP = keep/(1-r) * dO V^T; delta already equals
                    # rowsum(P_drop o dP) = rowsum(dO o O)
                    dpt = jnp.where(keep, dpt * inv, 0.0)
                else:
                    pt_drop = pt
                dv = dv + _dot(pt_drop.astype(do.dtype), do, _NN)  # (sub, d)
                dst = pt * (dpt - delta_ref[0, :, cols]) * scale
                dk = dk + _dot(dst.astype(q.dtype), q, _NN)
            if accumulate:
                dk_scr[keys] += dk
                dv_scr[keys] += dv
            else:
                dk_ref[0, keys] = dk.astype(dk_ref.dtype)
                dv_ref[0, keys] = dv.astype(dv_ref.dtype)

    _by_tile(causal, qi, ki, nq, tile)

    if accumulate:
        @pl.when(qi == nq - 1)
        def _finalize():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, bias_kind, dropout_rate, sub, nk):
    seed_ref, ins, bias_ref, refs = _split_refs(
        refs, dropout_rate, bias_kind, 6)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = ins
    dq_ref, *scratch = refs
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    bi, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    accumulate = nk > 1
    if accumulate:
        dq_scr, = scratch

        @pl.when(ki == 0)
        def _init():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(diagonal):
        for lo, pieces in _walk(block_q, sub, block_k, diagonal):
            rows = slice(lo, lo + sub)
            do = do_ref[0, rows]
            lse = _to_col(lse_ref[0, :, rows])
            delta = _to_col(delta_ref[0, :, rows])
            dq = 0.0
            for k_lo, k_hi, masked in pieces:
                cols = slice(k_lo, k_hi)
                k = k_ref[0, cols]
                s = _dot(q_ref[0, rows], k, _NT) * scale
                if bias_kind != "none":
                    s = s + _bias_block(bias_ref, bias_kind, rows, cols)
                if masked:
                    s = _causal_mask(s, lo, k_lo)
                p = jnp.exp(s - lse)
                dp = _dot(do, v_ref[0, cols], _NT)
                if dropout_rate > 0.0:
                    keep = _dropout_keep(seed_ref, bi, qi * block_q + lo,
                                         ki * block_k + k_lo, sub,
                                         k_hi - k_lo, dropout_rate)
                    dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
                ds = p * (dp - delta) * scale
                dq = dq + _dot(ds.astype(k.dtype), k, _NN)
            if accumulate:
                dq_scr[rows] += dq
            else:
                dq_ref[0, rows] = dq.astype(dq_ref.dtype)

    _by_tile(causal, qi, ki, nk, tile)

    if accumulate:
        @pl.when(ki == nk - 1)
        def _finalize():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd(res, g, *, scale, causal, bias_kind, num_heads, dropout_rate,
               block_q, block_k, interpret):
    q, k, v, bias, seed, out, lse = res
    do = g
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    nq, nk = s_q // block_q, s_k // block_k
    statics = dict(scale=scale, causal=causal, bias_kind=bias_kind,
                   dropout_rate=dropout_rate)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    # delta_i = rowsum(dO_i * O_i) — standard flash backward precompute;
    # one value a row, as lse
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]

    def stat_spec(index_q):
        """BlockSpec for the lse/delta operands; index_q maps grid ids to
        the q-block index."""
        return pl.BlockSpec((1, 1, block_q),
                            lambda b, x, y: (b, 0, index_q(x, y)))

    seed_ops, seed_specs = _seed_ops(seed, dropout_rate)
    qi_of = _from_diagonal(causal)      # grid (bh, key block, query block)
    bias_ops, bias_specs = _bias_specs(
        bias, bias_kind, num_heads, block_q, block_k,
        qmap=qi_of, kmap=lambda j, i: j)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, nq=nq,
                          sub=_fit_block(_SUB, block_k), **statics),
        grid=(bh, nk, nq),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, qi_of(j, i), 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, qi_of(j, i), 0)),
            stat_spec(qi_of),
            stat_spec(qi_of),
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
        ] if nq > 1 else [],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(*seed_ops, q, k, v, do, lse, delta, *bias_ops)

    kj = _up_to_diagonal(causal)
    bias_ops, bias_specs = _bias_specs(
        bias, bias_kind, num_heads, block_q, block_k,
        qmap=lambda i, j: i, kmap=kj)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk,
                          sub=_fit_block(_SUB, block_q), **statics),
        grid=(bh, nq, nk),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kj(i, j), 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kj(i, j), 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            stat_spec(lambda i, j: i),
            stat_spec(lambda i, j: i),
        ] + bias_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]
        if nk > 1 else [],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(*seed_ops, q, k, v, do, lse, delta, *bias_ops)
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
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
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

    block_q/block_k: the queries and keys a grid step holds; by default
    what the lengths allow up to `_BLOCK` (tests ask for small ones to walk
    a grid of several at a short length).
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
    # shrink each block to the largest 128-aligned divisor of the sequence
    # length: any s % 128 == 0 stays on the kernel (e.g. 640 is one block,
    # 1536 three of 512 — partial Pallas blocks would silently corrupt the
    # softmax, so divisibility is non-negotiable and unaligned lengths fail
    # loudly).  A full bias holds a (block_q, block_k) f32 tile twice over
    # beside the scores: half the side
    block = _BLOCK // 2 if bias_kind == "full" else _BLOCK
    block_q = _fit_block(block_q or block, s_q)
    block_k = _fit_block(block_k or block, s_k)
    assert block_q is not None and block_k is not None, (
        f"seq lengths ({s_q}, {s_k}) have no 128-aligned block divisor; "
        f"pad the sequence to a multiple of 128 or use the jnp path")
    assert _fit_block(_SUB, block_q) and _fit_block(_SUB, block_k), (
        f"blocks ({block_q}, {block_k}) have no 128-aligned divisor")
    assert not causal or block_q == block_k, (
        f"causal tiles are square, got blocks ({block_q}, {block_k})")
    scale = (d ** -0.5) if scale is None else scale
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
