"""The residual mixes of a model that carries ``n`` streams (mHC), as two
kernels that read the streams once each way.

A sublayer F of such a model reads ``u = sum_i H_pre[i] X[i]`` and writes
``X = H_res X + H_post (x) F(norm(u))``; the three mixes are computed from
the token's own streams ``X (N, n E)``, side by side:
``abc = RMSNorm(X) Phi``, ``H_pre = sigmoid(alpha_0 abc[:n] + beta[:n])``,
``H_post = 2 sigmoid(alpha_1 abc[n:2n] + beta[n:2n])``, ``H_res`` the exp
of ``alpha_2 abc[2n:] + beta[2n:]`` (n x n, less its largest) made doubly
stochastic by ``iters`` Sinkhorn iterations, rows then columns.  The
equations stand letter for letter in ``benchmark/architectures/motif.py``.

:func:`mhc_pre_mix` holds a block of rows of X in VMEM and makes the sum of
squares, the product with ``Phi``, the sigmoids, every Sinkhorn iteration
and ``u`` from it; :func:`mhc_post_mix` holds a block of X and of the
sublayer's output and writes the new streams once.  f32 inside, as the
``jax.numpy`` chain they replace was; no f32 array of X's shape is written
to HBM.

**The product with Phi** is exact in f32, as ``Precision.HIGHEST`` was,
at a pass of the MXU a piece of X: :func:`fold_phi` folds the norm's weight
into ``Phi`` and cuts each f32 value into three bf16 pieces (``hi``,
``mid``, ``lo``: 8 + 8 + 8 significand bits, their sum the value), laid
side by side as the 128 columns of ONE matrix (held transposed).  A bf16 X is one piece, so ONE pass
with f32 accumulation gives all three partial products, each exact (8 x 8
bits fit f32's 24), and their sum is the product.  An f32 X is split in
three as well and its pieces' products kept down to ``lo x hi``: the six
passes of ``HIGHEST``.

**Where the small things live.**  Per token there are 2 n + n n scores and
one rsqrt: a lane-sparse shape.  The kernel transposes the block's
(rows, 128) products once, so that TOKENS lie on the lanes and every score
is a sublane, sums the three pieces' tiles, and reorders H_res's scores
(:func:`_by_column`) so that tile j holds column j, row i at sublanes i and
i + n (the column twice: a tile has 8 sublanes).  Row sums are then sums of
tiles, column sums two sublane rotations of a tile, both dense; nothing
leaves the registers between iterations.  The result goes back, row-major
again, through one transpose as a (rows, 128) slab, from which ``u``'s
coefficients are read and which is the kernel's second output: the wrapper
slices H_post, H_res and the Sinkhorn error out of it.

**Blocks, from what the call can see.**  Rows are independent, so a ragged
last block needs no mask (what it holds past N is computed and dropped).
A call of at most 128 rows is one block of the rows rounded up to whole
sublane tiles; a longer one takes the largest multiple of 128 rows whose
pipelined blocks (two buffers each) fit ``_BLOCK_BYTES``: 256 rows for the
pre kernel and 128 for the post kernel at 4 x 4096 bf16.  Inside a block
the f32 working set is a few vregs: the sums walk the block in chunks.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import \
    _interpret_default

PRE_KERNEL = "mhc_pre_mix"
POST_KERNEL = "mhc_post_mix"
_LANES = 128
_SUBLANES = 8
_PIECES = 3                 # bf16 pieces of an f32 value
_BLOCK_BYTES = 24 << 20     # a call's pipelined blocks, both buffers
_MAX_ROWS = 256             # of a block: what the transposed part holds
_VMEM_LIMIT = 48 << 20


def _scores(n):
    """Scores a token: H_pre's n, H_post's n, H_res's n x n; whole sublane
    tiles of them, and three pieces of each beside the sum of squares."""
    count = 2 * n + n * n
    assert count % _SUBLANES == 0 and 2 * n <= _SUBLANES \
        and _SUBLANES % n == 0 and _PIECES * count < _LANES, n
    return count


def _bf16_pieces(x):
    """f32 -> three bf16 whose sum it is, the leading one first: each the
    leading 8 significand bits of what is left, CUT by a mask over the
    bits, not rounded by a convert: XLA may keep the excess precision of
    ``convert(convert(x, bf16), f32)`` inside a fusion (on the TPU it does,
    and a split by rounding left ``mid`` and ``lo`` zero there), a mask it
    may not."""
    pieces = []
    for _ in range(_PIECES):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        top = jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)
        pieces.append(top.astype(jnp.bfloat16))
        x = x - top
    return pieces


def fold_phi(norm, phi, alpha, beta, n):
    """What the pre kernel reads beside X: ``norm (n E,)``, ``phi
    (n E, 2 n + n n)``, ``alpha (3,)``, ``beta (2 n + n n,)`` -> the folded
    ``norm * phi`` TRANSPOSED, in three bf16 pieces one under the other
    (``hi`` at rows 0.., ``mid`` at 2 n + n n.., ``lo`` behind, zeros to
    128), (128, n E) bf16; and ``(2 (2 n + n n), 1)`` f32: every score's
    alpha, then its beta.  Transposed because (n E, 24) pads each row of 24
    to a tile's 128 lanes in HBM, five times its bytes an operation: after
    the one transpose every array here is whole lanes."""
    f32 = jnp.float32
    S = _scores(n)
    pieces = _bf16_pieces(phi.T.astype(f32) * norm.astype(f32))
    folded = jnp.concatenate(pieces + [jnp.zeros(
        (_LANES - _PIECES * S, phi.shape[0]), jnp.bfloat16)], axis=0)
    alpha = alpha.astype(f32)
    consts = jnp.concatenate(
        [jnp.broadcast_to(alpha[k], (size,))
         for k, size in enumerate((n, n, n * n))] + [beta.astype(f32)])
    return folded, consts[:, None]


def _fit_rows(N, row_bytes, sublanes):
    """Rows of a block (module docstring): ``row_bytes`` is one row of
    every pipelined block."""
    if N <= _LANES:
        return -(-N // sublanes) * sublanes
    fit = _BLOCK_BYTES // (2 * row_bytes) // _LANES * _LANES
    return max(_LANES, min(fit, _MAX_ROWS, -(-N // _LANES) * _LANES))


def _period_sum(x, n, op=jnp.add):
    """(8, T) whose sublane s belongs to phase ``s % n`` -> every sublane
    the ``op`` over the n phases: log2 n sublane rotations."""
    shift = n // 2
    while shift:
        x = op(x, pltpu.roll(x, shift, 0))
        shift //= 2
    return x


def _sinkhorn(cols, n, iters):
    """``cols[j]`` (8, T): column j of the n x n scores, row ``s % n`` at
    sublane s -> the same of exp(scores - max) after ``iters`` times rows
    divided by their sums, then columns by theirs, and (8, T) the largest
    |row or column sum - 1| left."""
    top = functools.reduce(jnp.maximum, cols)
    top = _period_sum(top, n, jnp.maximum)
    cols = [jnp.exp(c - top) for c in cols]

    def iteration(_, cols):
        rows = functools.reduce(jnp.add, cols)
        cols = [c / rows for c in cols]
        return [c / _period_sum(c, n) for c in cols]

    # a loop INSIDE the kernel: a trip costs a few cycles, and a body
    # traced once where twenty were most of what tracing a program's mixes
    # took (a second and a half of a warm start's set-up)
    cols = jax.lax.fori_loop(0, iters, iteration, cols)
    err = _period_sum(jnp.abs(functools.reduce(jnp.add, cols) - 1.0), n,
                      jnp.maximum)
    for c in cols:
        err = jnp.maximum(err, jnp.abs(_period_sum(c, n) - 1.0))
    return cols, err


def _by_column(scores, n):
    """(2 n + n n, T) scores in Phi's order, H_res's entry (i, j) at row
    ``2 n + i n + j`` -> n tiles (8, T), tile j column j of H_res with row
    ``s % n`` at sublane s (the column ``8 / n`` times over): the order
    :func:`_sinkhorn` wants.  A rotation and a select a sublane."""
    sublane = jax.lax.broadcasted_iota(
        jnp.int32, (_SUBLANES,) + scores.shape[1:], 0)
    cols = []
    for j in range(n):
        tile = None
        for s in range(_SUBLANES):
            t, at = divmod(2 * n + (s % n) * n + j, _SUBLANES)
            rows = scores[_SUBLANES * t:_SUBLANES * (t + 1)]
            shift = (s - at) % _SUBLANES
            moved = pltpu.roll(rows, shift, 0) if shift else rows
            tile = moved if tile is None else \
                jnp.where(sublane == s, moved, tile)
        cols.append(tile)
    return cols


def _row_major(cols, n):
    """The column tiles of :func:`_sinkhorn` -> tiles whose sublane
    ``8 t + s`` holds entry ``(i, j)`` with ``i n + j = 8 t + s``: what a
    transpose turns into H_res's lanes.  A rotation and a select an
    entry."""
    sublane = jax.lax.broadcasted_iota(jnp.int32, cols[0].shape, 0)
    tiles = []
    for t in range(-(-n * n // _SUBLANES)):
        tile = jnp.zeros_like(cols[0])
        for s in range(min(_SUBLANES, n * n - _SUBLANES * t)):
            i, j = divmod(_SUBLANES * t + s, n)
            shift = (s - i) % _SUBLANES
            moved = pltpu.roll(cols[j], shift, 0) if shift else cols[j]
            tile = jnp.where(sublane == s, moved, tile)
        tiles.append(tile)
    return tiles


def _pieces_of(x):
    """x -> its bf16 pieces, the leading one first: itself where it is
    bf16, else the three of :func:`_bf16_pieces`."""
    if x.dtype == jnp.bfloat16:
        return [x]
    return _bf16_pieces(x.astype(jnp.float32))


def _chunks(rows, width, row_step, lane_step):
    """How a kernel walks a (rows, width) block: ``(rows a step, steps,
    lanes a step, steps, lanes a vreg)``, the steps whole tiles where the
    block is."""
    rs = math.gcd(rows, row_step)
    cw = math.gcd(width, lane_step)
    return rs, rows // rs, cw, width // cw, math.gcd(cw, _LANES)


def _pre_kernel(x_ref, phi_ref, c_ref, u_ref, mix_ref, *, n, E, iters, eps):
    R, nE = x_ref.shape
    f32 = jnp.float32
    S = _scores(n)
    column = jax.lax.broadcasted_iota(jnp.int32, (R, _LANES), 1)

    # -- the block's product with Phi's pieces: one walk of the MXU over
    # the whole width, its accumulator never popped between
    abc = None
    for p, piece in enumerate(_pieces_of(x_ref[...])):
        part = jax.lax.dot_general(piece, phi_ref[...],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)
        # piece p of X meets the pieces of Phi down to lo x hi
        abc = part if p == 0 else abc + jnp.where(
            column < (_PIECES - p) * S, part, 0.0)

    # -- and its sum of squares, a few rows at a time so that the running
    # sums stay in registers; it rides to the lanes in the first unused
    # column of the products
    rs, row_steps, cw, lane_steps, lw = _chunks(R, nE, 32, 8 * _LANES)

    def squares(r, _):
        r0 = pl.multiple_of(r * rs, rs)

        def lanes_(c, acc):
            c0 = pl.multiple_of(c * cw, cw)
            sq = jnp.square(x_ref[pl.ds(r0, rs), pl.ds(c0, cw)].astype(f32))
            for g in range(cw // lw):
                acc = acc + sq[:, g * lw:(g + 1) * lw]
            return acc

        acc = jax.lax.fori_loop(0, lane_steps, lanes_,
                                jnp.zeros((rs, lw), f32))
        mix_ref[pl.ds(r0, rs), :] = jnp.broadcast_to(
            jnp.sum(acc, axis=1, keepdims=True), (rs, _LANES))
        return 0

    jax.lax.fori_loop(0, row_steps, squares, 0)
    abc = jnp.where(column == _PIECES * S, mix_ref[...], abc)

    # -- tokens on the lanes: the sigmoids and the Sinkhorn iterations
    Rp = -(-R // _LANES) * _LANES
    if Rp != R:
        abc = jnp.concatenate([abc, jnp.zeros((Rp - R, _LANES), f32)], 0)
    t = abc.T                                               # (128, Rp)
    inv = jax.lax.rsqrt(t[_PIECES * S:_PIECES * S + 1] / nE + eps)
    scores = (t[:S] + t[S:2 * S] + t[2 * S:3 * S]) * inv
    scores = c_ref[:S] * scores + c_ref[S:]
    # the first tile: H_pre, then H_post = 2 sigmoid
    sublane = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, Rp), 0)
    gates = jax.nn.sigmoid(scores[:_SUBLANES]) * jnp.where(
        (sublane >= n) & (sublane < 2 * n), 2.0, 1.0)
    cols, err = _sinkhorn(_by_column(scores, n), n, iters)
    res = _row_major(cols, n)
    rest = _LANES - _SUBLANES * (2 + len(res))
    slab = jnp.concatenate(
        [gates] + res + [err, jnp.zeros((rest, Rp), f32)], axis=0).T
    mix_ref[...] = slab[:R]

    # -- u = sum_i H_pre[i] X[i], walked in chunks of a few vregs
    rs, row_steps, cw, lane_steps, lw = _chunks(R, E, 32, 4 * _LANES)

    def rows(r, _):
        r0 = pl.multiple_of(r * rs, rs)
        mix = mix_ref[pl.ds(r0, rs), :]
        pre = [jnp.broadcast_to(mix[:, i:i + 1], (rs, lw)) for i in range(n)]

        def lanes_(c, _):
            c0 = pl.multiple_of(c * cw, cw)
            for g in range(cw // lw):
                acc = None
                for i in range(n):
                    xi = x_ref[pl.ds(r0, rs),
                               pl.ds(i * E + c0 + g * lw, lw)].astype(f32)
                    acc = pre[i] * xi if acc is None else acc + pre[i] * xi
                u_ref[pl.ds(r0, rs), pl.ds(c0 + g * lw, lw)] = \
                    acc.astype(u_ref.dtype)
            return 0

        jax.lax.fori_loop(0, lane_steps, lanes_, 0)
        return 0

    jax.lax.fori_loop(0, row_steps, rows, 0)


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps",
                                             "interpret"))
def mhc_pre_mix(X, folded, consts, *, n, iters, eps, interpret=None):
    """X (N, n E) and :func:`fold_phi`'s two -> u (N, E) in X's dtype,
    H_post (N, n) f32, H_res (N, n n) f32 (row i, column j at ``i n + j``)
    and (N,) f32 the largest |row or column sum - 1| of H_res."""
    N, nE = X.shape
    E = nE // n
    S = _scores(n)
    assert folded.shape == (_LANES, nE) and consts.shape == (2 * S, 1), \
        (X.shape, folded.shape, consts.shape)
    if interpret is None:
        interpret = _interpret_default()
    size = X.dtype.itemsize
    R = _fit_rows(N, (nE + E) * size + _LANES * 4,
                  _SUBLANES * 4 // size)
    u, mix = pl.pallas_call(
        functools.partial(_pre_kernel, n=n, E=E, iters=iters, eps=eps),
        grid=(pl.cdiv(N, R),),
        in_specs=[pl.BlockSpec((R, nE), lambda r: (r, 0)),
                  pl.BlockSpec((_LANES, nE), lambda r: (0, 0)),
                  pl.BlockSpec((2 * S, 1), lambda r: (0, 0))],
        out_specs=[pl.BlockSpec((R, E), lambda r: (r, 0)),
                   pl.BlockSpec((R, _LANES), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, E), X.dtype),
                   jax.ShapeDtypeStruct((N, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=PRE_KERNEL,
    )(X, folded, consts)
    # the slab's lanes: H_pre, H_post, (to a whole tile) H_res, the error
    res = _SUBLANES + n * n
    return (u, mix[:, n:2 * n], mix[:, _SUBLANES:res],
            mix[:, -(-res // _SUBLANES) * _SUBLANES])


def _post_kernel(x_ref, y_ref, post_ref, res_ref, o_ref, *, n, E, clamp):
    R = x_ref.shape[0]
    f32 = jnp.float32
    rs, row_steps, cw, lane_steps, lw = _chunks(R, E, 32, _LANES)

    def rows(r, _):
        r0 = pl.multiple_of(r * rs, rs)
        h_post, h_res = post_ref[pl.ds(r0, rs), :], res_ref[pl.ds(r0, rs), :]
        post = [jnp.broadcast_to(h_post[:, i:i + 1], (rs, lw))
                for i in range(n)]
        res = [jnp.broadcast_to(h_res[:, k:k + 1], (rs, lw))
               for k in range(n * n)]

        def lanes_(c, _):
            c0 = pl.multiple_of(c * cw, cw)
            for g in range(cw // lw):
                at = c0 + g * lw
                xs = [x_ref[pl.ds(r0, rs), pl.ds(j * E + at, lw)].astype(f32)
                      for j in range(n)]
                y = y_ref[pl.ds(r0, rs), pl.ds(at, lw)].astype(f32)
                for i in range(n):
                    acc = res[i * n] * xs[0]
                    for j in range(1, n):
                        acc = acc + res[i * n + j] * xs[j]
                    acc = acc + post[i] * y
                    o_ref[pl.ds(r0, rs), pl.ds(i * E + at, lw)] = \
                        jnp.clip(acc, -clamp, clamp).astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, lane_steps, lanes_, 0)
        return 0

    jax.lax.fori_loop(0, row_steps, rows, 0)


@functools.partial(jax.jit, static_argnames=("clamp", "interpret"))
def mhc_post_mix(X, y, h_post, h_res, *, clamp, interpret=None):
    """``clip(H_res X + H_post (x) y)``: X (N, n E), y (N, E), H_post
    (N, n) f32, H_res (N, n n) f32 -> (N, n E) in X's dtype."""
    N, nE = X.shape
    n = h_post.shape[1]
    E = nE // n
    assert y.shape == (N, E) and h_res.shape == (N, n * n), \
        (X.shape, y.shape, h_post.shape, h_res.shape)
    if interpret is None:
        interpret = _interpret_default()
    size = X.dtype.itemsize
    R = _fit_rows(N, 2 * nE * size + E * y.dtype.itemsize + 2 * _LANES * 4,
                  _SUBLANES * 4 // size)
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, E=E, clamp=clamp),
        grid=(pl.cdiv(N, R),),
        in_specs=[pl.BlockSpec((R, nE), lambda r: (r, 0)),
                  pl.BlockSpec((R, E), lambda r: (r, 0)),
                  pl.BlockSpec((R, n), lambda r: (r, 0)),
                  pl.BlockSpec((R, n * n), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((R, nE), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((N, nE), X.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=POST_KERNEL,
    )(X, y, h_post, h_res)
