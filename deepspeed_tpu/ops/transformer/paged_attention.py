"""Decode attention over the serving engine's paged pool, read in place.

ONE WALK (:func:`_walk`, one grid step a lane).  One query a lane attends
the cached rows of ITS pages where they lie in the pool ``(L, NB, bs, row)``
(``kv_cache.pool_shapes``).  The pool stays in HBM; the kernel copies to
VMEM only the pages a lane has filled (``ceil(length / bs)`` of its page
table, none for an idle lane), ``pages_per_step`` at a time into one of two
buffers while it computes on the other, and starts the next live lane's
first pages under the current lane's last.  A page is one contiguous
``(bs, row)`` tile, so one copy brings every row whole.  Scores and the
running max and sum are f32 (online softmax), the probabilities meet the
values in the values' dtype with f32 accumulation.

Isolation: a row at or past a lane's length (the stale tail of its last
page, rows of the buffer no copy filled) scores -1e30 whatever it holds and
has its VALUES zeroed before the product, so NaN or inf there changes no
output (``0 * NaN`` would).

A window (``starts``), the walk's one static choice: a lane sees rows
``start .. length - 1`` only, copies only the pages that hold them, masks
the rows of its first page below ``start`` and zeroes their values.  Without
it the traced kernel is what it was (GPT-2's instance, its cells' yardstick).

THREE VARIATIONS of what a cached row is, each a query for the score product
and a cut of the accumulator, fixed when the kernel is traced:

- Keys and values with heads (``paged_decode_attention``; GPT-2): two pools
  of rows of ``H*D``, one token's H heads of D side by side, never relaid by
  head.  The per-head dot products come off the MXU from a block-diagonal
  query, made in the kernel from the one row it is given: ``Qbd (H, H*D)``
  holds ``q_h`` in columns ``h*D .. h*D + D - 1`` of row ``h`` and zeros
  elsewhere, so ``Qbd . K^T (H, S)`` is every head's scores over S cached
  rows and ``P (H, S) . V (S, H*D)`` holds head h's output in the same
  columns of row ``h`` (the cut sums these blocks into one row): H times
  the needed operations, still far under the time the bytes take.
- Grouped-query heads (the same entry point, ``n_head`` a multiple of the
  heads a row holds; Mellum): the ``G`` query heads that share key head
  ``j = h // G`` are ``G`` rows of the block-diagonal query with ``q_h`` in
  head ``j``'s columns (``(32, 512)`` against rows of 512 for 32 query heads
  over 4 key heads of 128; made OUTSIDE the kernel, 32 KB a lane), so no key
  is repeated in memory or in VMEM, the scale is the head's ``D^-0.5``, and
  the cut is ``(H, D)``: the one block of each row that is not zero.
- Raw latent (MLA) rows (``paged_latent_decode_attention``; ``mistral4``,
  LongCat, Motif): ONE pool ``(L, NB, bs, stored)`` whose row every head
  reads, key and value at once, so there is one buffer a step, the query
  arrives whole (``[q . W_uk | q_rope | zeros]`` a head, scale folded in)
  and the cut is the accumulator's leading ``rank`` columns.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import (
    NEG_INF, _interpret_default)

KERNEL_NAME = "paged_decode_attn"
LATENT_KERNEL_NAME = "paged_latent_decode_attn"
_LANES = 128
_STEP_BYTES = 512 * 1024
_LATENT_STEP_BYTES = 1024 * 1024


def reads_in_place(pool_shape):
    """Whether the compiled kernel can copy a page of this pool as it lies:
    Mosaic slices VMEM by whole (8, 128) tiles, so a page's rows come in
    eights and a row in whole lanes (gpt2-xl's 25 heads of 64 are 12.5).
    The interpreter takes any shape."""
    _, _, bs, row = pool_shape
    return bs % 8 == 0 and row % _LANES == 0


def latent_reads_in_place(pool_shape, latent_rank):
    """The same for pages of latent rows, whose leading ``latent_rank``
    values the kernel cuts out of its accumulator: whole lanes too."""
    return reads_in_place(pool_shape) and latent_rank % _LANES == 0


def latent_pages_per_step(pool_shape, itemsize, table_width):
    """The pages of latent rows a step of the walk copies: a MiB of them in
    each of the two buffers, no more than a lane's table holds."""
    _, _, bs, stored = pool_shape
    return max(1, min(table_width,
                      _LATENT_STEP_BYTES // (bs * stored * itemsize)))


def _diagonal_query(q_ref, o_ref, *, n_head, D, grouped):
    """Rows of heads of ``D`` side by side: the block-diagonal query
    ``(H, HD)``, and the cut of the accumulator ``(H, HD)``, where head h's
    output is the diagonal block of row h."""
    HD = q_ref.shape[-1]
    head = jax.lax.broadcasted_iota(jnp.int32, (n_head, HD), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_head, HD), 1)
    if grouped:
        # arrives block-diagonal: q_h in key head h // G's columns of row h
        group = n_head // (HD // D)
        own = jnp.logical_and(col >= head // group * D,
                              col < (head // group + 1) * D)
        q_bd = q_ref[0]
    else:
        own = jnp.logical_and(col >= head * D, col < (head + 1) * D)
        # (the select runs in 32 bits: the mask has that layout)
        q_bd = jnp.where(own, jnp.broadcast_to(
            q_ref[0].astype(jnp.float32), (n_head, HD)), 0.0) \
            .astype(q_ref.dtype)

    def cut(acc_scr, l_scr):
        out = jnp.where(own, acc_scr[:] / l_scr[:, 0:1], 0.0)
        if grouped:     # (H, D): the one block of its row that is not zero
            return sum(out[:, j * D:(j + 1) * D] for j in range(HD // D))
        return jnp.sum(out, axis=0, keepdims=True)

    return q_bd, cut


def _whole_query(q_ref, o_ref):
    """Latent rows: the query as it arrives ``(H, stored)``, and the cut of
    the accumulator ``(H, stored)``, its leading ``rank`` columns."""
    rank = o_ref.shape[-1]
    return q_ref[0], lambda acc_scr, l_scr: acc_scr[:, :rank] / l_scr[:, 0:1]


def _walk(layer_ref, tables_ref, lengths_ref, next_ref,    # prefetched
          *refs, pages, scale, windowed, query):
    """One lane's walk over its pages.  After the prefetched scalars: the
    query's block, a pool a kind of cached row (keys and values, or latent
    rows that are both), the output's block, a two-slot buffer a pool, the
    copies' semaphores, the slot the lane starts in (carried from lane to
    lane), the online softmax's ``m / l / acc``.  ``scale``: of the scores
    (None: folded into the query); ``query``: one of the variations."""
    if windowed:        # prefetched too: the first row a lane sees
        starts_ref, *refs = refs
    q_ref, *pool_refs, sems, slot_ref, m_scr, l_scr, acc_scr = refs
    n = len(pool_refs) // 2             # pools: in HBM, then a buffer each
    hbms, o_ref, bufs = pool_refs[:n], pool_refs[n], pool_refs[n + 1:]
    values = bufs[-1]                   # of latent rows, the keys too
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    bs = values.shape[1] // pages
    S = pages * bs                      # cached rows a step
    n_head, row_width = acc_scr.shape
    table_width = tables_ref.shape[0] // lengths_ref.shape[0]
    layer = layer_ref[0]
    length = lengths_ref[b]

    def first_page(lane, c):
        """The first page of step ``c`` of ``lane``: a windowed lane's
        steps begin at the page that holds its first row."""
        if windowed:
            return starts_ref[lane] // bs + c * pages
        return c * pages

    def page_copies(lane, c, slot, act):
        """``act`` ("start" or "wait" for) the copies of step ``c`` of
        ``lane``: the pages of that step the lane has filled, from every
        pool.  (A rolled loop: unrolled, sixteen pages at three sites were
        most of the time it takes to trace and lower the kernel.)"""
        first = first_page(lane, c)
        filled = (lengths_ref[lane] + bs - 1) // bs - first

        def page(i, carry):
            src = tables_ref[lane * table_width + first + i]
            rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
            for j, (hbm, buf) in enumerate(zip(hbms, bufs)):
                sem = sems.at[j, slot] if n > 1 else sems.at[slot]
                getattr(pltpu.make_async_copy(
                    hbm.at[layer, src], buf.at[slot, rows], sem), act)()
            return carry

        jax.lax.fori_loop(0, jnp.clip(filled, 0, pages), page, 0)

    @pl.when(length == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _attend():
        if windowed:
            first_row = starts_ref[b]
            steps = ((length + bs - 1) // bs - first_row // bs
                     + pages - 1) // pages
        else:
            steps = (length + S - 1) // S

        @pl.when(b == next_ref[n_lanes])        # the first live lane
        def _first():
            slot_ref[0] = 0
            page_copies(b, 0, 0, "start")

        slot0 = slot_ref[0]
        following = next_ref[b]                 # next live lane, or n_lanes
        q, cut = query(q_ref, o_ref)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

        def step(c, carry):
            slot = (slot0 + c) % 2
            last = c + 1 == steps

            @pl.when(jnp.logical_not(last))
            def _():
                page_copies(b, c + 1, 1 - slot, "start")

            @pl.when(jnp.logical_and(last, following < n_lanes))
            def _():
                page_copies(following, 0, 1 - slot, "start")

            page_copies(b, c, slot, "wait")
            # rows no query may see: past the length, below a window's start
            if windowed:
                row0 = first_page(b, c) * bs    # the step's first row
                ragged = jnp.logical_or(row0 + S > length, c == 0)
            else:
                ragged = (c + 1) * S > length

            def seen(shape, axis):      # the step's rows along ``axis``
                at = (row0 if windowed else c * S) \
                    + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
                if windowed:
                    return jnp.logical_and(at < length, at >= first_row)
                return at < length

            @pl.when(ragged)
            def _():
                values[slot] = jnp.where(seen((S, row_width), 0), values[slot],
                                         jnp.zeros((), values.dtype))

            rows = [buf[slot] for buf in bufs]
            k, v = rows[0], rows[-1]            # a latent row is both
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)              # (H, S)
            if scale is not None:
                s = s * scale
            s = jnp.where(seen((n_head, S), 1), s, NEG_INF)
            m_prev = m_scr[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (H, row)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
            return carry

        jax.lax.fori_loop(0, steps, step, 0)
        slot_ref[0] = (slot0 + steps) % 2
        o_ref[0] = cut(acc_scr, l_scr).astype(o_ref.dtype)


def _prefetched(layer, tables, lengths, starts):
    """The scalars the walk prefetches: the layer, the page tables flat, the
    lengths, the next live lane after each (B: none; in [B] the first one)
    and, under a window, each lane's first row clipped into its rows."""
    B, = lengths.shape
    lengths = lengths.astype(jnp.int32)
    lane = jnp.arange(B, dtype=jnp.int32)
    live_from = jax.lax.cummin(jnp.where(lengths > 0, lane, B), reverse=True)
    following = jnp.concatenate([live_from[1:], jnp.full((1,), B, jnp.int32),
                                 live_from[:1]])
    prefetched = [jnp.asarray(layer, jnp.int32).reshape(1),
                  tables.astype(jnp.int32).reshape(-1), lengths, following]
    if starts is not None:
        prefetched.append(jnp.clip(starts.astype(jnp.int32), 0,
                                   jnp.maximum(lengths - 1, 0)))
    return prefetched


def _walk_call(prefetched, q, pools, out_block, *, n_head, pages, scale,
               query, interpret, name):
    """:func:`_walk` over ``pools`` (left in HBM), one grid step a lane: q
    (B, ...) and the output (B, *out_block) go a lane at a time."""
    B = q.shape[0]
    _, _, bs, row = pools[0].shape

    def a_lane(block):
        return pl.BlockSpec((1, *block), lambda b, *_: (b, 0, 0))

    return pl.pallas_call(
        functools.partial(_walk, pages=pages, scale=scale, query=query,
                          windowed=len(prefetched) == 5),   # starts too
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched), grid=(B,),
            in_specs=[a_lane(q.shape[1:])]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=a_lane(out_block),
            scratch_shapes=[pltpu.VMEM((2, pages * bs, row), pool.dtype)
                            for pool in pools] + [
                pltpu.SemaphoreType.DMA((2, 2) if len(pools) > 1 else (2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((n_head, _LANES), jnp.float32),
                pltpu.VMEM((n_head, _LANES), jnp.float32),
                pltpu.VMEM((n_head, row), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, *out_block), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(*prefetched, q, *pools)


@functools.partial(jax.jit, static_argnames=("n_head", "pages_per_step",
                                             "interpret", "name"))
def paged_decode_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                           n_head, starts=None, pages_per_step=None,
                           interpret=None, name=KERNEL_NAME):
    """q: (B, H*D), one query a lane, heads side by side; k_pool / v_pool:
    (L, NB, bs, Hkv*D), left where they are, ``H`` a multiple of ``Hkv``
    (query head h reads key head ``h // (H // Hkv)``); ``layer``: the layer
    attended (traced or not); tables: (B, W) page ids in position order
    (entry i holds rows ``i * bs .. (i + 1) * bs - 1``); lengths: (B,)
    cached rows a lane may see (rows 0 .. length - 1; 0: an idle lane, which
    reads nothing and gets zeros); starts: (B,) the first row a lane sees
    (a window; None: row 0).  Returns (B, H*D) in q's dtype:
    softmax(q_h . K_j^T * D^-0.5) . V_j per head.  Entries of ``tables``
    past a lane's filled pages, and before the page of its first row, are
    never read."""
    B, HD = q.shape
    L, NB, bs, row = k_pool.shape
    W = tables.shape[1]
    D = HD // n_head
    assert row % D == 0 and n_head % (row // D) == 0 \
        and v_pool.shape == k_pool.shape, \
        (q.shape, n_head, k_pool.shape, v_pool.shape)
    grouped = row != HD
    assert tables.shape == (B, W) and lengths.shape == (B,)
    if interpret is None:
        interpret = _interpret_default()
    assert interpret or reads_in_place(k_pool.shape), \
        f"pages of {bs} rows of {row} do not fill whole (8, 128) tiles"
    # a step's keys fill half a MiB of VMEM (as do its values, in each of
    # two buffers): 256 rows of gpt2-350m's in bf16.  Measured on a v5e at
    # 8 and 16 pages of 16 such rows a step: 71 and 90 % of the bytes' time
    # with every page of 28 lanes filled
    pages = pages_per_step or max(1, min(
        W, _STEP_BYTES // (bs * row * k_pool.dtype.itemsize)))
    prefetched = _prefetched(layer, tables, lengths, starts)
    if grouped:
        # block-diagonal here, not in the kernel: q_h tiled over the key
        # heads' columns and kept in those of head h // G
        G = n_head // (row // D)
        mine = (jnp.arange(row) // D)[None, :] \
            == (jnp.arange(n_head) // G)[:, None]
        q_in = jnp.where(mine, jnp.tile(q.reshape(B, n_head, D),
                                        (1, 1, row // D)), 0)
    else:
        q_in = q.reshape(B, 1, HD)
    return _walk_call(
        prefetched, q_in, (k_pool, v_pool),
        (n_head, D) if grouped else (1, HD), n_head=n_head, pages=pages,
        scale=D ** -0.5,
        query=functools.partial(_diagonal_query, n_head=n_head, D=D,
                                grouped=grouped),
        interpret=interpret, name=name).reshape(B, HD)


@functools.partial(jax.jit, static_argnames=("latent_rank", "pages_per_step",
                                             "interpret", "name"))
def paged_latent_decode_attention(q_lat, q_rope, pool, layer, tables, lengths,
                                  *, latent_rank, starts=None,
                                  pages_per_step=None, interpret=None,
                                  name=LATENT_KERNEL_NAME):
    """One query a lane over ITS pages of latent rows, up-projections
    absorbed: what ``rect_attention.mla_decode_attention`` computes over the
    gathered view, read where the rows lie.

    q_lat: (B, H, R) = q_nope . W_uk, scale folded in; q_rope: (B, H, Dr);
    pool: (L, NB, bs, stored) rows ``[c_kv | k_rope | zeros]``, left where
    they are; ``layer``: the layer attended (traced or not); tables: (B, W)
    page ids in position order; lengths: (B,) cached rows a lane may see
    (0: an idle lane, which reads nothing and gets zeros); starts: (B,) the
    first row a lane sees (a window; None: row 0).  Returns
    (B, H, R) in the query's dtype: ``softmax(scores) . c_kv``.  Entries of
    ``tables`` past a lane's filled pages, and before the page of its first
    row, are never read.  ``name``: what the kernel is called in the
    compiled program and the device trace."""
    B, H, R = q_lat.shape
    L, NB, bs, stored = pool.shape
    W = tables.shape[1]
    assert R == latent_rank and R + q_rope.shape[-1] <= stored, \
        (q_lat.shape, q_rope.shape, pool.shape)
    assert tables.shape == (B, W) and lengths.shape == (B,)
    if interpret is None:
        interpret = _interpret_default()
    assert interpret or latent_reads_in_place(pool.shape, R), \
        f"pages of {bs} rows of {stored}, of which {R} are the latent, do " \
        f"not fill whole (8, 128) tiles"
    # the stored row is wider than the query (padded to whole lanes): the
    # query is padded, not the rows cut
    q = jnp.concatenate([q_lat, q_rope], axis=-1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, stored - q.shape[-1])))
    return _walk_call(
        _prefetched(layer, tables, lengths, starts), q, (pool,), (H, R),
        n_head=H, scale=None, query=_whole_query, interpret=interpret,
        name=name, pages=pages_per_step or latent_pages_per_step(
            pool.shape, pool.dtype.itemsize, W))
