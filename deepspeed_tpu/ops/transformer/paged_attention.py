"""Decode attention over the serving engine's paged KV pool, read in place.

One query a lane attends the keys and values of ITS pages where they lie in
the pool (``kv_cache.pool_shapes``: ``(L, NB, bs, H*D)``, one token's H
heads of D side by side in a row).  The pool stays in HBM; the kernel copies
to VMEM only the pages a lane has filled (``ceil(length / bs)`` of its page
table, none for an idle lane), ``pages_per_step`` at a time into one of two
buffers while it computes on the other, and starts the next lane's first
pages under the current lane's last.  A page is one contiguous
``(bs, H*D)`` tile, so one copy brings every head.

Rows are never relaid by head.  The per-head dot products come off the MXU
from a block-diagonal query: ``Qbd (H, H*D)`` holds ``q_h`` in columns
``h*D .. h*D + D - 1`` of row ``h`` and zeros elsewhere, so
``Qbd . K^T (H, S)`` is every head's scores over S cached rows and
``P (H, S) . V (S, H*D)`` holds head h's output in the same columns of row
``h``: H times the needed operations, still far under the time the bytes
take.  Scores and the running max and sum are f32 (online softmax), the
probabilities meet the values in the values' dtype with f32 accumulation.

Isolation: a row at or past a lane's length (the stale tail of its last
page, rows of the buffer no copy filled) scores -1e30 whatever it holds and
has its VALUES zeroed before the product, so NaN or inf there changes no
output (``0 * NaN`` would).

Grouped-query heads (``n_head`` a multiple of the heads a row holds): the
``G`` query heads that share key head ``j`` are ``G`` rows of the
block-diagonal query with ``q_h`` in the columns of head ``j = h // G``
(``(32, 512)`` against rows of 512 for 32 query heads over 4 key heads of
128; made outside the kernel, 32 KB a lane), so no key is repeated in memory
or in VMEM, and the scale is the head's ``D^-0.5``.  A window (``starts``):
a lane sees rows ``start .. length - 1`` only, copies only the pages that
hold them, masks the rows of its first page below ``start`` and zeroes their
values.  Without either the traced kernel is what it was (GPT-2's instance,
its cells' yardstick).

``paged_latent_decode_attention`` is the same walk over pages of raw latent
(MLA) rows, ``(L, NB, bs, stored)``: every head reads the SAME row, which is
key and value at once, so there is one pool, one buffer a step, and the
query arrives whole (``[q . W_uk | q_rope | zeros]`` a head), no
block-diagonal; ``starts`` is the same window over it.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import \
    _interpret_default

KERNEL_NAME = "paged_decode_attn"
LATENT_KERNEL_NAME = "paged_latent_decode_attn"
NEG_INF = -1e30
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


def _kernel(layer_ref, tables_ref, lengths_ref, next_ref, *refs,
            n_head, pages, table_width, scale, grouped=False,
            windowed=False):
    if windowed:        # prefetched too: the first row a lane sees
        starts_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref,
     k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr) = refs
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    bs = k_buf.shape[1] // pages
    S = pages * bs                      # cached rows a step
    HD = k_buf.shape[-1]                # a cached row: its heads side by side
    D = o_ref.shape[-1] if grouped else HD // n_head
    layer = layer_ref[0]
    length = lengths_ref[b]

    def first_page(lane, c):
        """The first page of step ``c`` of ``lane``: a windowed lane's
        steps begin at the page that holds its first row."""
        if windowed:
            return starts_ref[lane] // bs + c * pages
        return c * pages

    def page_copies(lane, c, slot, act):
        """Start or wait for the copies of step ``c`` of ``lane``: the
        pages of that step the lane has filled, keys and values.  (A
        rolled loop: unrolled, sixteen pages at three sites were most of
        the time it takes to trace and lower the kernel.)"""
        first = first_page(lane, c)
        filled = (lengths_ref[lane] + bs - 1) // bs - first

        def page(i, carry):
            src = tables_ref[lane * table_width + first + i]
            rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
            for j, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                            (v_hbm, v_buf))):
                act(pltpu.make_async_copy(hbm.at[layer, src],
                                          buf.at[slot, rows],
                                          sems.at[j, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(filled, 0, pages), page, 0)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

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
            page_copies(b, 0, 0, start)

        slot0 = slot_ref[0]
        following = next_ref[b]                 # next live lane, or n_lanes
        head = jax.lax.broadcasted_iota(jnp.int32, (n_head, HD), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n_head, HD), 1)
        if grouped:
            # the query arrives block-diagonal: q_h in key head h // G's
            # columns of row h
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
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

        def step(c, carry):
            slot = (slot0 + c) % 2
            last = c + 1 == steps

            @pl.when(jnp.logical_not(last))
            def _():
                page_copies(b, c + 1, 1 - slot, start)

            @pl.when(jnp.logical_and(last, following < n_lanes))
            def _():
                page_copies(following, 0, 1 - slot, start)

            page_copies(b, c, slot, wait)
            if windowed:
                row0 = first_page(b, c) * bs    # the step's first row

                # rows no query may see: past the length, below the start
                @pl.when(jnp.logical_or(row0 + S > length, c == 0))
                def _():
                    row = row0 + jax.lax.broadcasted_iota(
                        jnp.int32, (S, HD), 0)
                    v_buf[slot] = jnp.where(
                        jnp.logical_and(row < length, row >= first_row),
                        v_buf[slot], jnp.zeros((), v_buf.dtype))
            else:
                @pl.when((c + 1) * S > length)      # rows no query may see
                def _():
                    row = c * S + jax.lax.broadcasted_iota(
                        jnp.int32, (S, HD), 0)
                    v_buf[slot] = jnp.where(row < length, v_buf[slot],
                                            jnp.zeros((), v_buf.dtype))

            k, v = k_buf[slot], v_buf[slot]
            s = jax.lax.dot_general(
                q_bd, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (H, S)
            if windowed:
                pos = row0 + jax.lax.broadcasted_iota(
                    jnp.int32, (n_head, S), 1)
                s = jnp.where(jnp.logical_and(pos < length,
                                              pos >= first_row), s, NEG_INF)
            else:
                pos = c * S + jax.lax.broadcasted_iota(
                    jnp.int32, (n_head, S), 1)
                s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_scr[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=-1,
                                                    keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # (H, H*D)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
            return carry

        jax.lax.fori_loop(0, steps, step, 0)
        slot_ref[0] = (slot0 + steps) % 2
        # head h's output is the diagonal block of row h
        out = jnp.where(own, acc_scr[:] / l_scr[:, 0:1], 0.0)
        if grouped:     # (H, D): the one block of its row that is not zero
            o_ref[0] = sum(out[:, j * D:(j + 1) * D]
                           for j in range(HD // D)).astype(o_ref.dtype)
        else:
            o_ref[0] = jnp.sum(out, axis=0, keepdims=True) \
                .astype(o_ref.dtype)


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
    windowed = starts is not None
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
    lengths = lengths.astype(jnp.int32)
    lane = jnp.arange(B, dtype=jnp.int32)
    # the next live lane after each (B: none), and in [B] the first one
    live_from = jax.lax.cummin(jnp.where(lengths > 0, lane, B), reverse=True)
    following = jnp.concatenate([live_from[1:], jnp.full((1,), B, jnp.int32),
                                 live_from[:1]])
    prefetched = [jnp.asarray(layer, jnp.int32).reshape(1),
                  tables.astype(jnp.int32).reshape(-1), lengths, following]
    if windowed:
        prefetched.append(jnp.clip(starts.astype(jnp.int32), 0,
                                   jnp.maximum(lengths - 1, 0)))
    if grouped:
        # block-diagonal here, not in the kernel: q_h tiled over the key
        # heads' columns and kept in those of head h // G
        G = n_head // (row // D)
        mine = (jnp.arange(row) // D)[None, :] \
            == (jnp.arange(n_head) // G)[:, None]
        q_in = jnp.where(mine, jnp.tile(q.reshape(B, n_head, D),
                                        (1, 1, row // D)), 0)
        q_spec = pl.BlockSpec((1, n_head, row), lambda b, *_: (b, 0, 0))
        out_spec = pl.BlockSpec((1, n_head, D), lambda b, *_: (b, 0, 0))
        out_shape = (B, n_head, D)
    else:
        q_in = q.reshape(B, 1, HD)
        q_spec = out_spec = pl.BlockSpec((1, 1, HD), lambda b, *_: (b, 0, 0))
        out_shape = (B, 1, HD)
    kernel = functools.partial(
        _kernel, n_head=n_head, pages=pages, table_width=W,
        scale=D ** -0.5, grouped=grouped, windowed=windowed)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(B,),
            in_specs=[q_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, row), k_pool.dtype),
                pltpu.VMEM((2, pages * bs, row), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((n_head, _LANES), jnp.float32),
                pltpu.VMEM((n_head, _LANES), jnp.float32),
                pltpu.VMEM((n_head, row), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*prefetched, q_in, k_pool, v_pool)
    return out.reshape(B, HD)


def _latent_kernel(layer_ref, tables_ref, lengths_ref, next_ref,  # prefetched
                   *refs, pages, table_width, windowed=False):
    if windowed:        # prefetched too: the first row a lane sees
        starts_ref, *refs = refs
    q_ref, hbm, o_ref, buf, sems, slot_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    bs = buf.shape[1] // pages
    S = pages * bs                      # cached rows a step
    H, stored = q_ref.shape[1:]
    rank = o_ref.shape[-1]
    layer = layer_ref[0]
    length = lengths_ref[b]

    def first_page(lane, c):
        """The first page of step ``c`` of ``lane``: a windowed lane's
        steps begin at the page that holds its first row."""
        if windowed:
            return starts_ref[lane] // bs + c * pages
        return c * pages

    def page_copies(lane, c, slot, act):
        """Start or wait for the copies of step ``c`` of ``lane``: the
        pages of that step the lane has filled."""
        first = first_page(lane, c)
        filled = (lengths_ref[lane] + bs - 1) // bs - first

        def page(i, carry):
            src = tables_ref[lane * table_width + first + i]
            rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
            act(pltpu.make_async_copy(hbm.at[layer, src],
                                      buf.at[slot, rows], sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(filled, 0, pages), page, 0)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

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
            page_copies(b, 0, 0, start)

        slot0 = slot_ref[0]
        following = next_ref[b]                 # next live lane, or n_lanes
        q = q_ref[0]                            # (H, stored)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

        def step(c, carry):
            slot = (slot0 + c) % 2
            last = c + 1 == steps

            @pl.when(jnp.logical_not(last))
            def _():
                page_copies(b, c + 1, 1 - slot, start)

            @pl.when(jnp.logical_and(last, following < n_lanes))
            def _():
                page_copies(following, 0, 1 - slot, start)

            page_copies(b, c, slot, wait)
            if windowed:
                row0 = first_page(b, c) * bs    # the step's first row

                # rows no query may see: past the length, below the start
                @pl.when(jnp.logical_or(row0 + S > length, c == 0))
                def _():
                    row = row0 + jax.lax.broadcasted_iota(
                        jnp.int32, (S, stored), 0)
                    buf[slot] = jnp.where(
                        jnp.logical_and(row < length, row >= first_row),
                        buf[slot], jnp.zeros((), buf.dtype))
            else:
                @pl.when((c + 1) * S > length)      # rows no query may see
                def _():
                    row = c * S + jax.lax.broadcasted_iota(
                        jnp.int32, (S, stored), 0)
                    buf[slot] = jnp.where(row < length, buf[slot],
                                          jnp.zeros((), buf.dtype))

            rows = buf[slot]                    # keys and values at once
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)              # (H, S)
            if windowed:
                pos = row0 + jax.lax.broadcasted_iota(jnp.int32, (H, S), 1)
                s = jnp.where(jnp.logical_and(pos < length,
                                              pos >= first_row), s, NEG_INF)
            else:
                pos = c * S + jax.lax.broadcasted_iota(jnp.int32, (H, S), 1)
                s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_scr[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=-1,
                                                    keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (H, stored)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
            return carry

        jax.lax.fori_loop(0, steps, step, 0)
        slot_ref[0] = (slot0 + steps) % 2
        o_ref[0] = (acc_scr[:, :rank] / l_scr[:, 0:1]).astype(o_ref.dtype)


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
    pages = pages_per_step or latent_pages_per_step(
        pool.shape, pool.dtype.itemsize, W)
    lengths = lengths.astype(jnp.int32)
    lane = jnp.arange(B, dtype=jnp.int32)
    # the next live lane after each (B: none), and in [B] the first one
    live_from = jax.lax.cummin(jnp.where(lengths > 0, lane, B), reverse=True)
    following = jnp.concatenate([live_from[1:], jnp.full((1,), B, jnp.int32),
                                 live_from[:1]])
    prefetched = [jnp.asarray(layer, jnp.int32).reshape(1),
                  tables.astype(jnp.int32).reshape(-1), lengths, following]
    windowed = starts is not None
    if windowed:
        prefetched.append(jnp.clip(starts.astype(jnp.int32), 0,
                                   jnp.maximum(lengths - 1, 0)))
    kernel = functools.partial(_latent_kernel, pages=pages, table_width=W,
                               windowed=windowed)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, stored), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, R), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, stored), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, _LANES), jnp.float32),
                pltpu.VMEM((H, _LANES), jnp.float32),
                pltpu.VMEM((H, stored), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, R), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*prefetched, q, pool)
