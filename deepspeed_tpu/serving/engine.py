"""Continuous-batching inference engine over the paged KV pool.

The compiled surface is deliberately tiny and FIXED-SHAPE:

- ONE decode jit over all ``max_slots`` lanes, with per-slot page
  tables, positions and an active mask — requests joining, leaving,
  finishing or being evicted only change ARRAY CONTENTS, never shapes,
  so steady-state serving triggers zero recompilations (pinned by the
  CompilationCounter acceptance test);
- a small family of length-bucketed chunked-prefill jits (one per
  power-of-two bucket x final/non-final), so a long prompt is absorbed
  ``prefill_chunk`` tokens per step between decode steps and never
  stalls running decodes.

Both programs DONATE the pool tensors (kv_cache.PoolTensors) and update
them in place: steady-state decode is allocation-free, and the HLO
contracts in tests/unit/test_hlo_contracts.py pin the decode jit to
"host-transfer-free + pool donated + (sharded) zero collective bytes".

The model enters through the decoder-block contract
(``serving/decoder.py``): it gives the engine its embedding, its block
(positions in; the cache written and viewed through the engine's hooks,
:class:`_LayerCache`), its final norm and head, the rows it caches a token
and the dtype its served weights are held in.  The engine knows no
architecture: GPT-2 implements the contract with models/generation.py's
functions (``GPT2Decoder``: the shared masked core over a gathered page
view, whose exact -1e30 masking makes greedy tokens bit-identical to
single-sequence ``generate()`` under staggered arrivals, eviction and
cancellation churn, the parity acceptance test; lowered for a TPU, the
decode program attends each lane's filled pages where they lie instead,
``_LayerCache.attend_heads``); ``models/mistral4.py``
brings latent (MLA) pages, a routed feed-forward over the experts this
chip holds, and per-step routing counters that ride the step's one fetch;
``models/longcat_flash.py`` a block of two latent attentions, hence two
raw rows a token.  A model whose cache is not (keys, values), by the kind
its configuration states (``kv_cache.cache_kind``), is served by the dense decode
program and the chunked prefill programs; the other variants refuse it by
name (``decoder.UnsupportedForModel``).

Sharding: with ``shards > 1`` the decode program runs under a shard_map
over the slot axis — slots, page tables and the block pool are all split
on the same mesh axis, params replicated.  Every decode operator is
batch-uniform in the slot dimension, so the compiled program contains NO
collectives (runtime/comm_accounting.serving_decode_collectives prices
this placement against the tensor-parallel alternative).

Reliability (serving/reliability.py): per-request deadlines and work
budgets enforced at step boundaries, an SLO-aware predicted-TTFT
admission gate with lowest-priority-first load shedding, graceful
``drain()`` (SIGTERM via ``install_preemption_handler``), a per-step
request journal driving ``recover()`` (bit-identical greedy
continuations after a host crash), and per-request poison quarantine —
non-finite logits abort only the offending lane, detected on the same
batched fetch as the sampled tokens.  None of it touches the compiled
surface's contracts: still ONE decode jit, zero recompiles, zero
collectives.
"""
import functools
import itertools
import time
from typing import Any, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.generation import _attn_core, _dense, _sample
from deepspeed_tpu.ops.transformer.paged_attention import (
    latent_reads_in_place, paged_decode_attention,
    paged_latent_decode_attention, reads_in_place)
from deepspeed_tpu.ops.transformer.rect_attention import \
    mla_decode_attention
from deepspeed_tpu.runtime.quantization import (dequantize_rows,
                                                quantize_rows)
from deepspeed_tpu.runtime.resilience import chaos
from deepspeed_tpu.serving.decoder import (decoder_for,
                                           refuse_unless_plain)
from deepspeed_tpu.serving.kv_cache import (TRASH_BLOCK, PagedKVPool,
                                            cache_groups, cache_rows,
                                            window_table_width)
from deepspeed_tpu.serving.metrics import ServingMetrics
from deepspeed_tpu.serving.reliability import (ABORT_BUDGET, ABORT_EXPIRED,
                                               ABORT_POISONED, ABORT_SHED,
                                               Reliability, ReliabilityConfig,
                                               RequestJournal)
from deepspeed_tpu.serving.scheduler import (Request, RequestState,
                                             Scheduler)
from deepspeed_tpu.serving.sparse_context import (SparseContext,
                                                  _policy_layout)
from deepspeed_tpu.utils.logging import logger

_MIN_BUCKET = 4


def _slot_key(seed, pos):
    """Per-request sampling key: a function of (request seed, absolute
    position) only — the token stream of a sampled request does not
    depend on which slot or step it lands in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), pos)


def _pool_write(pool, scales, l, blk, off, rows, quantized):
    """Scatter one token row per lane into the block pool
    (``kv_cache.pool_shapes``: index dims major, the row minor).
    rows: (N, H, D), stored as (N, H*D) at ``[l, blk, off]``; blk/off:
    (N,) local block id / in-block offset.  Quantized: one scale per
    (token, head), the (N, H) scale rows land at the same index.  Masked
    lanes arrive with blk == TRASH_BLOCK and land in the trash block —
    the scatter itself is always dense."""
    N, H, D = rows.shape
    rows = rows.reshape(N, H * D)
    if quantized:
        q, s = quantize_rows(rows, block_size=D)         # (N, H*D), (N, H)
        pool = pool.at[l, blk, off].set(q)
        scales = scales.at[l, blk, off].set(s.astype(jnp.float32))
    else:
        pool = pool.at[l, blk, off].set(rows.astype(pool.dtype))
    return pool, scales


def _pool_view(pool, scales, l, tables, n_head, quantized, out_dtype):
    """Gather per-sequence page views back to contiguous position order:
    (B, W) tables over the (L, NB, bs, H*D) pool -> (B, H, W*bs, D).
    Only the GATHERED pages are reshaped and transposed, never the pool.
    View position j IS absolute sequence position j, so the attention
    mask of the contiguous cache applies unchanged.  ONE gather over
    (layer, page): slicing ``pool[l]`` out first made the compiler
    materialise the layer before every gather."""
    B, W = tables.shape
    _, _, bs, HD = pool.shape
    H, D = n_head, HD // n_head
    g = pool[l, tables.reshape(-1)]                      # (B*W, bs, H*D)
    if quantized:
        s = scales[l, tables.reshape(-1)]                # (B*W, bs, H)
        g = dequantize_rows(g.reshape(B * W * bs, HD),
                            s.reshape(B * W * bs, H), HD, out_dtype)
    return g.reshape(B, W * bs, H, D).transpose(0, 2, 1, 3)


class _Group(NamedTuple):
    """One cache group's part of a serving program's arguments
    (``kv_cache.cache_groups``): its pool slots ``(k, v, k_scale,
    v_scale)``, its page tables (B, Wg), where this step's rows land in it,
    and, for a group whose table slides, ``base`` (B,): the position of the
    table's first row (None: 0), and the ``window`` it keeps (None:
    everything)."""
    pools: tuple
    tables: Any
    blk: Any
    off: Any
    base: Any = None
    window: Optional[int] = None
    name: str = "full"


class _GroupState:
    """A :class:`_Group` inside :func:`_forward_groups`: the pools as the
    blocks update them, the table the views gather, the two masks of that
    view and, for the paged kernels, the rows a lane may see of it."""

    def __init__(self, group, gtables, masks, lengths, starts):
        self.pools = list(group.pools)      # [k, v, k_scale, v_scale]
        self.blk, self.off = group.blk, group.off
        self.base, self.window, self.name = \
            group.base, group.window, group.name
        self.gtables = gtables
        self.valid_scores, self.valid_keys = masks
        self.lengths, self.starts = lengths, starts


def _gqa_core(q, keys, values, valid):
    """The masked core for ``H = G * Hkv`` query heads over ``Hkv`` cached
    heads, no key repeated: q (B, H, Q, D), keys / values (B, Hkv, K, D),
    ``valid`` broadcast against (B, 1, Q, K).  Scores and softmax in f32,
    masked positions exactly zero, as ``generation._attn_core``; returns
    (B, Q, H * D), not projected."""
    B, H, Q, D = q.shape
    Hkv = keys.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, Q, D)
    s = jnp.einsum("bjgqd,bjkd->bjgqk", qg, keys,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    s = jnp.where(valid[:, :, None], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(values.dtype)
    y = jnp.einsum("bjgqk,bjkd->bjgqd", probs, values)
    return y.reshape(B, H, Q, D).transpose(0, 2, 1, 3).reshape(B, Q, H * D)


class _LayerCache:
    """A block's hook to ONE layer of ONE cache group of the pool: what the
    decoder-block contract calls ``cache`` (``serving/decoder.py``).
    Writes land at this step's ``(blk, off)``, views gather the step's page
    tables; the pool tensors threaded through the program are updated here
    and read back by :func:`_forward_groups` after the block.  A model of
    several groups reaches the others through :meth:`at`."""

    def __init__(self, states, state, l, quantized, dtype, positions,
                 maxpos, row_valid):
        self._states, self._state = states, state
        self.l = l
        self.quantized, self.dtype = quantized, dtype
        self.positions, self.maxpos = positions, maxpos
        self.row_valid = row_valid

    def at(self, group, l):
        """The hook to layer ``l`` (counted within the group) of the cache
        group named ``group``."""
        return _LayerCache(self._states, self._named(group), l,
                           self.quantized, self.dtype, self.positions,
                           self.maxpos, self.row_valid)

    def carry(self, group):
        """The pool tensors of the group named ``group`` as they stand, a
        tuple: what a loop of the model's own (``lax.scan`` over the layers
        of one kind) carries, and hands back with :meth:`restore`."""
        return _held(self._named(group).pools)

    def restore(self, group, arrays):
        st = self._named(group)
        it = iter(arrays)
        st.pools = [None if t is None else next(it) for t in st.pools]

    def _named(self, group):
        return next(st for st in self._states if st.name == group)

    # this hook's group
    pools = property(lambda self: self._state.pools)
    blk = property(lambda self: self._state.blk)
    off = property(lambda self: self._state.off)
    gtables = property(lambda self: self._state.gtables)
    valid_scores = property(lambda self: self._state.valid_scores)
    valid_keys = property(lambda self: self._state.valid_keys)
    lengths = property(lambda self: self._state.lengths)
    window = property(lambda self: self._state.window)

    @property
    def k_start(self):
        """(B,) the position of the first row of this group's views: 0
        where the group keeps everything."""
        base = self._state.base
        return jnp.zeros_like(self.maxpos) if base is None else base

    # keys and values with heads (GPT-2): quantizable, (B, H, K, D) views
    def write_heads(self, i, rows):
        """rows (N, Hkv, D): the cached heads, whatever the query's."""
        self.pools[i], self.pools[2 + i] = _pool_write(
            self.pools[i], self.pools[2 + i], self.l, self.blk, self.off,
            rows, self.quantized)

    def view_heads(self, i, n_head):
        """(B, n_head, K, D) of cache tensor ``i`` in view order, ``n_head``
        the CACHED heads; view row j stands at position ``k_start + j``."""
        return _pool_view(self.pools[i], self.pools[2 + i], self.l,
                          self.gtables, n_head, self.quantized, self.dtype)

    def attend_heads(self, q, n_head, p, name=None):
        """Masked attention of q (B, H, T, D) over this layer's cached keys
        and values, through the output projection ``p["c_proj"]``:
        (B, T, E); with ``p`` None not projected, (B, T, H * D).  ``n_head``
        is the QUERY's heads, a multiple of the cached ones (query head h
        reads cached head ``h // G``, no key repeated in memory); a group
        that keeps a window shows each query its last ``window`` positions.
        One algorithm whose best form differs with the query
        count.  Where the program states ``lengths`` (one query a lane
        over a dense unquantized pool of whole-tile pages) and is lowered
        for a TPU, the paged kernel reads each lane's filled pages where
        they lie (``ops/transformer/paged_attention.py``; ``name``: what the
        kernel is called in the compiled program, its own default where
        None); everywhere else
        the shared ``jax.numpy`` core attends the gathered view, whose
        rows past ``maxpos`` are zeroed first (:func:`_forward_groups`)."""
        D = q.shape[-1]
        n_kv = self.pools[0].shape[3] // D

        def over_view(q):
            kview, vview = (jnp.where(self.valid_keys,
                                      self.view_heads(i, n_kv), 0)
                            for i in (0, 1))
            if n_kv == n_head and p is not None:
                return _attn_core(q, kview, vview, self.valid_scores, p,
                                  self.dtype)
            y = _gqa_core(q, kview, vview, self.valid_scores)
            return y if p is None else _dense(y, p["c_proj"])

        if self.lengths is None:
            return over_view(q)

        def over_pages(q):
            B = q.shape[0]
            y = paged_decode_attention(
                q.reshape(B, -1), self.pools[0], self.pools[1], self.l,
                self.gtables, self.lengths, n_head=n_head,
                starts=self._state.starts, interpret=False,
                **({} if name is None else {"name": name}))
            return y[:, None] if p is None else _dense(y[:, None],
                                                      p["c_proj"])

        # known only when the program is lowered (a described chip, a
        # host-side run of the same program): only that branch is lowered
        return jax.lax.platform_dependent(q, tpu=over_pages,
                                          default=over_view)

    # raw rows (a latent row has no head in it)
    def write_rows(self, i, rows):
        pool = self.pools[i]
        stored = pool.shape[3]          # the row, padded to whole lanes
        if rows.shape[1] < stored:
            rows = jnp.pad(rows, ((0, 0), (0, stored - rows.shape[1])))
        self.pools[i] = pool.at[self.l, self.blk, self.off].set(
            rows.astype(pool.dtype))

    def view_rows(self, i):
        B, K = self.gtables.shape
        pool = self.pools[i]
        # ONE gather over (layer, page): the layer is not sliced out first
        return pool[self.l, self.gtables.reshape(-1)] \
            .reshape(B, K * pool.shape[2], pool.shape[3])

    def attend_rows(self, i, q_lat, q_rope, rank, name=None):
        """Latent (MLA) decode attention of one query a lane over cache
        tensor ``i``, the up-projections absorbed by the caller: q_lat
        (B, H, rank), q_rope (B, H, Dr) -> (B, H, rank).  As
        ``attend_heads``, the engine chooses the form: where the program
        states ``lengths``, the latent fills whole lanes and it is lowered
        for a TPU, the paged kernel reads each lane's filled pages where
        they lie (``name``: what it is called in the compiled program, its
        own default where None); everywhere else ``mla_decode_attention``
        over the gathered view.  In a group that keeps a window each lane
        sees its last ``window`` rows, of a view that begins at
        ``k_start``."""
        def over_view(q_lat, q_rope):
            view = self.view_rows(i)
            n_keys = self.maxpos + 1
            if self._state.base is not None:
                n_keys = n_keys - self._state.base
            return mla_decode_attention(
                q_lat, q_rope, view, n_keys, rank,
                starts=None if self.window is None
                else jnp.maximum(n_keys - self.window, 0))

        if self.lengths is None \
                or not latent_reads_in_place(self.pools[i].shape, rank):
            return over_view(q_lat, q_rope)

        def over_pages(q_lat, q_rope):
            return paged_latent_decode_attention(
                q_lat, q_rope, self.pools[i], self.l, self.gtables,
                self.lengths, latent_rank=rank, starts=self._state.starts,
                interpret=False, **({} if name is None else {"name": name}))

        return jax.lax.platform_dependent(q_lat, q_rope, tpu=over_pages,
                                          default=over_view)


def _forward_groups(params, dec, groups, pos, maxpos, x, quantized,
                    sparse=None, allowed=None, row_valid=None):
    """Shared transformer pass of decode and chunked prefill: per layer
    the model's block (``serving/decoder.py``) writes this step's rows
    into the pool and attends over the gathered page view (or, one query a
    lane on a TPU, the pages themselves: ``attend_heads``) through a
    :class:`_LayerCache`.  ``groups``: one :class:`_Group` a cache group of
    the model (``kv_cache.cache_groups``), the first the one a block's
    ``cache`` is a hook to (the others: ``cache.at``).  x: (B, T, E) with
    T == number of query tokens
    per lane; pos: (B*T?,) absolute positions of the query tokens,
    flattened to match blk/off.  Returns the final-normed x, the pools
    group by group and
    the blocks' counters summed over the layers (None without).

    ``maxpos``: (B,) last VALID absolute position per lane.  View
    positions beyond it have their VALUES zeroed before the attention
    einsum: their softmax weight is already exactly 0 (the -1e30 score
    mask), but ``0 * NaN = NaN`` — without the value mask, stale
    non-finite garbage in a reused/trash block (a quarantined request's
    poisoned writes) would leak into every lane that merely gathers the
    block at a masked position.  For finite garbage the zeroing is
    bit-neutral (0 * garbage was already exactly +/-0), so the parity
    contract is untouched while per-request fault ISOLATION becomes
    unconditional.

    A group with a ``base``: its table begins at the oldest page a lane
    still holds, view row j stands at position ``base + j``; with a
    ``window`` a query sees its last ``window`` positions only, and rows
    below the FIRST query's window are zeroed like those past ``maxpos``.

    ``sparse`` (serving/sparse_context.py): ``(stables, sbase)`` — a
    (B, K) physical-page gather table plus the absolute view position of
    each page's first token.  The GATHER then reads only K active pages
    per lane while WRITES keep addressing the full page table through
    ``blk``/``off``; padded/expired entries carry the sentinel position
    (>= every valid pos/maxpos), so both masks reject them exactly like
    dense trash padding.  Attention is permutation-invariant over keys,
    so view order no longer being position order changes nothing — the
    masks are built from the TRUE absolute positions.  ``allowed``
    (B?, T, K*bs) further restricts each query to its OWN policy blocks
    (chunked prefill gathers the chunk's union set).

    ``row_valid`` (B, T): which query rows are real tokens (a routed block
    sends padding nowhere); None where the model does not ask."""
    B, T, _ = x.shape
    assert sparse is None or len(groups) == 1

    def state(group):
        tables, pools = group.tables, group.pools
        W = tables.shape[1]
        bs = pools[0].shape[2]
        starts = None
        if sparse is not None:
            gtables, sbase = sparse
            K = gtables.shape[1]
            view_pos = (sbase[:, :, None]
                        + jnp.arange(bs)[None, None, :]) \
                .reshape(B, K * bs)                      # (B, K*bs)
            validj = view_pos[:, None, :] <= pos.reshape(B, T)[:, :, None]
            if allowed is not None:
                validj = validj & allowed
            validj = validj[:, None]                     # (B, 1, T, K*bs)
            validk = (view_pos <= maxpos[:, None])[:, None, :, None]
        elif group.base is None and group.window is None:
            gtables = tables
            validj = (jnp.arange(W * bs)[None, :]
                      <= pos.reshape(B, T)[:, :, None]) \
                .reshape(B, T, W * bs)[:, None]          # (B, 1, T, K)
            validk = (jnp.arange(W * bs)[None, :] <= maxpos[:, None]) \
                [:, None, :, None]                       # (B, 1, K, 1)
        else:
            gtables = tables
            base = 0 if group.base is None else group.base[:, None]
            view_pos = base + jnp.arange(W * bs)[None, :]    # (B?, K)
            qpos = pos.reshape(B, T)
            validj = view_pos[:, None, :] <= qpos[:, :, None]
            validk = view_pos <= maxpos[:, None]
            if group.window is not None:
                validj = validj & (view_pos[:, None, :]
                                   > qpos[:, :, None] - group.window)
                validk = validk & (view_pos
                                   > qpos[:, :1] - group.window)
            validj = validj[:, None]                     # (B, 1, T, K)
            validk = jnp.broadcast_to(validk, (B, W * bs)) \
                [:, None, :, None]                       # (B, 1, K, 1)
        # one query a lane over the dense unquantized pool, its pages
        # whole tiles: the rows each lane may see (of its table; from
        # ``starts`` on under a window), none for a lane that is not
        # live, for the paged kernel
        lengths = None
        if T == 1 and sparse is None and not quantized \
                and reads_in_place(pools[0].shape):
            lengths = maxpos + 1
            if group.base is not None:
                lengths = lengths - group.base
            if group.window is not None:
                starts = jnp.maximum(lengths - group.window, 0)
            if row_valid is not None:
                lengths = jnp.where(row_valid[:, 0], lengths, 0)
        return _GroupState(group, gtables, (validj, validk), lengths,
                           starts)

    states = [state(g) for g in groups]
    n_rows = sum(t is not None for t in groups[0].pools[:2])

    def layer(l, x, pools):
        for st, held in zip(states, pools):
            st.pools = list(held)
        cache = _LayerCache(states, states[0], l, quantized, x.dtype,
                            pos.reshape(B, T), maxpos, row_valid)
        out = dec.block(params, l, x, cache)
        x, row = out if dec.stat_names else (out, None)
        return x, [tuple(st.pools) for st in states], row

    stats = None
    pools = [g.pools for g in groups]
    if dec.scan_layers:
        # one traced block, the layer index a traced scalar: the model
        # reads its own layer out of stacked weights, the pool is carried
        # and updated in place, and the device trace has one operation a
        # kernel, not one a layer
        def body(carry, l):
            x, held, stats = carry
            x, pools, row = layer(
                l, x, [_pools_of(h, n_rows, quantized) for h in held])
            return (x, [_held(p) for p in pools],
                    None if row is None else stats + row), None

        stats = jnp.zeros(len(dec.stat_names), jnp.int32) \
            if dec.stat_names else None
        (x, held, stats), _ = jax.lax.scan(
            body, (x, [_held(p) for p in pools], stats),
            jnp.arange(dec.n_layer))
        pools = [_pools_of(h, n_rows, quantized) for h in held]
    else:
        for l in range(dec.n_layer):
            x, pools, row = layer(l, x, pools)
            if row is not None:
                stats = row if stats is None else stats + row
    return dec.final_norm(params, x), pools, stats


def _pools_of(args, n_rows, quantized):
    """The leading pool arguments of a serving program as the four slots
    ``(k, v, k_scale, v_scale)``, None where the model (``n_rows`` cached
    rows a token, 2 or 1: keys and values, or the model's raw rows in the
    same two slots) or the storage has none."""
    slots = list(args[:n_rows]) + [None] * (2 - n_rows)
    slots += list(args[n_rows:2 * n_rows]) if quantized else []
    return tuple(slots + [None] * (4 - len(slots)))


def _held(pools):
    return tuple(t for t in pools if t is not None)


def _n_pool(n_rows, quantized):
    return n_rows * (2 if quantized else 1)


def _n_pool_args(cfg, quantized):
    """Pool tensors a dense serving program threads: every cache group's
    (``kv_cache.cache_groups``)."""
    return _n_pool(len(cache_rows(cfg)), quantized) * len(cache_groups(cfg))


def _split_groups(cfg, args, quantized):
    """The leading pool arguments of a dense serving program, group by
    group, each as the four slots of :func:`_pools_of`, and how many
    arguments they were."""
    n_rows = len(cache_rows(cfg))
    n = _n_pool(n_rows, quantized)
    total = _n_pool_args(cfg, quantized)
    return [_pools_of(args[at:at + n], n_rows, quantized)
            for at in range(0, total, n)], total


def _held_groups(pools):
    """Every group's pool tensors in argument order."""
    return tuple(t for p in pools for t in _held(p))


def _stats_out(stats):
    """A program's counter output: none (a model without), else one."""
    return () if stats is None else (stats,)


def _pick_next(logits, seeds, pos, temperature, top_k, top_p):
    """Greedy argmax (the bit-parity path) or per-lane sampled token."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    keys = jax.vmap(_slot_key)(seeds, pos)
    return jax.vmap(
        lambda lg, k: _sample(lg[None], k, temperature, top_k, top_p)[0]
    )(logits, keys).astype(jnp.int32)


def _shard_wrap(core, name, mesh, axis_name, n_pool, in_streams,
                n_out_streams):
    """jit(shard_map(core)) with pool tensors split on the block axis,
    per-slot streams split on the slot axis and params replicated; plain
    jit when mesh is None.  ``in_streams``/``n_out_streams`` mark which
    trailing args / leading-after-pool outputs carry the slot axis.
    ``name`` is the program's ONE name: the jit's (``jit_<name>`` in the
    profiler's module line, the compile log and the persistent cache) and,
    read back from the jit's ``__name__``, the program registry's."""
    core.__name__ = core.__qualname__ = name
    donate = tuple(range(1, 1 + n_pool))
    if mesh is None:
        return jax.jit(core, donate_argnums=donate)
    from jax.sharding import PartitionSpec as P

    pool_spec = P(None, axis_name)
    in_specs = (P(),) + (pool_spec,) * n_pool + tuple(
        P(axis_name) if s else P() for s in in_streams)
    out_specs = (pool_spec,) * n_pool + (P(axis_name),) * n_out_streams
    sm = jax.shard_map(core, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(sm, donate_argnums=donate)


@functools.lru_cache(maxsize=64)
def _make_decode_step(cfg, W, bs, quantized, temperature, top_k, top_p,
                      mesh, axis_name, K=None):
    """ONE fixed-shape decode program over every (local) slot lane.

    ``poison`` is a per-lane additive fault-injection stream (0.0 in
    production — bit-neutral on the embedding sum): chaos writes NaN
    into one lane to model a numeric blow-up, and the per-lane
    ``finite`` output (non-finite logits detector) rides the same
    batched fetch as the sampled tokens — per-request quarantine costs
    zero extra host syncs and zero recompiles.

    ``K`` (a sparse policy's fixed gather width, serving/sparse_context.py;
    None: dense): the program takes two more streams after ``tables``,
    ``stables`` / ``sbase`` (S, K), and the KV gather reads that K-page
    active table instead of the full W-page one.  K is STATIC, so this is
    still one fixed-shape program inside the zero-recompile pin; the host
    refreshes ``stables``/``sbase`` per step with the same
    no-mutation-before-fetch discipline as ``_pos``/``_tok``.  The single
    decode query needs no per-query ``allowed`` mask: its active row IS
    exactly its own policy set (lut row of its query block)."""
    dec, specs = decoder_for(cfg), cache_groups(cfg)

    def run(params, *args):
        pools, n_pool = _split_groups(cfg, args, quantized)
        tables, *sparse, pos, tok, active, seeds, poison = args[n_pool:]
        # a model of several cache groups: a table and a base a group
        tables, bases = tables if len(specs) > 1 \
            else ((tables,), (None,))
        S = tok.shape[0]
        x = dec.embed(params, tok, pos)[:, None, :]              # (S, 1, E)
        x = x + poison.astype(dec.dtype)[:, None, None]
        off = pos % bs
        groups = []
        for spec, held, table, base in zip(specs, pools, tables, bases):
            page = pos // bs if base is None else (pos - base) // bs
            blk = jnp.where(active, table[jnp.arange(S), page],
                            TRASH_BLOCK)
            groups.append(_Group(held, table, blk, off, base, spec.window,
                                 spec.name))
        x, pools, stats = _forward_groups(
            params, dec, groups, pos, pos, x, quantized,
            sparse=tuple(sparse) or None, row_valid=active[:, None])
        logits = dec.logits(params, x[:, 0])
        finite = jnp.isfinite(logits).all(axis=-1)
        nxt = _pick_next(logits, seeds, pos, temperature, top_k, top_p)
        nxt = jnp.where(active, nxt, 0).astype(jnp.int32)
        return (*_held_groups(pools), *_stats_out(stats), nxt, finite)

    return _shard_wrap(run, "decode_step" if K is None
                       else "sparse_decode_step", mesh, axis_name,
                       _n_pool_args(cfg, quantized),
                       in_streams=(True,) * (6 if K is None else 8),
                       n_out_streams=2)


@functools.lru_cache(maxsize=64)
def _make_spec_verify(cfg, K, W, bs, quantized, mesh, axis_name):
    """Self-speculative draft-verify: K+1 query tokens per lane — the
    current token plus K drafted — scored in ONE fixed-shape batched
    step.  The host accepts the longest prefix of drafts matching the
    program's own argmax continuations, plus one bonus token; that is
    bit-identical to step-by-step greedy BY CONSTRUCTION, because output
    i is only ever consumed when drafts 1..i already equal the true
    greedy tokens — at which point the KV rows written for them are
    exactly what sequential decode would have written, and rejected
    positions are overwritten by the next dispatch before any query can
    attend them unmasked.  Greedy-only (the arming gate enforces
    temperature == 0), so no sampling seeds enter the program.

    ``nvalid`` (per lane) bounds the query positions that may write and
    that feed the finiteness detector: a lane within K tokens of its
    token budget masks the surplus positions to the trash block, so
    near-capacity lanes neither write past their page table nor trip
    false poison quarantines on clamped-gather garbage."""
    dec, n_rows = decoder_for(cfg), len(cache_rows(cfg))

    def run(params, *args):
        pools = _pools_of(args, n_rows, quantized)
        tables, pos, toks, nvalid, active, poison = args[-6:]
        S, T = toks.shape
        posns = pos[:, None] + jnp.arange(T)[None, :]          # (S, T)
        x = dec.embed(params, toks,
                      jnp.minimum(posns, cfg.n_positions - 1))  # (S, T, E)
        x = x + poison.astype(dec.dtype)[:, None, None]
        valid_q = jnp.arange(T)[None, :] < nvalid[:, None]     # (S, T)
        writable = active[:, None] & valid_q & (posns < W * bs)
        blk = jnp.where(
            writable,
            tables[jnp.arange(S)[:, None],
                   jnp.minimum(posns // bs, W - 1)],
            TRASH_BLOCK)
        off = posns % bs
        maxpos = pos + nvalid - 1                              # (S,)
        x, (pools,), _ = _forward_groups(
            params, dec, (_Group(pools, tables, blk.reshape(-1),
                                 off.reshape(-1)),), posns, maxpos, x,
            quantized)
        logits = dec.logits(params,
                            x.reshape(S * T, -1)).reshape(S, T, -1)
        finite = jnp.where(valid_q, jnp.isfinite(logits).all(-1),
                           True).all(axis=1)                   # (S,)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # (S, T)
        nxt = jnp.where(active[:, None], nxt, 0)
        return (*_held(pools), nxt, finite)

    return _shard_wrap(run, "spec_verify", mesh, axis_name,
                       _n_pool(n_rows, quantized),
                       in_streams=(True,) * 6, n_out_streams=2)


@functools.lru_cache(maxsize=256)
def _make_prefill_chunk(cfg, C, W, bs, quantized, final, temperature,
                        top_k, top_p, mesh, axis_name, K=None, win=None,
                        g=None):
    """One prefill chunk of (padded) length C for ONE sequence.  Under
    sharding every shard executes the chunk against its LOCAL pool with
    its own table row / n_valid — non-owner shards get n_valid == 0, so
    their writes all land in the trash block and their (finite) outputs
    are ignored by the host.

    ``K, win, g`` (a sparse policy, serving/sparse_context.py; None:
    dense): the program takes two more streams after ``table_rows``,
    ``stab_rows`` / ``sbase_rows`` (1, K), the gather row and each of its
    pages' first position.  That row is the UNION of the chunk queries'
    active sets (globals + one contiguous window run — fixed width K per
    bucket, see ``SparseContext.prefill_K``), so an early query's gather
    would include blocks below its OWN window; the trace-constant policy
    layout masks those per (query, key-block) pair inside the jit.
    Non-owner shards get all-sentinel sparse rows."""
    dec, specs = decoder_for(cfg), cache_groups(cfg)

    def run(params, *args):
        pools, n_pool = _split_groups(cfg, args, quantized)
        table_rows, *sparse_rows, tokens, start, n_valids, seed = \
            args[n_pool:]
        # a model of several cache groups: a table and a base a group
        table_rows, bases = table_rows if len(specs) > 1 \
            else ((table_rows,), (None,))
        n_valid = n_valids[0]
        posns = start + jnp.arange(C)                      # (C,)
        x = dec.embed(params, tokens, posns)[None]         # (1, C, E)
        valid_i = jnp.arange(C) < n_valid
        off = posns % bs
        groups = []
        for spec, held, rows, base in zip(specs, pools, table_rows, bases):
            row = rows[0]
            page = posns // bs if base is None \
                else jnp.clip((posns - base[0]) // bs, 0, row.shape[0] - 1)
            blk = jnp.where(valid_i, row[page], TRASH_BLOCK)
            groups.append(_Group(held, row[None], blk, off, base,
                                 spec.window, spec.name))
        maxpos = (start + n_valid - 1)[None]             # (1,)
        sparse = allowed = None
        if K is not None:
            srow, sbase = (rows[0] for rows in sparse_rows)
            layout = jnp.asarray(_policy_layout(win, g, W) > 0)
            qb = jnp.minimum(posns // bs, W - 1)               # (C,)
            view_pos = sbase[:, None] + jnp.arange(bs)[None, :]
            sblk = jnp.minimum(view_pos // bs, W - 1)          # (K, bs)
            allowed = layout[qb[:, None, None], sblk[None]] \
                .reshape(C, K * bs)[None]                      # (1, C, K*bs)
            sparse = (srow[None], sbase[None])
        x, pools, stats = _forward_groups(
            params, dec, groups, posns, maxpos, x, quantized, sparse,
            allowed, row_valid=valid_i[None] if dec.stat_names else None)
        out = (*_held_groups(pools), *_stats_out(stats))
        if not final:
            return out
        xe = jax.lax.dynamic_index_in_dim(x[0], n_valid - 1, 0,
                                          keepdims=False)
        logits = dec.logits(params, xe[None])
        finite = jnp.isfinite(logits).all(axis=-1)       # (1,)
        nxt = _pick_next(logits, seed[None], (start + n_valid - 1)[None],
                         temperature, top_k, top_p)
        return (*out, nxt, finite)

    name = f"prefill_chunk{C}" + ("_final" if final else "")
    streams = (True, False, False, True, False)
    if K is not None:       # the two sparse rows follow the table's
        name, streams = "sparse_" + name, (True, True, True) + streams[1:]
    return _shard_wrap(run, name, mesh, axis_name,
                       _n_pool_args(cfg, quantized), in_streams=streams,
                       n_out_streams=2 if final else 0)


def group_table_widths(cfg, W, bs, prefill_chunk):
    """Per cache group of a model (``kv_cache.cache_groups``) the width of
    its page table in the decode program and in a chunk program: ``W``
    (``max_blocks_per_seq``, the whole context) for a group that keeps
    everything, what a window can need for one that keeps a window
    (``kv_cache.window_table_width``)."""
    return [(W, W) if g.window is None else tuple(
        min(W, window_table_width(g.window, bs, queries))
        for queries in (1, prefill_chunk)) for g in cache_groups(cfg)]


def default_pool_blocks(cfg, shards, max_slots, W, bs, prefill_chunk):
    """Pages a cache group of the engine's default pool, which never
    evicts: a group that keeps everything gets the trash block(s) and
    ``max_slots x max_blocks_per_seq`` pages; a group that keeps a window
    gets what ``max_slots`` lanes hold BETWEEN programs, ``ceil(W / bs) +
    1`` each whether they decode or wait between two chunks of a prompt
    (the pages below the next query's window go back after EVERY program,
    ``release_expired``), and on top what the ONE lane with a chunk in
    flight holds beyond that, the chunk's own pages (there is one prefill
    lane a step)."""
    return [shards + max_slots * W if g.window is None
            else 1 + max_slots * decode + (chunk - decode)
            for g, (decode, chunk) in zip(
                cache_groups(cfg),
                group_table_widths(cfg, W, bs, prefill_chunk))]


class InferenceEngine:
    """Continuous-batching serving engine (see module docstring).

    ``temperature``/``top_k``/``top_p`` are ENGINE-static (baked into the
    compiled programs); per-request randomness comes from each request's
    ``seed``.  temperature=0 (greedy) is the bit-parity configuration.
    """

    def __init__(self, model, params, *, max_slots=4, kv_block_size=16,
                 kv_blocks=None, max_blocks_per_seq=None, prefill_chunk=16,
                 quantize_kv=False, temperature=0.0, top_k=0, top_p=0.0,
                 shards=1, mesh=None, axis_name="data", watchdog=None,
                 clock=time.monotonic, reliability=None, telemetry=None,
                 prefix_cache=False, speculative=None, sparse_context=None,
                 prefill_fairness=0):
        cfg = model.config
        dec = decoder_for(cfg)      # GPT-2's refuses its capacity-gated MoE
        for variant, asked in (("quantize_kv", quantize_kv),
                               ("prefix_cache", prefix_cache),
                               ("speculative", speculative),
                               ("sparse_context", sparse_context),
                               ("shards", shards > 1)):
            if asked:
                refuse_unless_plain(cfg, dec, variant)
        assert prefill_chunk >= _MIN_BUCKET \
            and (prefill_chunk & (prefill_chunk - 1)) == 0, \
            f"prefill_chunk must be a power of two >= {_MIN_BUCKET}"
        assert max_slots % shards == 0, (max_slots, shards)
        if mesh is not None:
            assert shards == mesh.shape[axis_name], \
                f"shards={shards} != mesh axis {axis_name} size"
        else:
            assert shards == 1, "shards > 1 requires a mesh"
        self.model, self.cfg, self.dec = model, cfg, dec
        # the served weights as the model holds them (decoder.py, ``hold``):
        # cast once, here, and the tree as given is not kept
        self.params = dec.hold(params)
        self.max_slots = int(max_slots)
        self.shards = int(shards)
        self.mesh = mesh
        self.axis_name = axis_name
        self.bs = int(kv_block_size)
        self.W = int(max_blocks_per_seq
                     or -(-int(cfg.n_positions) // self.bs))
        self.prefill_chunk = int(prefill_chunk)
        # the model's cache groups (kv_cache.cache_groups) and, a group,
        # the width of its table in the decode program and in a chunk
        # program: the whole context for a group that keeps everything,
        # what a window can need for one that keeps a window
        self.groups = cache_groups(cfg)
        self._widths = group_table_widths(cfg, self.W, self.bs,
                                          self.prefill_chunk)
        blocks = default_pool_blocks(cfg, shards, max_slots, self.W,
                                     self.bs, self.prefill_chunk)
        if kv_blocks is not None:
            # the first group's count; a window group keeps its default
            blocks = [int(kv_blocks)] + blocks[1:]
        self.pool = PagedKVPool(
            cfg, num_blocks=blocks[0] if len(blocks) == 1 else blocks,
            block_size=self.bs, shards=shards, mesh=mesh,
            axis_name=axis_name, quantize_kv=quantize_kv)
        self.temperature = float(temperature)
        self.top_k = int(top_k or 0)
        self.top_p = float(top_p or 0.0)
        self.scheduler = Scheduler(max_slots)
        # admission placement: prefer the slot whose shard already holds
        # the candidate's cached prefix (prefix-cache locality beats raw
        # headroom — a hit skips whole prefill chunks), then the slot
        # whose shard has the most free KV blocks, so new sequences
        # spread across shard pools instead of piling evictions onto
        # shard 0
        self.scheduler.slot_ranker = self._rank_slot
        self.scheduler.prefix_probe = self._prefix_probe
        self.clock = clock
        self.metrics = ServingMetrics(clock)
        self.results = {}
        self._watchdog = watchdog
        self._last_metrics = {}
        self._step_idx = 0
        self._rids = itertools.count()
        self._warming = False
        self._drain_requested = False
        # fleet identity: set by serving/fleet.py's router so per-replica
        # chaos (kill_replica / slow_replica) can target THIS engine;
        # None = not part of a fleet, fleet hooks are no-ops
        self._replica_index = None
        rel_cfg = reliability if isinstance(reliability, ReliabilityConfig) \
            else ReliabilityConfig(**(reliability or {}))
        self.reliability = Reliability(self, rel_cfg)
        self._arm_telemetry(telemetry)
        # compiled-program registry (telemetry/programs.py): ALWAYS on —
        # every serving jit registers its shape capture + HLO contract at
        # first dispatch for tools/graftlint/program_lint.py; the pool
        # registers its COW-split program through the same seam
        from deepspeed_tpu.telemetry import ProgramRegistry

        self._programs = ProgramRegistry("serving")
        self.pool.programs = self._programs
        S = self.max_slots
        self._tables = np.full((S, self.W), TRASH_BLOCK, np.int32)
        # the further groups' decode tables and the position of each
        # lane's first row in them (kv_cache: a window group's table
        # slides)
        self._gtables = [np.full((S, decode), TRASH_BLOCK, np.int32)
                         for decode, _ in self._widths[1:]]
        self._gbases = [np.zeros(S, np.int32) for _ in self._widths[1:]]
        self._freed_in_step = 0
        self._pos = np.zeros(S, np.int32)
        self._tok = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        self._seeds = np.zeros(S, np.int32)
        self._poison = np.zeros(S, np.float32)
        self.prefix_cache = self._arm_prefix_cache(prefix_cache,
                                                   quantize_kv)
        self._readmit_rids = set()
        self.spec_k = self._arm_speculative(speculative)
        self._spec = None
        self._drafts = np.zeros((S, max(1, self.spec_k)), np.int32)
        if self.spec_k:
            self._spec = _make_spec_verify(
                cfg, self.spec_k, self.W, self.bs, self.pool.quantized,
                mesh, axis_name)
        # sparse page attention (serving/sparse_context.py) arms AFTER
        # speculation — draft-k is one of its DISARMED blockers — and
        # picks which decode program the engine serves
        self.sparse = self._arm_sparse_context(sparse_context)
        self.prefill_fairness = int(prefill_fairness or 0)
        self._stables = self._sbase = None
        if self.sparse is not None:
            self._stables = np.full((S, self.sparse.K), TRASH_BLOCK,
                                    np.int32)
            self._sbase = np.full((S, self.sparse.K),
                                  int(self.sparse.sentinel), np.int32)
        self._decode = _make_decode_step(
            cfg, self.W, self.bs, self.pool.quantized, self.temperature,
            self.top_k, self.top_p, mesh, axis_name,
            **({} if self.sparse is None else {"K": self.sparse.K}))
        # a program's one name is its jit's (_shard_wrap)
        self._decode_name = self._decode.__name__

    @property
    def program_registry(self):
        """The engine's compiled-program registry (always armed): every
        serving jit dispatched so far, with its declarative HLO contract.
        Read by ``python -m tools.graftlint --programs``."""
        return self._programs

    def _pool_contract(self, **extra):
        """The contract every pool-threading serving jit shares: pure
        device work, ZERO collective bytes under batch-axis sharding
        (comm_accounting.serving_decode_collectives' placement-semantics
        claim), and the paged KV pool (argnums 1..n_pool) donated —
        steady-state serving is allocation-free on the pool."""
        contract = {
            "host_transfer_free": True,
            "collective_free": True,
            "donates_argnums": tuple(range(1, 1 + self.n_pool_tensors())),
        }
        contract.update(extra)
        return contract

    def _register_serving_program(self, name, jit_fn, args, **extra):
        from deepspeed_tpu.telemetry import register_program

        register_program(self._programs, name, jit_fn, args,
                         mesh=None, contract=self._pool_contract(**extra))

    def _arm_prefix_cache(self, requested, quantize_kv_requested):
        """COW shared-prefix caching arms only where its bookkeeping is
        honest; every blocked request warns loudly naming the blocker
        (the armed-or-warns DISARMED discipline).  The cache itself is
        sampling-safe — cached KV rows are a pure function of the token
        prefix — so unlike speculation it does NOT require greedy."""
        if not requested:
            return False
        if quantize_kv_requested and not self.pool.quantized:
            logger.warning(
                "prefix cache: DISARMED — int8 KV was requested but the "
                "pool disarmed it (off-profitability: scale overhead >= "
                "byte savings at this head_dim/dtype); refusing to stack "
                "block sharing on a pool whose storage already silently "
                "differs from the asked-for config.  Serving without "
                "prefix caching.")
            return False
        if self.scheduler.draining:
            logger.warning(
                "prefix cache: DISARMED — the engine is draining: "
                "admission is closed, so no request could ever consult "
                "the tree; arming now would only pin blocks a successor "
                "cannot inherit.")
            return False
        return True

    def _arm_speculative(self, spec):
        """Self-speculative decoding (``speculative=k`` or
        ``{"draft_len": k}``) arms only in the greedy configuration:
        acceptance compares ARGMAX continuations token-for-token, so
        with sampling (temperature > 0) the accepted prefix would not
        equal what the sampled step-by-step stream emits — blocked
        requests warn DISARMED naming the blocker and serve the plain
        one-token decode jit instead.  Returns the armed draft length
        (0 = disarmed)."""
        if not spec:
            return 0
        k = int(spec.get("draft_len", 4)) if isinstance(spec, dict) \
            else int(spec)
        if k < 1:
            logger.warning(
                "speculative decoding: DISARMED — draft_len=%d < 1 "
                "drafts nothing; serving the plain decode jit.", k)
            return 0
        if self.temperature != 0.0:
            logger.warning(
                "speculative decoding: DISARMED — sampling != greedy: "
                "temperature=%g, but the acceptance rule (accepted "
                "prefix == step-by-step greedy argmax) is only defined "
                "at temperature=0; a sampled stream would diverge from "
                "the verified continuations.  Serving the plain decode "
                "jit.", self.temperature)
            return 0
        return k

    def _arm_sparse_context(self, spec):
        """Sparse page attention (``sparse_context=`` as a policy dict,
        an ``ops/sparse_attention`` SparsityConfig-style object, or a
        prebuilt :class:`SparseContext`) arms only where the policy maps
        soundly onto the paged pool — every blocked request warns
        DISARMED naming the blocker and the engine serves the dense
        decode jit instead (the armed-or-warns discipline).  Blockers:
        a token window that is not a multiple of the pool block size
        (the window edge would land mid-page), beam search (active-page
        lists are single-hypothesis), draft-k speculation (the verify
        jit gathers the full table — composing them is future work),
        and non-prefix global anchors.  Returns the armed SparseContext
        or None."""
        if not spec:
            return None
        if self.spec_k:
            logger.warning(
                "sparse context: DISARMED — draft-k speculative decoding "
                "is armed (draft_len=%d): the verify jit scores K+1 "
                "query tokens against the FULL page table and its "
                "acceptance rule assumes dense attention; composing the "
                "two gather policies is not supported yet.  Serving "
                "dense attention.", self.spec_k)
            return None
        if isinstance(spec, SparseContext):
            if spec.bs != self.bs or spec.W != self.W:
                logger.warning(
                    "sparse context: DISARMED — the supplied "
                    "SparseContext was compiled for block_size=%d/"
                    "table_width=%d but this engine runs %d/%d; its LUT "
                    "would address the wrong pages.  Serving dense "
                    "attention.", spec.bs, spec.W, self.bs, self.W)
                return None
            return spec
        if isinstance(spec, dict):
            d = dict(spec)
            beam = int(d.pop("beam_width", 1) or 1)
            if beam > 1:
                logger.warning(
                    "sparse context: DISARMED — beam_width=%d > 1: beam "
                    "lanes share pages under different hypotheses and "
                    "the per-lane active-page lists are single-"
                    "hypothesis.  Serving dense attention.", beam)
                return None
            wt = d.pop("window_tokens", None)
            if wt is not None:
                if int(wt) % self.bs != 0:
                    logger.warning(
                        "sparse context: DISARMED — window_tokens=%d is "
                        "not a multiple of the KV block size %d: the "
                        "policy's block granularity must BE the pool's "
                        "block size or the window edge lands mid-page.  "
                        "Round the window to a block multiple (e.g. %d "
                        "or %d).  Serving dense attention.",
                        int(wt), self.bs,
                        (int(wt) // self.bs) * self.bs,
                        (int(wt) // self.bs + 1) * self.bs)
                    return None
                d.setdefault("num_sliding_window_blocks",
                             int(wt) // self.bs)
            win = int(d.get("num_sliding_window_blocks", 0))
            if win < 1:
                logger.warning(
                    "sparse context: DISARMED — num_sliding_window_"
                    "blocks=%d < 1 cannot cover the query's own block.  "
                    "Serving dense attention.", win)
                return None
            return SparseContext(block_size=self.bs, table_width=self.W,
                                 **d)
        try:
            return SparseContext.from_sparsity_config(
                spec, block_size=self.bs, table_width=self.W)
        except ValueError as e:
            logger.warning(
                "sparse context: DISARMED — %s.  Serving dense "
                "attention.", e)
            return None

    def _arm_telemetry(self, spec):
        """Arm the serving telemetry session from the ``telemetry=``
        kwarg: ``None`` (off), a ``Telemetry`` instance, or a dict of
        Telemetry kwargs (plus ``"enabled"``).  Disarmed serving holds
        ``self._tracer = None`` — one attribute check per step, the
        compiled decode surface untouched (zero recompiles pinned by the
        telemetry test's CompilationCounter).  A config handed in with
        ``enabled=false`` or with every channel off would observe
        nothing, so it warns DISARMED instead of silently dropping the
        ask."""
        self.telemetry = None
        self._tracer = None
        self._owns_telemetry = False
        self._lane_serve = 0
        # kept only while a tracer is armed (_dispatch / _fetch): the
        # open run_* span while a program of ours is unfetched, else the
        # open host_gap (None before the first dispatch), and whether a
        # step() found the engine empty during that gap
        self._run = None
        self._gap = None
        self._gap_idle = False
        # one level down (_mark): the open phase span, the a0 it will end
        # with, and when the last fetch returned (step_host_us)
        self._phase = None
        self._phase_a0 = -1
        self._fetched_at = None
        # per program dispatched since the last fetch, while a tracer is
        # armed: (group, counters known on the host, the model's counters
        # still on the device or None) -- see _note_program
        self._stats_pending = []
        self._memacct = None
        if spec is None:
            return
        from deepspeed_tpu.telemetry import Telemetry

        if isinstance(spec, Telemetry):
            tel = spec
        else:
            self._owns_telemetry = True
            cfg = dict(spec)
            if not cfg.pop("enabled", True):
                logger.warning(
                    "serving telemetry: DISARMED — a telemetry config was "
                    "passed with enabled=false; no trace, step stream or "
                    "MFU accounting will be produced")
                return
            tel = Telemetry(**cfg)
        if tel.tracer is None and tel.stream is None and tel.mfu is None:
            logger.warning(
                "serving telemetry: every channel is off (trace=false, "
                "metrics_jsonl unset, mfu=false) — effectively DISARMED")
        self.telemetry = tel
        self._tracer = tel.tracer
        if self._tracer is not None:
            self._lane_serve = self._tracer.lane("serve")
            self._tracer.intern("serving_step", args=("step",))
            self._tracer.intern("decode_step", args=("lanes",))
            self._tracer.intern("admit", args=("rid",))
            self._tracer.intern("host_gap", args=("engine_busy",))
            self._tracer.intern("run_decode", args=("lanes",))
            self._tracer.intern("run_prefill", args=("bucket",))
            self._tracer.intern("run_prefill_decode", args=("lanes",))
            self._tracer.intern("dispatch", args=("bucket",))
            for phase in ("tables", "fetch", "tokens"):
                self._tracer.intern(phase, args=("lanes",))
            self._tracer.intern("step_host_us", args=("us",))
        # measured HBM accounting (ISSUE 15): per-jit memory_analysis()
        # registered capture-by-shape alongside MFU, sharing its lazy
        # compile cache — one compile per jit, zero on the decode path
        from deepspeed_tpu.runtime.memory_accounting import \
            MemoryAccounting

        self._memacct = MemoryAccounting(shared=tel.mfu)

    def export_trace(self, path):
        """Chrome-trace JSON of the retained events (None when tracing
        is disarmed)."""
        tr = self._tracer
        if tr is None:
            return None
        return tr.export_chrome_trace(path)

    def close_telemetry(self):
        """Close the metrics-stream file handle of a telemetry session
        THIS engine created from a dict spec (a caller-provided
        ``Telemetry`` instance is the caller's to close).  Idempotent;
        also runs at GC so loops that build engines never leak JSONL
        fds."""
        if getattr(self, "_owns_telemetry", False) \
                and self.telemetry is not None:
            self.telemetry.close()

    def __del__(self):
        try:
            self.close_telemetry()
        except Exception:  # lint: allow-broad-except — interpreter
            # teardown can fail imports mid-GC; never raise from __del__
            pass

    # -- public API -----------------------------------------------------
    @property
    def capacity_per_seq(self) -> int:
        """Longest admissible prompt+max_new: the position budget
        (n_positions), the page-table width, AND one shard's usable
        block pool all bound it."""
        return min(int(self.cfg.n_positions), self.W * self.bs,
                   (self.pool.blocks_per_shard - 1) * self.bs)

    def submit(self, prompt, max_new_tokens, *, priority=0,
               eos_token_id=None, seed=0, deadline_s=None,
               work_budget=None, _generated=None, _rid=None,
               _work_done=0, _readmit=False) -> int:
        """Submit one request.  ``deadline_s``/``work_budget`` (engine
        defaults from the ReliabilityConfig) bound its wall-clock life
        and total scheduled token-writes; under predicted SLO overload
        the admission gate may shed lower-priority queued work or turn
        this request away (``results[rid]["status"] == "shed"``).
        ``_generated``/``_rid``/``_work_done`` are the :meth:`recover`
        re-submission hooks (journal replay through the eviction
        re-prefill path; the restored ``_work_done`` keeps work budgets
        accumulating across crash-migrate cycles instead of granting
        each recovery a fresh budget).  ``_readmit=True`` marks a
        recovery/migration re-submission: the request was ADMITTED once
        already, so the SLO admission gate must not shed it again — it
        is journaled directly."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert prompt.size >= 1 and max_new_tokens >= 1
        total = prompt.size + int(max_new_tokens)
        assert total <= self.capacity_per_seq, \
            f"prompt+max_new={total} exceeds per-sequence capacity " \
            f"{self.capacity_per_seq} (W={self.W} blocks x {self.bs}, " \
            f"{self.pool.blocks_per_shard - 1} usable blocks/shard)"
        rel_cfg = self.reliability.config
        if self._warming:
            deadline_s = work_budget = None   # synthetic warmup traffic
        else:
            if deadline_s is None:
                deadline_s = rel_cfg.default_deadline_s
            if work_budget is None:
                work_budget = rel_cfg.default_work_budget
        if deadline_s is not None and float(deadline_s) <= 0:
            raise ValueError(
                f"deadline_s={deadline_s} is not a positive budget: the "
                f"request would expire before its first step ever runs. "
                f"Submit with deadline_s=None (no deadline) or a positive "
                f"number of seconds.")
        rid = next(self._rids) if _rid is None else int(_rid)
        if deadline_s is not None and not self._warming and not _readmit:
            # deadline-impossible max_new: even PERFECT service — an
            # empty queue, every step at the measured EMA — cannot fit
            # the minimum step count inside the budget.  Reject at
            # admission instead of burning prefill work that is
            # guaranteed to expire mid-flight.  Strict lower bound only:
            # a request feasible in isolation is never turned away here
            # (queueing delay stays the reliability layer's call).
            ema = self.metrics.step_time()
            min_steps = -(-int(prompt.size) // self.prefill_chunk) \
                + int(max_new_tokens)
            if ema is not None and min_steps * ema > float(deadline_s):
                logger.warning(
                    "submit(rid=%d): deadline-impossible — prompt=%d "
                    "tokens + max_new=%d needs >= %d engine steps; at "
                    "the measured %.4fs/step that is a %.3fs zero-queue "
                    "lower bound, over deadline_s=%.3f.  Rejected at "
                    "admission (no prefill work wasted).  Raise "
                    "deadline_s or shrink max_new_tokens.",
                    rid, prompt.size, int(max_new_tokens), min_steps,
                    ema, min_steps * ema, float(deadline_s))
                self.results[rid] = {
                    "tokens": prompt.copy(), "status": ABORT_EXPIRED,
                    "evictions": 0,
                }
                self.metrics.record_finish(rid, ABORT_EXPIRED)
                if self._tracer is not None:
                    self._tracer.instant(f"abort_{ABORT_EXPIRED}",
                                         self._lane_serve, a0=rid)
                return rid
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      priority=int(priority), eos_token_id=eos_token_id,
                      seed=int(seed), deadline_s=deadline_s,
                      work_budget=work_budget)
        if deadline_s is not None:
            req.deadline = self.clock() + float(deadline_s)
        if _generated:
            req.generated = [int(t) for t in _generated]
        if _work_done:
            req.work_done = int(_work_done)
        # TTFT class: "long" prompts (several prefill chunks) vs chatty
        # "short" ones — the per-class view the fairness guard's tests
        # read
        self.metrics.record_submit(
            rid, klass="long" if prompt.size >= 4 * self.prefill_chunk
            else "short")
        if not self._warming:
            if _readmit:
                # already-admitted work (recovery/migration): bypass the
                # shedding gate, but journal it here so THIS engine's
                # crash covers it too.  Tagged so the prefix probe can
                # attribute cache savings to the recovery path.
                self._readmit_rids.add(rid)
                if self.reliability.journal is not None:
                    self.reliability.journal.record_submit(req)
            elif self.reliability.on_submit(req) == "reject":
                self.results[rid] = {
                    "tokens": np.asarray(req.full_tokens, np.int32),
                    "status": ABORT_SHED, "evictions": 0,
                }
                self.metrics.record_finish(rid, ABORT_SHED)
                return rid
        self.scheduler.submit(req)
        return rid

    def cancel(self, rid) -> bool:
        req = self.scheduler.cancel(rid)
        if req is None:
            return False
        self._cleanup(req, "cancelled")
        return True

    def step(self) -> dict:
        """One serving tick: chaos hooks, deadline/budget enforcement,
        at most one prefill chunk, one batched decode dispatch, then
        host-side bookkeeping on a SINGLE batched token+finiteness
        fetch, and the journal's step-boundary commit."""
        self._step_idx += 1
        tr = self._tracer
        # serving_step, prefill_tick and decode_step time a call from its
        # own boundary (their readers: decode_step_ms, prefill_tick_ms and
        # their twins).  Each begins and ends at the instant of a mark,
        # after the phase that ends there and before the one that begins,
        # so that the profiler's annotations nest
        now = None
        if tr is not None:
            now = self._end_phase()
            _step = tr.span("serving_step", self._lane_serve, t0=now)
        self._mark("step_begin", at=now)
        slow = chaos.serving_slow_step_s(self._step_idx) \
            + chaos.fleet_slow_replica_s(self._replica_index,
                                         self._step_idx)
        if slow:
            time.sleep(slow)
        if self._watchdog is not None:
            self._watchdog.observe_serving_step(self._step_idx)
        if self._drain_requested:
            if tr is not None and not self.scheduler.draining:
                tr.instant("drain_requested", self._lane_serve,
                           a0=self._step_idx)
            self.scheduler.draining = True
        events = {"admitted": [], "finished": [], "evicted": [],
                  "cancelled": [], "expired": [], "budget": [],
                  "poisoned": []}
        self._freed_in_step = 0
        rid = self.scheduler.chaos_cancel()
        if rid is not None and self.cancel(rid):
            events["cancelled"].append(rid)
        self._enforce_deadlines(events)
        if tr is not None:
            now = self._end_phase()
            _tick = tr.span("prefill_tick", self._lane_serve, t0=now)
        self._mark("prefill_prep", at=now)
        self._prefill_tick(events)
        if tr is not None:
            now = self._end_phase()
            _tick.end(at=now)
            _tick = tr.span("decode_step", self._lane_serve, t0=now)
        self._mark("tables", len(self.scheduler.running), at=now)
        decoded = self._decode_tick(events)
        if tr is not None:
            now = self._end_phase()
            _tick.end(a0=decoded, at=now)
        self._mark("step_end", at=now)
        if tr is not None:
            for rid_ in events["admitted"]:
                tr.instant("admit", self._lane_serve, a0=rid_)
        self.reliability.on_step_end()
        occ = self.pool.occupancy()
        frag = self.pool.fragmentation()
        qd = self.scheduler.queue_depth()
        self.metrics.record_step(
            queue_depth=qd, running=decoded, slots=self.max_slots,
            occupancy=occ, fragmentation=frag, decoded=decoded > 0)
        rel = self.reliability
        self._last_metrics = {
            "step": self._step_idx, "queue_depth": qd,
            "running": len(self.scheduler.running),
            "kv_occupancy": occ, "kv_fragmentation": frag,
            "decoded_lanes": decoded,
            "events": {k: len(v) for k, v in events.items()},
            "shed": rel.aborts[ABORT_SHED],
            "expired": rel.aborts[ABORT_EXPIRED],
            "poisoned": rel.aborts[ABORT_POISONED],
            "journal_depth": rel.journal_depth(),
            "draining": self.scheduler.draining,
            # prefix cache + speculation ride the same host-dict idiom:
            # scalar values flow into the fleet's flattened
            # replica_metrics automatically, the histogram dict is
            # aggregated explicitly by FleetRouter.telemetry_report()
            "prefix_hit_rate": self.metrics.prefix_hit_rate(),
            "prefix_avoided_tokens": self.metrics.prefix_avoided_tokens,
            "prefill_tokens_computed":
                self.metrics.prefill_computed_tokens,
            "tokens_per_verify": self.metrics.tokens_per_verify(),
            "spec_accept_hist": dict(self.metrics.spec_accept_hist),
            # sparse page attention (ISSUE 20): scalars only, so the
            # fleet's flattened replica_metrics carry them for free
            "active_page_fraction": self.metrics.active_page_fraction(),
            "window_expired_frees": self.metrics.window_expired_frees,
            "short_ttft_p95": self.metrics.class_ttft_p95("short"),
        }
        if tr is not None:
            if self._gtables:
                # the pool by cache group after the step: live pages,
                # usable pages, and the window pages the step returned
                for g in self.pool.group_stats():
                    tr.count(f"kv_pages_{g['name']}", self._lane_serve,
                             g["blocks_in_use"])
                    tr.count(f"kv_pool_pages_{g['name']}", self._lane_serve,
                             g["blocks_total"])
                tr.count("kv_window_pages_freed", self._lane_serve,
                         self._freed_in_step)
            if self._gap is not None and qd == 0 \
                    and not self.scheduler.in_flight():
                # this gap is want of demand, not the host's doing
                self._gap_idle = True
            now = self._end_phase()
            _step.end(a0=self._step_idx, at=now)
            self._mark("caller", at=now)
        if self.telemetry is not None and not self._warming:
            self.telemetry.on_step(self._step_idx, self._last_metrics)
        return events

    def serve(self, *, max_steps=100000) -> dict:
        steps = 0
        while self.scheduler.has_work():
            if self._drain_requested and not self.scheduler.in_flight():
                break    # drained: waiting work stays journaled
            if steps >= max_steps:
                raise RuntimeError(
                    f"serve() exceeded max_steps={max_steps} with "
                    f"{self.scheduler.queue_depth()} queued")
            self.step()
            steps += 1
        return self.results

    # -- reliability lifecycle (drain / recover) ------------------------
    def request_drain(self) -> None:
        """Ask for a graceful drain: admission stops at the next step
        boundary, in-flight requests run to completion, queued requests
        stay journaled for a successor's :meth:`recover`.  Signal-
        handler safe: only sets a flag (the PR 7
        ``request_preemption`` idiom)."""
        self._drain_requested = True
        # NOTE: no tracer event here — this runs in signal-handler
        # context and the tracer takes a lock; the step loop emits the
        # drain instant at the next (safe) step boundary instead

    def install_preemption_handler(self, signals=None) -> None:
        """Route SIGTERM (the preemption notice on TPU pods) into
        :meth:`request_drain` — the serving analog of the training
        engine's ``install_preemption_handler``.  Any previously
        installed Python-level handler is CHAINED, not replaced: a
        process hosting BOTH a training engine and a serving engine
        (the fine-tune-and-serve colocation) must graceful-preempt the
        trainer AND drain the server on one SIGTERM — ``signal.signal``
        alone is last-wins and silently dropped whichever handler
        registered first.  Main thread only (a Python signal-handler
        constraint)."""
        import signal as signal_mod

        from deepspeed_tpu.runtime.resilience.watchdog import \
            chain_signal_handlers

        sigs = chain_signal_handlers(self.request_drain, signals)
        logger.info("serving preemption handler installed for %s",
                    [signal_mod.Signals(s).name for s in sigs])

    def drain(self, *, max_steps=100000) -> dict:
        """Graceful shutdown: stop admission, finish every in-flight
        request (deadlines still enforced — a hung request cannot stall
        the drain past its budget), commit the journal, and return the
        results so far.  Queued requests stay live in the journal; a
        replacement engine picks them up via :meth:`recover`."""
        self.request_drain()
        self.scheduler.draining = True
        steps = 0
        while self.scheduler.in_flight():
            if steps >= max_steps:
                raise RuntimeError(
                    f"drain() exceeded max_steps={max_steps} with "
                    f"{len(self.scheduler.running)} still running")
            self.step()
            steps += 1
        self.reliability.on_step_end()
        left = self.scheduler.queue_depth()
        if left and self.reliability.journal is None:
            logger.warning(
                "drain: %d queued requests have NO journal armed "
                "(ReliabilityConfig.journal_path unset) — they are lost "
                "on exit instead of recoverable.", left)
        return self.results

    def recover(self, journal_path) -> list:
        """Crash recovery: replay a (dead predecessor's) request journal
        and re-submit every live request — with its journaled generated
        tokens — through the SAME re-prefill path eviction uses, so
        greedy continuations are bit-identical to the uninterrupted
        run.  Original rids and FCFS order are preserved; deadlines
        restart (wall clocks do not survive processes; the journal
        stores the relative budget).  Returns the recovered rids."""
        assert not self.scheduler.has_work(), "recover() on a busy engine"
        entries = RequestJournal.replay(journal_path)
        rids = []
        max_rid = -1
        for e in entries:
            rid = self.submit(
                np.asarray(e["prompt"], np.int32),
                e["max_new"], priority=e["priority"],
                eos_token_id=e["eos"], seed=e["seed"],
                deadline_s=e["deadline_s"], work_budget=e["work_budget"],
                _generated=e["generated"], _rid=e["rid"],
                _work_done=e.get("work_done", 0), _readmit=True)
            rids.append(rid)
            max_rid = max(max_rid, rid)
        self._rids = itertools.count(max_rid + 1)
        if self._tracer is not None:
            self._tracer.instant("recover", self._lane_serve,
                                 a0=len(rids))
        logger.info("recover: re-submitted %d journaled requests from %s",
                    len(rids), journal_path)
        return rids

    # -- fleet migration (serving/fleet.py drives these) ----------------
    def export_request(self, rid) -> dict:
        """Detach one RUNNING request for migration to another replica:
        ONE batched device fetch of its paged KV blocks (a fixed-shape
        (L, W, ...) gather — compiles once, shared by every same-config
        replica), then scheduler/pool/journal bookkeeping that removes
        the request WITHOUT a terminal result — its journal end record
        says ``migrated``, so this replica's journal no longer lists it
        live (the destination's journal does, from its re-submission).
        Returns the state dict :meth:`import_request` consumes.

        The KV handoff is the disaggregated prefill/decode transfer of
        PAPERS.md 2601.02311: prefill is compute-bound, decode is
        memory-bound, and moving the finished prompt's KV blocks once
        is what makes separately-provisioned replicas composable.  The
        payload is priced analytically by
        ``comm_accounting.serving_kv_handoff_collectives``.

        Sharded pools (``shards > 1``) hand off through the same path:
        the gather addresses GLOBAL block ids (local + the owning
        shard's base — ``pool.global_table_row``), so the host copy is
        shard-layout-free and imports into a destination with ANY shard
        count."""
        refuse_unless_plain(self.cfg, self.dec,
                            "export_request (fleet hand-off)")
        req = self.scheduler.requests.get(rid)
        assert req is not None and req.state is RequestState.RUNNING, \
            f"export_request({rid}): not a RUNNING request"
        assert req.generated, "RUNNING request with no first token"
        row = self.pool.global_table_row(rid, self.W)
        n_blocks = len(self.pool._blocks[rid])
        n_positions = self.pool._positions[rid]
        # one fixed-shape gather + ONE batched fetch: per pool tensor its
        # ``kv_cache.pool_shapes`` shape with W blocks on the block axis,
        # trash-padded rows included (their content is garbage by
        # contract; the value mask keeps it inert)
        kv = jax.device_get(tuple(
            a[:, row] for a in self.pool.tensors.arrays))
        slot = req.slot
        self.scheduler.finish(req, "migrated")
        self.pool.free(rid)
        self._clear_slot(slot)
        self.metrics.record_finish(rid, "migrated")
        if not self._warming:
            self.reliability.on_finish(req, "migrated")
        return {
            "rid": req.rid, "prompt": req.prompt,
            "generated": list(req.generated),
            "max_new_tokens": req.max_new_tokens,
            "priority": req.priority, "eos": req.eos_token_id,
            "seed": req.seed, "deadline_s": req.deadline_s,
            "work_budget": req.work_budget, "work_done": req.work_done,
            "evictions": req.evictions,
            "kv": kv, "n_blocks": n_blocks, "n_positions": n_positions,
        }

    def import_request(self, entry) -> str:
        """Adopt a migrated RUNNING request with its transferred KV:
        allocate blocks, scatter the paged rows into the local pool (one
        fixed-shape ``.at[].set`` per pool tensor — compiles once), and
        join the decode batch DIRECTLY, no re-prefill.  Decoding resumes
        at the exact position the source stopped, so greedy
        continuations stay bit-identical.  Falls back to the journal
        re-prefill path (a normal re-submission) when no slot or not
        enough blocks are free here — always correct, just re-pays the
        prefill.  Deadlines restart relative (the :meth:`recover`
        semantics — clocks do not cross replicas); work budgets carry
        over.  Returns ``"adopted"`` or ``"requeued"``."""
        refuse_unless_plain(self.cfg, self.dec,
                            "import_request (fleet hand-off)")
        rid = int(entry["rid"])
        assert rid not in self.scheduler.requests, \
            f"import_request({rid}): rid already live here"
        slot = self.scheduler.free_slot()
        shard = 0 if slot is None else self._shard_for_slot(slot)
        if slot is None \
                or self.pool.free_blocks(shard) < entry["n_blocks"]:
            self.submit(np.asarray(entry["prompt"], np.int32),
                        entry["max_new_tokens"],
                        priority=entry["priority"],
                        eos_token_id=entry["eos"], seed=entry["seed"],
                        deadline_s=entry["deadline_s"],
                        work_budget=entry["work_budget"],
                        _generated=entry["generated"], _rid=rid,
                        _work_done=entry["work_done"], _readmit=True)
            return "requeued"
        req = Request(rid=rid,
                      prompt=np.asarray(entry["prompt"], np.int32),
                      max_new_tokens=int(entry["max_new_tokens"]),
                      priority=int(entry["priority"]),
                      eos_token_id=entry["eos"], seed=int(entry["seed"]),
                      deadline_s=entry["deadline_s"],
                      work_budget=entry["work_budget"])
        req.generated = [int(t) for t in entry["generated"]]
        assert req.generated, "adopted request must carry a first token"
        req.work_done = int(entry["work_done"])
        req.evictions = int(entry.get("evictions", 0))
        req.prefill_done = len(req.full_tokens)
        req.shard = shard
        if req.deadline_s is not None:
            req.deadline = self.clock() + float(req.deadline_s)
        ok = self.pool.alloc(rid, shard, entry["n_positions"])
        assert ok, "free_blocks precheck lied"
        # the scatter addresses GLOBAL rows (trash padding lands in the
        # adopting shard's own trash block); the decode table stays
        # LOCAL — inside the sharded decode shard_map each shard sees
        # only its local block range
        dst_row = self.pool.global_table_row(rid, self.W)
        t = self.pool.tensors.arrays
        new = tuple(a.at[:, dst_row].set(jnp.asarray(part))
                    for a, part in zip(t, entry["kv"]))
        if self.shards > 1 and self.pool.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # the out-of-jit scatter may resolve to a different layout;
            # pin the pool's (None, 'data') block-axis split back so the
            # donated decode jit sees its expected input sharding
            spec = NamedSharding(self.pool.mesh,
                                 P(None, self.pool.axis_name))
            new = tuple(jax.device_put(x, spec) for x in new)
        self._rebind(new)
        self.scheduler.adopt_running(req, slot)
        self._tables[slot] = self.pool.table_row(rid, self.W)
        self._pos[slot] = len(req.full_tokens) - 1
        self._tok[slot] = req.generated[-1]
        self._seeds[slot] = req.seed
        self._active[slot] = True
        if self.sparse is not None:
            self._stables[slot], self._sbase[slot] = \
                self.sparse.active_row(self._tables[slot],
                                       int(self._pos[slot]))
        # journal directly (no admission gate: this work was admitted
        # once already); no metrics.record_submit — TTFT stays at the
        # replica that admitted it
        if not self._warming and self.reliability.journal is not None:
            self.reliability.journal.record_submit(req)
        return "adopted"

    def can_adopt(self, n_blocks) -> bool:
        """True when :meth:`import_request` would adopt directly (a
        free slot whose shard has ``n_blocks`` free) — the router
        checks BEFORE exporting, so a full decode tier never pays a
        device fetch just to discard the computed KV and re-prefill."""
        slot = self.scheduler.free_slot()
        return slot is not None and \
            self.pool.free_blocks(self._shard_for_slot(slot)) \
            >= n_blocks

    def warmup(self) -> None:
        """Compile every program the steady state can need — the decode
        jit plus each (bucket, final/non-final) prefill variant that an
        ADMISSIBLE request can reach — by serving throwaway requests,
        then reset results/metrics.  After warmup, request churn
        triggers ZERO new compilations.

        Coverage argument: a final chunk of residue r compiles the same
        program as any residue in its power-of-two bucket, and every
        reachable bucket admits a single-chunk prompt of length r
        (multi-chunk prompts only shrink the admissible residue), so one
        short prompt per bucket plus ONE prompt longer than
        prefill_chunk (iff any admissible prompt is) covers everything."""
        assert not self.scheduler.has_work(), "warmup on a busy engine"
        if self._tracer is not None:
            # the width a reader should price the weights at: what is held
            from deepspeed_tpu.runtime.memory_accounting import \
                tree_device_bytes

            self._tracer.count("served_weight_bytes", self._lane_serve,
                               tree_device_bytes(self.params))
        # warmup traffic is synthetic: bypass the admission gate and the
        # journal (a recovery replay must never see throwaway requests)
        self._warming = True
        cap = self.capacity_per_seq
        lens = set()
        for b in self._buckets():
            n = b if b == _MIN_BUCKET else b // 2 + 1
            if n + 1 <= cap:
                lens.add(n)               # single-chunk final, bucket b
        if cap - 1 > self.prefill_chunk:
            # some admissible prompt spans chunks: compile the non-final
            # (always full-chunk) variant too
            lens.add(min(2 * self.prefill_chunk, cap - 1))
        for ln in sorted(lens):
            self.submit(np.zeros(ln, np.int32),
                        max_new_tokens=min(2, cap - ln))
        if cap >= 3:
            # the first token comes from the prefill-final jit; the
            # decode jit only compiles on a SECOND token — guarantee one
            # even when every bucket prompt above could only afford
            # max_new=1
            self.submit(np.zeros(1, np.int32), max_new_tokens=2)
        self.serve()
        if self.prefix_cache:
            # the COW-split copy is the one non-jit device program the
            # cache can reach — compile it here, inside warmup
            self.pool.warm_cow()
        self._warming = False
        self.results.clear()
        self.metrics.reset()
        self._last_metrics = {}
        self._step_idx = 0

    def result(self, rid) -> np.ndarray:
        """prompt + generated tokens of a finished/cancelled request."""
        return self.results[rid]["tokens"]

    def serving_report(self) -> dict:
        """TTFT / TPOT / throughput / queue-depth / KV-pool occupancy of
        the run so far — the serving analog of the training engine's
        comm_volume_report(): pure host accounting, no device sync."""
        rep = self.metrics.report()
        rep["config"] = {
            "max_slots": self.max_slots, "shards": self.shards,
            "kv_block_size": self.bs, "kv_blocks": self.pool.num_blocks,
            "max_blocks_per_seq": self.W,
            "prefill_chunk": self.prefill_chunk,
            "quantized_kv": self.pool.quantized,
            "temperature": self.temperature, "top_k": self.top_k,
            "top_p": self.top_p,
            "prefix_cache": self.prefix_cache,
            "speculative_draft_len": self.spec_k,
            "sparse_context": self.sparse.describe()
            if self.sparse is not None else None,
            "prefill_fairness": self.prefill_fairness,
        }
        rep["kv_pool"]["now"] = self.pool.stats()
        rep["reliability"] = self.reliability.report()
        return rep

    def telemetry_report(self) -> dict:
        """Unified observability report (the serving face of the training
        engines' ``telemetry_report()``): the full legacy
        ``serving_report()`` plus the telemetry sections — metrics
        registry snapshot, trace summary, and the decode MFU/HFU ledger
        (``mfu``, populated from the decode jit's
        ``cost_analysis()``)."""
        rep = self.serving_report()
        tel = self.telemetry
        # same top-level schema as the training engines' report
        # (telemetry_armed/metrics/trace/mfu) so shared consumers never
        # branch on engine type; the nested "telemetry" section mirrors
        # them for back-compat
        rep["telemetry_armed"] = tel is not None
        rep["telemetry"] = {"armed": tel is not None}
        # memory leg (ISSUE 15): pool + params analytic always, measured
        # per-jit memory_analysis when telemetry is armed
        rep["memory"] = self.memory_report()
        if tel is None:
            return rep
        rep["metrics"] = rep["telemetry"]["metrics"] = \
            tel.registry.snapshot()
        if tel.tracer is not None:
            rep["trace"] = rep["telemetry"]["trace"] = \
                tel.tracer.summary()
        if tel.mfu is not None:
            from deepspeed_tpu.telemetry import model_flops_per_step

            n_params = sum(
                int(l.size)
                for l in jax.tree_util.tree_leaves(self.params))
            # decode model FLOPs: 2ND forward-only over every dispatched
            # lane (idle lanes still compute — multiply by
            # slot_utilization for a goodput-adjusted MFU)
            rep["mfu"] = tel.mfu.report(
                step_time_s=self.metrics.step_time() or tel.step_time_s(),
                n_devices=max(1, self.shards),
                model_flops=model_flops_per_step(n_params, self.max_slots,
                                                 fwd_only=True),
                device_kind=getattr(jax.devices()[0], "device_kind", None))
            rep["mfu"]["n_params"] = n_params
            rep["mfu"]["tokens_per_step"] = self.max_slots
        return rep

    def memory_report(self) -> dict:
        """The serving face of the memory accounting (ISSUE 15):
        analytic device bytes — replicated params plus the paged KV
        block pool, priced through the SAME
        ``memory_accounting.kv_pool_bytes`` builder the pool's own
        ``stats()`` uses (byte-exact vs the allocated arrays) — next to
        the measured per-jit ``memory_analysis()`` of the decode/prefill
        programs and the per-device ``memory_stats()`` watermark.  Cold
        report builder: never call it from the step loop."""
        from deepspeed_tpu.runtime import memory_accounting as mem_acc

        pool_bytes = self.pool.device_bytes()
        params_bytes = mem_acc.tree_device_bytes(self.params)
        analytic = {
            "components": {
                "params_bytes": params_bytes,
                "kv_pool_bytes": pool_bytes,
            },
            "persistent_bytes": params_bytes + pool_bytes,
            "transient_bytes": 0,
            "peak_bytes": params_bytes + pool_bytes,
        }
        devices = list(self.mesh.devices.reshape(-1)) \
            if self.mesh is not None else None
        return mem_acc.memory_report(
            analytic=analytic, accounting=self._memacct, devices=devices,
            extra={"engine": type(self).__name__})

    def _decode_args(self):
        """Full argument tuple of the armed decode program (dense or
        sparse) — shared by dispatch, program registration, telemetry
        shape capture and :meth:`decode_hlo`."""
        tables = self._tables if not self._gtables else (
            (self._tables, *self._gtables), (None, *self._gbases))
        sparse = () if self.sparse is None else (self._stables, self._sbase)
        return (self.params, *self.pool.all_arrays, tables, *sparse,
                self._pos, self._tok, self._active, self._seeds,
                self._poison)

    def decode_hlo(self) -> str:
        """Compiled HLO of the decode program (for the graftlint HLO
        contracts: host-transfer-free, pool donated, zero collectives)."""
        args = self._decode_args()
        return self._decode.lower(*args).compile().as_text()

    def spec_hlo(self) -> str:
        """Compiled HLO of the draft-verify program (same contracts as
        the decode jit: host-transfer-free, pool donated, zero
        collectives).  Only callable when speculation is armed."""
        assert self.spec_k, "spec_hlo() requires speculative decoding"
        toks = np.zeros((self.max_slots, self.spec_k + 1), np.int32)
        nvalid = np.zeros(self.max_slots, np.int32)
        args = (self.params, *self.pool.tensors.arrays, self._tables,
                self._pos, toks, nvalid, self._active, self._poison)
        return self._spec.lower(*args).compile().as_text()

    def n_pool_tensors(self) -> int:
        return len(self.pool.all_arrays)

    # -- internals ------------------------------------------------------
    def _buckets(self):
        b, out = _MIN_BUCKET, []
        while b <= self.prefill_chunk:
            out.append(b)
            b *= 2
        return out

    def _bucket(self, n):
        for b in self._buckets():
            if n <= b:
                return b
        raise AssertionError(f"chunk {n} > prefill_chunk")

    def _rebind(self, arrays):
        # the pool's own slots (k [, v] [, scales]) in ``.arrays`` order,
        # group by group
        self.pool.rebind(arrays)

    def _shard_for_slot(self, slot):
        return slot // (self.max_slots // self.shards)

    def _rank_slot(self, slot, req=None):
        """Admission slot score: (cached-prefix coverage on the slot's
        shard, free blocks).  Pure host walk of the radix tree — no
        device syncs on the admission path."""
        shard = self._shard_for_slot(slot)
        hit = 0
        if req is not None and self.prefix_cache and not self._warming:
            full, _, cow_len = self.pool.prefix_lookup(
                shard, req.full_tokens)
            hit = len(full) * self.bs + cow_len
        return (hit, self.pool.free_blocks(shard))

    def _prefix_probe(self, req):
        """Admission-time prefix consult (installed as the scheduler's
        ``prefix_probe``): map cached prompt blocks read-only into the
        new request's page table and advance ``prefill_done`` past them
        — the covered chunks are never dispatched.  Journal-replayed and
        migration-readmitted requests take the same path (their
        ``full_tokens`` re-prefill shares the prompt blocks), which is
        the fleet-honesty fix: recovery no longer re-prefills from
        token 0 when the prompt's KV is already resident."""
        req.shard = self._shard_for_slot(req.slot)
        if not self.prefix_cache or self._warming:
            return 0
        hit = self.pool.prefix_attach(req.rid, req.shard, req.full_tokens)
        if hit:
            req.prefill_done = hit
        self.metrics.record_prefix_lookup(
            hit, readmit=req.rid in self._readmit_rids)
        return hit

    def _ensure_blocks(self, req, n_positions, *, admission, events):
        """Grow ``req``'s page table to cover ``n_positions``, preempting
        victims from the scheduler's policy until the shard has room.
        False = req itself was deferred/evicted (caller must not use
        it this step)."""
        while not self.pool.alloc(req.rid, req.shard, n_positions):
            victim = self.scheduler.victim(for_req=req,
                                           admission=admission,
                                           shard=req.shard)
            if victim is None:
                if admission:
                    self.scheduler.drop_prefill(req, requeue=True)
                    self.pool.free(req.rid)
                else:
                    self._evict(req, events)
                return False
            self._evict(victim, events)
        return True

    def _evict(self, req, events):
        slot = req.slot
        self.scheduler.preempt(req)
        self.pool.free(req.rid)
        self._clear_slot(slot)
        self.metrics.record_eviction(req.rid)
        events["evicted"].append(req.rid)

    def _clear_slot(self, slot):
        if slot is None:
            return
        self._active[slot] = False
        self._tables[slot] = TRASH_BLOCK
        for table, base in zip(self._gtables, self._gbases):
            table[slot] = TRASH_BLOCK
            base[slot] = 0
        self._pos[slot] = 0
        self._tok[slot] = 0
        if self.sparse is not None:
            self._stables[slot] = TRASH_BLOCK
            self._sbase[slot] = int(self.sparse.sentinel)

    def _cleanup(self, req, reason):
        self.pool.free(req.rid)
        self._clear_slot(req.slot)
        self.results[req.rid] = {
            "tokens": np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)]),
            "status": reason, "evictions": req.evictions,
        }
        self.metrics.record_finish(req.rid, reason)
        if not self._warming:
            self.reliability.on_finish(req, reason)

    def _abort(self, req, reason, events=None):
        """Terminal non-completion in ANY live state (waiting, prefill,
        running): scheduler bookkeeping, KV blocks freed, slot scrubbed,
        result recorded with the explicit reason — an expired/poisoned
        request can never wedge the shared decode batch."""
        self.scheduler.finish(req, reason)
        self._cleanup(req, reason)
        if self._tracer is not None:
            self._tracer.instant(f"abort_{reason}", self._lane_serve,
                                 a0=req.rid)
        if events is not None and reason in events:
            events[reason].append(req.rid)

    def _enforce_deadlines(self, events):
        """Step-boundary deadline + work-budget enforcement over every
        live request.  Pure host accounting (the clock and two ints per
        request) — no device syncs, held to the hot-path lint bar."""
        now = self.clock()
        for req in list(self.scheduler.requests.values()):
            if req.state in (RequestState.FINISHED,
                             RequestState.CANCELLED):
                continue
            if req.deadline is not None and now > req.deadline:
                self._abort(req, ABORT_EXPIRED, events)
            elif req.work_budget is not None \
                    and req.work_done >= req.work_budget:
                self._abort(req, ABORT_BUDGET, events)

    def _finish(self, req, reason, events):
        self.scheduler.finish(req, reason)
        self._cleanup(req, reason)
        events["finished"].append(req.rid)

    def _on_new_token(self, req, token, events, *, promote):
        req.generated.append(int(token))
        self.metrics.record_token(req.rid)
        if not self._warming:
            self.reliability.on_token(req, int(token))
        if req.done:
            self._finish(req, "finished", events)
            return
        if promote:
            self.scheduler.promote(req)
            slot = req.slot
            self._refresh_tables(slot, req.rid)
            self._pos[slot] = len(req.full_tokens) - 1
            self._tok[slot] = req.generated[-1]
            self._seeds[slot] = req.seed
            self._active[slot] = True

    def _refresh_tables(self, slot, rid):
        """The lane's page tables as the pool has them now, a group."""
        self._tables[slot] = self.pool.table_row(rid, self.W)
        for g, (table, base) in enumerate(zip(self._gtables, self._gbases),
                                          1):
            table[slot] = self.pool.table_row(rid, table.shape[1], group=g)
            base[slot] = self.pool.table_base(rid, g)

    def _release_expired(self, req, next_pos):
        """After a program of ``req``: the pages of its window groups that
        lie wholly below the window of its next query (at ``next_pos``) go
        back to their group's free list, where the next ``alloc`` of ANY
        request finds them while this one still runs (programs run in the
        order dispatched: whoever is given a page writes it after this
        request last read it)."""
        if not self._gtables:
            return
        freed = self.pool.release_expired(req.rid, next_pos)
        if freed:
            self._freed_in_step += freed
            self.metrics.record_window_expired(freed)

    def _attended(self, counter, first, n):
        """What ``n`` queries in a row from position ``first`` attend, a
        cache group, as counters of the program: ``counter`` alone for a
        model of one group (each query every position up to its own),
        ``<counter>_<group>`` for one of several (a window group at most
        its window)."""
        if len(self.groups) == 1:
            return {counter: n * first + n * (n + 1) // 2}
        q = np.arange(first, first + n, dtype=np.int64) + 1
        return {f"{counter}_{g.name}": int(
            (q if g.window is None else np.minimum(q, g.window)).sum())
            for g in self.groups}

    def _mark(self, name, a0=-1, at=None):
        """The serve thread's ONE phase cursor, a level below ``host_gap``
        / ``run_*``: with a tracer armed the open phase span ends at
        ``at`` (now when None) and the span ``name`` opens at that very
        instant, to end with ``a0`` at the next mark, so the phases
        partition the thread's time by construction.  Disarmed this is
        the ``is None`` test alone.

        ``step_begin`` (entry of ``step()``: chaos hooks, watchdog, the
        deadline sweep), ``prefill_prep`` (``_prefill_tick`` up to its
        dispatch: admission, the chunk's pages, padding and arguments),
        ``dispatch`` (the call that sends a program and the host's work
        while it is in flight, ``_rebind`` and ``_note_program``, up to
        whatever is marked next; a0 the bucket of a chunk, 0 for a
        decode or verify program), ``tables`` (the decode tick up to its
        dispatch: growth, table rows, arguments; a0 the lanes running at
        its entry), ``fetch`` (``jax.device_get`` alone; a0 the lanes
        decoded under it), ``tokens`` (from its return to the end of the
        tick: the bookkeeping a lane), ``step_end`` (metrics and reports
        up to the end of ``serving_step``), ``caller`` (from there to the
        entry of the next ``step()``: the hub's ``on_step`` and the
        caller's own time, its ``submit()`` calls among it)."""
        tr = self._tracer
        if tr is not None:
            at = self._end_phase(at)
            self._phase = tr.span(name, self._lane_serve, t0=at)
            self._phase_a0 = a0

    def _end_phase(self, at=None):
        """End the open phase span, if any, at ``at`` (now when None:
        read once, returned).  Armed only."""
        if at is None:
            at = self._tracer.clock()
        phase, self._phase = self._phase, None
        if phase is not None:
            phase.end(a0=self._phase_a0, at=at)
        return at

    def _dispatch(self, span, fn, args, bucket=0):
        """Every serving program goes to the device through here, and
        every result comes back through :meth:`_fetch`: between them a
        traced engine knows whether anything of its own is unfetched.
        The stretch from the first dispatch after a drain to the fetch
        that drains the device again is ONE span, named at its start by
        what opens it (``span``: ``run_decode``, ``run_prefill`` for a
        final chunk, ``run_prefill_decode`` for a non-final chunk, which
        runs on under whatever is dispatched next); the rest of the
        serve thread's time is ``host_gap``.  The call, and what the
        host does before it marks anything else, is the phase
        ``dispatch`` (:meth:`_mark`), which begins at the instant the
        stretch does and lies inside it, in the ring and among the
        profiler's annotations: the phase before is left before the gap
        is, and this one entered after the stretch.  Disarmed this is the
        call alone."""
        tr = self._tracer
        if tr is not None:
            now = self._end_phase()
            if self._run is None:
                if self._gap is not None:
                    self._gap.end(a0=0 if self._gap_idle else 1, at=now)
                    self._gap = None
                self._run = tr.span(span, self._lane_serve, t0=now)
            self._mark("dispatch", bucket, at=now)
        return fn(*args)

    def _note_program(self, group, out, n_pool, **counters):
        """While a tracer is armed, keep what the next :meth:`_fetch`
        records of a program just dispatched: ``group`` (``decode``,
        ``prefill_<bucket>``), counters the host knows, and the model's
        own (``decoder.stat_names``), which are still on the device and
        come to the host WITH that fetch, never by a sync of their own."""
        if self._tracer is not None:
            self._stats_pending.append(
                (group, counters,
                 out[n_pool] if self.dec.stat_names else None))

    def _fetch(self, arrays, *, lanes=0, bucket=0):
        """The step's ONE batched fetch.  It waits for every program
        still in flight, so it ends the open ``run_*`` span (a0: the
        bucket of a final chunk that ran alone, else the lanes decoded
        under it) and opens the next ``host_gap`` at the same instant.
        The wait itself is the phase ``fetch``, which ends at that
        instant too, where ``tokens`` begins (:meth:`_mark`)."""
        pending, self._stats_pending = self._stats_pending, []
        self._mark("fetch", lanes)
        fetched, stats = jax.device_get(
            (arrays, [row for _, _, row in pending if row is not None]))
        tr = self._tracer
        if tr is not None:
            began = self._phase.t0
            now = self._end_phase()
            run, self._run = self._run, None
            run.end(a0=bucket if run.name == "run_prefill" else lanes,
                    at=now)
            # the serve thread's time since the last fetch returned that
            # was NOT spent waiting in this one (this fetch's return less
            # the last one's less this wait: the last one's return to this
            # one's entry), unless the engine stood empty in between: the
            # host's share of a step, exact a step
            if self._fetched_at is not None and not self._gap_idle:
                tr.count("step_host_us", self._lane_serve, int(round(
                    1e6 * (began - self._fetched_at))), at=now)
            self._fetched_at = now
            # every program this fetch waited for: its counters as
            # zero-length spans ``<counter>_<group>`` (a0 the value), and
            # under ``clock_ms_<group>`` the tracer's clock, so that a
            # reader can tell which programs fell into a stretch of time
            stats = iter(stats)
            for group, counters, row in pending:
                if row is not None:
                    counters = dict(counters, **dict(zip(
                        self.dec.stat_names, map(int, next(stats)))))
                tr.count(f"clock_ms_{group}", self._lane_serve,
                         int(now * 1e3), at=now)
                for name, value in counters.items():
                    tr.count(f"{name}_{group}", self._lane_serve, value,
                             at=now)
            self._gap = tr.span("host_gap", self._lane_serve, t0=now)
            self._gap_idle = False
            self._mark("tokens", lanes, at=now)
        return fetched

    def _prefill_args(self, req, n):
        rows = np.full((self.shards, self.W), TRASH_BLOCK, np.int32)
        nv = np.zeros(self.shards, np.int32)
        rows[req.shard] = self.pool.table_row(req.rid, self.W)
        nv[req.shard] = n
        if len(self.groups) > 1:    # a table and a base a group
            more = [self.pool.table_row(req.rid, chunk, group=g)[None]
                    for g, (_, chunk) in enumerate(self._widths[1:], 1)]
            bases = [np.full(1, self.pool.table_base(req.rid, g), np.int32)
                     for g in range(1, len(self.groups))]
            rows = ((rows, *more), (None, *bases))
        return rows, nv

    def _prefill_tick(self, events):
        sch = self.scheduler
        req = sch.prefilling
        if req is None:
            req = sch.start_admission()
            if req is not None:
                req.shard = self._shard_for_slot(req.slot)
                events["admitted"].append(req.rid)
            else:
                # no fresh admission (empty queue or no free slot): give
                # the lane back to the oldest fairness-paused prefill.
                # Trying admissions FIRST is what makes the quantum
                # round-robin — a paused giant never starves newcomers.
                req = sch.resume_prefill()
            if req is None:
                return
        toks = req.full_tokens
        total = len(toks)
        start = req.prefill_done
        n = min(self.prefill_chunk, total - start)
        final = start + n == total
        # the final chunk also reserves the first decode write position
        if not self._ensure_blocks(req, start + n + (1 if final else 0),
                                   admission=True, events=events):
            return
        if self.sparse is not None:
            # blocks below the chunk's FIRST query window (keeping the
            # global anchors) are already unreachable — free them before
            # building the table row, exactly like the decode tick
            freed = self.pool.window_expired_free(
                req.rid, self.sparse.first_active_block(start),
                keep_blocks=self.sparse.g)
            if freed:
                self.metrics.record_window_expired(freed)
        bucket = self._bucket(n)
        tok_pad = np.zeros(bucket, np.int32)
        tok_pad[:n] = toks[start:start + n]
        rows, nv = self._prefill_args(req, n)
        policy, sparse_rows, kind = {}, (), "prefill"
        if self.sparse is not None:
            K_pf = self.sparse.prefill_K(bucket)
            policy = {"K": K_pf, "win": self.sparse.win, "g": self.sparse.g}
            srows = np.full((self.shards, K_pf), TRASH_BLOCK, np.int32)
            sbases = np.full((self.shards, K_pf),
                             int(self.sparse.sentinel), np.int32)
            srows[req.shard], sbases[req.shard] = \
                self.sparse.prefill_active_row(rows[req.shard], start, n,
                                               bucket)
            sparse_rows, kind = (srows, sbases), "sparse_prefill"
        fn = _make_prefill_chunk(
            self.cfg, bucket, self.W, self.bs, self.pool.quantized, final,
            self.temperature, self.top_k, self.top_p, self.mesh,
            self.axis_name, **policy)
        pf_args = (self.params, *self.pool.all_arrays, rows, *sparse_rows,
                   tok_pad, np.int32(start), nv, np.int32(req.seed))
        group = f"serving:{kind}_final" if final else f"serving:{kind}"
        pf_name = fn.__name__       # [sparse_]prefill_chunk<bucket>[_final]
        # bucketed prefill programs at the same schedule slot must post
        # identical collective sequences (uniform_group) — a divergence
        # between buckets would deadlock a multi-host SPMD dispatch
        self._register_serving_program(pf_name, fn, pf_args,
                                       uniform_group=group)
        if self.telemetry is not None:
            # every bucketed prefill jit joins the MFU + memory ledgers
            # (capture-by-shape, no-op after the first registration)
            from deepspeed_tpu.runtime import memory_accounting as mem_acc
            from deepspeed_tpu.telemetry import register_by_shape

            register_by_shape(self.telemetry.mfu, pf_name, fn, pf_args)
            mem_acc.register_by_shape(self._memacct, pf_name, fn, pf_args)
        out = self._dispatch(
            "run_prefill" if final else "run_prefill_decode", fn, pf_args,
            bucket=bucket)
        req.work_done += n
        self.metrics.record_prefill(n)
        n_pool = self.n_pool_tensors()
        # (query, key) pairs this chunk attends: n queries from ``start``,
        # each over every position up to its own (a cache group that keeps
        # a window: over its window at most)
        self._note_program(f"prefill_{bucket}", out, n_pool,
                           **self._attended("attn_pairs", start, n))
        if final:
            # ONE batched fetch: the sampled token and the non-finite-
            # logits detector travel together (no extra host sync)
            fetched = self._fetch((out[-2], out[-1]), bucket=bucket)
            self._rebind(out[:n_pool])
            first = int(np.asarray(fetched[0]).reshape(-1)[req.shard])
            ok = bool(np.asarray(fetched[1]).reshape(-1)[req.shard])
            req.prefill_done = total
            if not ok:
                self._abort(req, ABORT_POISONED, events)
                return
            if self.prefix_cache and not self._warming:
                # publish the (finite-checked) prompt blocks into the
                # radix tree — the next request sharing this prefix
                # skips their prefill chunks entirely
                self.pool.prefix_insert(req.rid, req.shard, req.prompt)
            self._release_expired(req, total)
            self._on_new_token(req, first, events, promote=True)
        else:
            self._rebind(out[:n_pool])
            req.prefill_done = start + n
            self._release_expired(req, start + n)
            if self.prefill_fairness:
                # chunked-prefill fairness: after a quantum of chunks a
                # huge prompt yields the lane IF anyone is waiting for
                # it — chatty short requests interleave instead of
                # queueing behind the whole giant
                req.fair_chunks += 1
                if req.fair_chunks >= self.prefill_fairness and \
                        (sch.peek_waiting() is not None or sch.paused):
                    sch.pause_prefill(req)

    def _draft_tokens(self, req, k):
        """Host-side n-gram drafter: propose the continuation that
        followed the most recent earlier occurrence of the current last
        token (repeating the last token when history has none).
        Deterministic and correctness-free — the verify step accepts
        only the bit-exact greedy prefix, so a bad draft costs speed,
        never parity."""
        toks = req.full_tokens
        last = int(toks[-1])
        out = None
        for i in range(len(toks) - 2, -1, -1):
            if int(toks[i]) == last:
                cont = [int(t) for t in toks[i + 1:i + 1 + k]]
                if cont:
                    out = cont
                break
        if out is None:
            out = [last]
        while len(out) < k:
            out.append(out[-1])
        return out[:k]

    def _spec_decode_tick(self, events):
        """Speculative variant of the decode tick: ONE fixed-shape
        draft-verify dispatch scores the current token plus K drafts per
        lane; the host accepts the longest draft prefix matching the
        program's own argmax stream (plus the bonus token).  Same
        single-batched-fetch / poison-quarantine / zero-recompile
        discipline as the plain tick."""
        sch = self.scheduler
        if not sch.running:
            return 0
        K = self.spec_k
        # growth: each lane writes up to min(K+1, remaining) positions
        # this step — cover them, preempting within the shard if needed
        for slot in sorted(sch.running):
            req = sch.running.get(slot)
            if req is None:
                continue
            n = min(K + 1, req.max_new_tokens - len(req.generated))
            self._ensure_blocks(req, int(self._pos[slot]) + n,
                                admission=False, events=events)
        running = dict(sch.running)
        if not running:
            return 0
        if chaos.serving_poison_step(self._step_idx):
            victim = max(running.values(), key=lambda r: r.submit_seq)
            self._poison[victim.slot] = np.nan
            chaos.record_serving_poison(victim.rid)
        nvalid = np.zeros(self.max_slots, np.int32)
        toks_in = np.zeros((self.max_slots, K + 1), np.int32)
        for slot, req in running.items():
            self._tables[slot] = self.pool.table_row(req.rid, self.W)
            n = min(K + 1, req.max_new_tokens - len(req.generated))
            nvalid[slot] = n
            toks_in[slot, 0] = self._tok[slot]
            drafts = self._draft_tokens(req, K)
            toks_in[slot, 1:] = drafts
            self._drafts[slot] = drafts
            req.work_done += n
        self.metrics.record_gather(
            len(running), len(running) * self.W, len(running) * self.W,
            sum(self.pool.blocks_of(r.rid) for r in running.values()))
        tel = self.telemetry
        spec_args = (self.params, *self.pool.tensors.arrays,
                     self._tables, self._pos, toks_in, nvalid,
                     self._active, self._poison)
        self._register_serving_program(self._spec.__name__, self._spec,
                                       spec_args)
        if tel is not None:
            from deepspeed_tpu.runtime import memory_accounting as mem_acc
            from deepspeed_tpu.telemetry import register_by_shape

            register_by_shape(tel.mfu, self._spec.__name__, self._spec,
                              spec_args)
            mem_acc.register_by_shape(
                self._memacct, self._spec.__name__, self._spec, spec_args,
                expect_label="serving draft-verify step: donated "
                "in-place KV block pool + argmax continuations")
        out = self._dispatch("run_decode", self._spec, spec_args)
        self._rebind(out[:-2])
        chaos.serving_kill_step(self._step_idx)
        chaos.fleet_kill_replica_step(self._replica_index, self._step_idx)
        # ONE batched fetch per step: K+1 argmax tokens per lane + the
        # per-lane finiteness detector travel together
        outs, fins = self._fetch((out[-2], out[-1]), lanes=len(running))
        outs = np.asarray(outs)
        fins = np.asarray(fins)
        self._poison[:] = 0.0
        for slot, req in running.items():
            if not fins[slot]:
                self._abort(req, ABORT_POISONED, events)
                continue
            row = outs[slot]
            drafts = self._drafts[slot]
            m = 1
            while m <= K and drafts[m - 1] == row[m - 1]:
                m += 1
            m = min(m, int(nvalid[slot]))
            consumed = 0
            for i in range(m):
                consumed += 1
                self._on_new_token(req, int(row[i]), events,
                                   promote=False)
                if req.done:
                    break
            self.metrics.record_verify(consumed)
            if sch.running.get(slot) is req:
                self._pos[slot] += consumed
                self._tok[slot] = int(row[consumed - 1])
        return len(running)

    def _decode_tick(self, events):
        if self.spec_k:
            return self._spec_decode_tick(events)
        sch = self.scheduler
        if not sch.running:
            return 0
        # growth: each lane writes position pos this step — make sure its
        # page table covers it, preempting within the lane's shard if the
        # pool is full
        for slot in sorted(sch.running):
            req = sch.running.get(slot)
            if req is None:
                continue
            self._ensure_blocks(req, int(self._pos[slot]) + 1,
                                admission=False, events=events)
        running = dict(sch.running)
        if not running:
            return 0
        # chaos poison: NaN into the youngest DISPATCHED lane's embedding
        # (chosen after the growth loop so an evicted lane is never the
        # victim) — its logits go non-finite and must be quarantined
        if chaos.serving_poison_step(self._step_idx):
            victim = max(running.values(), key=lambda r: r.submit_seq)
            self._poison[victim.slot] = np.nan
            chaos.record_serving_poison(victim.rid)
        for slot, req in running.items():
            if self.sparse is not None:
                # pages below every remaining query's window (keeping
                # the global anchors) can never be gathered again —
                # return them to the allocator before refreshing the
                # table row, so this step's row already shows the holes
                freed = self.pool.window_expired_free(
                    req.rid,
                    self.sparse.first_active_block(int(self._pos[slot])),
                    keep_blocks=self.sparse.g)
                if freed:
                    self.metrics.record_window_expired(freed)
            self._refresh_tables(slot, req.rid)
            if self.sparse is not None:
                # host-side LUT maintenance: same no-mutation-before-
                # fetch discipline as _pos/_tok (the previous dispatch's
                # batched fetch already completed)
                self._stables[slot], self._sbase[slot] = \
                    self.sparse.active_row(self._tables[slot],
                                           int(self._pos[slot]))
            req.work_done += 1
        lanes = len(running)
        if self.sparse is not None:
            nonpad = int(sum(
                (self._sbase[slot] != int(self.sparse.sentinel)).sum()
                for slot in running))
            self.metrics.record_gather(lanes, lanes * self.sparse.K,
                                       lanes * self.W, nonpad)
        else:
            self.metrics.record_gather(
                lanes, lanes * self.W, lanes * self.W,
                sum(self.pool.blocks_of(r.rid) for r in running.values()))
        tel = self.telemetry
        # capture-by-shape BEFORE dispatch (the pool is donated by it);
        # the lower+compile runs lazily at report/lint time, outside any
        # recompile-guard window
        decode_args = self._decode_args()
        self._register_serving_program(self._decode_name, self._decode,
                                       decode_args)
        if tel is not None:
            from deepspeed_tpu.runtime import memory_accounting as mem_acc
            from deepspeed_tpu.telemetry import register_by_shape

            register_by_shape(tel.mfu, self._decode_name, self._decode,
                              decode_args)
            mem_acc.register_by_shape(
                self._memacct, self._decode_name, self._decode,
                decode_args,
                expect_label="serving decode step: donated-in-place KV "
                "block pool + sampled tokens")
        out = self._dispatch("run_decode", self._decode, decode_args)
        n_pool = self.n_pool_tensors()
        self._rebind(out[:n_pool])
        if self._tracer is not None:
            # keys the program's live lanes attend (positions 0 .. pos):
            # with the touched experts, the bytes a decode step has to
            # move; and the pages those keys lie in, which over max_slots
            # x max_blocks_per_seq is the share of a fixed-shape view that
            # attention reading live pages only still reads
            keys = self._pos[list(running)] + 1
            if len(self.groups) == 1:
                counters = {"attn_keys": int(keys.sum()),
                            "attn_pages": int((-(-keys // self.bs)).sum())}
            else:       # a cache group: a window group its window at most
                counters = {}
                for g in self.groups:
                    seen = keys if g.window is None \
                        else np.minimum(keys, g.window)
                    counters[f"attn_keys_{g.name}"] = int(seen.sum())
            self._note_program("decode", out, n_pool, **counters)
        # kill-mid-decode chaos: the dispatch happened, NO host
        # bookkeeping has — the journal holds the last committed step
        chaos.serving_kill_step(self._step_idx)
        chaos.fleet_kill_replica_step(self._replica_index, self._step_idx)
        # ONE batched fetch per step: sampled tokens + per-lane
        # finiteness (the poison detector) travel together
        toks, fins = self._fetch((out[-2], out[-1]), lanes=lanes)
        toks = np.asarray(toks)
        fins = np.asarray(fins)
        # one-step injection, reset only AFTER the fetch: the CPU
        # backend may alias numpy inputs zero-copy, so host mutation
        # must wait for the execution to complete (same discipline as
        # _pos/_tok below)
        self._poison[:] = 0.0
        for slot, req in running.items():
            if not fins[slot]:
                # per-request fault isolation: quarantine THIS request;
                # its blocks are freed and the value mask keeps any NaN
                # it wrote from ever reaching another lane's einsum
                self._abort(req, ABORT_POISONED, events)
                continue
            self._pos[slot] += 1
            self._tok[slot] = int(toks[slot])
            self._release_expired(req, int(self._pos[slot]))
            self._on_new_token(req, int(toks[slot]), events,
                               promote=False)
        return len(running)
