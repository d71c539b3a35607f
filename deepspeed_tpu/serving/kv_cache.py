"""Paged KV cache: fixed block pool + per-sequence page tables.

The single-sequence decode loop in models/generation.py preallocates one
contiguous (L, B, H, S_max, D) cache per call — fine for a batch that
lives and dies together, fatal for serving where sequences of wildly
different lengths join and leave every step.  This module is the
vLLM-style answer (PagedAttention, arXiv 2309.06180): KV lives in a
fixed pool of ``block_size``-token blocks, each sequence holds an
ordered page table of block ids, and the pool arrays are DONATED into
the decode jit and updated in place — steady-state decode allocates no
device memory at all.

Layout (:func:`pool_shapes`, the one place that states it): ``k``/``v``
are ``(L, num_blocks, block_size, H*D)`` — the dims a write indexes
(layer, block, in-block offset) are major and one token's row, its H
heads of D side by side, is minor.  That is the layout the TPU compiler
runs the per-layer scatter and page gather in, so the donated pool is
updated in place and no program relays it (H*D = 1024 fills 8 lane
tiles; a minor dim of D = 64 would pad every row to 128).  The gathered
per-sequence view reassembles ``(H, W*block_size, D)`` in
absolute-position order, so the attention math (shared
``generation._attn_core``) is bit-identical to the contiguous cache.

Cache kinds: what one token's row holds is the MODEL's to state
(``cfg.cache_rows``; a configuration without it keeps keys and values of
``n_head * head_dim``), and what KIND of rows they are
(``cfg.cache_kind``: keys and values of heads, or raw rows the model alone
reads; stated, never told from how many there are).  A model with latent
attention keeps ONE raw row a token and layer (``kv_lora_rank +
qk_rope_head_dim`` values) and no separate value tensor: ``pool_shapes``
then gives ``v = None`` and the pool holds one tensor; a block of TWO
latent attentions keeps two raw rows, one pool tensor each, and neither is
a value.  One allocator and one page table serve every kind:
the block ids, the trash block and the refcounts do not know what a row
holds.

Cache groups: a configuration may state, beside its rows, its cache GROUPS
(``cfg.cache_groups``, read through :func:`cache_groups`): for each a name,
how many layers write to it and what it keeps, every position or a window
of the last ``W``.  A configuration that states none has ONE group of all
its layers that keeps everything, and its pool, tables and programs are
what they always were.  ``PagedKVPool`` holds each group's tensors
``(L_group, NB_group, bs, row)``, free list and page table a request behind
the calls it has: ``alloc(rid, shard, n_positions)`` covers every group or
changes nothing, ``free(rid)`` returns all.  A window group's table is a
SLIDING one: it begins at the oldest page the request still holds
(:meth:`PagedKVPool.table_base` is that page's first position), and
:meth:`PagedKVPool.release_expired` returns the pages that lie wholly below
the next query's window to THAT group's free list, where the next
``alloc``, of any request, finds them.  So a window layer pays for a window
and not for the context: at most :func:`window_table_width` pages a lane
(``ceil(W / bs) + 1`` while it decodes, ``ceil((W - 1 + chunk) / bs) + 1``
in a prefill chunk).  The variants that know one group (``prefix_cache``,
``quantize_kv``, ``shards``, ``window_expired_free``, the fleet hand-off)
work on a one-group pool only: the engine refuses a model of several by
name before it builds one.

Block 0 of every shard is a reserved TRASH block: masked lanes (inactive
slots, prefill padding) route their writes there, which keeps every
scatter in the jit fully dense — no branches, no recompiles.

Optional int8 storage (``quantize_kv=True``) stores one symmetric scale
per (token, head) row via runtime/quantization.py's row quantizers —
per-row layout = ``block_layout(D, D)`` so the scale tensor is exactly
``(L, num_blocks, block_size, H)`` f32 (the same rule: index dims major,
one token's H scales minor).  Arming follows the repo's
DISARMED discipline: when the configuration cannot profit (scale
overhead >= byte savings, or an unsupported pool dtype) the pool warns
loudly naming the blocker and serves full-precision instead.

Sharding (``shards > 1``): the block axis and the allocator are split
into per-shard ranges so a shard_map over the slot axis sees only local
blocks — the placement-semantics argument for why sharded decode moves
zero collective bytes (see runtime/comm_accounting.
serving_decode_collectives).

Prefix caching (SGLang-style RadixAttention, arXiv 2312.07104): each
shard additionally keeps a radix tree over block CONTENT — a node per
physical block, keyed by the token tuple whose KV the block holds,
chained parent→child in position order.  A new request walks the tree
(:meth:`prefix_lookup`), maps every fully-matching block read-only into
its own page table (:meth:`prefix_attach`, refcounted), and COW-splits
the first divergent block: the partial match is device-copied into a
private block the request may write into.  Completed prefills publish
their prompt blocks back into the tree (:meth:`prefix_insert`).  Shared
blocks are returned to the free list only when BOTH every mapping
request has freed them AND the cache reclaims the node (LRU,
unreferenced leaves first) — eviction never touches a block a live
request still maps, and the trash block (0) is never cached.

Window-expired reclamation (serving/sparse_context.py): under a
sliding-window attention policy, pages below every remaining query's
window can never be gathered again — :meth:`window_expired_free`
returns those PRIVATE blocks to the allocator early, recording the gap
as a ``None`` hole in the page table so logical position ↔ list index
stays intact (``table_row`` maps holes to the trash block; the sparse
gather's sentinel positions mask them).  Tree-owned blocks are NEVER
window-freed: the prefix cache's refcounts outrank the window policy,
so a shared prefix stays resident for the requests (and the tree) that
still hold it.
"""
import functools
from typing import Dict, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.utils.logging import logger

TRASH_BLOCK = 0          # per-shard block 0 absorbs masked writes
LANES = 128              # minor-dim tile of the TPU's memory layout


@functools.partial(jax.jit, donate_argnums=0)
def _cow_copy_rows(arrs, src, dst):
    """Copy one block's rows across every pool tensor (the COW split).
    ``src``/``dst`` are TRACED scalars, so every (src, dst) pair reuses
    ONE compiled program per pool shape — block churn never recompiles —
    and the donated input keeps the copy allocation-free on the pool."""
    return tuple(a.at[:, dst].set(a[:, src]) for a in arrs)


KEYS_VALUES = "keys_values"      # rows with heads in them: the default
RAW_ROWS = "rows"                # rows of the model's own (latent rows)


def cache_rows(cfg):
    """Widths of the rows a layer caches a token, one pool tensor each:
    the configuration's own ``cache_rows`` (one latent row: ``(R + Dr,)``;
    a block of two latent attentions: two), else keys and values of
    ``n_head * head_dim``."""
    own = getattr(cfg, "cache_rows", None)
    if own is not None:
        return tuple(int(w) for w in own)
    return (cfg.n_head * cfg.head_dim,) * 2


def cache_kind(cfg):
    """WHAT the cached rows are, as the configuration states it
    (``cfg.cache_kind``): :data:`KEYS_VALUES`, two rows of ``n_head``
    heads each (what a configuration that says nothing caches), or
    :data:`RAW_ROWS`, rows the model alone can read.  Never inferred from
    how many rows there are: two raw rows are not keys and values."""
    return getattr(cfg, "cache_kind", KEYS_VALUES)


class CacheGroup(NamedTuple):
    """Layers of a model that cache alike: ``n_layer`` of them write to
    this group's tensors, which keep every position (``window`` None) or
    the last ``window`` positions a query can still see."""
    name: str
    n_layer: int
    window: Optional[int] = None


def cache_groups(cfg):
    """The configuration's cache groups (``cfg.cache_groups``: tuples
    ``(name, n_layer, window)``); one group of every layer that keeps every
    position where it states none."""
    own = getattr(cfg, "cache_groups", None)
    if own is None:
        return (CacheGroup("full", int(cfg.n_layer)),)
    groups = tuple(CacheGroup(str(n), int(l), None if w is None else int(w))
                   for n, l, w in own)
    assert groups and len({g.name for g in groups}) == len(groups), groups
    return groups


def window_table_width(window, block_size, queries=1):
    """Pages a lane of a window group can hold while ``queries`` queries
    in a row attend: the ``window - 1`` positions before the first, the
    queries' own, and (a final chunk reserves it) the position after the
    last; a run of n positions touches at most ``ceil((n - 1) / bs) + 1``
    pages.  One query, a decoding lane: ``ceil(W / bs) + 1``."""
    return -(-(int(window) - 1 + int(queries)) // int(block_size)) + 1


def pool_shapes(cfg, num_blocks, block_size, quantized, group=0):
    """The four shapes ``(k, v, k_scale, v_scale)`` of cache group
    ``group`` (an index into :func:`cache_groups`; ``num_blocks`` is that
    group's); the scales
    are None unless ``quantized``, and ``v`` is None for a model that
    caches one row a token (:func:`cache_rows`; a model of two raw rows
    has its second in ``v``'s slot).  One rule for all: the dims a write
    indexes — layer, block, offset in the block — are major, one token's
    row is minor (keys and values: its ``n_head * head_dim`` values, its
    ``n_head`` scales; latent: its ``R + Dr`` values).  Every reader of
    the layout asks here."""
    rows = cache_rows(cfg)
    assert 1 <= len(rows) <= 2, rows
    index = (cache_groups(cfg)[group].n_layer, int(num_blocks),
             int(block_size))
    if cache_kind(cfg) == RAW_ROWS:
        # a row that does not fill whole 128-lane tiles is STORED padded to
        # the next multiple: the TPU's tiling pads it in memory either way
        # (320 values occupy 384, 576 occupy 640), and stated in the shape
        # the compiler updates the donated pool in place instead of
        # unpadding and padding all of it around every program
        rows = tuple(-(-w // LANES) * LANES for w in rows)
    k = index + (rows[0],)
    v = index + (rows[1],) if len(rows) == 2 else None
    scale = index + (cfg.n_head,) if quantized else None
    return k, v, scale, scale


class _FurtherGroup:
    """A cache group of a pool beyond its first: its tensors, free list
    and, a request, the pages it holds from logical page ``first`` on (a
    sliding table, no holes).  The pool's own attributes are group 0."""

    def __init__(self, spec, num_blocks, tensors):
        self.spec = spec
        self.num_blocks = int(num_blocks)
        self.tensors = tensors
        self.free = list(range(1, self.num_blocks))     # 0: the trash block
        self.blocks: Dict[int, List[int]] = {}
        self.first: Dict[int, int] = {}

    @property
    def in_use(self):
        return self.num_blocks - 1 - len(self.free)


class PoolTensors(NamedTuple):
    """The device-side pool state threaded through (and donated into)
    the decode/prefill jits.  ``k_scale``/``v_scale`` are None unless
    int8 KV is armed; ``v`` is None where one row a token is cached
    (``k`` then holds it).  The two slots are the two cached rows, keys and
    values or not (``cache_kind``)."""
    k: jax.Array
    v: Optional[jax.Array] = None
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def arrays(self):
        return tuple(t for t in self if t is not None)

    def with_arrays(self, arrays):
        """The same slots, holding ``arrays`` (in ``.arrays`` order)."""
        it = iter(arrays)
        return PoolTensors(*(None if t is None else next(it)
                             for t in self))


class _PrefixNode:
    """One physical block in a shard's prefix tree.  ``tokens`` is the
    (≤ block_size) token tuple whose KV rows the block holds; ``refs``
    counts live requests currently mapping the block read-only.  The
    node itself keeps the block resident after refs drop to zero — that
    is the cache — until LRU reclaim returns it to the free list."""
    __slots__ = ("tokens", "block", "parent", "children", "refs", "tick")

    def __init__(self, tokens, block, parent, tick):
        self.tokens = tokens
        self.block = block
        self.parent = parent
        self.children = {}
        self.refs = 0
        self.tick = tick


def _common_prefix_len(a, b):
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


class PagedKVPool:
    """Fixed device block pool + host-side block allocator/page tables.

    ``num_blocks`` is the TOTAL block count across shards (must divide by
    ``shards``); one block per shard is reserved as trash, so the usable
    capacity is ``num_blocks - shards`` blocks.  For a model of several
    cache groups (:func:`cache_groups`) it is a sequence, one count a
    group, the first of which keeps every position; the attributes and the
    methods that take no ``group`` speak of that first group, as they do of
    a one-group pool's only one (the variants that know one group use
    them), and :attr:`all_arrays`, :meth:`alloc`, :meth:`free`,
    :meth:`release_expired`, :meth:`table_row`, :meth:`occupancy`,
    :meth:`group_stats` of all.
    """

    def __init__(self, cfg, *, num_blocks, block_size=16, shards=1,
                 mesh=None, axis_name="data", quantize_kv=False,
                 dtype=None):
        self.groups = cache_groups(cfg)
        counts = [num_blocks] if np.ndim(num_blocks) == 0 \
            else list(num_blocks)
        assert len(counts) == len(self.groups), (counts, self.groups)
        assert self.groups[0].window is None, \
            "the first cache group stated keeps every position"
        num_blocks = int(counts[0])
        assert num_blocks % shards == 0, \
            f"num_blocks={num_blocks} must divide shards={shards}"
        assert num_blocks // shards >= 2, \
            "need at least one usable block per shard beyond the trash block"
        assert block_size >= 1
        self.cfg = cfg
        self.block_size = int(block_size)
        self.shards = int(shards)
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_blocks = int(num_blocks)
        self.blocks_per_shard = self.num_blocks // self.shards
        self.dtype = dtype or cfg.dtype
        self.quantized = self._arm_quantized_kv(quantize_kv)
        # compiled-program registry seam (telemetry/programs.py): the
        # owning InferenceEngine installs its registry here so the
        # COW-split copy joins the same program view the serving jits
        # report to; None (standalone pools) skips registration
        self.programs = None

        store = jnp.int8 if self.quantized else self.dtype
        tensors = [
            None if shape is None else jnp.zeros(shape, dtype)
            for shape, dtype in zip(
                pool_shapes(cfg, self.num_blocks, self.block_size,
                            self.quantized),
                (store, store, jnp.float32, jnp.float32))]
        if mesh is not None and shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            split = NamedSharding(mesh, P(None, axis_name))   # block axis
            tensors = [None if t is None else jax.device_put(t, split)
                       for t in tensors]
        self.tensors = PoolTensors(*tensors)
        self._further: List[_FurtherGroup] = []
        for g, count in enumerate(counts[1:], 1):
            assert shards == 1 and not self.quantized, \
                "a pool of several cache groups is unsharded, unquantized"
            assert count >= 2, count
            self._further.append(_FurtherGroup(
                self.groups[g], count, PoolTensors(*(
                    None if shape is None else jnp.zeros(shape, self.dtype)
                    for shape in pool_shapes(cfg, count, self.block_size,
                                             False, g)))))

        # host-side allocator: per-shard sorted free lists (popping the
        # smallest id keeps runs deterministic), local block ids — the
        # trash block (0) is never handed out
        self._free: List[List[int]] = [
            list(range(1, self.blocks_per_shard))
            for _ in range(self.shards)]
        self._blocks: Dict[int, List[int]] = {}    # rid -> local block ids
        self._shard_of: Dict[int, int] = {}
        self._positions: Dict[int, int] = {}       # rid -> covered positions

        # prefix cache: per-shard radix tree over block content.  The
        # sentinel roots hold no block; ``_nodes`` maps local block id ->
        # node; ``_shared`` lists, per rid, the tree-owned blocks the rid
        # maps read-only (free() derefs these instead of recycling them).
        self._roots: List[_PrefixNode] = [
            _PrefixNode((), None, None, 0) for _ in range(self.shards)]
        self._nodes: List[Dict[int, _PrefixNode]] = [
            {} for _ in range(self.shards)]
        self._shared: Dict[int, List[int]] = {}
        self._tick = 0
        self.cow_splits = 0
        self.cache_reclaims = 0
        self.window_frees = 0      # blocks early-freed by window expiry

    # -- arming ---------------------------------------------------------
    def _arm_quantized_kv(self, requested):
        """int8 KV arms only where it actually saves bytes; every blocked
        request warns loudly (the armed-or-warns DISARMED discipline)."""
        if not requested:
            return False
        if cache_kind(self.cfg) != KEYS_VALUES:
            raise ValueError(
                "quantize_kv: the int8 pool scales one (token, head) row "
                "of keys and of values; this model caches rows of widths "
                f"{cache_rows(self.cfg)} with no head in them")
        elem = np.dtype(self.dtype).itemsize
        D = self.cfg.head_dim
        if np.dtype(self.dtype) == np.float64:
            logger.warning(
                "PagedKVPool: int8 KV quantization DISARMED — pool dtype "
                "float64 is not supported by the symmetric per-row scheme "
                "(scales are f32); serving full-precision KV instead.")
            return False
        if D * (elem - 1) <= 4:
            logger.warning(
                "PagedKVPool: int8 KV quantization DISARMED — head_dim=%d "
                "at %s saves %d bytes/row but the per-(token,head) f32 "
                "scale costs 4; int8 would GROW the pool. Serving "
                "full-precision KV instead.",
                D, np.dtype(self.dtype).name, D * (elem - 1))
            return False
        return True

    # -- allocator ------------------------------------------------------
    def blocks_needed(self, n_positions: int) -> int:
        return -(-int(n_positions) // self.block_size)

    def alloc(self, rid: int, shard: int, n_positions: int) -> bool:
        """Ensure ``rid`` (pinned to ``shard``) owns enough blocks to
        cover ``n_positions`` absolute positions.  Returns False — with
        NOTHING changed — when the shard's free list cannot cover the
        growth; the caller preempts a victim and retries."""
        assert 0 <= shard < self.shards
        have = self._blocks.setdefault(rid, [])
        prev = self._shard_of.setdefault(rid, shard)
        assert prev == shard, f"rid {rid} moved shards {prev}->{shard}"
        need = self.blocks_needed(n_positions) - len(have)
        while need > len(self._free[shard]) and self._reclaim_block(shard):
            pass
        # every group covers the growth or none changes
        further = [self.blocks_needed(n_positions) - g.first.get(rid, 0)
                   - len(g.blocks.get(rid, ())) for g in self._further]
        if need > len(self._free[shard]) or any(
                n > len(g.free) for n, g in zip(further, self._further)):
            if not have:
                self._drop(rid)
            return False
        for _ in range(max(0, need)):
            have.append(self._free[shard].pop(0))
        for n, g in zip(further, self._further):
            g.first.setdefault(rid, 0)
            held = g.blocks.setdefault(rid, [])
            for _ in range(max(0, n)):
                held.append(g.free.pop(0))
        self._positions[rid] = max(self._positions.get(rid, 0),
                                   int(n_positions))
        return True

    def free(self, rid: int) -> None:
        """Release every block of ``rid``: private blocks return to the
        shard's free list; tree-owned (prefix-shared) blocks are DEREFED
        instead — they stay resident in the cache until LRU reclaim."""
        blocks = self._blocks.pop(rid, [])
        shard = self._shard_of.pop(rid, 0)
        self._positions.pop(rid, None)
        shared = set(self._shared.pop(rid, ()))
        nodes = self._nodes[shard]
        recycled = []
        for b in blocks:
            if b is None:             # window-expired hole, already freed
                continue
            node = nodes.get(b) if b in shared else None
            if node is not None:
                node.refs -= 1
            else:
                recycled.append(b)
        self._free[shard] = sorted(self._free[shard] + recycled)
        self._free_further(rid)

    def _free_further(self, rid):
        """Every further group's pages of ``rid`` back to its free list."""
        for g in self._further:
            g.free = sorted(g.free + g.blocks.pop(rid, []))
            g.first.pop(rid, None)

    def release_expired(self, rid: int, next_pos: int) -> int:
        """Return to their group's free list the pages of ``rid`` that lie
        wholly below the window of a query at ``next_pos`` (the next the
        request will ask, so below every remaining query's): its table in
        that group then begins at the first page still held.  Groups that
        keep every position return nothing.  The count of pages returned."""
        freed = 0
        for g in self._further:
            held = g.blocks.get(rid)
            if g.spec.window is None or not held:
                continue
            keep_from = max(0, int(next_pos) - g.spec.window + 1) \
                // self.block_size
            gone = min(max(0, keep_from - g.first[rid]), len(held))
            if gone:
                g.free = sorted(g.free + held[:gone])
                del held[:gone]
                g.first[rid] += gone
                freed += gone
        self.window_frees += freed
        return freed

    def window_expired_free(self, rid: int, first_active_block: int, *,
                            keep_blocks: int = 0) -> int:
        """Early-free the PRIVATE blocks of ``rid`` whose logical index
        has fallen below ``first_active_block`` — under a sliding-window
        policy no remaining query can ever gather them again.  The first
        ``keep_blocks`` logical blocks (the policy's global anchors) are
        always kept.  Freed slots become ``None`` holes so the page
        table keeps its positional indexing; tree-owned (prefix-shared)
        blocks are SKIPPED, refs untouched — the radix tree's ownership
        outranks the window.  Returns the number of blocks freed."""
        blocks = self._blocks.get(rid)
        if not blocks:
            return 0
        shard = self._shard_of[rid]
        shared = set(self._shared.get(rid, ()))
        nodes = self._nodes[shard]
        hi = min(int(first_active_block), len(blocks))
        recycled = []
        for i in range(max(0, int(keep_blocks)), hi):
            b = blocks[i]
            if b is None or b in shared or b in nodes:
                continue
            blocks[i] = None
            recycled.append(b)
        if recycled:
            self._free[shard] = sorted(self._free[shard] + recycled)
            self.window_frees += len(recycled)
        return len(recycled)

    def _drop(self, rid):
        self._blocks.pop(rid, None)
        self._shard_of.pop(rid, None)
        self._positions.pop(rid, None)
        self._shared.pop(rid, None)
        self._free_further(rid)

    def table_base(self, rid: int, group: int) -> int:
        """The position of the first row of ``rid``'s table in ``group``:
        0 in a group that keeps everything, the first position of the
        oldest page still held in one that keeps a window."""
        if group == 0:
            return 0
        return self._further[group - 1].first.get(rid, 0) * self.block_size

    def table_row(self, rid: int, width: int, group: int = 0) -> np.ndarray:
        """LOCAL block ids of ``rid`` padded with the trash block to the
        fixed table width (the decode jit's static W).  Window-expired
        holes (``None``) map to the trash block too — their positions
        are masked out by the policy before they could be gathered.
        ``group``: the cache group's table (a window group's begins at
        :meth:`table_base`)."""
        blocks = self._blocks.get(rid, []) if group == 0 \
            else self._further[group - 1].blocks.get(rid, [])
        assert len(blocks) <= width, \
            f"rid {rid} holds {len(blocks)} blocks > table width {width}"
        row = np.full(width, TRASH_BLOCK, np.int32)
        row[:len(blocks)] = [TRASH_BLOCK if b is None else b
                             for b in blocks]
        return row

    def global_table_row(self, rid: int, width: int) -> np.ndarray:
        """GLOBAL block ids of ``rid``: local ids offset by the owning
        shard's base (``shard * blocks_per_shard``), padding mapped to
        that shard's OWN trash block.  The decode shard_map sees only
        local ids (:meth:`table_row`); a host-side gather/scatter over
        the full pool tensors — the KV-handoff export/import path —
        addresses the unsplit block axis and needs these."""
        shard = self._shard_of.get(rid, 0)
        base = np.int32(shard * self.blocks_per_shard)
        return self.table_row(rid, width) + base

    def free_blocks(self, shard: int) -> int:
        """Free blocks on one shard — the admission slot-ranking signal
        (the engine steers new sequences toward the least-loaded shard)."""
        return len(self._free[shard])

    def blocks_of(self, rid: int) -> int:
        """Blocks currently allocated to ``rid`` (0 when unknown) — the
        payload size a KV handoff of this request would transfer.
        Window-expired holes no longer hold pool capacity."""
        return sum(1 for b in self._blocks.get(rid, ()) if b is not None)

    # -- prefix cache (copy-on-write shared blocks) ---------------------
    def _touch(self, node):
        self._tick += 1
        node.tick = self._tick

    def prefix_lookup(self, shard: int, tokens) -> tuple:
        """Walk ``shard``'s radix tree along ``tokens``.  Returns
        ``(full_nodes, cow_node, cow_len)``: the chain of exactly-matching
        full blocks, then the child sharing the longest strict prefix of
        the next block (the COW-split candidate, ``cow_len`` trusted
        positions).  Coverage is capped at ``len(tokens) - 1`` so the
        final prompt position is always computed — the final prefill
        chunk must still run to produce the first-token logits."""
        bs = self.block_size
        limit = len(tokens) - 1
        node = self._roots[shard]
        full = []
        pos = 0
        while pos + bs <= limit:
            key = tuple(int(t) for t in tokens[pos:pos + bs])
            child = node.children.get(key)
            if child is None:
                break
            full.append(child)
            node = child
            pos += bs
        rest = tuple(int(t) for t in tokens[pos:min(pos + bs, limit)])
        cow, cow_len = None, 0
        for child in node.children.values():
            p = _common_prefix_len(child.tokens, rest)
            if p > cow_len:
                cow, cow_len = child, p
        return full, cow, cow_len

    def prefix_attach(self, rid: int, shard: int, tokens) -> int:
        """Map the longest cached prefix of ``tokens`` into ``rid``'s
        (empty) page table: fully-matching blocks are shared read-only
        (refcounted); the first divergent block is COW-split — its
        trusted prefix rows are device-copied into a private block the
        request may write into.  Returns the number of positions covered,
        which the request's prefill can skip entirely."""
        assert not self._blocks.get(rid), \
            f"prefix_attach on rid {rid} with blocks already allocated"
        full, cow, cow_len = self.prefix_lookup(shard, tokens)
        if not full and cow_len == 0:
            return 0
        covered = len(full) * self.block_size
        blocks = []
        for node in full:
            node.refs += 1
            self._touch(node)
            blocks.append(node.block)
        if cow is not None and cow_len > 0:
            if not self._free[shard]:
                self._reclaim_block(shard)
            if self._free[shard]:
                dst = self._free[shard].pop(0)
                self._cow_copy(shard, cow.block, dst)
                self._touch(cow)
                blocks.append(dst)
                covered += cow_len
                self.cow_splits += 1
        self._blocks[rid] = blocks
        self._shard_of[rid] = shard
        self._positions[rid] = covered
        self._shared[rid] = [n.block for n in full]
        return covered

    def prefix_insert(self, rid: int, shard: int, tokens) -> int:
        """Publish ``rid``'s prompt blocks into ``shard``'s radix tree so
        later requests can share them.  Blocks already attached from the
        tree descend without re-insertion; content already cached under a
        DIFFERENT physical block keeps the existing entry (rid's copy
        stays private).  Returns the number of blocks newly shared."""
        bs = self.block_size
        blocks = self._blocks.get(rid, [])
        node = self._roots[shard]
        nodes = self._nodes[shard]
        inserted = 0
        pos = 0
        i = 0
        n = len(tokens)
        while pos < n and i < len(blocks):
            chunk = tuple(int(t) for t in tokens[pos:pos + bs])
            child = node.children.get(chunk)
            if child is not None:
                node = child          # cached already (ours or a twin's)
                self._touch(node)
            else:
                blk = blocks[i]
                if blk is None:       # window-expired hole: the KV
                    break             # content is gone, nothing past it
                                      # can be published
                if blk in nodes:      # block published by an earlier
                    break             # insert of this rid under another
                                      # key — never double-own a block
                child = _PrefixNode(chunk, blk, node, 0)
                child.refs = 1        # rid still maps it
                node.children[chunk] = child
                nodes[blk] = child
                self._touch(child)
                self._shared.setdefault(rid, []).append(blk)
                node = child
                inserted += 1
            pos += bs
            i += 1
        return inserted

    def _cow_copy(self, shard: int, src: int, dst: int) -> None:
        """Device-side copy of one block's rows (the COW split): global
        ids address the unsplit block axis, exactly like the KV-handoff
        scatter, and the result is re-pinned to the pool's sharding so
        the donated dispatch path sees identically-placed arrays."""
        base = shard * self.blocks_per_shard
        g_src, g_dst = np.int32(base + src), np.int32(base + dst)
        if self.programs is not None and not self.programs.has("cow_copy"):
            from deepspeed_tpu.telemetry import register_program

            # first dispatch (warm_cow's trash self-copy in production):
            # the COW split is pure device work, collective-free, and
            # donates the pool — block churn never allocates or syncs
            register_program(
                self.programs, "cow_copy", _cow_copy_rows,
                (self.tensors.arrays, g_src, g_dst),
                contract={"host_transfer_free": True,
                          "collective_free": True,
                          "donates_argnums": (0,)})
        arrs = _cow_copy_rows(self.tensors.arrays, g_src, g_dst)
        if self.mesh is not None and self.shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            spec = NamedSharding(self.mesh, P(None, self.axis_name))
            arrs = tuple(jax.device_put(a, spec) for a in arrs)
        self.tensors = self.tensors.with_arrays(arrs)

    def warm_cow(self) -> None:
        """Compile the COW-split copy program up front (a trash-block
        self-copy — bit-neutral) so the first REAL split inside a
        recompile-guard window compiles nothing."""
        self._cow_copy(0, TRASH_BLOCK, TRASH_BLOCK)

    def _reclaim_block(self, shard: int) -> bool:
        """Evict ONE least-recently-used unreferenced leaf node from the
        shard's prefix tree, returning its block to the free list.
        Blocks still mapped by a live request (refs > 0) are never
        reclaimed — eviction respects refcounts."""
        best = None
        stack = [self._roots[shard]]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if (node.block is not None and not node.children
                    and node.refs <= 0):
                if best is None or node.tick < best.tick:
                    best = node
        if best is None:
            return False
        del best.parent.children[best.tokens]
        self._nodes[shard].pop(best.block, None)
        self._free[shard] = sorted(self._free[shard] + [best.block])
        self.cache_reclaims += 1
        return True

    def cached_blocks(self, shard: Optional[int] = None) -> int:
        """Blocks currently owned by the prefix tree (shared + resident)."""
        if shard is not None:
            return len(self._nodes[shard])
        return sum(len(n) for n in self._nodes)

    # -- accounting -----------------------------------------------------
    def device_bytes(self) -> int:
        """Per-shard device bytes of the pool tensors, priced through
        the shared analytic builder (``memory_accounting.
        kv_pool_bytes``) — byte-exact against the allocated k/v (+
        scale) arrays, asserted by tests/unit/test_memory_accounting."""
        from deepspeed_tpu.runtime.memory_accounting import kv_pool_bytes

        cfg = self.cfg
        if cache_kind(cfg) != KEYS_VALUES or self._further:
            # raw rows, several groups: as allocated
            return sum(t.size * t.dtype.itemsize
                       for t in self.all_arrays) // self.shards
        return kv_pool_bytes(
            cfg.n_layer, self.num_blocks, cfg.n_head, self.block_size,
            cfg.head_dim, kv_dtype=np.dtype(self.dtype).name,
            quantized=self.quantized, shards=self.shards)

    @property
    def all_arrays(self):
        """Every group's pool tensors, group by group in ``.arrays``
        order: what the serving programs thread and donate."""
        return self.tensors.arrays + tuple(
            a for g in self._further for a in g.tensors.arrays)

    def rebind(self, arrays):
        """The same slots, holding ``arrays`` (in :attr:`all_arrays`
        order)."""
        n = len(self.tensors.arrays)
        self.tensors = self.tensors.with_arrays(arrays[:n])
        for g in self._further:
            m = len(g.tensors.arrays)
            g.tensors = g.tensors.with_arrays(arrays[n:n + m])
            n += m

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - self.shards          # minus trash blocks

    @property
    def blocks_in_use(self) -> int:
        """DISTINCT blocks not on a free list — refcount-shared blocks
        count ONCE no matter how many page tables map them, and
        cache-resident blocks (refs == 0, awaiting reclaim) count too:
        they genuinely occupy pool capacity.  Of the first group; every
        group's: :meth:`group_stats`."""
        return self.usable_blocks - sum(len(f) for f in self._free)

    def group_stats(self) -> list:
        """Per cache group: its name, window, layers, usable and live
        pages, and the bytes a page costs it (its layers' rows)."""
        page = self.block_size * sum(
            t.shape[3] * t.dtype.itemsize for t in self.tensors.arrays)
        rows = [(self.groups[0], self.usable_blocks, self.blocks_in_use)] \
            + [(g.spec, g.num_blocks - 1, g.in_use) for g in self._further]
        return [{"name": spec.name, "window": spec.window,
                 "layers": spec.n_layer, "blocks_total": total,
                 "blocks_in_use": used,
                 "occupancy": used / max(1, total),
                 "block_bytes": page * spec.n_layer}
                for spec, total, used in rows]

    def occupancy(self, group=None) -> float:
        """Live over usable: pages of one ``group`` (an index), or with
        None BYTES over all groups, which for a one-group pool is its
        pages' share."""
        if group is not None:
            return self.group_stats()[group]["occupancy"]
        if not self._further:
            return self.blocks_in_use / max(1, self.usable_blocks)
        stats = self.group_stats()
        return sum(g["blocks_in_use"] * g["block_bytes"] for g in stats) \
            / max(1, sum(g["blocks_total"] * g["block_bytes"]
                         for g in stats))

    def fragmentation(self) -> float:
        """Internal fragmentation: fraction of MAPPED pool positions not
        covered by live tokens (tail slack of each sequence's last
        block).  Shared blocks appear once per mapping request on both
        sides of the ratio, so this stays a pure slack measure under
        prefix sharing.  0 = every mapped slot holds a token.  Clamped
        at 0: window-expired frees can leave more live positions than
        mapped slots (the freed tokens are no longer resident)."""
        allocated = sum(
            sum(1 for blk in b if blk is not None)
            for b in self._blocks.values()) * self.block_size
        if allocated == 0:
            return 0.0
        used = sum(self._positions.values())
        return max(0.0, 1.0 - used / allocated)

    def stats(self) -> dict:
        return {
            "pool_device_bytes": self.device_bytes(),
            "blocks_total": self.usable_blocks,
            "blocks_in_use": self.blocks_in_use,
            "occupancy": self.occupancy(),
            "fragmentation": self.fragmentation(),
            "block_size": self.block_size,
            "shards": self.shards,
            "quantized": self.quantized,
            "free_per_shard": [len(f) for f in self._free],
            "prefix_cached_blocks": self.cached_blocks(),
            "prefix_shared_refs": sum(
                n.refs for nodes in self._nodes for n in nodes.values()),
            "prefix_cow_splits": self.cow_splits,
            "prefix_cache_reclaims": self.cache_reclaims,
            "window_expired_frees": self.window_frees,
            "groups": self.group_stats(),
        }
