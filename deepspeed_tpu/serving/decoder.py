"""The decoder-block contract between a model and the serving engine.

``InferenceEngine`` owns pages, page tables, masks, scheduling, sampling
and the programs' shapes; the MODEL owns everything that differs between
architectures.  A configuration states the rows it caches a token
(``cfg.cache_rows``, read through ``kv_cache.cache_rows``: one pool tensor
each, ``(H*D, H*D)`` for keys and values where it states none,
``(R + Dr,)`` for one latent row, ``(R + Dr, R + Dr)`` for a block of two
latent attentions) and of what KIND they are (``cfg.cache_kind``, read
through ``kv_cache.cache_kind``: ``"keys_values"`` where it states none,
``"rows"`` for raw rows; the engine never tells the kind from the number
of rows), hands the engine a decoder object
(``cfg.decoder()``; a configuration without one is GPT-2,
``models.generation.GPT2Decoder``)

and, where its layers do not all cache alike, its cache GROUPS
(``cfg.cache_groups``, read through ``kv_cache.cache_groups``: tuples
``(name, layers, window)``; the layers that keep every position and the
layers that keep a window of W, each group with its own pool tensors, pages
and page table behind one ``alloc`` / ``free``; a configuration that states
none has one group that keeps everything, and the first group stated always
does).  The decoder object has:

``dtype``
    the compute dtype.
``hold(params) -> params``
    the tree the engine HOLDS the served weights in: the model casts, once,
    the leaves its block would otherwise cast in every program, and leaves
    the rest as given (:func:`held_as` does it in ONE program that keeps
    each leaf's sharding).  The engine calls it once, at construction, and
    keeps nothing else: a caller that drops its own tree frees it.
    Idempotent: a tree already held comes back as the same object, no
    program run, so ``FleetRouter`` holds once and its replicas share.
    ``Mistral4Decoder``: every floating leaf in ``cfg.dtype``.
    ``GPT2Decoder``: the same but for its LayerNorm leaves, which ``_ln``
    reads in f32.  ``bf16(w)`` computed once is ``bf16(w)`` computed in
    every program: the served tokens and logits are the same bits.
``stat_names``
    names of the int32 counters a block reports a step (``()``: none, and
    the programs have the outputs they always had).  A decoder adds a
    counter of its own by naming it here and returning it in its row:
    ``LongCatFlashDecoder.stat_names`` is ``moe.dropless.STAT_NAMES`` plus
    ``moe_zero_rows``, and nothing else in the engine changes.  The engine sums them
    over the layers, brings them to the host ON THE STEP'S ONE FETCH, and
    (tracer armed) records each as a zero-length span
    ``<name>_<group>``, a0 the value, ``group`` being ``decode`` or
    ``prefill_<bucket>``; beside them, for every model, ``attn_pairs_<group>``
    / ``attn_keys_decode`` (what ONE attention of a block attended: the
    live lanes' positions, the host's arithmetic; a block of two
    attentions reads that many keys twice; a model of several cache groups
    records ``attn_pairs_<cache group>_<group>`` /
    ``attn_keys_<cache group>_decode`` instead, a window group at most its
    window a query) and ``clock_ms_<group>`` (when
    it was fetched).
``n_layer``, ``scan_layers``
    how many blocks, and whether the engine runs them as ONE traced block
    under ``lax.scan`` (``l`` then a traced scalar and the weights stacked
    by layer: one operation a kernel in the compiled program and in the
    device trace, a fifth of the tracing) or as a Python loop (``l`` an
    int).
``embed(params, tokens, positions) -> (..., E)``
``block(params, l, x, cache) -> x`` (or ``(x, stats)`` with ``stat_names``)
    block ``l`` over x (B, T, E), residuals included (the last dim is the
    MODEL's: the engine reads ``B, T, _ = x.shape``, so a model that
    carries several residual streams side by side, ``MotifDecoder``'s
    (B, T, 4 E), embeds into them and contracts them in ``final_norm``);
    the model reads its own layer's weights out of ``params``.  ``cache`` is
    the engine's hook to layer ``l`` of the pool (``engine._LayerCache``):
    ``positions`` (B, T) and ``maxpos`` (B,) absolute, ``row_valid``
    (B, T) bool or None, ``write_rows(i, rows)`` / ``view_rows(i)`` for
    raw rows of cache tensor ``i`` ((B*T, width) in; (B, K*bs, stored) out
    in view order, which is position order on the dense path, ``stored``
    being the width padded with zeros to whole 128-lane tiles; a block
    that caches several raw rows writes and views each by its index;
    ``attend_rows(i, q_lat, q_rope, rank, name=None)``: latent decode
    attention of one query a lane over cache tensor ``i``, up-projections
    absorbed, (B, H, rank); as with ``attend_heads`` the engine chooses its
    form: lowered for a TPU it reads each lane's filled pages where they
    lie (under the kernel name ``name``), elsewhere
    ``mla_decode_attention`` over the view; raw rows may live in a group
    that keeps a WINDOW (``MotifDecoder``): a view's row j then stands at
    ``cache.k_start + j`` and ``attend_rows`` shows each lane its last
    ``cache.window`` rows), and
    for a
    model with heads ``write_heads`` / ``view_heads`` with the two masks
    ``valid_scores`` / ``valid_keys`` of the gathered view, and
    ``attend_heads(q, n_head, p)``: the masked attention of q
    (B, H, T, D) over the layer's cached keys and values through
    ``p["c_proj"]``, (B, T, E).  The ENGINE chooses its form from what it
    observes, since pages, tables and masks are its own: one query a lane
    over the dense unquantized pool, lowered for a TPU, reads each lane's
    filled pages where they lie (``ops/transformer/paged_attention.py``);
    every other program, pool and platform attends the gathered view with
    the ``jax.numpy`` core ``generate`` shares.
    Grouped-query heads and windows: ``write_heads(i, rows)`` takes rows
    (N, Hkv, D) of the CACHED heads, ``view_heads(i, Hkv)`` gives (B, Hkv,
    K, D) whose row j stands at position ``cache.k_start + j``, and
    ``attend_heads(q, n_head, p, name=None)`` takes ``n_head`` QUERY heads,
    a multiple of the cached ones (query head h reads cached head
    ``h // G``; no key is repeated in memory; ``p`` None: the result is not
    projected, (B, T, H * D); ``name``: what the paged kernel is called in
    the compiled program); in a group that keeps a window
    (``cache.window``) each query sees its last ``window`` positions.
    Cache groups: ``cache`` is the hook to layer ``l`` of the FIRST group;
    ``cache.at(name, layer_in_group)`` is the hook to a layer of the group
    called ``name``, and a model that loops over the layers of one kind
    itself carries that group's pool through its loop with
    ``cache.carry(name)`` / ``cache.restore(name, arrays)``
    (``MellumDecoder``: its traced unit is one period of sliding layers and
    a full one, ``n_layer`` the periods; ``MotifDecoder``: ONE block, the
    whole held stage, each run of layers of one kind a scan of its own).
``final_norm(params, x)``, ``logits(params, xe (N, E)) -> (N, vocab) f32``

The engine builds its programs in three factories (``engine.py``:
``_make_decode_step``, ``_make_prefill_chunk``, ``_make_spec_verify``), all
over the one pass ``_forward_groups``.  What serves a model whose cache is
not ``(keys, values)`` (by its stated kind), whose blocks report counters or
which caches in several groups: the first two, dense (the decode program
and the chunked prefill programs).  The other variants (``speculative``,
the third factory; ``sparse_context``, the first two with a sparse policy's
widths; ``quantize_kv``, ``prefix_cache``, ``shards``, the fleet hand-off)
know one group of one kind and refuse it by name with
:class:`UnsupportedForModel`.
"""
import functools

import jax
import jax.numpy as jnp


class UnsupportedForModel(ValueError):
    """An engine variant that knows keys and values only was asked to
    serve a model that caches something else."""


@functools.partial(jax.jit, static_argnums=1)
def _cast(leaves, dtype):
    return [leaf.astype(dtype) for leaf in leaves]


def held_as(params, dtype, keep=lambda path: False):
    """``params`` with every floating leaf in ``dtype``, but for the leaves
    whose key path ``keep`` names, which stay as given: what a decoder's
    ``hold`` is made of.  ONE program over the leaves that differ; where
    none does, ``params`` itself."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    cast = [i for i, (path, leaf) in enumerate(leaves)
            if jnp.issubdtype(leaf.dtype, jnp.floating)
            and leaf.dtype != dtype and not keep(path)]
    if not cast:
        return params
    out = [leaf for _, leaf in leaves]
    for i, leaf in zip(cast, _cast([out[i] for i in cast], dtype)):
        out[i] = leaf
    return jax.tree_util.tree_unflatten(tree, out)


def decoder_for(cfg):
    """The decoder object of a configuration."""
    own = getattr(cfg, "decoder", None)
    if own is not None:
        return own()
    from deepspeed_tpu.models.generation import GPT2Decoder

    return GPT2Decoder(cfg)


def refuse_unless_plain(cfg, dec, variant):
    """Keys and values, no counters, one cache group: what every engine
    variant serves."""
    from deepspeed_tpu.serving.kv_cache import (KEYS_VALUES, cache_groups,
                                                cache_kind, cache_rows)

    groups = cache_groups(cfg)
    if len(groups) > 1:
        raise UnsupportedForModel(
            f"{variant}: this engine variant knows ONE cache group, one "
            f"page table a request; {type(dec).__name__} caches in "
            f"{len(groups)} ("
            + ", ".join(f"{g.name}: {g.n_layer} layers keeping "
                        + ("every position" if g.window is None
                           else f"a window of {g.window}") for g in groups)
            + "). The dense decode program and the chunked prefill "
            "programs serve it (docs/tutorials/serving.md, 'The "
            "decoder-block contract').")
    if cache_kind(cfg) != KEYS_VALUES or dec.stat_names:
        raise UnsupportedForModel(
            f"{variant}: this engine variant serves models that cache "
            f"(keys, values) only; {type(dec).__name__} caches "
            f"{cache_kind(cfg)} of widths {cache_rows(cfg)} and reports "
            f"{len(dec.stat_names)} counters. The dense decode program and "
            f"the chunked prefill programs serve it "
            f"(docs/tutorials/serving.md, 'The decoder-block contract').")
