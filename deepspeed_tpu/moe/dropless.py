"""Dropless top-k routing over a HELD SHARE of the experts.

One chip of an expert-parallel layer: the router scores every expert, each
token takes its ``top_k`` (weights renormalised over the chosen), and this
chip computes the part of ``sum_e w_e * expert_e(x)`` whose experts it
holds, ``experts_held = (first, count)``.  What the absent experts would
add is left out: on the chips that hold them it is computed the same way,
and the parts add up (``tests/unit/test_mistral4.py``).  The exchange
between chips is not here, and nothing stands in for it.  No token is
dropped: there is no capacity, the row buffer is sized for the worst case
(every choice of every token on a held expert).

A router may score more choices than there are routed experts: the last
``zero_experts`` columns are ZERO-COMPUTE ("identity") experts, whose
choice adds ``weight * x`` and no matmul (LongCat-Flash), so the work a
token costs varies from none to ``top_k`` expert rows.  The identity term is
computed whole on every chip (each computes it alike for the tokens it
sees; counted once when shares are added).  A ``choice_bias`` moves which
choices are TAKEN and never their weights.

The held part runs through ``grouped_matmul``: the (token, choice) pairs on
held experts are laid out by expert, each expert's rows starting on a tile
boundary, so a tile of rows meets one expert's matrix.  The layout is made
with a one-hot running count, not a sort.

``moe/sharded_moe.py`` (GShard top-2 with a capacity factor) stays the
trainer's layer.
"""
import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.grouped_matmul import KERNEL_NAME, grouped_matmul

# what a routed layer reports a step, in this order (int32)
STAT_NAMES = ("moe_routed_rows", "moe_held_rows", "moe_busiest_scaled_rows",
              "moe_experts_touched", "moe_expert_slots")
# one more where the router scores zero-compute experts: the (token, choice)
# pairs that fell on them (``moe_routed_rows`` counts those pairs too)
ZERO_STAT_NAME = "moe_zero_rows"


SCORES = {"softmax": functools.partial(jax.nn.softmax, axis=-1),
          "sigmoid": jax.nn.sigmoid}


def route_top_k(x, router, top_k, *, norm_topk_prob=True, scaling=1.0,
                choice_bias=None, score="softmax"):
    """The router's scores of all choices in f32 (``score``: a softmax over
    them, or a sigmoid of each: :data:`SCORES`), the ``top_k`` largest (of
    ``probs + choice_bias`` where a bias (E,) is given: it moves the choice,
    the weights stay the scores), their weights renormalised to sum
    1 (``norm_topk_prob``) and scaled.
    x: (T, H); router: (H, E).  Returns weights (T, k) f32, ids (T, k)."""
    logits = jnp.dot(x, router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    probs = SCORES[score](logits)
    if choice_bias is None:
        weights, ids = jax.lax.top_k(probs, top_k)
    else:
        _, ids = jax.lax.top_k(probs + choice_bias.astype(jnp.float32),
                               top_k)
        weights = jnp.take_along_axis(probs, ids, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * scaling, ids


def held_layout(ids, valid, experts_held, tile_m):
    """Where each (token, choice) pair goes in the row buffer.

    ids: (T, k) chosen experts; valid: (T,) bool or None (padding rows
    route nowhere).  Returns a dict: ``held`` (T*k,) bool, ``dest`` (T*k,)
    row of the pair in the buffer (the buffer's length where not held),
    ``row_token`` (M,) the token each buffer row reads, ``tile_expert``
    (M // tile_m,), ``n_tiles`` (), ``sizes`` (count,) rows by held
    expert."""
    T, k = ids.shape
    first, count = experts_held
    local = ids - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    held = held.reshape(-1)
    expert = jnp.where(held, local.reshape(-1), count)
    onehot = (expert[:, None] == jnp.arange(count)[None, :]) \
        .astype(jnp.int32)                                  # (T*k, count)
    before = jnp.cumsum(onehot, axis=0) - onehot
    safe = jnp.minimum(expert, count - 1)
    rank = jnp.take_along_axis(before, safe[:, None], axis=1)[:, 0]
    sizes = onehot.sum(axis=0)
    tiles = (sizes + tile_m - 1) // tile_m
    tile_end = jnp.cumsum(tiles)
    m_tiles = -(-T * k // tile_m) + count       # worst case, static
    M = m_tiles * tile_m
    dest = jnp.where(held, (tile_end - tiles)[safe] * tile_m + rank, M)
    row_token = jnp.zeros(M, jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(m_tiles), side="right"),
        count - 1).astype(jnp.int32)
    return {"held": held, "dest": dest, "row_token": row_token,
            "tile_expert": tile_expert, "n_tiles": tile_end[-1],
            "sizes": sizes}


def dropless_moe(x, router, experts, *, top_k, experts_held, tile_m,
                 valid=None, norm_topk_prob=True, scaling=1.0,
                 interpret=None, first_matrix=0, kernel_name=KERNEL_NAME,
                 choice_bias=None, zero_experts=0, live_tiles=False,
                 score="softmax", activation=None):
    """The held experts' part of the routed sum over x (T, H), plus the
    identity term where the router scores zero-compute experts.

    ``experts``: ``gate_up`` (n, H, 2 I) = [gate | up] and ``down``
    (n, I, H), SwiGLU; held expert ``e`` of this layer is matrix
    ``first_matrix + e`` (0, and n = count, for one layer's own tensors;
    a model that stacks its layers' experts in one tensor passes
    ``layer * count``, traced or not: the kernel reads the matrix where it
    lies).  The two grouped matmuls are called ``<kernel_name>_up`` and
    ``<kernel_name>_down`` in the compiled program and the device trace.

    ``zero_experts``: the router's last columns that are zero-compute
    experts (ids ``router.shape[1] - zero_experts`` and up): each such
    choice adds ``weight * x``.  ``choice_bias``: see :func:`route_top_k`.

    ``score``: the router's scoring function (:func:`route_top_k`).
    ``activation``: what stands between the two grouped matmuls, a function
    ``(gate_up (M, 2 I) f32, matrix (M,)) -> (M, I) f32`` of the first one's
    rows and the matrix each row met (an activation with parameters an
    expert reads its own by it); None: SiLU(gate) * up.

    ``live_tiles``: the row buffer is sized for the worst case, ``T * k``
    rows and a tile's slack an expert; where few choices can fall on the
    held experts (top-12 over 768 with 16 held: a forty-eighth in the mean)
    nearly all of it is never live, yet the gather that fills it, the
    kernels' grids and the elementwise passes between them walk all of it.
    With ``live_tiles`` the held part runs on the SHORTEST of a ladder of
    buffers that holds the step's live tiles (``count`` tiles, doubling up
    to the worst case; ``lax.switch`` on the layout's own tile count): the
    same rows through the same kernels, so the same numbers, no capacity
    and no token dropped, the work bounded by what is live.

    Returns (T, H) in x's dtype and the step's ``STAT_NAMES`` row (one
    longer, ``ZERO_STAT_NAME``, with ``zero_experts``)."""
    T, H = x.shape
    count = experts_held[1]
    weights, ids = route_top_k(x, router, top_k,
                               norm_topk_prob=norm_topk_prob,
                               scaling=scaling, choice_bias=choice_bias,
                               score=score)
    lay = held_layout(ids, valid, experts_held, tile_m)
    worst = lay["tile_expert"].shape[0]
    inner = experts["down"].shape[1]

    def held_part(m_tiles):
        """The held sum through the first ``m_tiles`` tiles of the buffer
        (every live tile lies in them)."""
        row_token, tile_expert = lay["row_token"], lay["tile_expert"]
        if m_tiles < worst:
            row_token = row_token[:m_tiles * tile_m]
            tile_expert = tile_expert[:m_tiles]
        rows = x[row_token]                                 # (M, H)
        matrix = tile_expert + first_matrix
        gate_up = grouped_matmul(rows, experts["gate_up"], matrix,
                                 lay["n_tiles"], tile_m=tile_m,
                                 interpret=interpret,
                                 name=kernel_name + "_up") \
            .astype(jnp.float32)
        if activation is None:
            hidden = jax.nn.silu(gate_up[:, :inner]) * gate_up[:, inner:]
        else:
            hidden = activation(gate_up, jnp.repeat(matrix, tile_m))
        hidden = hidden.astype(x.dtype)
        out_rows = grouped_matmul(hidden, experts["down"], matrix,
                                  lay["n_tiles"], tile_m=tile_m,
                                  interpret=interpret,
                                  name=kernel_name + "_down")   # (M, H)
        # rows past the active tiles were never written: select, never
        # multiply
        picked = jnp.where(
            lay["held"][:, None],
            out_rows[jnp.minimum(lay["dest"], out_rows.shape[0] - 1)], 0)
        return jnp.sum(picked.astype(jnp.float32).reshape(T, top_k, H)
                       * weights[:, :, None], axis=1).astype(x.dtype)

    if live_tiles:
        rungs, m = [], count    # worst = ceil(T k / tile) + count tiles
        while m < worst:
            rungs.append(m)
            m *= 2
        # the shortest rung that holds every live tile
        rung = sum((lay["n_tiles"] > r).astype(jnp.int32) for r in rungs)
        y = jax.lax.switch(rung, [functools.partial(held_part, r)
                                  for r in rungs + [worst]])
    else:
        y = held_part(worst)
    sizes = lay["sizes"]
    n_valid = T if valid is None else jnp.sum(valid.astype(jnp.int32))
    stats = [n_valid * top_k, sizes.sum(), sizes.max() * count,
             jnp.sum((sizes > 0).astype(jnp.int32)), jnp.int32(count)]
    if zero_experts:
        # the identity experts: their weights on the token itself
        zero = ids >= router.shape[1] - zero_experts
        if valid is not None:
            zero = zero & valid[:, None]
        y = y + (jnp.sum(jnp.where(zero, weights, 0), axis=1)[:, None]
                 * x.astype(jnp.float32)).astype(x.dtype)
        stats.append(jnp.sum(zero.astype(jnp.int32)))
    return y, jnp.stack(stats).astype(jnp.int32)
