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

The held part runs through ``grouped_matmul``: the (token, choice) pairs on
held experts are laid out by expert, each expert's rows starting on a tile
boundary, so a tile of rows meets one expert's matrix.  The layout is made
with a one-hot running count, not a sort.

``moe/sharded_moe.py`` (GShard top-2 with a capacity factor) stays the
trainer's layer.
"""
import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.grouped_matmul import KERNEL_NAME, grouped_matmul

# what a routed layer reports a step, in this order (int32)
STAT_NAMES = ("moe_routed_rows", "moe_held_rows", "moe_busiest_scaled_rows",
              "moe_experts_touched", "moe_expert_slots")


def route_top_k(x, router, top_k, *, norm_topk_prob=True, scaling=1.0):
    """Softmax over all experts in f32, the ``top_k`` largest, their
    weights renormalised to sum 1 (``norm_topk_prob``) and scaled.
    x: (T, H); router: (H, E).  Returns weights (T, k) f32, ids (T, k)."""
    logits = jnp.dot(x, router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
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
                 interpret=None, first_matrix=0, kernel_name=KERNEL_NAME):
    """The held experts' part of the routed sum over x (T, H).

    ``experts``: ``gate_up`` (n, H, 2 I) = [gate | up] and ``down``
    (n, I, H), SwiGLU; held expert ``e`` of this layer is matrix
    ``first_matrix + e`` (0, and n = count, for one layer's own tensors;
    a model that stacks its layers' experts in one tensor passes
    ``layer * count``, traced or not: the kernel reads the matrix where it
    lies).  The two grouped matmuls are called ``<kernel_name>_up`` and
    ``<kernel_name>_down`` in the compiled program and the device trace.
    Returns (T, H) in x's dtype and the step's ``STAT_NAMES`` row."""
    T, H = x.shape
    count = experts_held[1]
    weights, ids = route_top_k(x, router, top_k,
                               norm_topk_prob=norm_topk_prob,
                               scaling=scaling)
    lay = held_layout(ids, valid, experts_held, tile_m)
    rows = x[lay["row_token"]]                              # (M, H)
    matrix = lay["tile_expert"] + first_matrix
    gate_up = grouped_matmul(rows, experts["gate_up"], matrix,
                             lay["n_tiles"], tile_m=tile_m,
                             interpret=interpret,
                             name=kernel_name + "_up").astype(jnp.float32)
    inner = experts["down"].shape[1]
    hidden = (jax.nn.silu(gate_up[:, :inner]) * gate_up[:, inner:]) \
        .astype(x.dtype)
    out_rows = grouped_matmul(hidden, experts["down"], matrix,
                              lay["n_tiles"], tile_m=tile_m,
                              interpret=interpret,
                              name=kernel_name + "_down")   # (M, H)
    # rows past the active tiles were never written: select, never multiply
    picked = jnp.where(
        lay["held"][:, None],
        out_rows[jnp.minimum(lay["dest"], out_rows.shape[0] - 1)], 0)
    y = jnp.sum(picked.astype(jnp.float32).reshape(T, top_k, H)
                * weights[:, :, None], axis=1).astype(x.dtype)
    sizes = lay["sizes"]
    n_valid = T if valid is None else jnp.sum(valid.astype(jnp.int32))
    stats = jnp.stack([n_valid * top_k, sizes.sum(), sizes.max() * count,
                       jnp.sum((sizes > 0).astype(jnp.int32)),
                       jnp.int32(count)]).astype(jnp.int32)
    return y, stats
