"""The two attention walks as grouped-query, windowed kernels, interpret mode
on the CPU, against ``jax.numpy`` written here from the definition:

- ``paged_decode_attention``: G query heads a key head (the block-diagonal
  query in the columns of head ``h // G``), a first row a lane (``starts``),
  scattered page tables, idle lanes, a NaN planted below a window lane's
  first row and past its length;
- ``rect_flash_attention``: query head h reads key head ``h // G``, key j is
  position ``k_start + j``, a window bounds what a query sees from below,
  the last key block ragged, the chunk padded past the view's end.

G = 1 without a window is the kernels' older instance, which
``test_paged_attention.py`` and ``test_mistral4.py`` hold.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.transformer.paged_attention import \
    paged_decode_attention
from deepspeed_tpu.ops.transformer.rect_attention import rect_flash_attention

D = 16


def _softmax_attend(q, k, v, seen):
    """q (H, Q, D), k/v (H, K, D) already repeated by head, seen (Q, K)."""
    s = np.einsum("hqd,hkd->hqk", q, k) * D ** -0.5
    s = np.where(seen[None], s, -np.inf)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("hqk,hkd->hqd", p, np.where(np.isfinite(v), v, 0.0))


# ---------------------------------------------------------------------------
# paged decode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("G", [1, 8])
@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("pages_per_step", [None, 3])
def test_paged_decode_groups_and_window(G, window, pages_per_step):
    rng = np.random.default_rng(7 * G + (window or 0))
    Hkv, bs, W = 2, 8, 40
    H = G * Hkv
    lengths = np.array([1, 0, 9, 127, 128, 129, 200, 320, 317], np.int32)
    B = len(lengths)
    NB = 1 + B * W
    k, v = (rng.standard_normal((2, NB, bs, Hkv * D)).astype(np.float32)
            for _ in range(2))
    q = rng.standard_normal((B, H * D)).astype(np.float32)
    tables = (1 + rng.permutation(B * W).reshape(B, W)).astype(np.int32)
    starts = None if window is None \
        else np.maximum(lengths - window, 0).astype(np.int32)
    if window is not None:
        # what lies below a lane's first row, past its length and in the
        # trash block reaches nothing, whatever it holds
        for b in range(B):
            for r in list(range(0, starts[b])) + list(
                    range(lengths[b], W * bs)):
                k[1, tables[b, r // bs], r % bs] = np.nan
                v[1, tables[b, r // bs], r % bs] = np.nan
        k[:, 0] = v[:, 0] = np.nan
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 1,
        jnp.asarray(tables), jnp.asarray(lengths), n_head=H,
        starts=None if starts is None else jnp.asarray(starts),
        pages_per_step=pages_per_step, interpret=True))
    assert np.isfinite(out).all()
    for b in range(B):
        if lengths[b] == 0:
            assert (out[b] == 0).all()
            continue
        view_k = k[1, tables[b]].reshape(W * bs, Hkv, D).transpose(1, 0, 2)
        view_v = v[1, tables[b]].reshape(W * bs, Hkv, D).transpose(1, 0, 2)
        first = 0 if starts is None else starts[b]
        seen = (np.arange(W * bs) < lengths[b]) & (np.arange(W * bs) >= first)
        want = _softmax_attend(
            q[b].reshape(H, 1, D),
            np.where(seen[None, :, None], np.repeat(view_k, G, 0), 0.0),
            np.repeat(view_v, G, 0), seen[None])
        np.testing.assert_allclose(out[b].reshape(H, D), want[:, 0],
                                   rtol=2e-5, atol=2e-5)


def test_paged_decode_window_copies_only_its_pages():
    """Entries of the table before the page of a lane's first row are never
    read: they may name a block that does not exist."""
    rng = np.random.default_rng(3)
    Hkv, G, bs, W = 2, 4, 8, 32
    H = G * Hkv
    k, v = (jnp.asarray(rng.standard_normal((1, 1 + W, bs, Hkv * D)),
                        jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((1, H * D)), jnp.float32)
    tables = np.arange(1, 1 + W, dtype=np.int32)[None]
    length, window = 201, 64
    start = length - window
    ref = np.asarray(paged_decode_attention(
        q, k, v, 0, jnp.asarray(tables), jnp.asarray([length]), n_head=H,
        starts=jnp.asarray([start]), interpret=True))
    wrong = tables.copy()
    wrong[0, :start // bs] = 10 ** 6            # no such block
    out = np.asarray(paged_decode_attention(
        q, k, v, 0, jnp.asarray(wrong), jnp.asarray([length]), n_head=H,
        starts=jnp.asarray([start]), interpret=True))
    np.testing.assert_array_equal(out, ref)


# ---------------------------------------------------------------------------
# rectangle (chunked prefill)
# ---------------------------------------------------------------------------
_SMALL = [
    (0, 0, 96, 96),             # the first chunk, smaller than a tile
    (0, 200, 100, 300),         # ragged last key block
    (64, 300, 128, 400),        # the view begins at the oldest page held
    (192, 500, 72, 384),        # ... and the chunk is padded past its end
]
# (G, window, k_start, q_start, C, S, block_q, block_k)
RECT_CASES = [(G, window, *shape, 64, 96) for shape in _SMALL
              for window in (None, 128) for G in (1, 8)] + [
    # the band at the cells' shapes, the kernel's own blocks: a chunk of
    # 2,048 whose view begins at the oldest page the window still holds
    (5, 128, 6016, 6144, 2048, 2240, None, None),
    (5, 128, 5953, 6144, 2048, 2240, None, None),   # ... 191 rows before
    (8, 128, 6016, 6144, 2048, 2240, None, None),
    (8, 1024, 5120, 6144, 2048, 3136, None, None),
    (5, 1024, 5057, 6144, 2048, 3136, None, None),
    # the first chunk: the first queries' windows begin before position 0
    (5, 128, 0, 0, 2048, 2240, None, None),
    (8, 1024, 0, 64, 2048, 3136, None, None),
    # the chunk runs past the view's end, and the view's rows are no
    # whole tiles; a chunk that is no whole block of queries
    (5, 128, 6016, 6144, 2048, 1500, None, None),
    (8, 1024, 5120, 6144, 2000, 3136, 64, None),
    # a view shorter than one band
    (5, 128, 0, 0, 200, 208, None, None),
]


@pytest.mark.parametrize("G,window,k_start,q_start,C,S,block_q,block_k",
                         RECT_CASES)
def test_rect_groups_offset_and_window(G, window, k_start, q_start, C, S,
                                       block_q, block_k):
    rng = np.random.default_rng(11 * G + (window or 0) + q_start)
    Hkv = 2 if C * S < 2 ** 20 else 1
    H = G * Hkv
    q = rng.standard_normal((H, C, D)).astype(np.float32) * D ** -0.5
    k = rng.standard_normal((Hkv, S, D)).astype(np.float32)
    v = rng.standard_normal((Hkv, S, D)).astype(np.float32)
    kpos = k_start + np.arange(S)
    qpos = q_start + np.arange(C)
    last = qpos.max()
    # rows past the last query hold anything; under a window so do rows
    # below the first query's
    dead = kpos > last
    if window is not None:
        dead |= kpos < q_start - window + 1
    k[:, dead] = np.nan
    v[:, dead] = np.nan
    out = np.asarray(rect_flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), q_start,
        k_start=k_start if k_start or window else None, window=window,
        block_q=block_q, block_k=block_k, interpret=True))
    assert np.isfinite(out).all()
    # a query past the view's end sees what the last one inside it sees
    qpos = np.minimum(qpos, kpos.max())
    seen = kpos[None, :] <= qpos[:, None]
    if window is not None:
        seen &= kpos[None, :] > qpos[:, None] - window
    want = _softmax_attend(
        q * D ** 0.5, np.where(dead[None, :, None], 0.0, np.repeat(k, G, 0)),
        np.repeat(v, G, 0), seen)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_rect_skips_blocks_below_the_window():
    """What lies below the first query's window or past the last query
    reaches nothing: what those rows hold, NaN and infinity included,
    changes no bit, whether they lie outside every step's band or in the
    slack a band's start at a whole tile takes in."""
    rng = np.random.default_rng(5)
    H, C, S, window = 4, 128, 1024, 128
    q = jnp.asarray(rng.standard_normal((H, C, D)), jnp.float32)
    k = rng.standard_normal((2, S, D)).astype(np.float32)
    v = rng.standard_normal((2, S, D)).astype(np.float32)
    args = dict(k_start=0, window=window, block_q=64, block_k=128,
                interpret=True)
    ref = np.asarray(rect_flash_attention(q, jnp.asarray(k), jnp.asarray(v),
                                          800, **args))
    dead = (np.arange(S) < 800 - 127) | (np.arange(S) > 800 + C - 1)
    k[:, dead] = np.nan
    v[:, dead] = np.inf
    out = np.asarray(rect_flash_attention(q, jnp.asarray(k), jnp.asarray(v),
                                          800, **args))
    np.testing.assert_array_equal(out, ref)
