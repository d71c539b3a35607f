"""The paged decode-attention kernel (``ops/transformer/paged_attention.py``),
interpret mode on the CPU, against what every other platform runs: the shared
``jax.numpy`` core over the one-gather page view.

- it IS that attention, to f32 rounding, at every length around a page's and
  a step's edge, through shuffled page tables and beside idle lanes;
- the isolation promise of ``_forward_groups``: no row past a lane's length
  (its last page's tail, its unfilled pages, the trash block, a stranger's
  page) reaches its output, whatever that row holds;
- lanes that share pages read the same rows;
- the engine's decode program, forced onto the kernel, serves ``generate``'s
  greedy tokens;
- the latent (MLA) kernel over pages of raw rows is
  ``mla_decode_attention`` over the gathered view under the same promises
  (the engine forced onto it: ``test_longcat_flash.py``).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models.generation import _attn_core, generate
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.ops.transformer.paged_attention import (
    KERNEL_NAME, LATENT_KERNEL_NAME, latent_pages_per_step,
    latent_reads_in_place, paged_decode_attention,
    paged_latent_decode_attention, reads_in_place)
from deepspeed_tpu.ops.transformer.rect_attention import mla_decode_attention
from deepspeed_tpu.serving import engine as serving
from deepspeed_tpu.serving.kv_cache import TRASH_BLOCK

# heads, head size, rows a page, pages a lane: W * bs = 256 rows in both
WIDTHS = {"toy": (4, 8, 4, 64), "hd1024": (16, 64, 16, 16)}
ROWS = 256
LAYERS, LAYER = 2, 1


def _case(rng, width, lengths, dtype=jnp.float32):
    """A pool whose pages lie scattered (block 0 is the trash block, every
    other block belongs to one lane's table), one query a lane."""
    H, D, bs, W = WIDTHS[width]
    B, HD = len(lengths), H * D
    NB = 1 + B * W
    k, v = (jnp.asarray(rng.standard_normal((LAYERS, NB, bs, HD)), dtype)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, HD)), dtype)
    tables = jnp.asarray(1 + rng.permutation(B * W).reshape(B, W), jnp.int32)
    return q, k, v, tables, jnp.asarray(lengths, jnp.int32), H


def _kernel(q, k, v, tables, lengths, H, **kw):
    return np.asarray(paged_decode_attention(
        q, k, v, LAYER, tables, lengths, n_head=H, interpret=True, **kw),
        np.float32)


def _core_over_view(q, k, v, tables, lengths, H):
    """What ``_LayerCache.attend_heads`` runs off the TPU, without the
    output projection."""
    B, HD = q.shape
    rows = tables.shape[1] * k.shape[2]
    seen = jnp.arange(rows)[None, :] < lengths[:, None]
    kview, vview = (jnp.where(
        seen[:, None, :, None],
        serving._pool_view(t, None, LAYER, tables, H, False, q.dtype), 0)
        for t in (k, v))
    unprojected = {"c_proj": {"kernel": jnp.eye(HD, dtype=q.dtype),
                              "bias": jnp.zeros(HD, q.dtype)}}
    return np.asarray(_attn_core(
        q.reshape(B, H, 1, HD // H), kview, vview, seen[:, None, None, :],
        unprojected, q.dtype)[:, 0], np.float32)


@pytest.mark.parametrize("length", [1, 15, 16, 17, 255, ROWS])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_equals_the_core_over_the_view(width, length):
    rng = np.random.default_rng(length)
    lengths = [length, 0, ROWS + 1 - length, 0, int(rng.integers(1, ROWS))]
    case = _case(rng, width, lengths)
    got, want = _kernel(*case), _core_over_view(*case)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-6)
    assert not got[~live].any(), "an idle lane reads nothing and gets zeros"


@pytest.mark.parametrize("pages_per_step", [1, 3, 16])
def test_any_step_size_gives_the_same_attention(pages_per_step):
    """Steps that do not divide a lane's pages, and a page a step."""
    rng = np.random.default_rng(pages_per_step)
    case = _case(rng, "toy", [ROWS, 0, 37, 1, 0, 130])
    got = _kernel(*case, pages_per_step=pages_per_step)
    live = np.asarray(case[4]) > 0
    np.testing.assert_allclose(got[live], _core_over_view(*case)[live],
                               rtol=2e-5, atol=2e-6)


def test_bf16_probabilities_meet_bf16_values():
    """The cell's dtype: scores and softmax in f32, as the core's."""
    rng = np.random.default_rng(7)
    case = _case(rng, "hd1024", [200, 0, 17, ROWS], jnp.bfloat16)
    got, want = _kernel(*case), _core_over_view(*case)
    live = np.asarray(case[4]) > 0
    err = np.linalg.norm(got[live] - want[live]) / np.linalg.norm(want[live])
    assert err < 1e-2, err


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_rows_past_a_lane_change_nothing(width, poison):
    """The isolation promise: values are masked, not only scores."""
    rng = np.random.default_rng(11)
    lengths = [1, 0, 17, ROWS - 1, 130]
    q, k, v, tables, lens, H = _case(rng, width, lengths)
    bs = k.shape[2]
    clean = _kernel(q, k, v, tables, lens, H)
    seen = np.arange(ROWS)[None, :] < np.asarray(lens)[:, None]
    filled = np.zeros(k.shape[1:3], bool)         # (NB, bs); trash: unseen
    filled[np.asarray(tables)] = seen.reshape(len(lengths), -1, bs)
    assert not filled[TRASH_BLOCK].any() and not filled.all()
    k, v = (jnp.where(filled[None, :, :, None], t, poison) for t in (k, v))
    np.testing.assert_array_equal(_kernel(q, k, v, tables, lens, H), clean)


@pytest.mark.parametrize("length", [5, 48, 49, 100])
def test_lanes_sharing_pages_read_the_same_rows(length):
    """A prefix cache hands two lanes the same leading pages (here 3 of
    16 rows); with one query they agree while the length lies inside them
    and part ways with their own pages after."""
    rng = np.random.default_rng(3)
    q, k, v, tables, _, H = _case(rng, "hd1024", [length, 0, length])
    shared = 3
    q = q.at[2].set(q[0])
    tables = tables.at[2, :shared].set(tables[0, :shared])
    lens = jnp.asarray([length, 0, length], jnp.int32)
    got = _kernel(q, k, v, tables, lens, H)
    np.testing.assert_allclose(got[[0, 2]],
                               _core_over_view(q, k, v, tables, lens, H)[[0, 2]],
                               rtol=2e-5, atol=2e-6)
    if length <= shared * k.shape[2]:
        np.testing.assert_array_equal(got[0], got[2])
    else:
        assert np.abs(got[0] - got[2]).max() > 1e-3


@pytest.mark.parametrize("pool_shape, whole", [
    ((24, 1793, 16, 1024), True),       # gpt2-350m, the serving cells'
    ((48, 257, 16, 1600), False),       # gpt2-xl: 25 heads of 64, 12.5 lanes
    ((2, 25, 4, 1024), False),          # a page of 4 rows
    ((2, 25, 8, 128), True)])
def test_only_whole_tile_pages_are_read_in_place(pool_shape, whole):
    """Mosaic slices VMEM by (8, 128) tiles; the engine keeps the view for
    a pool the compiled kernel would refuse."""
    assert reads_in_place(pool_shape) is whole
    q = jnp.zeros((3, pool_shape[3]), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct(pool_shape, jnp.bfloat16)
    tables, lens = jnp.zeros((3, 4), jnp.int32), jnp.zeros((3,), jnp.int32)

    def compiled(q, k, v):
        return paged_decode_attention(q, k, v, 0, tables, lens,
                                      n_head=pool_shape[3] // 64,
                                      interpret=False)

    if whole:
        assert jax.eval_shape(compiled, q, pool, pool).shape == q.shape
    else:
        with pytest.raises(AssertionError, match="whole"):
            jax.eval_shape(compiled, q, pool, pool)


def test_the_program_names_the_kernel():
    """``device_ops`` and ``benchmark/tools/top_ops.py`` show a kernel under
    its ``pallas_call``'s name."""
    q, k, v, tables, lens, H = _case(np.random.default_rng(0), "hd1024", [3])
    program = jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, n_head=H, interpret=False))(q, k, v, LAYER, tables, lens)
    assert KERNEL_NAME == "paged_decode_attn"
    assert f"name={KERNEL_NAME}" in str(program)


def test_engine_decodes_through_the_kernel(monkeypatch):
    """The decode program on the branch a TPU takes (the kernel in
    interpret mode here) serves ``generate``'s greedy tokens: staggered
    arrivals, mixed lengths, idle lanes."""
    # the smallest pool the compiled kernel would take: pages of 8 rows of
    # 128 (``reads_in_place``); a narrower one keeps the view on every chip
    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=128, n_layer=2,
                     n_head=4, dtype=jnp.float32, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 97, n).astype(np.int32) for n in (5, 11, 3, 9)]
    maxnew = [6, 9, 12, 5]
    want = [generate(model, params, p[None], max_new_tokens=m)[0]
            for p, m in zip(prompts, maxnew)]

    calls = []

    def on_the_kernel(*args, **kw):
        calls.append(args[0].shape)
        return paged_decode_attention(*args, **{**kw, "interpret": True})

    monkeypatch.setattr(serving, "paged_decode_attention", on_the_kernel)
    monkeypatch.setattr(serving.jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    # a program traced by an earlier test holds the other branch, and this
    # one must not be left for a later test
    serving._make_decode_step.cache_clear()
    try:
        eng = serving.InferenceEngine(model, params, max_slots=3,
                                      kv_block_size=8, prefill_chunk=8,
                                      max_blocks_per_seq=8)
        rids = []
        for p, m in zip(prompts, maxnew):
            rids.append(eng.submit(p, max_new_tokens=m))
            eng.step()
            eng.step()
        res = eng.serve(max_steps=500)
    finally:
        serving._make_decode_step.cache_clear()
    assert calls and set(calls) == {(3, 128)}, \
        "only the decode program (one query a lane) takes the kernel"
    for rid, tokens in zip(rids, want):
        np.testing.assert_array_equal(res[rid]["tokens"], tokens)


# ---------------------------------------------------------------------------
# pages of latent rows: one pool, every head reads the same row
# ---------------------------------------------------------------------------
# heads, latent rank, rotary size, stored row, rows a page, pages a lane;
# "m4": mistral-small-4-ep4's row and pages, a table of three steps a lane
LATENT = {"toy": (4, 16, 8, 128, 4, 64), "r128": (8, 128, 64, 256, 16, 16),
          "m4": (32, 256, 64, 384, 64, 64)}


def _rows(width):
    """The rows a lane's table covers."""
    return LATENT[width][4] * LATENT[width][5]


def _latent_case(rng, width, lengths, dtype=jnp.float32):
    H, R, Dr, stored, bs, W = LATENT[width]
    B = len(lengths)
    pool = rng.standard_normal((LAYERS, 1 + B * W, bs, stored))
    pool[..., R + Dr:] = 0              # a row is padded to whole lanes
    q_lat, q_rope = (jnp.asarray(rng.standard_normal((B, H, n)) * n ** -0.5,
                                 dtype) for n in (R, Dr))
    tables = jnp.asarray(1 + rng.permutation(B * W).reshape(B, W), jnp.int32)
    return q_lat, q_rope, jnp.asarray(pool, dtype), tables, \
        jnp.asarray(lengths, jnp.int32)


def _latent_kernel(q_lat, q_rope, pool, tables, lengths, **kw):
    # the pages a step of the compiled path: of the pool held in bf16, as
    # every cell holds it (the f32 cases here would halve them)
    kw.setdefault("pages_per_step", latent_pages_per_step(
        pool.shape, 2, tables.shape[1]))
    return np.asarray(paged_latent_decode_attention(
        q_lat, q_rope, pool, LAYER, tables, lengths,
        latent_rank=q_lat.shape[-1], interpret=True, **kw), np.float32)


def _latent_over_view(q_lat, q_rope, pool, tables, lengths):
    """What ``_LayerCache.attend_rows`` runs off the TPU."""
    B, W = tables.shape
    view = pool[LAYER, tables.reshape(-1)].reshape(B, -1, pool.shape[3])
    return np.asarray(mla_decode_attention(
        q_lat, q_rope, view, lengths, q_lat.shape[-1]), np.float32)


# ends inside a page, on a page's edge, on a step's; a lane of 0 in each
LATENT_LENGTHS = {"toy": [1, 15, 16, 17, 255, ROWS],
                  "r128": [1, 15, 16, 17, 255, ROWS],
                  "m4": [1, 1280, 1344, 2600, 4096]}


@pytest.mark.parametrize("width,length", [
    pytest.param(width, length, id=f"{width}-{length}")
    for width, lengths in LATENT_LENGTHS.items() for length in lengths])
def test_latent_equals_mla_decode_attention_over_the_view(width, length):
    rng = np.random.default_rng(length)
    rows = _rows(width)
    lengths = [length, 0, rows + 1 - length, 0, int(rng.integers(1, rows))]
    case = _latent_case(rng, width, lengths)
    got, want = _latent_kernel(*case), _latent_over_view(*case)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-6)
    assert not got[~live].any(), "an idle lane reads nothing and gets zeros"


@pytest.mark.parametrize("pages_per_step", [1, 3, 64])
def test_latent_any_step_size_gives_the_same_attention(pages_per_step):
    rng = np.random.default_rng(pages_per_step)
    case = _latent_case(rng, "toy", [ROWS, 0, 37, 1, 0, 130])
    got = _latent_kernel(*case, pages_per_step=pages_per_step)
    live = np.asarray(case[4]) > 0
    np.testing.assert_allclose(got[live], _latent_over_view(*case)[live],
                               rtol=2e-5, atol=2e-6)


def test_latent_bf16_probabilities_meet_bf16_rows():
    rng = np.random.default_rng(7)
    case = _latent_case(rng, "r128", [200, 0, 17, ROWS], jnp.bfloat16)
    got, want = _latent_kernel(*case), _latent_over_view(*case)
    live = np.asarray(case[4]) > 0
    err = np.linalg.norm(got[live] - want[live]) / np.linalg.norm(want[live])
    assert err < 1e-2, err


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
@pytest.mark.parametrize("width", list(LATENT))
def test_latent_rows_past_a_lane_change_nothing(width, poison):
    rng = np.random.default_rng(11)
    rows = _rows(width)
    lengths = [1, 0, 17, rows - 1, 130]
    q_lat, q_rope, pool, tables, lens = _latent_case(rng, width, lengths)
    bs = pool.shape[2]
    clean = _latent_kernel(q_lat, q_rope, pool, tables, lens)
    seen = np.arange(rows)[None, :] < np.asarray(lens)[:, None]
    filled = np.zeros(pool.shape[1:3], bool)      # (NB, bs); trash: unseen
    filled[np.asarray(tables)] = seen.reshape(len(lengths), -1, bs)
    assert not filled[TRASH_BLOCK].any() and not filled.all()
    pool = jnp.where(filled[None, :, :, None], pool, poison)
    np.testing.assert_array_equal(
        _latent_kernel(q_lat, q_rope, pool, tables, lens), clean)


# a cell's pool, its latent, and where the pool is read in place its tables'
# width and the pages a step: a MiB of bf16 rows
@pytest.mark.parametrize("pool_shape, rank, walk", [
    ((4, 1665, 64, 640), 512, (26, 12)),    # longcat-flash-chat-ep32's cell
    ((5, 6209, 64, 384), 256, (388, 21)),   # mistral-small-4-ep4's
    ((2, 25, 8, 128), 16, None),            # a latent of a part of a lane
    ((2, 25, 4, 256), 128, None)])          # a page of 4 rows
def test_latent_only_whole_tiles_are_read_in_place(pool_shape, rank, walk):
    whole = walk is not None
    assert latent_reads_in_place(pool_shape, rank) is whole
    if whole:
        assert latent_pages_per_step(pool_shape, 2, walk[0]) == walk[1]
    stored = pool_shape[3]
    q_lat = jnp.zeros((3, 4, rank), jnp.bfloat16)
    q_rope = jnp.zeros((3, 4, min(64, stored - rank)), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct(pool_shape, jnp.bfloat16)
    tables, lens = jnp.zeros((3, 4), jnp.int32), jnp.zeros((3,), jnp.int32)

    def compiled(q_lat, q_rope, pool):
        return paged_latent_decode_attention(
            q_lat, q_rope, pool, 0, tables, lens, latent_rank=rank,
            interpret=False)

    if whole:
        assert jax.eval_shape(compiled, q_lat, q_rope, pool).shape \
            == q_lat.shape
    else:
        with pytest.raises(AssertionError, match="whole"):
            jax.eval_shape(compiled, q_lat, q_rope, pool)


def test_the_program_names_the_latent_kernel():
    case = _latent_case(np.random.default_rng(0), "r128", [3])
    program = jax.make_jaxpr(lambda q_lat, q_rope, pool, tables, lens:
                             paged_latent_decode_attention(
                                 q_lat, q_rope, pool, LAYER, tables, lens,
                                 latent_rank=128, interpret=False))(*case)
    assert LATENT_KERNEL_NAME == "paged_latent_decode_attn"
    assert f"name={LATENT_KERNEL_NAME}" in str(program)


# ---------------------------------------------------------------------------
# a window over pages of latent rows: a lane sees rows start .. length - 1
# ---------------------------------------------------------------------------
# "m3": motif-3-beta-ep8's row (512 | 64 stored as 640), pages and 80 heads
LATENT["m3"] = (80, 512, 64, 640, 64, 8)


def _latent_over_view_from(q_lat, q_rope, pool, tables, lengths, starts):
    """What ``_LayerCache.attend_rows`` runs off the TPU in a group that
    keeps a window."""
    B, W = tables.shape
    view = pool[LAYER, tables.reshape(-1)].reshape(B, -1, pool.shape[3])
    return np.asarray(mla_decode_attention(
        q_lat, q_rope, view, lengths, q_lat.shape[-1], starts=starts),
        np.float32)


@pytest.mark.parametrize("width,window,length", [
    pytest.param(width, window, length, id=f"{width}-w{window}-{length}")
    for width, window, lengths in (
        ("toy", 6, [1, 5, 6, 7, 130, ROWS]),
        ("toy", 17, [3, 16, 17, 18, 255]),      # a window of several pages
        ("r128", 40, [1, 40, 41, 200, ROWS]),
        # a window inside ONE page: the lane's first page is its last, rows
        # masked and zeroed on both sides of it
        ("r128", 8, [30, 32, 33]),
        ("m3", 128, [1, 128, 129, 200, 512]))
    for length in lengths])
def test_latent_window_equals_mla_decode_attention_from_the_start(
        width, window, length):
    """``starts``: the lane's first visible row; the kernel copies only the
    pages that hold rows ``start .. length - 1`` and masks the rest of its
    first page.  Beside idle lanes and lanes whose window is not yet
    full."""
    rng = np.random.default_rng(length)
    rows = _rows(width)
    lengths = np.asarray(
        [length, 0, rows + 1 - length, 0, int(rng.integers(1, rows))])
    starts = jnp.asarray(np.maximum(lengths - window, 0), jnp.int32)
    case = _latent_case(rng, width, lengths)
    got = _latent_kernel(*case, starts=starts)
    want = _latent_over_view_from(*case, starts)
    live = lengths > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-6)
    assert not got[~live].any()
    # and it is a window: the rows below the start change nothing
    q_lat, q_rope, pool, tables, lens = case
    W, bs = tables.shape[1], pool.shape[2]
    row = np.arange(W * bs)[None, :] < np.asarray(starts)[:, None]  # (B, rows)
    poisoned = np.array(pool)
    for b in range(len(lengths)):
        for page in range(W):
            below = row[b, page * bs:(page + 1) * bs]
            poisoned[LAYER, int(tables[b, page]), below] = np.nan
    again = _latent_kernel(q_lat, q_rope, jnp.asarray(poisoned), tables,
                           lens, starts=starts)
    np.testing.assert_array_equal(again[live], got[live])


def test_latent_window_kernel_carries_the_name_it_is_given():
    case = _latent_case(np.random.default_rng(0), "r128", [30])
    program = jax.make_jaxpr(lambda q_lat, q_rope, pool, tables, lens:
                             paged_latent_decode_attention(
                                 q_lat, q_rope, pool, LAYER, tables, lens,
                                 latent_rank=128, starts=lens - 8,
                                 interpret=False, name="gdla_window"))(*case)
    assert "name=gdla_window" in str(program)
    assert f"name={LATENT_KERNEL_NAME}" not in str(program)


# ---------------------------------------------------------------------------
# the rectangle kernel: shared rotary key + grouped query heads + window
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q_start,k_start,window", [
    (0, 0, 7), (40, 24, 7), (40, 24, 20), (100, 64, 33), (100, 0, None)])
def test_rect_kernel_with_shared_key_grouped_heads_and_window_at_once(
        q_start, k_start, window):
    """Five query heads a key/value head, the rotary key shared by all, a
    window and a view that begins at ``k_start``, in ONE call, against the
    ``jax.numpy`` core (what a chunk of Motif's sliding layers asks)."""
    from deepspeed_tpu.ops.transformer.rect_attention import \
        rect_flash_attention

    rng = np.random.default_rng(q_start + (window or 0))
    Hkv, G, C, S, D, Ds, Dv = 2, 5, 24, 160, 16, 8, 16
    H = Hkv * G
    q, qs = (jnp.asarray(rng.standard_normal((H, C, n)) * n ** -0.5,
                         jnp.float32) for n in (D, Ds))
    k = jnp.asarray(rng.standard_normal((Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((Hkv, S, Dv)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((S, Ds)), jnp.float32)
    got = rect_flash_attention(
        q, k, v, q_start, qs, ks, k_start=k_start, window=window,
        block_q=8, block_k=32, interpret=True)
    qpos = q_start + np.arange(C)[:, None]
    kpos = k_start + np.arange(S)[None, :]
    seen = kpos <= qpos
    if window is not None:
        seen &= kpos > qpos - window
    s = jnp.einsum("jgcd,jsd->jgcs", q.reshape(Hkv, G, C, D), k) \
        + jnp.einsum("jgcd,sd->jgcs", qs.reshape(Hkv, G, C, Ds), ks)
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("jgcs,jsv->jgcv", p, v).reshape(H, C, Dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
