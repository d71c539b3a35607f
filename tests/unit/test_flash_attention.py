"""Flash-attention kernel parity vs the jnp reference path.

Mirrors the reference's kernel parity strategy
(reference tests/unit/test_cuda_forward.py / test_cuda_backward.py: fused
kernel vs Python BertEncoder with atol~1e-2); here the Pallas kernel runs in
interpreter mode on the CPU mesh and is compared against the dense jnp
softmax-attention implementation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.ops.transformer.flash_attention as fa
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
from deepspeed_tpu.ops.transformer.functional import scaled_dot_product_attention


def _rand_qkv(rng, b, h, s, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    return q, k, v


def _dropped_reference(seed, rate, causal):
    """The jnp attention with the KERNEL's keep mask: the hash is plain
    uint32 arithmetic, so the same function draws it here."""
    def ref(q, k, v):
        b, h, s_q, d = q.shape
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
        if causal:
            logits = jnp.where(jnp.tril(jnp.ones((s_q, s_q), bool)), logits,
                               -1e30)
        keep = jnp.stack([fa._dropout_keep(
            np.asarray([seed]), i, 0, 0, s_q, k.shape[2], rate)
            for i in range(b * h)]).reshape(b, h, s_q, -1)
        probs = jnp.where(keep, jax.nn.softmax(logits, axis=-1) / (1 - rate),
                          0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return ref


def _padding_bias(rng, b, h, s):
    bias = np.zeros((b, 1, 1, s), np.float32)
    for row in range(b):
        bias[row, ..., int(rng.integers(s // 3, s)):] = -1e9
    return bias


def _full_bias(rng, b, h, s):
    return rng.standard_normal((b, h, s, s)).astype(np.float32)


# name -> (b, h, s, d), kernel arguments, bias maker
FLASH_CASES = {
    f"{'causal' if causal else 'full'}-{s}-{d}":
        ((1, 2 if s == 128 else 1, s, d), {"causal": causal}, None)
    for causal in (True, False) for s in (128, 640, 1024, 1536)
    for d in (64, 128)}
FLASH_CASES.update({
    # a grid of several blocks at a short length: the running state, the
    # skipped steps and the clamped fetches (what the deleted block knobs
    # of the backward used to reach)
    "causal-256-in-blocks-of-128":
        ((1, 2, 256, 64), {"causal": True, "block_q": 128, "block_k": 128},
         None),
    "full-512x256-in-blocks-of-128":
        ((1, 1, 512, 64), {"block_q": 128, "block_k": 128, "s_k": 256},
         None),
    "key-padding-bias": ((2, 3, 256, 64), {}, _padding_bias),
    "key-padding-bias-in-blocks-of-128":
        ((2, 2, 256, 64), {"block_q": 128, "block_k": 128}, _padding_bias),
    "full-bias": ((2, 2, 256, 64), {}, _full_bias),
    "full-bias-causal-in-blocks-of-128":
        ((1, 2, 256, 64), {"causal": True, "block_q": 128, "block_k": 128},
         _full_bias),
    "dropout": ((1, 2, 256, 64), {"dropout_rate": 0.25, "dropout_seed": 7},
                None),
    "dropout-causal-in-blocks-of-128":
        ((1, 2, 256, 64), {"causal": True, "dropout_rate": 0.25,
                           "dropout_seed": 11, "block_q": 128,
                           "block_k": 128}, None),
})


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_matches_f32_reference(case):
    """Output AND dq/dk/dv against the f32 jnp attention: every walk the
    wrapper derives (one block, the staircase of a diagonal tile at 640 /
    1024, a grid of three blocks of 512 at 1536), both head sizes, the
    biases, and dropout with the kernel's own mask drawn outside it, which
    fails unless the forward and both sweeps regenerate the same one."""
    (b, h, s, d), kw, make_bias = FLASH_CASES[case]
    kw = dict(kw)
    s_k = kw.pop("s_k", s)
    rng = np.random.default_rng(sum(map(ord, case)))
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, h, s_k, d)), jnp.float32)
            for _ in range(2))
    bias = None if make_bias is None else jnp.asarray(make_bias(rng, b, h, s))
    if "dropout_rate" in kw:
        reference = _dropped_reference(kw["dropout_seed"], kw["dropout_rate"],
                                       kw.get("causal", False))
    else:
        def reference(q, k, v):
            return scaled_dot_product_attention(
                q, k, v, bias=bias, causal=kw.get("causal", False),
                use_pallas=False)

    def kernel(q, k, v):
        return flash_attention(q, k, v, bias=bias, interpret=True, **kw)

    def with_grads(attn):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out, *vjp(jnp.cos(out)))

    for name, got, want in zip(("out", "dq", "dk", "dv"),
                               with_grads(kernel), with_grads(reference)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), err_msg=name,
            **(dict(atol=2e-5, rtol=2e-5) if name == "out"
               else dict(atol=5e-5, rtol=5e-4)))


@pytest.mark.parametrize("s,block,causal,want", [
    (1024, 256, True, (10, 4, 6)),      # the training cells' walk
    (1024, 128, True, (36, 8, 28)),
    (1024, 512, True, (3, 2, 1)),
    (1024, 1024, True, (1, 1, 0)),
    (1536, 512, True, (6, 3, 3)),
    (1024, 256, False, (16, 0, 0)),
])
def test_flash_tiles_counts_the_walk(s, block, causal, want):
    assert fa.flash_tiles(s, s, block, block, causal) == want
    # nothing above the diagonal is visited, only diagonal tiles are masked
    for q in range(0, s, block):
        for k in range(0, s, block):
            kind = fa._tile_kind(q, q + block, k, k + block, causal)
            assert kind == ("full" if not causal or k < q else
                            "mask" if k == q else "skip")


@pytest.mark.parametrize("keys_first", [False, True])
def test_a_diagonal_tile_is_walked_as_the_tiles_counted(keys_first):
    """The kernels' loop bounds are the counter's tiles: every piece of
    the staircase is a run of 'full' tiles or one 'mask' tile, and the
    pieces' area is what flash_tiles visits."""
    walk = fa._walk(1024, 256, 1024, True, keys_first)
    visited, masked, _ = fa.flash_tiles(1024, 1024, 256, 256, True)
    pieces = [p for _, row in walk for p in row]
    assert sum(hi - lo for lo, hi, _ in pieces) == visited * 256
    assert sum(m for _, _, m in pieces) == masked
    assert all(hi - lo == 256 for lo, hi, m in pieces if m)
    lo, row = walk[1]
    assert lo == 256 and row == (
        [(256, 512, True), (512, 1024, False)] if keys_first
        else [(0, 256, False), (256, 512, True)])
    assert fa._walk(512, 256, 384, False) == [
        (0, [(0, 384, False)]), (256, [(0, 384, False)])]


def test_lse_of_rows_whose_later_blocks_are_skipped():
    """Query block 0 of a grid of several sees key block 0 alone: its
    statistics are written after the skipped steps, from the state the one
    visited step left, one value a row."""
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 384, 64)), jnp.float32)
               for _ in range(3))
    out, lse = fa._flash_fwd(
        q, k, v, None, None, scale=0.125, causal=True, bias_kind="none",
        num_heads=1, dropout_rate=0.0, block_q=128, block_k=128,
        interpret=True)
    assert lse.shape == (2, 1, 384) and lse.dtype == jnp.float32
    logits = jnp.einsum("bqd,bkd->bqk", q, k) * 0.125
    logits = jnp.where(jnp.tril(jnp.ones((384, 384), bool)), logits, -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(lse[:, 0]),
        np.asarray(jax.scipy.special.logsumexp(logits, axis=-1)),
        atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jnp.einsum(
            "bqk,bkd->bqd", jax.nn.softmax(logits, axis=-1), v)),
        atol=2e-5, rtol=2e-5)


def test_nothing_of_the_kernel_is_read_from_the_environment():
    """Block sizes are derived from the lengths; the knobs are gone."""
    import inspect

    source = inspect.getsource(fa)
    assert "environ" not in source and "DSTPU_" not in source
    assert not hasattr(fa, "_lse_2d")
    # 640 is one block walked in rows of 128, 1536 three blocks of 512
    assert (fa._fit_block(fa._BLOCK, 640), fa._fit_block(fa._SUB, 640)) \
        == (640, 128)
    assert fa._fit_block(fa._BLOCK, 1536) == 512


def test_flash_bias_constant_no_grad():
    """The kernel treats bias as constant: its cotangent is zero (a learned
    bias must use the jnp path — functional._pallas_attention_ok guards the
    auto-dispatch accordingly)."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    B, H, S, D = 1, 2, 128, 64
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((B, 1, 1, S)), jnp.float32)
    g = jax.grad(lambda b: flash_attention(
        q, q, q, bias=b, interpret=True).sum())(bias)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_boolean_keypad_mask_dispatches_and_matches():
    """A boolean keep-mask (B,1,1,S) converts to additive bias in-kernel and
    matches the jnp reference path."""
    from deepspeed_tpu.ops.transformer.functional import (
        scaled_dot_product_attention)

    rng = np.random.default_rng(5)
    B, H, S, D = 2, 2, 256, 64
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    mask = np.ones((B, 1, 1, S), bool)
    mask[0, ..., 180:] = False
    mask = jnp.asarray(mask)
    ref = scaled_dot_product_attention(q, q, q, mask=mask, use_pallas=False)
    got = scaled_dot_product_attention(q, q, q, mask=mask, use_pallas=True)
    # compare only unmasked query rows? mask is over KEYS: all rows valid
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# in-kernel counter-based dropout
# ---------------------------------------------------------------------------

def _flash(q, k, v, **kw):
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    return flash_attention(q, k, v, interpret=True, **kw)


def test_dropout_zero_rate_matches_no_dropout():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    base = _flash(q, q, q)
    # rate 0 never builds the seeded path, seed ignored
    same = _flash(q, q, q, dropout_rate=0.0, dropout_seed=123)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(same))


def test_dropout_deterministic_per_seed():
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    a = _flash(q, q, q, dropout_rate=0.3, dropout_seed=5)
    b = _flash(q, q, q, dropout_rate=0.3, dropout_seed=5)
    c = _flash(q, q, q, dropout_rate=0.3, dropout_seed=6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.max(jnp.abs(a - c))) > 1e-4, "seed has no effect"


def test_dropout_mean_preserving():
    """E[dropout(attn)] == attn: average over many seeds approaches the
    undropped output (inverted-scaling check)."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    base = np.asarray(_flash(q, q, q))
    acc = np.zeros_like(base)
    n = 24
    for s in range(n):
        acc += np.asarray(_flash(q, q, q, dropout_rate=0.4,
                                 dropout_seed=1000 + s))
    mean = acc / n
    # per-element agreement is noisy at n=24; the overall scale must match
    np.testing.assert_allclose(mean.mean(), base.mean(), rtol=0.05,
                               atol=0.02)
    np.testing.assert_allclose(
        np.abs(mean).mean(), np.abs(base).mean(), rtol=0.15)


def test_dropout_gradients_match_forward_mask():
    """Finite-difference check: backward regenerates the same keep mask
    the forward used (a mask mismatch fails check_grads immediately)."""
    from jax.test_util import check_grads

    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.standard_normal((1, 1, 128, 64)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 128, 64)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 128, 64)) * 0.3, jnp.float32)

    def f(q, k, v):
        return _flash(q, k, v, dropout_rate=0.25, dropout_seed=42,
                      causal=True).astype(jnp.float32).sum()

    check_grads(f, (q, k, v), order=1, modes=["rev"], rtol=2e-2, atol=2e-2)


def test_dropout_causal_blocks_consistent():
    """Multi-block grid (block 128 over seq 256): dropout + causal combine
    without breaking row normalization: rows with all-kept slots still
    average to the undropped scale across seeds."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, 1, 256, 64)), jnp.float32)
    base = np.asarray(_flash(q, q, q, causal=True, block_q=128, block_k=128))
    acc = np.zeros_like(base)
    n = 16
    for s in range(n):
        acc += np.asarray(_flash(q, q, q, causal=True, dropout_rate=0.3,
                                 dropout_seed=s, block_q=128, block_k=128))
    np.testing.assert_allclose((acc / n).mean(), base.mean(), rtol=0.1,
                               atol=0.03)


def test_dropout_dispatch_from_functional():
    """scaled_dot_product_attention routes dropout to the kernel when a
    rng is provided and use_pallas=True is forced (CPU backend here)."""
    from deepspeed_tpu.ops.transformer.functional import (
        scaled_dot_product_attention)

    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    out = scaled_dot_product_attention(
        q, q, q, causal=True, dropout_rng=jax.random.PRNGKey(0),
        dropout_rate=0.2, use_pallas=True)
    ref = scaled_dot_product_attention(q, q, q, causal=True,
                                       use_pallas=True)
    assert out.shape == q.shape
    assert float(jnp.max(jnp.abs(out - ref))) > 1e-4

def test_dropout_gradients_multiblock():
    """Same FD guard across a multi-block grid: the regenerated masks must
    use the right (q_start, k_start) offsets in BOTH backward sweeps."""
    from jax.test_util import check_grads

    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((1, 1, 256, 64)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 256, 64)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 256, 64)) * 0.3, jnp.float32)

    def f(q, k, v):
        return _flash(q, k, v, dropout_rate=0.25, dropout_seed=7,
                      causal=True, block_q=128, block_k=128)\
            .astype(jnp.float32).sum()

    check_grads(f, (q, k, v), order=1, modes=["rev"], rtol=2e-2, atol=2e-2)


def test_auto_dispatch_chooses_by_lowering_platform():
    """With use_pallas unset and shapes the kernel takes, the choice waits
    for the platform the program is lowered for: on the CPU that is the
    jnp path, bit for bit, and the kernel's branch is never lowered (not
    in interpret mode, it could not be)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 2, 128, 64)
    ref = scaled_dot_product_attention(q, k, v, causal=True,
                                       use_pallas=False)
    auto = jax.jit(lambda q, k, v: scaled_dot_product_attention(
        q, k, v, causal=True))
    np.testing.assert_array_equal(np.asarray(auto(q, k, v)),
                                  np.asarray(ref))
    jaxpr = str(jax.make_jaxpr(auto)(q, k, v))
    assert "platform_index" in jaxpr and "pallas_call" in jaxpr


def _mesh_2x2():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))


@pytest.mark.parametrize("keypad", [False, True])
def test_pallas_dispatch_runs_per_shard_under_a_mesh(keypad):
    """A Mosaic kernel cannot be partitioned by GSPMD, so under a mesh of
    more than one device the dispatch maps the kernel over every mesh axis
    (batch over 'data', heads over 'model'); results and gradients equal
    the unsharded call's."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 2, 2, 128, 64)
    mask = None
    if keypad:
        mask = (jnp.arange(128)[None, :]
                < jnp.asarray([128, 70])[:, None])[:, None, None, :]

    def loss(q, k, v):
        out = scaled_dot_product_attention(q, k, v, mask=mask,
                                           causal=not keypad,
                                           use_pallas=True)
        return out.sum(), out

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    (_, ref), ref_g = grad(q, k, v)
    with jax.set_mesh(_mesh_2x2()):
        jitted = jax.jit(grad)
        assert "shard_map" in str(jax.make_jaxpr(grad)(q, k, v))
        (_, out), out_g = jitted(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)
    for a, b in zip(out_g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_per_shard_dropout_masks_differ_between_shards():
    """Every shard sees the same seed and the same local (batch*head)
    indices; without the shard index folded into the seed the two batch
    rows below (one per 'data' shard, identical inputs) would drop the
    same positions."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 1, 2, 128, 64)
    q, k, v = (jnp.concatenate([t, t]) for t in (q, k, v))

    def f(q, k, v):
        return scaled_dot_product_attention(
            q, k, v, causal=True, dropout_rate=0.5,
            dropout_rng=jax.random.PRNGKey(3), use_pallas=True)

    with jax.set_mesh(_mesh_2x2()):
        out = jax.jit(f)(q, k, v)
    assert not np.allclose(np.asarray(out[0]), np.asarray(out[1]))


def test_auto_dispatch_leaves_what_the_mesh_does_not_divide_to_jnp():
    """The kernel is mapped over the mesh, which needs the batch to split
    over 'data' and the heads over 'model': where they do not, the auto
    path takes the jnp path instead of failing inside shard_map."""
    from deepspeed_tpu.ops.transformer.functional import _pallas_attention_ok
    from deepspeed_tpu.parallel import mesh as mesh_lib

    fits = _rand_qkv(jax.random.PRNGKey(7), 2, 2, 128, 64)
    odd_batch = _rand_qkv(jax.random.PRNGKey(7), 3, 2, 128, 64)
    odd_heads = _rand_qkv(jax.random.PRNGKey(7), 2, 3, 128, 64)
    for qkv in (fits, odd_batch, odd_heads):
        assert _pallas_attention_ok(*qkv, None, None, 0.0)
    with jax.set_mesh(_mesh_2x2()):
        assert _pallas_attention_ok(*fits, None, None, 0.0)
        assert not _pallas_attention_ok(*odd_batch, None, None, 0.0)
        assert not _pallas_attention_ok(*odd_heads, None, None, 0.0)
        assert mesh_lib.shards_evenly((4, 6), ("data", None))
        assert not mesh_lib.shards_evenly((4, 6), (None, ("model", "data")))
        out = jax.jit(lambda q, k, v: scaled_dot_product_attention(
            q, k, v, causal=True))(*odd_batch)
    ref = scaled_dot_product_attention(*odd_batch, causal=True,
                                       use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_dropout_seed_folds_only_the_axes_that_split_the_operands():
    """Along 'data' every shard draws its own mask; along 'pipe' the
    operands are replicas (the kernel is mapped over every mesh axis, the
    layout names only data/model/seq) and must draw the same one."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.ops.transformer.functional import _fold_shard_index
    from deepspeed_tpu.parallel import mesh as mesh_lib

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("pipe", "data"))
    with jax.set_mesh(mesh):
        seeds = jax.jit(mesh_lib.per_shard(
            _fold_shard_index, [P()], P(("pipe", "data"))))(
                jnp.full((1,), 11, jnp.int32))
    seeds = np.asarray(seeds).reshape(2, 2)          # [pipe, data]
    assert (seeds[0] == seeds[1]).all()
    assert seeds[0, 0] != seeds[0, 1] and seeds[0, 0] == 11
