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

from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
from deepspeed_tpu.ops.transformer.functional import scaled_dot_product_attention


def _rand_qkv(rng, b, h, s, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,d", [(128, 64), (256, 64)])
def test_flash_forward_matches_reference(causal, s, d):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 2, s, d)
    ref = scaled_dot_product_attention(q, k, v, causal=causal, use_pallas=False)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    s, d = 128, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 2, s, d)

    def loss_ref(q, k, v):
        o = scaled_dot_product_attention(q, k, v, causal=causal, use_pallas=False)
        return jnp.sum(jnp.sin(o))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(jnp.sin(o))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_flash_multiblock_causal_grad():
    # multiple q/k blocks exercises the block-skip logic under causality
    s, d = 256, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 1, s, d)

    def loss_fl(args):
        o = flash_attention(*args, causal=True, block_q=128, block_k=128,
                            interpret=True)
        return jnp.mean(o ** 2)

    def loss_ref(args):
        o = scaled_dot_product_attention(*args, causal=True, use_pallas=False)
        return jnp.mean(o ** 2)

    g_fl = jax.grad(loss_fl)((q, k, v))
    g_ref = jax.grad(loss_ref)((q, k, v))
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, rtol=5e-4)


@pytest.mark.parametrize("kind", ["key", "full"])
def test_flash_bias_matches_reference(kind):
    """Additive bias (HF extended mask / full scores bias) in-kernel must
    match the jnp reference path, forward and q/k/v gradients."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    from deepspeed_tpu.ops.transformer.functional import (
        scaled_dot_product_attention)

    rng = np.random.default_rng(3)
    B, H, S, D = 2, 3, 256, 64
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    if kind == "key":
        # key-padding: mask out the tail keys of each batch row
        bias = np.zeros((B, 1, 1, S), np.float32)
        bias[0, ..., 200:] = -1e9
        bias[1, ..., 100:] = -1e9
    else:
        bias = rng.standard_normal((B, H, S, S)).astype(np.float32)
    bias = jnp.asarray(bias)

    ref = scaled_dot_product_attention(q, k, v, bias=bias, use_pallas=False)
    got = flash_attention(q, k, v, bias=bias, interpret=True,
                          block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)

    def loss_ref(q, k, v):
        return scaled_dot_product_attention(
            q, k, v, bias=bias, use_pallas=False).sum()

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, bias=bias, interpret=True,
                               block_q=128, block_k=128).sum()

    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


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


def test_lse_compact_wire_format_matches(monkeypatch):
    """DSTPU_FLASH_LSE2D=1 carries lse/delta as compact (bh, s_q) tiles
    instead of 128-lane broadcasts; outputs and gradients must be
    bit-identical to the legacy layout (it is pure wire format)."""
    import deepspeed_tpu.ops.transformer.flash_attention as fa

    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)) * 0.3, jnp.float32)

    def run():
        def f(q, k, v):
            return fa.flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128,
                interpret=True).astype(jnp.float32).sum()
        return f(q, k, v), jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    monkeypatch.delenv("DSTPU_FLASH_LSE2D", raising=False)
    base_loss, base_g = run()
    monkeypatch.setenv("DSTPU_FLASH_LSE2D", "1")
    new_loss, new_g = run()
    np.testing.assert_array_equal(np.asarray(base_loss), np.asarray(new_loss))
    for a, b in zip(base_g, new_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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
