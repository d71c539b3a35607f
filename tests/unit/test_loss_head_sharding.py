"""Where the chunked loss head's chunks are cut (models/api.py).

On a mesh with a 'data' axis the partitioner places, every chip walks chunks
of its own rows' tokens: what crosses 'data' for the head is one (sum, count)
pair a chip forward and the ``wte`` gradient backward. Chunks cut through the
flattened global batch made the partitioner move each chunk's hidden rows (on
the chip: its float32 logits) across 'data' instead. Held here by the
compiled program's collectives, by numerics against one device, and by the
paths that must stay as they were.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models.api import chunked_lm_cross_entropy
from tools.graftlint.hlo_contracts import collective_ops


def _mesh(devices, **axes):
    n = int(np.prod(list(axes.values())))
    return Mesh(np.asarray(devices[:n]).reshape(*axes.values()), tuple(axes))


def _operands(B, T, E, V, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, T, E)), jnp.float32)
    wte = jnp.asarray(rng.standard_normal((V, E)) * 0.2, jnp.float32)
    labels = rng.integers(0, V, (B, T))
    return x, wte, labels


def _loss_and_grads(chunk, valid_vocab=None):
    return jax.value_and_grad(
        lambda x, w, y: chunked_lm_cross_entropy(
            x, w, y, chunk_tokens=chunk, valid_vocab=valid_vocab)[0], (0, 1))


def _token_sized(hlo, forbidden):
    return [(c.op, c.dtype, c.elements) for c in collective_ops(hlo)
            if c.op in ("all-gather", "all-reduce")
            and c.elements in forbidden]


def _head_hlo(devices):
    """Loss and gradients of the head alone: rows over data=4, wte whole."""
    B, T, E, V, chunk = 16, 64, 64, 384, 512
    x, wte, labels = _operands(B, T, E, V)
    mesh = _mesh(devices, data=4)
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    with jax.set_mesh(mesh):
        hlo = jax.jit(_loss_and_grads(chunk)).lower(
            jax.device_put(x, rows), jax.device_put(wte, whole),
            jax.device_put(jnp.asarray(labels), rows)).compile().as_text()
    return hlo, {chunk * V, chunk * E, B * T * E}, V * E


def _engine_hlo(devices):
    """The fused ZeRO-2 step of a tiny GPT-2 over data=4, a chunk (64)
    smaller than a chip's tokens (4 rows x 32)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Model, gpt2_config

    B, T, E, V, chunk = 16, 33, 48, 384, 64
    cfg = gpt2_config("gpt2-125m", n_positions=40, n_layer=2, n_embd=E,
                      n_head=2, vocab_size=V, dtype=jnp.float32,
                      loss_chunk_tokens=chunk)
    engine = deepspeed_tpu.initialize(model=GPT2Model(cfg), config_params={
        "train_batch_size": B, "train_micro_batch_size_per_gpu": B // 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": 4, "allow_partial": True},
        "steps_per_print": 10 ** 9})[0]
    ids = np.random.default_rng(0).integers(0, V, (1, B, T))
    batch = {"input_ids": ids, "labels": ids.copy()}
    assert np.isfinite(float(jax.device_get(engine.train_batch(batch=batch))))
    with jax.set_mesh(engine.mesh):
        hlo = engine._fused_callable().lower(
            engine.state, engine._shard_stacked_batch(batch),
            jnp.float32(1e-3)).compile().as_text()
    n = B * (T - 1)
    return hlo, {chunk * V, chunk * E, n * E, B * T * E,
                 (n // 4) * E, (n // 4) * V}, V * E


@pytest.mark.parametrize("program", [_head_hlo, _engine_hlo])
def test_no_token_sized_collective_crosses_data(eight_devices, program):
    """No all-gather and no all-reduce carries chunk x V, chunk x E or
    tokens x E elements; the ``wte`` gradient (V x E) does cross."""
    hlo, forbidden, wte_elements = program(eight_devices)
    assert not _token_sized(hlo, forbidden), _token_sized(hlo, forbidden)
    assert any(c.elements % wte_elements == 0 and c.elements
               for c in collective_ops(hlo)), "the wte gradient's exchange"


def _uneven_ignored(labels):
    # of two rows a chip: chip 0 counts nothing, chip 1 five tokens, the
    # last chip everything
    labels[:3] = -100
    labels[3, 5:] = -100
    labels[4, ::2] = -100
    return labels


@pytest.mark.parametrize("B,T,chunk,valid_vocab,mark", [
    pytest.param(8, 16, 16, None, _uneven_ignored, id="uneven-ignored"),
    pytest.param(8, 16, 16, 97, None, id="padded-vocab"),
    pytest.param(8, 15, 16, None, None, id="chunk-pads-inside-a-chip"),
    pytest.param(6, 16, 16, None, _uneven_ignored, id="rows-do-not-divide"),
])
def test_four_chips_match_one(eight_devices, B, T, chunk, valid_vocab, mark):
    """Loss, d hidden and d wte on data=4 equal the one-device function's to
    float32 summation order: the global sum over the global count."""
    E, V = 16, 128
    x, wte, labels = _operands(B, T, E, V, seed=1)
    labels = jnp.asarray(mark(labels) if mark else labels)
    fn = _loss_and_grads(chunk, valid_vocab)
    ref_loss, ref_grads = fn(x, wte, labels)
    if mark:
        rows = np.asarray((labels != -100).reshape(B, -1).sum(-1))
        assert len(set(rows.tolist())) > 2, "uneven by construction"

    mesh = _mesh(eight_devices, data=4)
    with jax.set_mesh(mesh):
        jaxpr = str(jax.make_jaxpr(fn)(x, wte, labels))
        assert ("shard_map" in jaxpr) == (B % 4 == 0)
        loss, grads = jax.jit(fn)(x, wte, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for got, ref in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-7)
    if valid_vocab:
        np.testing.assert_array_equal(np.asarray(grads[1][valid_vocab:]), 0.0)


def test_one_device_has_no_shard_map(eight_devices):
    x, wte, labels = _operands(4, 16, 16, 128)
    fn = _loss_and_grads(16)
    assert "shard_map" not in str(jax.make_jaxpr(fn)(x, wte, labels))
    with jax.set_mesh(_mesh(eight_devices, data=1)):
        assert "shard_map" not in str(jax.make_jaxpr(fn)(x, wte, labels))


def test_manual_data_axis_is_not_mapped_again(eight_devices):
    """Under a shard_map that maps 'data' already (the 1-bit Adam wire
    step's situation) the tokens are local: no nested map, no error, and
    each chip's loss is the one-device loss of its own rows."""
    x, wte, labels = _operands(8, 16, 16, 128)
    labels = jnp.asarray(labels)
    mesh = _mesh(eight_devices, data=4)

    def local(x, w, y):
        return chunked_lm_cross_entropy(x, w, y, chunk_tokens=16)[0][None]

    with jax.set_mesh(mesh):
        mapped = jax.shard_map(
            local, in_specs=(P("data"), P(), P("data")), out_specs=P("data"),
            check_vma=False)
        assert str(jax.make_jaxpr(mapped)(x, wte, labels)).count(
            "shard_map") == 1
        losses = jax.jit(mapped)(x, wte, labels)
    want = [float(local(x[i:i + 2], wte, labels[i:i + 2])[0])
            for i in range(0, 8, 2)]
    np.testing.assert_allclose(np.asarray(losses), want, rtol=1e-6)


@pytest.mark.parametrize("other", ["model", "seq"])
def test_axis_beside_data_stays_the_partitioners(eight_devices, other):
    """data=2 beside model=2 (wte over the vocabulary) or seq=2 (hidden
    over the sequence): the loss and gradients equal one device's."""
    x, wte, labels = _operands(4, 16, 16, 128, seed=2)
    labels = jnp.asarray(labels)
    fn = _loss_and_grads(16)
    ref_loss, ref_grads = fn(x, wte, labels)
    mesh = _mesh(eight_devices, **{"data": 2, other: 2})
    x_spec, w_spec = {"model": (P("data"), P("model", None)),
                      "seq": (P("data", "seq"), P())}[other]
    with jax.set_mesh(mesh):
        loss, grads = jax.jit(fn)(
            jax.device_put(x, NamedSharding(mesh, x_spec)),
            jax.device_put(wte, NamedSharding(mesh, w_spec)),
            jax.device_put(labels, NamedSharding(mesh, P("data"))))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for got, ref in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-7)
