"""The served weights are held in the dtype the model computes in
(``serving/decoder.py``, ``hold``): the model states its held tree, the
engine casts to it once at construction and keeps nothing else.

``bf16(w)`` computed once is ``bf16(w)`` computed in every program, so an
engine given the tree as trained (f32) and an engine given the tree already
held serve the same bits, and ``generate()``, which shares the model's
functions and keeps the casts inside its program, computes the same bits
from either tree.  (Engine against ``generate()`` is pinned in f32,
``test_serving.py``: in bf16 on the CPU the chunked and the whole-prompt
programs round differently in places, before this change as after it.)
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models.generation import _prefill, generate
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import engine as serving
from deepspeed_tpu.serving.decoder import decoder_for
from deepspeed_tpu.serving.engine import InferenceEngine
from deepspeed_tpu.serving.fleet import FleetRouter

E, L, V, NPOS = 32, 2, 97, 64
ENGINE = dict(max_slots=3, kv_block_size=4, prefill_chunk=8,
              max_blocks_per_seq=8)


def _toy(dtype=jnp.bfloat16, scan_layers=True):
    model = GPT2Model(GPT2Config(
        vocab_size=V, n_positions=NPOS, n_embd=E, n_layer=L, n_head=4,
        dtype=dtype, scan_layers=scan_layers, loss_chunk_tokens=0))
    ids = np.random.default_rng(0).integers(0, V, (2, 8))
    return model, model.init(jax.random.PRNGKey(0),
                             {"input_ids": ids, "labels": ids})


@pytest.fixture(scope="module", params=[True, False],
                ids=["stacked", "per-layer"])
def toy(request):
    return _toy(scan_layers=request.param)


def _is_layer_norm(path):
    return any(str(k.key).startswith("ln_") for k in path)


def _chunk_logits(engine, tokens):
    """Every row's logits of ONE prefill chunk through the engine's own
    forward (its pool, its masks, the model's block) and the model's head:
    the prefill program's body, keeping the logits it takes the argmax of."""
    dec, bs = engine.dec, engine.bs
    quantized = engine.pool.quantized
    C = len(tokens)
    row = jnp.arange(1, 1 + engine.W, dtype=jnp.int32)     # pages 1..W
    posns = jnp.arange(C)

    @jax.jit
    def run(params, *arrays):
        x = dec.embed(params, jnp.asarray(tokens), posns)[None]
        x, _, _ = serving._forward_groups(
            params, dec, (serving._Group(
                serving._pools_of(arrays, 2, quantized), row[None],
                row[posns // bs], posns % bs),), posns,
            jnp.asarray([C - 1]), x, quantized)
        return dec.logits(params, x[0])

    return np.asarray(run(engine.params, *engine.pool.tensors.arrays))


def _serve(engine, prompts, max_new):
    rids = []
    for p in prompts:
        rids.append(engine.submit(p, max_new_tokens=max_new))
        engine.step()                        # staggered arrivals
    engine.serve(max_steps=500)
    return [np.asarray(engine.result(r)) for r in rids]


def _prompts():
    rng = np.random.default_rng(5)
    shared = rng.integers(0, V, 8).astype(np.int32)       # a shared prefix
    return [np.concatenate([shared, rng.integers(0, V, n).astype(np.int32)])
            for n in (3, 9, 1)]


VARIANTS = {
    "dense": {},
    "prefix_cache": {"prefix_cache": True},
    "speculative": {"speculative": 3},
    "quantize_kv": {"quantize_kv": True},
}


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_given_and_held_trees_serve_the_same_bits(toy, variant):
    model, given = toy
    kwargs = VARIANTS[variant]
    held = decoder_for(model.config).hold(given)
    prompts = _prompts()
    served = [_serve(InferenceEngine(model, tree, **ENGINE, **kwargs),
                     prompts, 7) for tree in (given, held)]
    for a, b in zip(*served):
        np.testing.assert_array_equal(a, b)
    logits = [_chunk_logits(InferenceEngine(model, tree, **ENGINE, **kwargs),
                            prompts[1][:8]) for tree in (given, held)]
    assert logits[0].dtype == np.float32
    np.testing.assert_array_equal(logits[0], logits[1])


def test_generate_computes_the_same_bits_from_either_tree(toy):
    model, given = toy
    held = decoder_for(model.config).hold(given)
    for p in _prompts():
        np.testing.assert_array_equal(
            generate(model, given, p[None], max_new_tokens=7),
            generate(model, held, p[None], max_new_tokens=7))
        a, b = (_prefill(tree, model.config, jnp.asarray(p[None]))
                for tree in (given, held))
        for x, y in zip(a, b):                  # logits, keys, values
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))


def test_held_tree_layer_norm_as_given_the_rest_in_the_compute_dtype(toy):
    model, given = toy
    cfg = model.config
    held = decoder_for(cfg).hold(given)
    assert jax.tree_util.tree_structure(held) \
        == jax.tree_util.tree_structure(given)
    for (path, was), now in zip(
            jax.tree_util.tree_flatten_with_path(given)[0],
            jax.tree_util.tree_leaves(held)):
        assert was.dtype == jnp.float32
        if _is_layer_norm(path):
            assert now is was, jax.tree_util.keystr(path)
        else:
            assert now.dtype == jnp.bfloat16, jax.tree_util.keystr(path)
            np.testing.assert_array_equal(
                np.asarray(now), np.asarray(was.astype(jnp.bfloat16)))
    # by hand: a block is c_attn, c_proj, c_fc, mlp c_proj with biases
    # (2 bytes a value) and two LayerNorms (4); wte is padded to 128 rows
    block = E * 3 * E + 3 * E + E * E + E + E * 4 * E + 4 * E \
        + 4 * E * E + E
    vocab_rows = given["wte"].shape[0]
    assert vocab_rows == 128
    by_hand = 2 * (L * block + (vocab_rows + NPOS) * E) \
        + 4 * (L * 4 * E + 2 * E)
    engine = InferenceEngine(model, given, **ENGINE)
    assert engine.memory_report()["analytic"]["components"][
        "params_bytes"] == by_hand
    assert sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(held)) == by_hand


def test_holding_twice_returns_the_same_arrays(toy):
    model, given = toy
    dec = decoder_for(model.config)
    held = dec.hold(given)
    assert dec.hold(held) is held
    engine = InferenceEngine(model, held, **ENGINE)
    assert engine.params is held
    # and the engine keeps no tree but the held one
    assert not any(l.dtype == jnp.float32 and not _is_layer_norm(path)
                   for path, l in jax.tree_util.tree_flatten_with_path(
                       InferenceEngine(model, given, **ENGINE).params)[0])


def test_a_model_that_computes_in_f32_is_held_as_given():
    model, given = _toy(dtype=jnp.float32)
    assert decoder_for(model.config).hold(given) is given
    assert InferenceEngine(model, given, **ENGINE).params is given


def test_fleet_replicas_share_one_held_tree(toy):
    model, given = toy
    router = FleetRouter(model, given, replicas=3, engine_kwargs=ENGINE)
    trees = [rep.engine.params for rep in router.replicas]
    assert all(tree is trees[0] for tree in trees)
    assert trees[0]["wte"].dtype == jnp.bfloat16
    prompt = np.arange(1, 10, dtype=np.int32)
    rid = router.submit(prompt, max_new_tokens=5)
    router.serve(max_steps=200)
    np.testing.assert_array_equal(
        router.results[rid]["tokens"], _serve(
            InferenceEngine(model, given, **ENGINE), [prompt], 5)[0])


def test_holding_keeps_each_leafs_sharding(eight_devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    model, given = _toy()
    mesh = Mesh(np.array(eight_devices[:2]), ("data",))
    placed = jax.device_put(given, NamedSharding(mesh, P()))
    engine = InferenceEngine(model, placed, **dict(ENGINE, max_slots=4),
                             shards=2, mesh=mesh)
    for was, now in zip(jax.tree_util.tree_leaves(placed),
                        jax.tree_util.tree_leaves(engine.params)):
        assert now.sharding == was.sharding
    prompt = np.arange(1, 10, dtype=np.int32)
    np.testing.assert_array_equal(
        _serve(engine, [prompt], 5)[0],
        _serve(InferenceEngine(model, given, **ENGINE), [prompt], 5)[0])


def test_warm_up_records_the_bytes_held_once(toy):
    model, given = toy
    engine = InferenceEngine(model, given, **ENGINE,
                             telemetry={"trace": True, "mfu": False})
    engine.warmup()
    rid = engine.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    engine.serve(max_steps=100)
    seen = [e for e in engine.telemetry.tracer.events()
            if e["name"] == "served_weight_bytes"]
    assert len(seen) == 1 and seen[0]["ph"] == "X" and seen[0]["dur"] == 0
    assert seen[0]["a0"] == engine.memory_report()["analytic"][
        "components"]["params_bytes"]
    assert engine.results[rid]["status"] == "finished"
