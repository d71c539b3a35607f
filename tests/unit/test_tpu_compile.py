"""Compile-only rehearsals: every Pallas variant the models dispatch, lowered
with interpret=False and compiled by the TPU compiler for a described (not
attached) v5e chip at real widths.  Nothing runs, so this says nothing about
results or times — it catches what interpret mode cannot: block shapes the
Mosaic lowering refuses and memory a kernel may not use."""
import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import deepspeed_tpu.ops.transformer.flash_attention as fa
from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
from deepspeed_tpu.ops.sparse_attention.block_sparse_kernel import (
    SMEM_BYTES, pallas_block_sparse_attention)

GPT2_350M_ATTN = (8, 16, 1024, 64)      # micro-batch 8, 16 heads, seq 1024
BERT_LARGE_ATTN = (8, 16, 512, 64)
SPARSE_ATTN = (2, 12, 4096, 64)


@pytest.fixture(scope="module")
def v5e_host():
    """The four chips of a described v5e:2x2 host, compile cache off: an
    entry written from here cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever this installation raises without libtpu
        pytest.skip(f"no TPU compiler here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def v5e(v5e_host):
    return SingleDeviceSharding(v5e_host[0])


def _kernels_in(fn, args):
    """Number of Pallas kernels in ``fn`` compiled for the described chip."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _grad(attn):
    return jax.grad(lambda *a: attn(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


def _qkv(shape, sharding):
    return (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding),) * 3


# (batch, heads, seq, head size) of a call in the two training cells
TRAIN_PACK1024_ATTN = (16, 16, 1024, 64)        # gpt2-350m: bh = 256
TRAIN_ZERO2_DP4_ATTN = (4, 25, 1024, 64)        # gpt2-xl, a chip: bh = 100

FLASH_CASES = {
    "causal-fwd": (GPT2_350M_ATTN, {}, False),
    "causal-fwd+bwd": (GPT2_350M_ATTN, {}, True),
    "causal-dropout-fwd+bwd": (GPT2_350M_ATTN, {"dropout": 0.1}, True),
    "key-bias-fwd+bwd": (BERT_LARGE_ATTN, {"key_bias": True}, True),
    "train-pack1024-fwd+bwd": (TRAIN_PACK1024_ATTN, {}, True),
    "train-zero2-dp4-fwd+bwd": (TRAIN_ZERO2_DP4_ATTN, {}, True),
}


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_compiles_for_v5e(case, v5e):
    """The kernels under their names, and the row statistics one value a
    row: no (..., seq, 128) f32 operand or result anywhere in the program."""
    shape, opts, backward = FLASH_CASES[case]
    args = _qkv(shape, v5e)
    kw = {"interpret": False}
    if opts.get("key_bias"):
        # the BERT path: an HF extended mask, (B, 1, 1, S_k)
        args += (jax.ShapeDtypeStruct((shape[0], 1, 1, shape[2]),
                                      jnp.float32, sharding=v5e),)
    else:
        kw["causal"] = True
    if opts.get("dropout"):
        args += (jax.ShapeDtypeStruct((1,), jnp.int32, sharding=v5e),)
        kw["dropout_rate"] = opts["dropout"]

    def attn(q, k, v, *extra):
        if opts.get("key_bias"):
            return fa.flash_attention(q, k, v, bias=extra[0], **kw)
        if opts.get("dropout"):
            return fa.flash_attention(q, k, v, dropout_seed=extra[0], **kw)
        return fa.flash_attention(q, k, v, **kw)

    text = jax.jit(_grad(attn) if backward else attn).lower(
        *args).compile().as_text()
    names = ["flash_fwd"] + ["flash_bwd_dkdv", "flash_bwd_dq"] * backward
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert name in text
    assert not re.search(rf"f32\[[0-9,]*{shape[2]},128\]", text)


@pytest.mark.parametrize("key_bias", [False, True],
                         ids=["no-bias", "key-bias"])
@pytest.mark.parametrize("block", [64, 128])
def test_block_sparse_attention_compiles_for_v5e(block, key_bias, v5e):
    B, H, S, _ = SPARSE_ATTN
    layout = np.asarray(
        FixedSparsityConfig(num_heads=H, block=block).make_layout(S))
    args = _qkv(SPARSE_ATTN, v5e)
    if key_bias:
        args += (jax.ShapeDtypeStruct((B, S), jnp.float32, sharding=v5e),)

    def attn(q, k, v, *kb):
        return pallas_block_sparse_attention(
            q, k, v, layout, block, key_bias=kb[0] if kb else None,
            interpret=False)

    assert _kernels_in(_grad(attn), args) == 3


@pytest.mark.parametrize("kernel", ["flash", "flash-dropout-data2-model2",
                                    "block-sparse-key-bias"])
def test_kernels_compile_inside_a_four_chip_program(kernel, v5e_host,
                                                    monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: inside a program over four
    chips the dispatch has to map it over the mesh, or the lowering refuses
    the whole step ("Mosaic kernels cannot be automatically partitioned").
    Attention's dispatch chooses by the platform it is lowered for; the
    sparse one reads the default backend, so the test answers 'tpu'."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.sparse_attention import block_sparse_attention
    from deepspeed_tpu.ops.transformer.functional import (
        scaled_dot_product_attention)
    from deepspeed_tpu.parallel.mesh import AXIS_ORDER

    shape = (1, 2, 1, 2) if kernel.endswith("data2-model2") else (1, 4, 1, 1)
    mesh = Mesh(np.asarray(v5e_host).reshape(shape), AXIS_ORDER)
    batch_sharded = NamedSharding(mesh, P("data"))
    if kernel == "flash":
        args = _qkv((32,) + GPT2_350M_ATTN[1:], batch_sharded)

        def attn(q, k, v):
            return scaled_dot_product_attention(q, k, v, causal=True)
    elif kernel == "flash-dropout-data2-model2":
        # heads over 'model' as well, the shard's index folded into the seed
        args = _qkv((16,) + GPT2_350M_ATTN[1:], batch_sharded)

        def attn(q, k, v):
            return scaled_dot_product_attention(
                q, k, v, causal=True, dropout_rate=0.1,
                dropout_rng=jax.random.PRNGKey(0))
    else:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        B, H, S, D = 4, 12, 4096, 64
        layout = np.asarray(
            FixedSparsityConfig(num_heads=H, block=64).make_layout(S))
        args = _qkv((B, H, S, D), batch_sharded) + (
            jax.ShapeDtypeStruct((B, S), jnp.float32,
                                 sharding=batch_sharded),)

        def attn(q, k, v, kpm):
            return block_sparse_attention(q, k, v, layout, 64,
                                          key_padding_mask=kpm,
                                          use_pallas=True)

    with jax.set_mesh(mesh):
        assert _kernels_in(_grad(attn), args) == 3


SERVE_CHAT = dict(slots=28, pages=64, block=16, chunk=256)   # the chat cell


def _served_shapes(model, struct, held=True):
    """The model's parameters as shapes, as the engine holds them: through
    the model's own ``hold`` (``serving/decoder.py``), nothing run
    (``held=False``: as a trainer leaves them, f32)."""
    from deepspeed_tpu.serving.decoder import decoder_for

    ids = np.zeros((1, 8), np.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            {"input_ids": ids, "labels": ids})
    if held:
        params = jax.eval_shape(decoder_for(model.config).hold, params)
    return jax.tree_util.tree_map(lambda l: struct(l.shape, l.dtype), params)


@functools.lru_cache(maxsize=None)      # two tests read each program
def _chat_program(program, quantized, v5e, held=True):
    """One serving program at the chat cell's sizes and gpt2-350m widths,
    compiled for the described chip with arguments as the engine holds
    them (``held=False``: as a trainer leaves them, f32): ``(compiled,
    cfg, params, pool tensors)``."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving.kv_cache import pool_shapes

    S, W, bs, C = (SERVE_CHAT[k] for k in ("slots", "pages", "block",
                                           "chunk"))
    model = GPT2Model(GPT2Config(
        vocab_size=50257, n_positions=1024, n_embd=1024,
        n_layer=2 if quantized else 24, n_head=16, dtype=jnp.bfloat16,
        scan_layers=True))
    cfg = model.config

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = _served_shapes(model, struct, held)
    store = jnp.int8 if quantized else cfg.dtype
    tensors = [struct(shape, dtype) for shape, dtype in
               zip(pool_shapes(cfg, 1 + S * W, bs, quantized),
                   (store, store, jnp.float32, jnp.float32))
               if shape is not None]
    if program == "decode":
        jitted = serving._make_decode_step(cfg, W, bs, quantized, 0.0, 0,
                                           0.0, None, "data")
        streams = [struct((S, W), jnp.int32), struct((S,), jnp.int32),
                   struct((S,), jnp.int32), struct((S,), jnp.bool_),
                   struct((S,), jnp.int32), struct((S,), jnp.float32)]
    else:
        jitted = serving._make_prefill_chunk(
            cfg, C, W, bs, quantized, program.endswith("final"), 0.0, 0,
            0.0, None, "data")
        streams = [struct((1, W), jnp.int32), struct((C,), jnp.int32),
                   struct((), jnp.int32), struct((1,), jnp.int32),
                   struct((), jnp.int32)]
    compiled = jitted.lower(params, *tensors, *streams).compile()
    return compiled, cfg, params, tensors


CHAT_PROGRAMS = ["decode", "prefill256", "prefill256-final"]


@pytest.mark.parametrize("program", CHAT_PROGRAMS)
def test_serving_programs_cast_no_weight(program, v5e):
    """The engine holds GPT-2's weights in the dtype its programs compute
    in (``GPT2Decoder.hold``), so no program of the chat cell casts one:
    no ``convert`` whose result is a bf16 array of a weight's shape, and
    the arguments are 0.71 GB smaller than the f32 tree's (which every
    program used to read whole, 1.42 GB, and write again at half the
    width, before its first matmul).  LayerNorm's leaves stay f32."""
    import re

    compiled, cfg, params, tensors = _chat_program(program, False, v5e)
    L, E, V = cfg.n_layer, cfg.n_embd, params["wte"].shape[0]
    assert V == 50304
    weights = [(L, 4 * E, E), (L, E, 4 * E), (L, E, 3 * E), (L, E, E),
               (V, E)]
    # the program's own instructions, whose results lie in memory (a
    # fusion's inner instructions do not: the one-row head is a fused
    # multiply and sum that rounds its products as it goes)
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    casts = [line.strip()[:120] for line in entry.splitlines()
             if "convert" in line and any(
                 re.search(rf"= bf16\[{','.join(map(str, dims))}\]", line)
                 for dims in weights)]
    assert not casts, f"{program} casts a weight it holds: {casts}"
    cast_values = sum(l.size for l in jax.tree_util.tree_leaves(params)
                      if l.dtype == jnp.bfloat16)
    assert {jax.tree_util.keystr(path) for path, l in
            jax.tree_util.tree_flatten_with_path(params)[0]
            if l.dtype == jnp.float32} == {
        f"['{at}']['{leaf}']" if at == "ln_f"
        else f"['h']['block']['{at}']['{leaf}']"
        for at in ("ln_1", "ln_2", "ln_f") for leaf in ("scale", "bias")}
    assert 0.70e9 < 2 * cast_values < 0.72e9    # what an f32 tree adds
    held = sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(params))
    # what the compiler takes as arguments: the held tree, the pool and
    # the streams
    pool = sum(t.size * t.dtype.itemsize for t in tensors)
    args = compiled.memory_analysis().argument_size_in_bytes
    assert abs(args - held - pool) < 1e5, (args, held, pool)


@pytest.mark.parametrize("pool", [
    "bf16",
    pytest.param("int8", marks=pytest.mark.xfail(
        strict=True,
        reason="the int8 K and V are updated in place, but both f32 scale "
               "tensors (minor dim 16 heads, padded to 128 lanes) are "
               "relaid on the way in and out: PERF.md section 7"))])
@pytest.mark.parametrize("program", CHAT_PROGRAMS)
def test_serving_programs_update_the_pool_in_place(program, pool, v5e):
    """The KV pool's layout (``kv_cache.pool_shapes``) is the one the
    compiler runs the per-layer scatter and page gather in: at the chat
    cell's sizes and gpt2-350m widths no program relays a pool tensor (no
    ``copy`` of a pool tensor's shape), the temporaries stay under ONE
    pool tensor and the donated pool comes back in the same buffers.  The
    bf16 pool is compiled at the cell's 24 layers, as the cell runs it;
    the int8 pool, which no cell runs, at 2 layers and for the copies
    alone (they sit at a program's edge, whatever the depth).

    Nor does a bf16 program hold one layer of the pool on its own (ONE
    gather over (layer, page), ``_pool_view``), and the decode program
    builds no view at all: the paged kernel reads the pool where it lies."""
    import re

    from tools.graftlint import hlo_contracts as hc

    S, W, bs = (SERVE_CHAT[k] for k in ("slots", "pages", "block"))
    quantized = pool == "int8"
    compiled, cfg, _, tensors = _chat_program(program, quantized, v5e)
    text = compiled.as_text()

    shapes = {",".join(str(d) for d in t.shape) for t in tensors}
    relays = [line.strip()[:120] for line in text.splitlines()
              if any(re.search(rf"= \w+\[{dims}\]\S* copy\(", line)
                     for dims in shapes)]
    assert not relays, f"{program} relays the {pool} pool: {relays}"
    if quantized:
        return
    one_pool_tensor = tensors[0].size * tensors[0].dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < one_pool_tensor

    def made(*dims):
        """The instructions whose result has this shape."""
        shape = ",".join(str(d) for d in dims)
        return [line.strip()[:120] for line in text.splitlines()
                if re.search(rf"= \(?\w+\[{shape}\]", line)]

    _, NB, _, HD = tensors[0].shape
    assert not made(NB, bs, HD), f"{program} holds a layer of the pool"
    if program == "decode":
        H = cfg.n_head
        assert not made(S, W * bs, H, HD // H), "decode relays a page view"
        assert not made(S * W, bs, HD), "decode gathers every lane's pages"
        assert "paged_decode_attn" in text
    else:
        assert "paged_decode_attn" not in text
    # the pool tensors are the leading outputs; parameter numbers shift
    # with the weights a program leaves unused, output numbers do not
    assert hc.aliased_outputs(text) >= set(range(len(tensors)))


def _longcat_cell():
    from deepspeed_tpu.models.longcat_flash import (LongCatFlashConfig,
                                                    LongCatFlashModel)
    return LongCatFlashModel(LongCatFlashConfig(
        num_layers=2, vocab_size=16384, experts_held=(0, 16),
        pallas_interpret=False))


def _mistral4_cell():
    from deepspeed_tpu.models.mistral4 import Mistral4Config, Mistral4Model
    return Mistral4Model(Mistral4Config(
        num_hidden_layers=5, vocab_size=32768, experts_held=(0, 32),
        pallas_interpret=False))


# the model as its cell cuts it; slots, pages a lane, rows a page, the
# prefill chunk; the pool's tensors and the lanes a row is stored in
LATENT_CELLS = {
    "longcat": (_longcat_cell, (64, 26, 64, 1024), 2, 640),
    "mistral4": (_mistral4_cell, (16, 388, 64, 2048), 1, 384),
}


@pytest.mark.parametrize("cell,program", [
    pytest.param("longcat", "decode", id="decode"),
    pytest.param("longcat", "prefill", id="prefill1024"),
    pytest.param("mistral4", "decode", id="mistral4-decode"),
    pytest.param("mistral4", "prefill", id="mistral4-prefill2048")])
def test_two_raw_rows_a_block_are_updated_in_the_donated_pool(cell, program,
                                                              v5e):
    """LongCat-Flash's double block caches TWO latent rows a token, 576
    values each: ``kv_cache.pool_shapes`` states them as two tensors of 640
    stored lanes (whole 128-lane tiles), and at the published widths and
    the cell's sizes (64 slots x 26 pages of 64, chunks of 1,024; two of
    the four blocks, one traced block either way) no program relays either
    tensor, holds a layer of it on its own, or returns the pool in other
    buffers than the donated ones.  Stored unpadded, 576 wide, the compiler
    unpads and pads the whole pool around every program (PR 30).

    ``mistral-small-4-ep4``'s ONE row of 320 stored as 384 likewise, at its
    cell's sizes (16 slots x 388 pages of 64, chunks of 2,048, five layers
    of 32 held experts): both models' decode goes through
    ``cache.attend_rows`` and reads each lane's filled pages in place."""
    import re

    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving.kv_cache import pool_shapes
    from tools.graftlint import hlo_contracts as hc

    build, (S, W, bs, C), n_tensors, stored = LATENT_CELLS[cell]
    model = build()
    cfg = model.config

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree_util.tree_map(
        lambda l: struct(l.shape, l.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    shapes = [shape for shape in pool_shapes(cfg, 1 + S * W, bs, False)
              if shape is not None]
    k = (cfg.n_layer, 1 + S * W, bs, stored)
    assert shapes == [k] * n_tensors
    tensors = [struct(k, cfg.dtype)] * n_tensors
    if program == "decode":
        jitted = serving._make_decode_step(cfg, W, bs, False, 0.0, 0, 0.0,
                                           None, "data")
        streams = [struct((S, W), jnp.int32)] + [
            struct((S,), dtype) for dtype in
            (jnp.int32, jnp.int32, jnp.bool_, jnp.int32, jnp.float32)]
    else:
        jitted = serving._make_prefill_chunk(cfg, C, W, bs, False, False,
                                             0.0, 0, 0.0, None, "data")
        streams = [struct((1, W), jnp.int32), struct((C,), jnp.int32),
                   struct((), jnp.int32), struct((1,), jnp.int32),
                   struct((), jnp.int32)]
    text = jitted.lower(params, *tensors, *streams).compile().as_text()
    dims = ",".join(str(d) for d in k)
    relays = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"= \w+\[{dims}\]\S* copy\(", line)]
    assert not relays, f"{program} relays the pool: {relays}"
    layer = ",".join(str(d) for d in k[1:])
    assert not [line for line in text.splitlines()
                if re.search(rf"= \(?\w+\[{layer}\]", line)], \
        f"{program} holds a layer of the pool"
    assert hc.aliased_outputs(text) >= set(range(n_tensors))
    # nor a weight: the stacks are read where they lie, a matrix a slice
    # (LongCat's ``q_b``, ``kv_a``, ``kv_b`` are held (out, in) for that)
    stacks = {",".join(map(str, l.shape)) for l in
              jax.tree_util.tree_leaves(params) if l.ndim >= 3}
    copied = [line.strip()[:120] for line in text.splitlines()
              if any(re.search(rf"= bf16\[{dims}\]\S* (copy|fusion)\(", line)
                     for dims in stacks)]
    assert not copied, f"{program} copies a stack of weights: {copied}"
    # the kernels take heads of (128 | 64) and the stored row as they are
    assert "moe_grouped_matmul_" + program in text
    assert ("mla_prefill_attn" in text) == (program != "decode")
    # decode reads each lane's filled pages where they lie: no view of
    # every lane's W pages is gathered (nor selected against its mask)
    assert ("paged_latent_decode_attn" in text) == (program == "decode")
    if program == "decode":
        view = re.compile(rf"= \(?\w+\[({S},{W * bs}|{W * bs},{S}"
                          rf"|{S * W},{bs}),{stored}\]")
        made = [line.strip()[:120] for line in text.splitlines()
                if view.search(line)]
        assert not made, f"decode gathers every lane's pages: {made}"


def _pallas_grids(jaxpr):
    """``{kernel name: [(grid, the VMEM limit it asks for or None), a call
    site each]}`` of every ``pallas_call`` of a traced program, the loops'
    and the jits' bodies included."""
    from jax._src import core

    grids = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            asked = eqn.params["compiler_params"]["mosaic_tpu"]
            grids.setdefault(eqn.params["name"], []).append(
                (tuple(eqn.params["grid_mapping"].grid),
                 asked.vmem_limit_bytes))
        for inner in core.jaxprs_in_params(eqn.params):
            for name, found in _pallas_grids(inner).items():
                grids.setdefault(name, []).extend(found)
    return grids


_MELLUM_CELL = (32, 518, 64, 2048)     # slots, pages a lane, page, chunk
_mellum_programs = {}


def _mellum_program(program, v5e):
    """Mellum's decode or chunk program at the published widths and the
    cell's sizes (two periods of three sliding layers and a full one),
    compiled for the described chip once a session: ``(compiled, its text,
    pool shapes, the grids of its kernels, params)``."""
    if program in _mellum_programs:
        return _mellum_programs[program]
    from deepspeed_tpu.models.mellum import MellumConfig, MellumModel
    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving.kv_cache import pool_shapes

    S, W, bs, C = _MELLUM_CELL
    cfg = MellumConfig(num_hidden_layers=8, pallas_interpret=False)
    model = MellumModel(cfg)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree_util.tree_map(
        lambda l: struct(l.shape, l.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    blocks = serving.default_pool_blocks(cfg, 1, S, W, bs, C)
    widths = serving.group_table_widths(cfg, W, bs, C)
    assert blocks == [1 + S * W, 1 + S * 17 + 32] \
        and widths == [(W, W), (17, 49)]
    shapes = [pool_shapes(cfg, n, bs, False, g)[:2]
              for g, n in enumerate(blocks)]
    assert shapes == [((2, blocks[0], bs, 512),) * 2,
                      ((6, blocks[1], bs, 512),) * 2]
    tensors = [struct(shape, cfg.dtype) for pair in shapes for shape in pair]
    if program == "decode":
        jitted = serving._make_decode_step(cfg, W, bs, False, 0.0, 0, 0.0,
                                           None, "data")
        streams = [((struct((S, W), jnp.int32), struct((S, 17), jnp.int32)),
                    (None, struct((S,), jnp.int32)))] + [
            struct((S,), dtype) for dtype in
            (jnp.int32, jnp.int32, jnp.bool_, jnp.int32, jnp.float32)]
    else:
        jitted = serving._make_prefill_chunk(cfg, C, W, bs, False, False,
                                             0.0, 0, 0.0, None, "data")
        streams = [((struct((1, W), jnp.int32), struct((1, 49), jnp.int32)),
                    (None, struct((1,), jnp.int32))),
                   struct((C,), jnp.int32), struct((), jnp.int32),
                   struct((1,), jnp.int32), struct((), jnp.int32)]
    traced = jitted.trace(params, *tensors, *streams)
    compiled = traced.lower().compile()
    _mellum_programs[program] = (compiled, compiled.as_text(), shapes,
                                 _pallas_grids(traced.jaxpr), params)
    return _mellum_programs[program]


@pytest.mark.parametrize("program", ["decode", "prefill2048"])
def test_two_cache_groups_are_updated_in_the_donated_pools(program, v5e):
    """Mellum's sliding and full layers cache in TWO groups
    (``kv_cache.cache_groups``), each with its own pool tensors: at the
    published widths and the cell's sizes (32 slots x 518 pages of 64,
    chunks of 2,048; the cell's two periods, one traced period)
    both groups' scatters update the donated pools in place, no program
    relays a pool tensor or holds a layer of one on its own, none copies a
    stack of weights, and under the scan a kernel is ONE operation a KIND
    of layer: the sliding layers are a loop of their own inside the
    period."""
    from tools.graftlint import hlo_contracts as hc

    S, W, bs, _ = _MELLUM_CELL
    compiled, text, shapes, _, params = _mellum_program(program, v5e)
    for pair in shapes:
        dims = ",".join(str(d) for d in pair[0])
        relays = [line.strip()[:120] for line in text.splitlines()
                  if re.search(rf"= \w+\[{dims}\]\S* copy\(", line)]
        assert not relays, f"{program} relays a pool: {relays}"
        layer = ",".join(str(d) for d in pair[0][1:])
        assert not [line for line in text.splitlines()
                    if re.search(rf"= \(?\w+\[{layer}\]", line)], \
            f"{program} holds a layer of a pool"
    # all four pool tensors come back in the buffers that were donated
    assert hc.aliased_outputs(text) >= {0, 1, 2, 3}
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
    stacks = {",".join(map(str, l.shape)) for l in
              jax.tree_util.tree_leaves(params) if l.ndim >= 3}
    copied = [line.strip()[:120] for line in text.splitlines()
              if any(re.search(rf"= bf16\[{dims}\]\S* (copy|fusion)\(", line)
                     for dims in stacks)]
    assert not copied, f"{program} copies a stack of weights: {copied}"
    # one operation a kernel and KIND of layer, under its stable name
    kind = "decode" if program == "decode" else "prefill"
    attn = "gqa_paged_decode_attn" if program == "decode" \
        else "gqa_prefill_attn"
    assert _custom_calls(text) == sorted(
        [f"{attn}_full", f"{attn}_window"]
        + [f"moe_grouped_matmul_{kind}_{call}" for call in ("up", "down")]
        * 2)
    if program == "decode":
        # both groups' pages are read where they lie: no view of every
        # lane's pages is gathered (2.2 GB a full layer)
        view = re.compile(rf"= \w+\[{S},({W * bs}|{17 * bs}),")
        made = [line.strip()[:120] for line in text.splitlines()
                if view.search(line)]
        assert not made, f"decode gathers every lane's pages: {made}"


_MOTIF_CELL = (48, 776, 64, 2048)      # slots, pages a lane, page, chunk
_motif_programs = {}


def _motif_program(program, v5e):
    """Motif's decode or chunk program at the published widths and the
    cell's sizes (one dense and four routed layers), compiled for the
    described chip once a session: ``(compiled, its text, pool shapes, the
    grids of its kernels)``."""
    if program in _motif_programs:
        return _motif_programs[program]
    from deepspeed_tpu.models.motif import MotifConfig, MotifModel
    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving.kv_cache import pool_shapes

    S, W, bs, C = _MOTIF_CELL
    cfg = MotifConfig(num_hidden_layers=5, layers_held=(1, 4, 5, 6, 7),
                      experts_held=(0, 48), vocab_size=27520,
                      pallas_interpret=False)
    model = MotifModel(cfg)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree_util.tree_map(
        lambda l: struct(l.shape, l.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    blocks = serving.default_pool_blocks(cfg, 1, S, W, bs, C)
    widths = serving.group_table_widths(cfg, W, bs, C)
    assert blocks == [1 + S * W, 1 + S * 3 + 32] \
        and widths == [(W, W), (3, 35)]
    shapes = [pool_shapes(cfg, n, bs, False, g)[0]
              for g, n in enumerate(blocks)]
    assert shapes == [(1, blocks[0], bs, 640), (4, blocks[1], bs, 640)]
    tensors = [struct(shape, cfg.dtype) for shape in shapes]
    if program == "decode":
        jitted = serving._make_decode_step(cfg, W, bs, False, 0.0, 0, 0.0,
                                           None, "data")
        streams = [((struct((S, W), jnp.int32), struct((S, 3), jnp.int32)),
                    (None, struct((S,), jnp.int32)))] + [
            struct((S,), dtype) for dtype in
            (jnp.int32, jnp.int32, jnp.bool_, jnp.int32, jnp.float32)]
    else:
        jitted = serving._make_prefill_chunk(cfg, C, W, bs, False, False,
                                             0.0, 0, 0.0, None, "data")
        streams = [((struct((1, W), jnp.int32), struct((1, 35), jnp.int32)),
                    (None, struct((1,), jnp.int32))),
                   struct((C,), jnp.int32), struct((), jnp.int32),
                   struct((1,), jnp.int32), struct((), jnp.int32)]
    traced = jitted.trace(params, *tensors, *streams)
    compiled = traced.lower().compile()
    _motif_programs[program] = (compiled, compiled.as_text(), shapes,
                                _pallas_grids(traced.jaxpr))
    return _motif_programs[program]


def _custom_calls(text):
    """The names of a compiled program's Mosaic calls, a call site each."""
    import re

    return sorted(re.match(r"\s*%(\S+?)(?:\.\d+)? = ", line).group(1)
                  for line in text.splitlines() if "tpu_custom_call" in line)


@pytest.mark.parametrize("program", ["decode", "prefill2048"])
def test_raw_rows_in_a_window_group_are_updated_in_the_donated_pools(
        program, v5e):
    """Motif's sliding and full layers cache RAW LATENT ROWS in two groups:
    at the published widths and the cell's sizes (48 slots x 776 pages of
    64, chunks of 2,048; one dense and four routed layers) both groups'
    scatters update the donated pools in place, no program relays a pool
    tensor, and a kernel is ONE operation a RUN of layers of one kind: the
    window's attention twice (the dense layer, the scan of three routed
    sliding layers), the full layer's once, under the names the model
    gives them; decode reads both groups' pages where they lie (the
    window's from each lane's first visible row)."""
    import re

    from tools.graftlint import hlo_contracts as hc

    S, W, bs, _ = _MOTIF_CELL
    compiled, text, shapes, _ = _motif_program(program, v5e)
    for shape in shapes:
        dims = ",".join(str(d) for d in shape)
        relays = [line.strip()[:120] for line in text.splitlines()
                  if re.search(rf"= \w+\[{dims}\]\S* copy\(", line)]
        assert not relays, f"{program} relays a pool: {relays}"
    assert hc.aliased_outputs(text) >= {0, 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 0.9e9
    kind = "decode" if program == "decode" else "prefill"
    attn = "gdla_paged_decode_attn" if program == "decode" \
        else "gdla_prefill_attn"
    # a chunk that is not a prompt's last has no use for the last layer's
    # feed-forward: its experts' matmuls are not in the program
    assert [call for call in _custom_calls(text)
            if not call.startswith("mhc_")] == sorted(
        [f"{attn}_full"] + [f"{attn}_window"] * 2
        + [f"moe_grouped_matmul_{kind}_{call}" for call in ("up", "down")]
        * (2 if program == "decode" else 1))
    if program == "decode":
        view = re.compile(rf"= \w+\[{S},({W * bs}|{3 * bs}),")
        made = [line.strip()[:120] for line in text.splitlines()
                if view.search(line)]
        assert not made, f"decode gathers every lane's pages: {made}"


@pytest.mark.parametrize("model", ["motif", "mellum"])
def test_a_window_prefill_call_walks_a_band(model, v5e):
    """The chunk program of a model with sliding layers, at the cell's
    sizes (a chunk of 2,048): a window's rectangle call is ONE grid step a
    (key head, query block), no key dimension, no running state, under the
    name its roofline reader finds it by; the full layer's keeps its key
    grid; and both fit the chip's default scoped VMEM of 16 MB (neither
    asks for more, and the compile for the described chip passed)."""
    program, attn, key_heads, block_q, sites = {
        "motif": (_motif_program, "gdla_prefill_attn", 16, 128, 2),
        "mellum": (_mellum_program, "gqa_prefill_attn", 4, 64, 1)}[model]
    _, text, _, grids = program("prefill2048", v5e)[:4]
    calls = _custom_calls(text)
    assert calls.count(f"{attn}_window") == sites \
        and calls.count(f"{attn}_full") == 1, calls
    assert grids[f"{attn}_window"] == [((key_heads, 2048 // block_q), None)] \
        * sites
    ((full, asked),) = grids[f"{attn}_full"]
    assert len(full) == 3 and full[2] > 1 and asked is None, full


@pytest.mark.parametrize("program", ["decode", "prefill2048"])
def test_the_residual_mixes_are_two_kernels_and_no_f32_copy_of_the_streams(
        program, v5e):
    """Every sublayer of Motif's programs mixes its four streams through
    ``mhc_pre_mix`` and ``mhc_post_mix`` (a call site a traced sublayer:
    the dense layer's two, the scanned run's two, the full layer's two),
    and NO operation of either program writes the streams' shape in
    float32: the copy the ``jax.numpy`` mixes wrote and read back (134 MB
    at 2,048 tokens, twice a sublayer) is what the kernels remove."""
    import re

    S, _, _, C = _MOTIF_CELL
    _, text, _, _ = _motif_program(program, v5e)
    mixes = [call for call in _custom_calls(text) if call.startswith("mhc_")]
    # the last sublayer of a chunk that is not a prompt's last: its error
    # is counted, its feed-forward and the streams it would leave are not
    assert mixes == ["mhc_post_mix"] * (6 if program == "decode" else 5) \
        + ["mhc_pre_mix"] * 6, mixes
    # what an operation WRITES (inside a fusion XLA may widen a bf16 sum
    # in registers: the engine's own add over the streams does)
    streams = re.compile(
        rf"= (\(.*)?f32\[{S if program == 'decode' else C},16384\]")
    wide, fused = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split("(")[0]
        elif not fused and streams.search(line.split(" metadata=")[0]):
            wide.append(line.strip()[:120])
    assert not wide, f"{program} writes the streams in f32: {wide}"


def test_decode_program_keeps_the_view_where_pages_are_not_whole_tiles(v5e):
    """gpt2-xl's row of 25 heads x 64 is 12.5 lanes wide: Mosaic would
    refuse to slice it, so the engine, which sees the pool's shape, builds
    the one-gather view there and the program still compiles."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving.kv_cache import pool_shapes

    S, W, bs = 8, 16, 16
    model = GPT2Model(GPT2Config(
        vocab_size=50257, n_positions=1024, n_embd=1600, n_layer=1,
        n_head=25, dtype=jnp.bfloat16, scan_layers=True))
    cfg = model.config

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = _served_shapes(model, struct)
    tensors = [struct(shape, cfg.dtype) for shape in
               pool_shapes(cfg, 1 + S * W, bs, False) if shape is not None]
    streams = [struct((S, W), jnp.int32)] + [
        struct((S,), dtype) for dtype in
        (jnp.int32, jnp.int32, jnp.bool_, jnp.int32, jnp.float32)]
    jitted = serving._make_decode_step(cfg, W, bs, False, 0.0, 0, 0.0, None,
                                       "data")
    text = jitted.lower(params, *tensors, *streams).compile().as_text()
    assert "paged_decode_attn" not in text


def test_sharded_decode_program_runs_the_paged_kernel_on_each_chip(v5e_host):
    """``shards=4``: the decode program is a shard_map over the slot and
    block axes, and each chip's part attends ITS lanes' pages in ITS blocks
    of the pool with the paged kernel (a Mosaic kernel that GSPMD met
    outside a shard_map would be refused)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.serving import engine as serving
    from deepspeed_tpu.serving.kv_cache import pool_shapes

    S, W, bs = (SERVE_CHAT[k] for k in ("slots", "pages", "block"))
    mesh = Mesh(np.asarray(v5e_host), ("data",))
    model = GPT2Model(GPT2Config(
        vocab_size=50257, n_positions=1024, n_embd=1024, n_layer=2,
        n_head=16, dtype=jnp.bfloat16, scan_layers=True))
    cfg = model.config

    def struct(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    params = _served_shapes(model, struct)
    assert params["wte"].dtype == jnp.bfloat16
    tensors = [struct(shape, cfg.dtype, None, "data") for shape in
               pool_shapes(cfg, 4 + S * W, bs, False) if shape is not None]
    streams = [struct((S, W), jnp.int32, "data")] + [
        struct((S,), dtype, "data") for dtype in
        (jnp.int32, jnp.int32, jnp.bool_, jnp.int32, jnp.float32)]
    jitted = serving._make_decode_step(cfg, W, bs, False, 0.0, 0, 0.0, mesh,
                                       "data")
    text = jitted.lower(params, *tensors, *streams).compile().as_text()
    assert "paged_decode_attn" in text


def test_lut_beyond_smem_fails_at_trace_time():
    """The reference's default block 16 at S 4096: the transpose LUT of the
    dk/dv sweep needs 3 MiB of scalar memory.  That must surface while
    tracing, naming the limit and the block — not inside the compiler."""
    B, H, S, D = SPARSE_ATTN
    layout = np.asarray(
        FixedSparsityConfig(num_heads=H, block=16).make_layout(S))
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16)

    def attn(q, k, v):
        return pallas_block_sparse_attention(q, k, v, layout, 16,
                                             interpret=False)

    with pytest.raises(ValueError, match=rf"block size 16.*{SMEM_BYTES}"):
        jax.eval_shape(_grad(attn), q, q, q)
    # the forward's own table is smaller and fits
    assert jax.eval_shape(attn, q, q, q).shape == (B, H, S, D)
