"""Mistral 4 (``model_type: mistral4``) for the benchmark: how to build the
program's model from a configuration file, the plain reference the program
is held to, the rule its served tokens are held by, and the arithmetic
(parameters, bytes a decode step, operations and bytes of the two new
kernels) the utilisation metrics divide by.

The reference follows the published ``config.json`` keys of
``mistralai/Mistral-Small-4-119B-2603`` and the DeepSeek-V3 family's
convention that they follow: pre-RMSNorm blocks of latent attention (queries
through a rank-``q_lora_rank`` bottleneck with its own RMSNorm; keys and
values expanded from a normed rank-``kv_lora_rank`` latent, one rotary key
shared by all heads; interleaved RoPE with YaRN frequencies; softmax scale
``qk_head_dim**-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)**2``; the query
scaled by ``1 + llama_4_scaling_beta * ln(1 + floor(pos / original_max))``)
and of a routed SwiGLU feed-forward (softmax over all experts in f32, the
``num_experts_per_tok`` largest, renormalised, times
``routed_scaling_factor``) beside one shared expert; final RMSNorm; untied
head.  Written in jax.numpy in float32 under
``jax.default_matmul_precision("highest")``, with no kernel, no cache, no
batching and no absorption, and importing nothing from ``deepspeed_tpu``.
It is computed a layer at a time, a block of query rows at a time (rows of a
softmax do not meet) and an expert at a time, so that it fits beside the
served weights on the chip.

Departures from the source, each by the configuration's own statement:
the vision tower is absent (the catalog gives no size for it); only the
experts this chip holds are computed (``n_routed_experts_held``; the others
would add their part on the chips that hold them) and only the held quarter
of the vocabulary exists.  ``assumed`` in the configuration file lists what
the published config has no key for.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# the program's model, built from the configuration file
# ---------------------------------------------------------------------------
def build_model(config, overrides):
    """The program's ``Mistral4Model`` at the file's sizes.  ``overrides``
    are the job's settings of the program, never a size."""
    from deepspeed_tpu.models.mistral4 import Mistral4Config, Mistral4Model

    rope = config["rope_parameters"]
    if rope["rope_type"] != "yarn" or not config["rope_interleave"] \
            or config["first_k_dense_replace"] or config["n_group"] != 1 \
            or config["topk_group"] != 1 or config["hidden_act"] != "silu":
        raise ValueError(f"configuration {config['name']!r} is not the "
                         f"mistral4 layer this architecture file describes")
    return Mistral4Model(Mistral4Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        rope_theta=float(rope["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max_position_embeddings=rope[
            "original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        llama_4_scaling_beta=float(rope["llama_4_scaling_beta"]),
        experts_held=(int(config["first_routed_expert_held"]),
                      int(config["n_routed_experts_held"])),
        dtype=jnp.dtype(config["assumed"]["compute_dtype"]).type,
        initializer_range=float(config["assumed"]["initializer_range"]),
        **overrides))


def init_params(model, seed):
    """The served weights: made on the device from the seed, a layer a
    jitted call, in the dtype the configuration serves them in."""
    return model.init(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def reference_weights(params, config):
    """The program's parameter tree -> what the reference reads, in
    float32 and only when asked: ``embed``, ``norm``, ``head``, and
    ``layer(l)`` = the layer's matrices outside its routed experts (cut
    out of the program's stack by layer) plus ``expert(e)``, held expert
    ``e``'s three matrices.  Only names and shapes of the program's tree
    are used."""
    inner = config["moe_intermediate_size"]
    held = int(config["n_routed_experts_held"])
    cast = jax.jit(_f32)
    cut = jax.jit(lambda tree, i: _f32(jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)))

    def layer(l):
        dense = cut(params["layers"], jnp.int32(l))

        def expert(e):      # the layers' experts lie in one tensor
            w = cut(params["experts"], jnp.int32(l * held + e))
            return {"gate": w["gate_up"][:, :inner],
                    "up": w["gate_up"][:, inner:], "down": w["down"]}

        dense["shared"] = {
            "gate": dense["shared_gate_up"][:, :inner],
            "up": dense.pop("shared_gate_up")[:, inner:],
            "down": dense.pop("shared_down")}
        dense["expert"] = expert
        return dense

    return {"embed": params["embed"], "norm": cast(params["norm"]),
            "head": params["head"], "layer": layer}


def _kept(bits):
    """What the control does to every activation and weight a matmul reads
    or writes: round it to ``bits`` significand bits.  None: nothing, the
    reference itself."""
    if bits is None:
        return lambda x: x

    def keep(x):
        m, e = jnp.frexp(x)
        return jnp.ldexp(jnp.round(m * 2.0 ** bits) / 2.0 ** bits, e)
    return keep


def _matmul(keep):
    return lambda a, b: keep(keep(a) @ keep(b))


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_inv_freq(rope, dim):
    """DeepSeek-V3's ``YarnRotaryEmbedding``: plain frequencies where a
    dimension turns more than ``beta_fast`` times over the original
    context, frequencies divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear ramp between."""
    base, original = rope["rope_theta"], rope["original_max_position_embeddings"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def dim_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    return plain / rope["factor"] * ramp + plain * (1.0 - ramp)


def _rope(x, positions, rope):
    """Interleaved RoPE: the pairs (x[2i], x[2i+1]) turn by
    ``position * inv_freq[i]``.  x: (S, ..., d); positions: (S,)."""
    d = x.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(_yarn_inv_freq(rope, d), jnp.float32)
    scale = _yarn_mscale(rope["factor"], rope["mscale"]) \
        / _yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = (jnp.cos(angles) * scale).reshape(shape)
    sin = (jnp.sin(angles) * scale).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _static(config):
    """The hashable part of a configuration the jitted pieces close over."""
    rope = config["rope_parameters"]
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["kv_lora_rank"], config["rms_norm_eps"],
            tuple(sorted((k, v) for k, v in rope.items())))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _ref_qkv(x, p, static, bits):
    """x (S, E) -> q (S, H, Dn + Dr) scaled and rotated, k_nope (S, H, Dn),
    k_rope (S, Dr), v (S, H, Dv)."""
    H, Dn, Dr, Dv, R, eps, rope = static
    rope = dict(rope)
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        pos = jnp.arange(S)
        h = _rms_norm(x, p["attn_norm"], eps)
        c_q = _rms_norm(mm(h, p["q_a"]), p["q_a_norm"], eps)
        q = mm(c_q, p["q_b"]).reshape(S, H, Dn + Dr)
        kv = mm(h, p["kv_a"])
        c_kv = _rms_norm(kv[:, :R], p["kv_a_norm"], eps)
        k_rope = _rope(kv[:, R:], pos, rope)
        expanded = mm(c_kv, p["kv_b"]).reshape(S, H, Dn + Dv)
        q = jnp.concatenate([q[..., :Dn], _rope(q[..., Dn:], pos, rope)], -1)
        scale = (Dn + Dr) ** -0.5 \
            * _yarn_mscale(rope["factor"], rope["mscale_all_dim"]) ** 2
        q_scale = 1.0 + rope["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
            pos / rope["original_max_position_embeddings"]))
        return q * (scale * q_scale)[:, None, None], expanded[..., :Dn], \
            k_rope, expanded[..., Dn:]


_Q_ROWS = 128       # query rows whose (H, rows, keys) scores are alive at once


@functools.partial(jax.jit, static_argnums=(5, 6))
def _ref_attend(q, k_nope, k_rope, v, first, Dn, bits):
    """Rows ``first .. first + len(q)`` of causal attention over the keys
    given (positions 0 .. len(k) - 1), ``_Q_ROWS`` query rows at a time."""
    keep = _kept(bits)

    # q . k over both parts at once: the rotary key stands beside every
    # head's own (no sum of two half-deep products)
    keys = keep(jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, None], k_nope.shape[:2] + k_rope.shape[1:])], axis=-1))
    del Dn

    def rows(args):
        qb, start = args
        with jax.default_matmul_precision("highest"):
            s = keep(jnp.einsum("qhd,khd->hqk", keep(qb), keys))
            seen = (start + jnp.arange(qb.shape[0]))[:, None] \
                >= jnp.arange(k_nope.shape[0])[None, :]
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return keep(jnp.einsum("hqk,khv->qhv", keep(p), keep(v)))

    n = q.shape[0] // _Q_ROWS
    out = jax.lax.map(rows, (q.reshape(n, _Q_ROWS, *q.shape[1:]),
                             first + _Q_ROWS * jnp.arange(n)))
    return out.reshape(q.shape[0], -1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _ref_attn_out(x, a, p, eps, bits):
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        h = x + mm(a, p["o"])
        return h, _rms_norm(h, p["ffn_norm"], eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _ref_route(hn, router, top_k, norm, scaling, bits):
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(mm(hn, router), axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)
        if norm:
            weights = weights / weights.sum(-1, keepdims=True)
        return weights * scaling, ids


@functools.partial(jax.jit, static_argnums=(2,))
def _ref_swiglu(x, w, bits):
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        return mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])


# The shapes a run compiles.  What costs time, attention, is computed to the
# last judged row in blocks of ``_ROW_BLOCK`` rows over ``_KEY_BUCKET``
# keys: the same few programs whatever the length.  Everything else is a
# matmul over all rows and cheap, so the rows themselves come in ONE OF A
# FEW lengths (``_LENGTH_STEP``, or the width the harness pads to where that
# is less): a first run compiles two or three lengths, not one per request.
_LENGTH_STEP = 8192     # rows a sequence is held at, rounded up
_KEY_BUCKET = 4096      # keys a block of query rows is given, rounded up
_ROW_BLOCK = 1024       # query rows a call of ``_ref_attend`` takes
_EXPERT_ROWS = 256      # rows an expert is given, rounded up


def _ref_layer(x, p, config, bits, keep=None, live=None):
    """One block over (S, E) float32, S a multiple of ``_Q_ROWS``.  Only
    the first ``live`` rows (a multiple of ``_ROW_BLOCK``; None: all) are
    attended and routed: the rest is padding that nothing judged can see
    (causal).  ``keep = (first, last)``, multiples
    of ``_ROW_BLOCK``: return those rows only, the LAST layer's others feed
    nothing, so its attention and feed-forward are not computed for them
    (keys and values are)."""
    static = _static(config)
    S = x.shape[0]
    live = S if live is None else min(live, S)
    keep_from, keep_to = (0, S) if keep is None else keep
    expert = p["expert"]
    p = {k: v for k, v in p.items() if k != "expert"}
    q, k_nope, k_rope, v = _ref_qkv(x, p, static, bits)
    step = min(_ROW_BLOCK, S)
    parts = []
    for first in range(keep_from, min(keep_to, live), step):
        keys = min(S, -(-(first + step) // _KEY_BUCKET) * _KEY_BUCKET)
        parts.append(_ref_attend(q[first:first + step], k_nope[:keys],
                                 k_rope[:keys], v[:keys], first,
                                 config["qk_nope_head_dim"], bits))
    attended = jnp.concatenate(parts)
    if keep_to - keep_from > attended.shape[0]:
        attended = jnp.pad(attended, ((0, keep_to - keep_from
                                       - attended.shape[0]), (0, 0)))
    live -= keep_from
    h, hn = _ref_attn_out(x[keep_from:keep_to], attended, p,
                          config["rms_norm_eps"], bits)
    del q, k_nope, k_rope, v, parts
    weights, ids = _ref_route(hn, p["router"], config["num_experts_per_tok"],
                              bool(config["norm_topk_prob"]),
                              float(config["routed_scaling_factor"]), bits)
    out = h + _ref_swiglu(hn, p["shared"], bits)
    ids_host, weights_host = np.asarray(ids), np.asarray(weights)
    first = int(config.get("first_routed_expert_held", 0))
    held = int(config.get("n_routed_experts_held",
                          config["n_routed_experts"]))
    for e in range(held):                   # expert by expert, its rows only
        tokens, choice = np.nonzero(ids_host[:live] == first + e)
        if not len(tokens):
            continue
        # a few padded shapes: the padding repeats rows with weight zero
        n = -(-len(tokens) // _EXPERT_ROWS) * _EXPERT_ROWS
        weight = np.zeros(n, np.float32)
        weight[:len(tokens)] = weights_host[tokens, choice]
        out = _ref_add_expert(out, hn, expert(e), np.resize(tokens, n),
                              weight, bits)
    return out


@functools.partial(jax.jit, static_argnums=(5,))
def _ref_add_expert(out, hn, w, rows, weight, bits):
    return out.at[rows].add(weight[:, None] * _ref_swiglu(hn[rows], w, bits))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _ref_head(x, norm, head, eps, bits):
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        return mm(_rms_norm(x, norm, eps), head.astype(jnp.float32))


# rows of the head computed in one call: one compiled shape whatever number
# of rows a request asks for
_HEAD_ROWS = 128


def reference_logits(weights, config, ids, rows=None, control_bits=None):
    """(1, S) token ids -> float32 logits: (1, S, vocab_size) with
    ``rows=None``, else (1, len(rows), vocab_size), the head applied to the
    positions ``rows`` and to no others, ``_HEAD_ROWS`` of them at a time.
    S is padded to a multiple of ``_Q_ROWS`` inside (causal attention keeps
    the padding out of the rows that count).  ``control_bits``: not the
    reference but its control, every matmul's inputs and result rounded to
    that many significand bits (4: about fp8, the nearest precision under
    the bf16 the configuration states), which the rule of ``served_check``
    has to refuse."""
    ids = np.asarray(ids, np.int32)
    assert ids.shape[0] == 1, "the reference takes one sequence at a time"
    S = -(-ids.shape[1] // _Q_ROWS) * _Q_ROWS
    live = keep = None
    if rows is not None:
        # nothing after the last judged row can move it (causal): the
        # harness pads every request to its longest, the reference attends
        # and routes no row past the judged ones
        live = -(-(int(np.max(rows)) + 1) // _ROW_BLOCK) * _ROW_BLOCK
        S = min(S, -(-live // _LENGTH_STEP) * _LENGTH_STEP)
        live = min(live, S)
        keep = (int(np.min(rows)) // _ROW_BLOCK * _ROW_BLOCK, live)
    padded = np.zeros(S, np.int32)
    n = min(S, ids.shape[1])
    padded[:n] = ids[0, :n]
    x = weights["embed"][padded].astype(jnp.float32)
    L = config["num_hidden_layers"]
    keep_from = 0 if keep is None else keep[0]
    for l in range(L):
        x = _ref_layer(x, weights["layer"](l), config, control_bits,
                       keep if l == L - 1 else None, live)

    def head(x):
        return _ref_head(x, weights["norm"], weights["head"],
                         config["rms_norm_eps"], control_bits)

    if rows is None:
        return head(x[:ids.shape[1]])[None]
    rows = np.asarray(rows) - keep_from
    take = np.resize(rows, -(-len(rows) // _HEAD_ROWS) * _HEAD_ROWS)
    blocks = [head(x[take[i:i + _HEAD_ROWS]])
              for i in range(0, len(take), _HEAD_ROWS)]
    return jnp.concatenate(blocks)[None, :len(rows)]


# ---------------------------------------------------------------------------
# the rule for served tokens
# ---------------------------------------------------------------------------
# The served path computes in bf16, the reference in f32.  A dense model's
# rows all lie within a few bf16 spacings of the reference's best logit
# (``gpt2.py``).  A ROUTED model chooses discretely: where a token's 4th and
# 5th expert score within bf16's resolution the two paths may choose
# differently, that token's layer output differs by a whole expert's
# contribution, and every later position that attends it inherits a little
# of it.  Such rows are few and are not a fault, so the rule states a share
# of rows that must lie near and a bound, in the row's own logit sigma, that
# NO row may pass; a wrong cache row, mask, position or scale moves most
# rows by tens of spacings and fails the share.  The numbers are measured on
# the chip under the cell's traffic (PERF.md, section 6, PR 30).
def served_check(config):
    """What the serving driver's check takes from this architecture: the
    numbers of ``drive_serve.judge_rows``' rule with the reason for each,
    and ``width(longest)``, the padded length at which a checked request of
    ``longest`` tokens is run through the reference."""
    return {
        "rule": {"near_best_spacings": 4.0, "share": 0.9,
                 "every_row_sigma": 3.0},
        "why": {
            "near_best_spacings": "the dense model's distance (gpt2.py): "
                                  "served bf16 against f32, a few spacings",
            "share": "routing chooses discretely, so 0.3-2.1 % of rows "
                     "follow another expert than the reference's and lie "
                     "5-43 spacings out: the program's smallest share "
                     "0.972 over ~8,400 rows of 22 runs on the chip, the "
                     "4-bit control's largest 0.442 (PERF.md section 6, "
                     "PR 30); any matmul of the served path computed below "
                     "bf16 moves most rows out, as the control does",
            "every_row_sigma": "what a token picked blindly (4 sigma "
                               "under at 32,768 ids) or a broken head "
                               "fails; a routed row that follows another "
                               "expert lay 1.06 sigma under at the worst "
                               "of ~8,400 and the control's worst 1.85-"
                               "2.26, so this bound does not separate "
                               "them and is not meant to: share does",
        },
        # rotary positions: no table to fill, so the longest checked
        # request rounded up to 1,024, not the cap
        "width": lambda longest: -(-int(longest) // 1024) * 1024,
    }


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _layer_params(config, experts):
    E, H = config["hidden_size"], config["num_attention_heads"]
    I = config["moe_intermediate_size"]
    R, Dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    Dn, Dv = config["qk_nope_head_dim"], config["v_head_dim"]
    Q = config["q_lora_rank"]
    attention = E * Q + Q * H * (Dn + Dr) + E * (R + Dr) \
        + R * H * (Dn + Dv) + H * Dv * E
    norms = 2 * E + Q + R
    return attention + norms + E * config["n_routed_experts"] \
        + 3 * E * I * (experts + config["n_shared_experts"])


def n_params(config):
    """Parameters this chip holds: the layers with the held experts, the
    held vocabulary's embedding and head, the final norm."""
    E = config["hidden_size"]
    return config["num_hidden_layers"] * _layer_params(
        config, config["n_routed_experts_held"]) \
        + 2 * config["vocab_size"] * E + E


def counters_are_of(config, program):
    """Whether a program's counters (``harness/roofline.programs``) come
    from a model of THIS configuration's sizes, as far as they can tell:
    the held experts a program could read over all its layers.  A reader
    entered for one configuration gives nothing for another's run, where it
    would divide by the wrong sizes."""
    return program.get("moe_expert_slots") == \
        config["n_routed_experts_held"] * config["num_hidden_layers"]


def expert_bytes(config, weight_bytes=2):
    """One routed expert's three matrices, as held."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * weight_bytes


def decode_step_bytes(config, *, lanes, context_positions, weight_bytes,
                      kv_bytes, experts_touched=None):
    """Bytes one decode step has to move at the least: every weight
    outside the routed experts once, the experts touched a layer
    (``experts_touched``; None: all held), and for each lane the latent
    rows of ``context_positions`` positions in every layer."""
    L = config["num_hidden_layers"]
    held = config["n_routed_experts_held"]
    touched = held if experts_touched is None else experts_touched
    dense = (n_params(config) - L * held * 3 * config["hidden_size"]
             * config["moe_intermediate_size"]) * weight_bytes
    experts = L * touched * expert_bytes(config, weight_bytes)
    rows = lanes * context_positions * L * kv_bytes \
        * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
    return dense + experts + rows


def grouped_matmul_cost(config, *, held_rows, experts_touched, call,
                        weight_bytes=2):
    """Operations and bytes of ONE of the routed experts' two grouped
    matmuls (kernels ``moe_grouped_matmul_<kind>_up``: rows x [gate | up],
    two of an expert's three matrices; ``..._down``: the third) for
    ``held_rows`` (token, choice) pairs on held experts and
    ``experts_touched`` (layer, expert) pairs that received any: the
    matrices of the TOUCHED experts once, the call's rows in and out.
    Counted over layers already (both arguments are sums over the
    layers)."""
    E, I = config["hidden_size"], config["moe_intermediate_size"]
    matrices, row_values = {"up": (2, E + 2 * I), "down": (1, I + E)}[call]
    flops = 2 * held_rows * matrices * E * I
    moved = experts_touched * matrices * E * I * weight_bytes \
        + held_rows * row_values * weight_bytes
    return flops, moved


def prefill_attn_cost(config, *, pairs, queries=0, act_bytes=2):
    """Operations and bytes of the rectangle attention kernel
    (``mla_prefill_attn``) over ``pairs`` causal (query, key) pairs of
    ``queries`` query rows, per layer: QK^T and PV over the causal part
    only, H heads of (Dn + Dr) and Dv; each query block reads its keys and
    values once, every query and output row once."""
    H = config["num_attention_heads"]
    Dk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    Dv = config["v_head_dim"]
    flops = 2 * pairs * H * (Dk + Dv)
    block_q = 512                           # rect_attention's default tile
    moved = (pairs // block_q * H * (Dk + Dv)
             + queries * H * (Dk + Dv)) * act_bytes
    return flops, moved
