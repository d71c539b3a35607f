"""LongCat-Flash (``meituan-longcat/LongCat-Flash-Chat``) for the benchmark:
how to build the program's model from a configuration file, the plain
reference the program is held to, the rule its served tokens are held by,
and the arithmetic (parameters, bytes a decode step, operations and bytes of
the grouped matmul) the utilisation metrics divide by.

The reference follows the published ``config.json`` keys and the tech
report (arXiv:2509.01322).  One block is the shortcut-connected double
block::

    for i in (0, 1):
        h = h + MLA_i(norm_in[i](h))
        u = norm_post[i](h)
        if i == 0: s = ROUTED(u)
        h = h + SwiGLU_i(u)                    # dense, ffn_hidden_size
        if i == 1: h = h + s

``MLA``: queries through a rank-``q_lora_rank`` bottleneck with its own
RMSNorm, keys and values expanded from a normed rank-``kv_lora_rank``
latent, one rotary key shared by all heads, interleaved RoPE of base
``rope_theta`` with no scaling, softmax scale ``(nope + rope)**-0.5``, and
the scale correction of the two low-rank paths: the query times
``(hidden / q_lora_rank)**0.5``, the normed latent times
``(hidden / kv_lora_rank)**0.5`` before its up-projection.  ``ROUTED``:
softmax in f32 over ``n_routed_experts + zero_expert_num`` choices, the
``moe_topk`` largest of ``p + bias``, weights ``routed_scaling_factor * p``
not renormalised; a routed expert adds ``w * SwiGLU_e(u)``, a zero-compute
expert ``w * u``; no shared expert.  Final RMSNorm, untied head.  Written in
jax.numpy in float32 under ``jax.default_matmul_precision("highest")``, with
no kernel, no cache, no batching and no absorption, and importing nothing
from ``deepspeed_tpu``.  It is computed a sub-block at a time with the
weights upcast a group at a time (the largest, a dense feed-forward, 906 MB
in f32), a block of query rows at a time and an expert at a time, so that it
fits beside the 10.34 GB of served weights it reads.

Departures from the source, each by the configuration's own statement: only
the experts this chip holds are computed (``n_routed_experts_held``; the
others would add their part on the chips that hold them), the identity term
is computed whole, and only the held eighth of the vocabulary exists.
``assumed`` in the configuration file lists what the published config has no
key for.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# the program's model, built from the configuration file
# ---------------------------------------------------------------------------
def build_model(config, overrides):
    """The program's ``LongCatFlashModel`` at the file's sizes.
    ``overrides`` are the job's settings of the program, never a size."""
    from deepspeed_tpu.models.longcat_flash import (LongCatFlashConfig,
                                                    LongCatFlashModel)

    if config["attention_method"] != "MLA" or config["attention_bias"] \
            or config["zero_expert_type"] != "identity":
        raise ValueError(f"configuration {config['name']!r} is not the "
                         f"LongCat-Flash layer this architecture file "
                         f"describes")
    return LongCatFlashModel(LongCatFlashConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        ffn_hidden_size=config["ffn_hidden_size"],
        expert_ffn_hidden_size=config["expert_ffn_hidden_size"],
        num_layers=config["num_layers"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mla_scale_q_lora=bool(config["mla_scale_q_lora"]),
        mla_scale_kv_lora=bool(config["mla_scale_kv_lora"]),
        n_routed_experts=config["n_routed_experts"],
        zero_expert_num=config["zero_expert_num"],
        moe_topk=config["moe_topk"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        experts_held=(int(config["first_routed_expert_held"]),
                      int(config["n_routed_experts_held"])),
        dtype=jnp.dtype(config["assumed"]["compute_dtype"]).type,
        initializer_range=float(config["assumed"]["initializer_range"]),
        **overrides))


def init_params(model, seed):
    """The served weights: made on the device from the seed, a matrix a
    jitted call, in the dtype the configuration serves them in."""
    return model.init(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
_ATTENTION = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o",
              "norm_in", "norm_post")
_FFN = ("gate_up", "down")
_HELD_OUT_IN = ("q_b", "kv_a", "kv_b")


def reference_weights(params, config):
    """The program's parameter tree -> what the reference reads, in float32
    and only when asked: ``embed``, ``norm``, ``head``, and ``block(l)`` =
    ``attention(i)`` and ``ffn(i)`` of sub-block ``i`` (cut out of the
    program's stacks by block and sub-block), ``router``, ``router_bias``
    and ``expert(e)``, held expert ``e``'s three matrices.  Only names and
    shapes of the program's tree are used (it holds ``q_b``, ``kv_a`` and
    ``kv_b`` as (out, in); here they are (in, out) like the rest)."""
    inner = config["expert_ffn_hidden_size"]
    width = config["ffn_hidden_size"]
    held = int(config["n_routed_experts_held"])

    @jax.jit
    def cut(tree, *index):
        def one(a):
            for i in index:
                a = jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
            return a.astype(jnp.float32)
        return jax.tree_util.tree_map(one, tree)

    layers = params["layers"]

    def block(l):
        l = jnp.int32(l)

        def attention(i):
            w = cut({k: layers[k] for k in _ATTENTION}, l, jnp.int32(i))
            # the program holds these three (out, in)
            return dict(w, **{k: w[k].T for k in _HELD_OUT_IN})

        def ffn(i):
            w = cut({k: layers[k] for k in _FFN}, l, jnp.int32(i))
            return {"gate": w["gate_up"][:, :width],
                    "up": w["gate_up"][:, width:], "down": w["down"]}

        def expert(e):      # the blocks' experts lie in one tensor
            w = cut(params["experts"], l * held + e)
            return {"gate": w["gate_up"][:, :inner],
                    "up": w["gate_up"][:, inner:], "down": w["down"]}

        return {"attention": attention, "ffn": ffn, "expert": expert,
                "router": cut(layers["router"], l),
                "router_bias": cut(layers["router_bias"], l)}

    return {"embed": params["embed"],
            "norm": params["norm"].astype(jnp.float32),
            "head": params["head"], "block": block}


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


def _rope(x, positions, theta):
    """Interleaved RoPE, no scaling: the pairs (x[2i], x[2i+1]) turn by
    ``position * theta**(-2i/d)``.  x: (S, ..., d); positions: (S,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angles = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _static(config):
    """The hashable part of a configuration the jitted pieces close over."""
    E = config["hidden_size"]
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["kv_lora_rank"], config["rms_norm_eps"],
            float(config["rope_theta"]),
            (E / config["q_lora_rank"]) ** 0.5
            if config["mla_scale_q_lora"] else 1.0,
            (E / config["kv_lora_rank"]) ** 0.5
            if config["mla_scale_kv_lora"] else 1.0)


_Q_ROWS = 128       # query rows whose (H, rows, keys) scores are alive at once


@functools.partial(jax.jit, static_argnums=(2, 3))
def _ref_attention(x, p, static, bits):
    """x (S, E), S a multiple of ``_Q_ROWS`` -> x + MLA(norm_in(x)) and its
    ``norm_post``: the expanded form, every head's keys and values made from
    the latent, causal softmax in f32, ``_Q_ROWS`` query rows at a time."""
    H, Dn, Dr, Dv, R, eps, theta, q_lora, kv_lora = static
    keep = _kept(bits)
    mm = _matmul(keep)
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        pos = jnp.arange(S)
        h = _rms_norm(x, p["norm_in"], eps)
        c_q = _rms_norm(mm(h, p["q_a"]), p["q_a_norm"], eps)
        q = mm(c_q, p["q_b"]).reshape(S, H, Dn + Dr) * q_lora
        q = jnp.concatenate([q[..., :Dn], _rope(q[..., Dn:], pos, theta)],
                            -1) * (Dn + Dr) ** -0.5
        kv = mm(h, p["kv_a"])
        c_kv = _rms_norm(kv[:, :R], p["kv_a_norm"], eps) * kv_lora
        k_rope = _rope(kv[:, R:], pos, theta)
        expanded = mm(c_kv, p["kv_b"]).reshape(S, H, Dn + Dv)
        keys = keep(jnp.concatenate([expanded[..., :Dn], jnp.broadcast_to(
            k_rope[:, None], (S, H, Dr))], axis=-1))
        values = keep(expanded[..., Dn:])

        def rows(args):
            qb, start = args
            s = keep(jnp.einsum("qhd,khd->hqk", keep(qb), keys))
            seen = (start + jnp.arange(_Q_ROWS))[:, None] \
                >= jnp.arange(S)[None, :]
            w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return keep(jnp.einsum("hqk,khv->qhv", keep(w), values))

        n = S // _Q_ROWS
        attended = jax.lax.map(rows, (q.reshape(n, _Q_ROWS, H, Dn + Dr),
                                      _Q_ROWS * jnp.arange(n)))
        h = x + mm(attended.reshape(S, H * Dv), p["o"])
        return h, _rms_norm(h, p["norm_post"], eps)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _ref_route(u, router, bias, top_k, scaling, bits):
    """Weights (S, k) and ids (S, k): chosen by ``p + bias``, weighed by
    ``scaling * p``, not renormalised."""
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(mm(u, router), axis=-1)
        _, ids = jax.lax.top_k(probs + bias, top_k)
        return scaling * jnp.take_along_axis(probs, ids, axis=-1), ids


@functools.partial(jax.jit, static_argnums=(2,))
def _ref_swiglu(x, w, bits):
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        return mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])


@functools.partial(jax.jit, static_argnums=(5,))
def _ref_add_expert(out, u, w, rows, weight, bits):
    return out.at[rows].add(weight[:, None] * _ref_swiglu(u[rows], w, bits))


_EXPERT_ROWS = 64       # rows an expert is given, rounded up


def _ref_routed(u, block, config, bits):
    """The routed layer over u (S, E): the held experts' part, an expert at
    a time over its own rows, and the identity term whole."""
    routed = config["n_routed_experts"]
    weights, ids = _ref_route(
        u, block["router"], block["router_bias"], config["moe_topk"],
        float(config["routed_scaling_factor"]), bits)
    # a zero-compute expert is the identity: its weight on the token itself
    out = jnp.sum(jnp.where(ids >= routed, weights, 0.0), axis=-1,
                  keepdims=True) * u
    ids_host, weights_host = np.asarray(ids), np.asarray(weights)
    first = int(config.get("first_routed_expert_held", 0))
    held = int(config.get("n_routed_experts_held", routed))
    for e in range(held):                   # expert by expert, its rows only
        tokens, choice = np.nonzero(ids_host == first + e)
        if not len(tokens):
            continue
        # a few padded shapes: the padding repeats rows with weight zero
        n = -(-len(tokens) // _EXPERT_ROWS) * _EXPERT_ROWS
        weight = np.zeros(n, np.float32)
        weight[:len(tokens)] = weights_host[tokens, choice]
        out = _ref_add_expert(out, u, block["expert"](e),
                              np.resize(tokens, n), weight, bits)
    return out


def _ref_block(x, block, config, bits):
    """One shortcut-connected double block over (S, E) float32."""
    static = _static(config)
    for i in (0, 1):
        x, u = _ref_attention(x, block["attention"](i), static, bits)
        if i == 0:
            shortcut = _ref_routed(u, block, config, bits)
        x = x + _ref_swiglu(u, block["ffn"](i), bits)
    return x + shortcut


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
    padded = np.zeros(S, np.int32)
    padded[:ids.shape[1]] = ids[0]
    x = weights["embed"][padded].astype(jnp.float32)
    for l in range(config["num_layers"]):
        x = _ref_block(x, weights["block"](l), config, control_bits)

    def head(x):
        return _ref_head(x, weights["norm"], weights["head"],
                         config["rms_norm_eps"], control_bits)

    if rows is None:
        return head(x[:ids.shape[1]])[None]
    rows = np.asarray(rows)
    take = np.resize(rows, -(-len(rows) // _HEAD_ROWS) * _HEAD_ROWS)
    blocks = [head(x[take[i:i + _HEAD_ROWS]])
              for i in range(0, len(take), _HEAD_ROWS)]
    return jnp.concatenate(blocks)[None, :len(rows)]


# ---------------------------------------------------------------------------
# the rule for served tokens
# ---------------------------------------------------------------------------
# The served path computes in bf16, the reference in f32.  A dense model's
# rows all lie within a few bf16 spacings of the reference's best logit
# (``gpt2.py``).  A ROUTED model chooses discretely (``mistral4.py``): where
# a token's 12th and 13th choice score within bf16's resolution the two
# paths may choose differently.  Here a choice weighs ~0.07 (6 x a
# probability near 1/80, not renormalised) against mistral4's ~0.25, only a
# forty-eighth of the routed choices meet an expert this chip holds, and a
# third are identity experts whose swap moves the layer by 0.07 u: a row that
# follows another choice than the reference's moves less than there.  The
# rule keeps mistral4's form: a share of rows that must lie near, and a bound
# in the row's own logit sigma that NO row may pass.  The numbers are
# measured on the chip under the cell's traffic (PERF.md, section 6, PR 34).
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
            "share": "routing chooses discretely, so 0.5-1.0 % of rows "
                     "follow another choice than the reference's and lie "
                     "4-11 spacings out: the program's smallest share "
                     "0.990 over ~25,000 rows of 11 runs on the chip, the "
                     "4-bit control's largest 0.102 (PERF.md section 6, "
                     "PR 34); any matmul of the served path computed below "
                     "bf16 moves most rows out, as the control does",
            "every_row_sigma": "what a token picked blindly (~3.9 sigma "
                               "under at 16,384 ids) or a broken head "
                               "fails; the program's worst row lay 0.22 "
                               "sigma under, the control's 3.5-4.3 on its "
                               "two seeds, so it happened to refuse the "
                               "control too, with less room than share",
        },
        # rotary positions: no table to fill, so the longest checked
        # request rounded up to 256, not the cap
        "width": lambda longest: -(-int(longest) // 256) * 256,
    }


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _block_params(config, experts):
    """One double block: two attentions, two dense feed-forwards and their
    eight norms, the router with its bias, ``experts`` routed experts."""
    E, H = config["hidden_size"], config["num_attention_heads"]
    R, Dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    Dn, Dv = config["qk_nope_head_dim"], config["v_head_dim"]
    Q = config["q_lora_rank"]
    choices = config["n_routed_experts"] + config["zero_expert_num"]
    attention = E * Q + Q * H * (Dn + Dr) + E * (R + Dr) \
        + R * H * (Dn + Dv) + H * Dv * E
    norms = 2 * E + Q + R
    dense = 3 * E * config["ffn_hidden_size"]
    return 2 * (attention + norms + dense) + (E + 1) * choices \
        + 3 * E * config["expert_ffn_hidden_size"] * experts


def n_params(config):
    """Parameters this chip holds: the blocks with the held experts, the
    held vocabulary's embedding and head, the final norm."""
    E = config["hidden_size"]
    return config["num_layers"] * _block_params(
        config, config["n_routed_experts_held"]) \
        + 2 * config["vocab_size"] * E + E


def counters_are_of(config, program):
    """Whether a program's counters (``harness/roofline.programs``) come
    from a model of THIS configuration's sizes, as far as they can tell: it
    counts zero-compute choices at all, and the held experts it could read
    over all its blocks.  A reader entered for one configuration gives
    nothing for another's run, where it would divide by the wrong sizes."""
    return "moe_zero_rows" in program and program.get("moe_expert_slots") \
        == config["n_routed_experts_held"] * config["num_layers"]


def expert_bytes(config, weight_bytes=2):
    """One routed expert's three matrices, as held."""
    return 3 * config["hidden_size"] * config["expert_ffn_hidden_size"] \
        * weight_bytes


def decode_step_bytes(config, *, lanes, context_positions, weight_bytes,
                      kv_bytes, experts_touched=None):
    """Bytes one decode step has to move at the least: every weight of the
    blocks outside the routed experts once, the head and the final norm,
    the experts touched a block (``experts_touched``; None: all held), and
    for each lane the latent rows of ``context_positions`` positions in
    every block, once for EACH of its two attentions (the engine's
    ``attn_keys_decode`` counts one attention's keys).  The embedding is
    not read whole (a row a lane) and is left out."""
    L, E = config["num_layers"], config["hidden_size"]
    held = config["n_routed_experts_held"]
    touched = held if experts_touched is None else experts_touched
    dense = (L * _block_params(config, 0) + config["vocab_size"] * E + E) \
        * weight_bytes
    experts = L * touched * expert_bytes(config, weight_bytes)
    rows = lanes * context_positions * L * 2 * kv_bytes \
        * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
    return dense + experts + rows


def grouped_matmul_cost(config, *, held_rows, experts_touched, call,
                        weight_bytes=2):
    """Operations and bytes of ONE of the routed experts' two grouped
    matmuls (kernels ``moe_grouped_matmul_<kind>_up``: rows x [gate | up],
    two of an expert's three matrices; ``..._down``: the third) for
    ``held_rows`` (token, choice) pairs on held experts and
    ``experts_touched`` (block, expert) pairs that received any: the
    matrices of the TOUCHED experts once, the call's rows in and out.
    Counted over blocks already (both arguments are sums over the
    blocks)."""
    E, I = config["hidden_size"], config["expert_ffn_hidden_size"]
    matrices, row_values = {"up": (2, E + 2 * I), "down": (1, I + E)}[call]
    flops = 2 * held_rows * matrices * E * I
    moved = experts_touched * matrices * E * I * weight_bytes \
        + held_rows * row_values * weight_bytes
    return flops, moved


def latent_decode_attn_cost(config, *, keys):
    """Operations and bytes of ONE call site of the paged latent decode
    attention (kernel ``paged_latent_decode_attn``: one of a block's two
    attentions) over the blocks of a decode program whose live lanes attend
    ``keys`` keys together (the engine's ``attn_keys`` counter, which counts
    one attention's): every head's scores against the cached row
    (latent | rotary) and its probabilities against the latent, and the rows
    read once, as cached values, not as the padded lanes they are stored in
    nor as the whole pages they are copied by."""
    L, H = config["num_layers"], config["num_attention_heads"]
    R, Dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    flops = 2 * L * keys * H * (R + Dr + R)
    moved = L * keys * (R + Dr) * 2
    return flops, moved
