"""Mellum 2 (``JetBrains/Mellum2-12B-A2.5B-Instruct``, ``model_type: mellum``)
for the benchmark: how to build the program's model from a configuration
file, the plain reference the program is held to, the rule its served tokens
are held by, and the arithmetic (parameters, bytes a decode step, operations
and bytes of the grouped matmul and of the two attention kernels) the
utilisation metrics divide by.

The reference follows the published ``config.json`` keys.  One layer, for x
(S, hidden)::

    h = RMSNorm(x)
    q = h Wq  as num_attention_heads heads of head_dim
    k = h Wk, v = h Wv  as num_key_value_heads heads of head_dim
    q, k = RoPE(q), RoPE(k)    half-split: [x1, x2] -> [x1 cos - x2 sin,
                                                       x2 cos + x1 sin]
    scores = q_h . k_(h // G) * head_dim**-0.5, causal, softmax in f32
    x = x + (scores . v_(h // G)) Wo
    u = RMSNorm(x)
    p = softmax(u Wr) over num_experts; the num_experts_per_tok largest,
        their weights renormalised to sum 1 (norm_topk_prob)
    x = x + sum_e w_e * (silu(u Wg_e) * (u Wu_e)) Wd_e

``layer_types`` says which layers are SLIDING (a query at p sees keys
``p - sliding_window + 1 .. p``; plain RoPE of base ``rope_theta``) and which
FULL (every key up to p; YaRN's blended frequencies, factor 16 over 8,192,
beta 32 / 1, as Hugging Face's ``_compute_yarn_parameters`` computes them
with ``truncate`` true, and cos and sin times ``attention_factor``).  Final
RMSNorm, untied head.  Written in jax.numpy in float32 under
``jax.default_matmul_precision("highest")``, with no kernel, no cache and no
batching, and importing nothing from ``deepspeed_tpu``.  It is computed a
layer at a time, the attention's weights upcast a layer at a time; 1,024
query rows at a time and a key head at a time, against all the keys of a
full layer and against the 2,048 that hold the block's windows in a sliding
one; the routed layer over tiles of 512 (token, choice) pairs laid out by
expert on the host, a tile's expert upcast where it lies.  So the longest
request of the cell, up to 32,960 tokens, fits beside the 7.59 GB of served
weights it reads (the largest array alive is a key head's (8, 1024, keys)
scores, 1.2 GB at 36,864 keys), and a padded length costs FOUR compiled
programs (two kinds of attention, the router, the experts), whatever the
requests: the first form of this file, an expert and a block of queries a
call, spent 22 minutes of a run in some hundreds of small compilations
(PERF.md section 6, PR 37).

``assumed`` in the configuration file lists what the published config has no
key for.  ``reference_logits`` takes the window from
``config["sliding_window"]``: the control that takes the window OUT of the
sliding layers (``benchmark/tools/served_controls.py``) hands it a copy of
the configuration whose window is the whole context.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"


# ---------------------------------------------------------------------------
# the program's model, built from the configuration file
# ---------------------------------------------------------------------------
def build_model(config, overrides):
    """The program's ``MellumModel`` at the file's sizes.  ``overrides`` are
    the job's settings of the program, never a size."""
    from deepspeed_tpu.models.mellum import MellumConfig, MellumModel

    if config["attention_bias"] or config["hidden_act"] != "silu" \
            or config["tie_word_embeddings"] \
            or set(config["mlp_layer_types"]) != {"sparse"} \
            or not config["use_sliding_window"]:
        raise ValueError(f"configuration {config['name']!r} is not the "
                         f"Mellum layer this architecture file describes")
    return MellumModel(MellumConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        sliding_window=config["sliding_window"],
        layer_types=tuple(config["layer_types"]),
        rope_parameters=config["rope_parameters"],
        dtype=jnp.dtype(config["assumed"]["compute_dtype"]).type,
        initializer_range=float(config["assumed"]["initializer_range"]),
        **overrides))


def init_params(model, seed):
    """The served weights: made on the device from the seed, in the dtype
    the configuration serves them in."""
    return model.init(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
def reference_weights(params, config):
    """The program's parameter tree -> what the reference reads, in float32
    and only when asked: ``embed``, ``norm``, ``head``, and ``layer(l)`` =
    ``attention`` (``q``, ``k``, ``v`` cut out of the program's one
    ``qkv``, ``o``, the two norms), ``router`` and ``experts``, the two
    tensors that hold every layer's experts ([gate | up] and down) with the
    index of this layer's first.  Only names and shapes of the program's tree
    are used (it holds ``qkv`` and ``router`` as (out, in); here they are
    (in, out) like the rest)."""
    H, Hkv, D = config["num_attention_heads"], \
        config["num_key_value_heads"], config["head_dim"]
    n = config["num_experts"]

    @jax.jit
    def cut(tree, index):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False)
            .astype(jnp.float32), tree)

    def layer(l):
        w = cut(params["layers"], jnp.int32(l))
        # the program holds these two (out, in)
        qkv, router = w["qkv"].T, w["router"].T

        return {"attention": {"q": qkv[:, :H * D],
                              "k": qkv[:, H * D:(H + Hkv) * D],
                              "v": qkv[:, (H + Hkv) * D:], "o": w["o"],
                              "norm": w["attn_norm"]},
                "ffn_norm": w["ffn_norm"], "router": router,
                # the layers' experts lie in one tensor each: read (and
                # upcast) where they lie, this layer's from ``l * n`` on
                "experts": (params["experts"]["gate_up"],
                            params["experts"]["down"], l * n)}

    return {"embed": params["embed"],
            "norm": params["norm"].astype(jnp.float32),
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


def rope_table(config, kind):
    """(head_dim / 2,) float64 frequencies of a kind of layer and what its
    cos and sin are multiplied by, from ``rope_parameters[kind]``."""
    rope, dim = config["rope_parameters"][kind], config["head_dim"]
    base = float(rope["rope_theta"])
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return plain, 1.0
    assert rope["rope_type"] == "yarn", rope
    # Hugging Face's _compute_yarn_parameters, truncate = True

    def correction_dim(rotations):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    return plain / rope["factor"] * (1.0 - extrapolation) \
        + plain * extrapolation, float(rope["attention_factor"])


def _rope(x, positions, inv_freq, factor):
    """Half-split RoPE over the last dim.  x: (S, heads, d)."""
    angles = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = (jnp.cos(angles) * factor)[:, None]
    sin = (jnp.sin(angles) * factor)[:, None]
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


_Q_ROWS = 1024          # query rows whose scores are alive at once
_LENGTH_STEP = 4096     # a sequence is padded to a multiple of this
_TILE_ROWS = 512        # rows of one expert computed in one go


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _ref_attention(x, p, ffn_norm, static, rope, window, bits):
    """x (S, E), S a multiple of ``_Q_ROWS`` -> x + attention(norm(x)) and
    its ffn norm.  ``_Q_ROWS`` queries at a time (``lax.map``) and a key
    head at a time, against all S keys in a full layer (``window`` None)
    and against the keys that hold the block's windows in a sliding one.
    ONE compiled program a padded length and kind of layer."""
    H, Hkv, D, eps = static
    inv_freq, factor = rope
    keep = _kept(bits)
    mm = _matmul(keep)
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        pos = jnp.arange(S)
        h = _rms_norm(x, p["norm"], eps)
        inv_freq = np.asarray(inv_freq)
        q = _rope(mm(h, p["q"]).reshape(S, H, D), pos, inv_freq, factor)
        k = _rope(mm(h, p["k"]).reshape(S, Hkv, D), pos, inv_freq, factor)
        v = mm(h, p["v"]).reshape(S, Hkv, D)
        # the keys a block of queries is shown: all of them, or the
        # ``span`` that holds every window of the block
        span = S if window is None else min(
            S, _Q_ROWS + -(-(window - 1) // _Q_ROWS) * _Q_ROWS)
        k, v = keep(k.transpose(1, 0, 2)), keep(v.transpose(1, 0, 2))

        def block(args):
            qb, q_start = args              # (rows, H, D), ()
            k_start = jnp.clip(q_start + _Q_ROWS - span, 0, S - span)
            qpos = q_start + jnp.arange(_Q_ROWS)
            kpos = k_start + jnp.arange(span)
            seen = kpos[None, :] <= qpos[:, None]
            if window is not None:
                seen = seen & (kpos[None, :] > qpos[:, None] - window)
            qg = keep(qb.reshape(_Q_ROWS, Hkv, H // Hkv, D)) * D ** -0.5

            def head(args):
                qj, kj, vj = args   # (rows, G, D), (S, D), (S, D)
                kj = jax.lax.dynamic_slice_in_dim(kj, k_start, span)
                vj = jax.lax.dynamic_slice_in_dim(vj, k_start, span)
                s = keep(jnp.einsum("qgd,kd->gqk", qj, kj))
                w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf),
                                   axis=-1)
                return keep(jnp.einsum("gqk,kd->qgd", keep(w), vj))

            out = jax.lax.map(head, (qg.transpose(1, 0, 2, 3), k, v))
            return out.transpose(1, 0, 2, 3).reshape(_Q_ROWS, H * D)

        n = S // _Q_ROWS
        attended = jax.lax.map(block, (q.reshape(n, _Q_ROWS, H, D),
                                       _Q_ROWS * jnp.arange(n)))
        h = x + mm(attended.reshape(S, H * D), p["o"])
        return h, _rms_norm(h, ffn_norm, eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _ref_route(u, router, top_k, renormalise, bits):
    """Weights (S, k) and ids (S, k): softmax in f32 over all experts, the
    ``top_k`` largest, renormalised to sum 1."""
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(mm(u, router), axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)
        if renormalise:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights, ids


@functools.partial(jax.jit, static_argnums=(8,))
def _ref_experts(u, gate_up, down, first, n_tiles, tile_expert, rows,
                 weight, bits):
    """``sum_e w_e * SwiGLU_e(u)`` over tiles of ``_TILE_ROWS`` (token,
    choice) pairs that chose ONE expert each: tile i reads the tokens
    ``rows[i]`` and expert ``tile_expert[i]``'s three matrices (upcast from
    where they lie in the program's tensors, ``first`` on), and adds its
    result times ``weight[i]`` (zero on a tile's padding) to those tokens.
    ONE compiled program a padded length."""
    mm = _matmul(_kept(bits))
    inner = down.shape[1]
    with jax.default_matmul_precision("highest"):
        def tile(i, out):
            at = first + tile_expert[i]
            gu = jax.lax.dynamic_index_in_dim(gate_up, at, keepdims=False) \
                .astype(jnp.float32)
            d = jax.lax.dynamic_index_in_dim(down, at, keepdims=False) \
                .astype(jnp.float32)
            x = u[rows[i]]
            y = mm(jax.nn.silu(mm(x, gu[:, :inner])) * mm(x, gu[:, inner:]),
                   d)
            return out.at[rows[i]].add(weight[i][:, None] * y)

        return jax.lax.fori_loop(0, n_tiles, tile, jnp.zeros_like(u))


def _ref_routed(u, layer, config, bits):
    """The routed layer over u (S, E): the (token, choice) pairs laid out
    by expert on the host, each expert's padded to whole tiles."""
    weights, ids = _ref_route(u, layer["router"],
                              config["num_experts_per_tok"],
                              bool(config["norm_topk_prob"]), bits)
    ids_host = np.asarray(ids).reshape(-1)
    weights_host = np.asarray(weights).reshape(-1)
    S, k, n = u.shape[0], config["num_experts_per_tok"], \
        config["num_experts"]
    order = np.argsort(ids_host, kind="stable")
    counts = np.bincount(ids_host, minlength=n)
    tiles = -(-counts // _TILE_ROWS)
    worst = S * k // _TILE_ROWS + n             # static: a length's worst
    rows = np.zeros((worst, _TILE_ROWS), np.int32)
    weight = np.zeros((worst, _TILE_ROWS), np.float32)
    tile_expert = np.zeros(worst, np.int32)
    at = pair = 0
    for e in range(n):
        mine = order[pair:pair + counts[e]]
        pair += counts[e]
        flat_rows = rows[at:at + tiles[e]].reshape(-1)
        flat_weight = weight[at:at + tiles[e]].reshape(-1)
        flat_rows[:len(mine)] = mine // k
        flat_weight[:len(mine)] = weights_host[mine]
        tile_expert[at:at + tiles[e]] = e
        at += tiles[e]
    gate_up, down, first = layer["experts"]
    return _ref_experts(u, gate_up, down, jnp.int32(first), jnp.int32(at),
                        tile_expert, rows, weight, bits)


def _ref_layer(x, layer, config, kind, bits):
    static = (config["num_attention_heads"], config["num_key_value_heads"],
              config["head_dim"], config["rms_norm_eps"])
    inv_freq, factor = rope_table(config, kind)
    x, u = _ref_attention(
        x, layer["attention"], layer["ffn_norm"], static,
        (tuple(inv_freq), factor),
        config["sliding_window"] if kind == SLIDING else None, bits)
    return x + _ref_routed(u, layer, config, bits)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _ref_head(x, norm, head, eps, bits):
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        return mm(_rms_norm(x, norm, eps), head.astype(jnp.float32))


# rows of the head computed in one call: one compiled shape whatever number
# of rows a request asks for (128 x 98,304 logits in f32 are 50 MB)
_HEAD_ROWS = 128


def reference_logits(weights, config, ids, rows=None, control_bits=None):
    """(1, S) token ids -> float32 logits: (1, S, vocab_size) with
    ``rows=None``, else (1, len(rows), vocab_size), the head applied to the
    positions ``rows`` and to no others, ``_HEAD_ROWS`` of them at a time.
    The sequence is cut after the last judged row and padded to a multiple
    of ``_LENGTH_STEP`` inside (the harness pads every checked request to
    its longest; causal attention keeps what lies behind a row out of it;
    few lengths, so few compiled programs: four a length).
    ``control_bits``: not the reference but its control, every matmul's
    inputs and result rounded to that many significand bits (4: about fp8,
    the nearest precision under the bf16 the configuration states), which
    the rule of ``served_check`` has to refuse."""
    ids = np.asarray(ids, np.int32)
    assert ids.shape[0] == 1, "the reference takes one sequence at a time"
    n = ids.shape[1] if rows is None else int(np.max(rows)) + 1
    S = -(-n // _LENGTH_STEP) * _LENGTH_STEP
    padded = np.zeros(S, np.int32)
    padded[:min(n, ids.shape[1])] = ids[0, :n]
    x = weights["embed"][padded].astype(jnp.float32)
    for l in range(config["num_hidden_layers"]):
        x = _ref_layer(x, weights["layer"](l), config,
                       config["layer_types"][l], control_bits)

    def head(x):
        return _ref_head(x, weights["norm"], weights["head"],
                         config["rms_norm_eps"], control_bits)

    if rows is None:
        return jnp.concatenate(
            [head(x[i:i + _HEAD_ROWS]) for i in range(0, S, _HEAD_ROWS)]
        )[None, :ids.shape[1]]
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
# a token's 8th and 9th expert score within bf16's resolution the two paths
# may choose differently, that token's layer output differs by a whole
# expert's contribution (a weight near 1/8 here, all 64 experts held, so
# every such swap shows), and later positions that attend it inherit a
# little of it.  The rule keeps the two routed cells' form: a share of rows
# that must lie near, and a bound in the row's own logit sigma that NO row
# may pass.  The numbers are measured on the chip under the cell's traffic
# (PERF.md, section 6, PR 37).
def served_check(config):
    """What the serving driver's check takes from this architecture: the
    numbers of ``drive_serve.judge_rows``' rule with the reason for each,
    and ``width(longest)``, the padded length at which a checked request of
    ``longest`` tokens is run through the reference."""
    return {
        "rule": {"near_best_spacings": 4.0, "share": 0.95,
                 "every_row_sigma": 3.0},
        "why": {
            "near_best_spacings": "the dense model's distance (gpt2.py): "
                                  "served bf16 against f32, a few spacings",
            "share": "routing chooses discretely, so a row in a thousand "
                     "follows another expert than the reference's and lies "
                     "4.1-4.6 spacings out: the program's smallest share "
                     "0.9983 over ~9,400 rows of 15 runs on the chip; the "
                     "4-bit control's largest 0.892 (0.827-0.892, worst row "
                     "17-23 spacings), the no-window control's largest "
                     "0.019 (173-245 spacings) on its two seeds (PERF.md "
                     "section 6, PR 37).  The limit stands between 0.998 "
                     "and 0.892 with room on both sides; any matmul of the "
                     "served path computed below bf16, and a sliding layer "
                     "that sees past its window, move most rows out",
            "every_row_sigma": "what a token picked blindly (~4.3 sigma "
                               "under at 98,304 ids), a broken head or NO "
                               "WINDOW fails (the no-window control's worst "
                               "rows 4.3-5.6 sigma under); the program's "
                               "worst row lay 0.091 sigma under, the 4-bit "
                               "control's 0.55-0.74: this bound does not "
                               "separate those two and is not meant to, "
                               "share does",
        },
        # rotary positions: no table to fill, so the longest checked
        # request rounded up to a block of query rows, not the cap
        "width": lambda longest: -(-int(longest) // _Q_ROWS) * _Q_ROWS,
    }


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _layer_params(config, experts):
    """One layer: q, k, v, o, the router, two norms, ``experts`` experts."""
    E, D = config["hidden_size"], config["head_dim"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    attention = E * (H + 2 * Hkv) * D + H * D * E
    return attention + E * config["num_experts"] + 2 * E \
        + 3 * E * config["moe_intermediate_size"] * experts


def n_params(config):
    """Parameters this chip holds: every layer whole (all experts), the
    embedding, the head, the final norm."""
    E = config["hidden_size"]
    return config["num_hidden_layers"] * _layer_params(
        config, config["num_experts"]) + 2 * config["vocab_size"] * E + E


def counters_are_of(config, program):
    """Whether a program's counters (``harness/roofline.programs``) come
    from a model of THIS configuration's sizes, as far as they can tell: it
    counts what its lanes attended by cache group, and the experts it could
    read over all its layers.  A reader entered for one configuration gives
    nothing for another's run, where it would divide by the wrong sizes."""
    return any(k in program for k in ("attn_keys_full", "attn_pairs_full")) \
        and program.get("moe_expert_slots") \
        == config["num_experts"] * config["num_hidden_layers"]


def expert_bytes(config, weight_bytes=2):
    """One expert's three matrices, as held."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * weight_bytes


def layers_of(config):
    """(full layers, sliding layers)."""
    full = sum(t == FULL for t in config["layer_types"])
    return full, len(config["layer_types"]) - full


def kv_row_bytes(config, kv_bytes=2):
    """Keys AND values of one token in one layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * kv_bytes


def decode_step_bytes(config, *, keys_full, keys_window, weight_bytes,
                      kv_bytes, experts_touched=None):
    """Bytes one decode step has to move at the least: every weight outside
    the experts once, the head and the final norm, the experts touched a
    layer (``experts_touched``; None: all), and the cached rows its live
    lanes attend: ``keys_full`` rows (the lanes' contexts, summed) in every
    full layer, ``keys_window`` (a lane at most the window) in every sliding
    layer, keys and values.  The embedding is not read whole (a row a lane)
    and is left out."""
    L, E = config["num_hidden_layers"], config["hidden_size"]
    touched = config["num_experts"] if experts_touched is None \
        else experts_touched
    dense = (L * _layer_params(config, 0) + config["vocab_size"] * E + E) \
        * weight_bytes
    experts = L * touched * expert_bytes(config, weight_bytes)
    full, sliding = layers_of(config)
    rows = (full * keys_full + sliding * keys_window) \
        * kv_row_bytes(config, kv_bytes)
    return dense + experts + rows


def grouped_matmul_cost(config, *, held_rows, experts_touched, call,
                        weight_bytes=2):
    """Operations and bytes of ONE of the experts' two grouped matmuls
    (kernels ``moe_grouped_matmul_<kind>_up``: rows x [gate | up], two of
    an expert's three matrices; ``..._down``: the third) for ``held_rows``
    (token, choice) pairs and ``experts_touched`` (layer, expert) pairs
    that received any: the matrices of the TOUCHED experts once, the call's
    rows in and out.  Counted over layers already (both arguments are sums
    over the layers)."""
    E, I = config["hidden_size"], config["moe_intermediate_size"]
    matrices, row_values = {"up": (2, E + 2 * I), "down": (1, I + E)}[call]
    flops = 2 * held_rows * matrices * E * I
    moved = experts_touched * matrices * E * I * weight_bytes \
        + held_rows * row_values * weight_bytes
    return flops, moved


def prefill_attn_cost(config, *, pairs, queries, act_bytes=2):
    """Operations and bytes of ONE call of the rectangle attention kernel
    (``gqa_prefill_attn_<group>``: one layer of that kind) over ``pairs``
    (query, key) pairs that are causal and inside the window, of
    ``queries`` query rows: QK^T and PV over those pairs only, every query
    head; a key head's rows are read once for each of the G query heads
    that share it and each block of 1,024 query rows (the kernel's grid),
    every query and output row once."""
    H, D = config["num_attention_heads"], config["head_dim"]
    flops = 2 * pairs * H * 2 * D
    block_q = 1024                          # rect_attention's default tile
    moved = (pairs // block_q * H * 2 * D + queries * H * 2 * D) * act_bytes
    return flops, moved


def decode_attn_cost(config, *, keys, kv_bytes=2):
    """Operations and bytes of ONE call of the paged decode attention
    (``gqa_paged_decode_attn_<group>``: one layer of that kind) whose live
    lanes attend ``keys`` cached rows together: every query head's scores
    and its probabilities against the values, and the rows read once, keys
    and values, as cached values and not as the whole pages they are copied
    by."""
    H, D = config["num_attention_heads"], config["head_dim"]
    return 2 * keys * H * 2 * D, keys * kv_row_bytes(config, kv_bytes)
