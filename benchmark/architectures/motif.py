"""Motif 3 (``Motif-Technologies/Motif-3-Beta``, ``model_type: Motif``) for the
benchmark: how to build the program's model from a configuration file, the
plain reference the program is held to, the rule its served tokens are held
by with its controls, and the arithmetic (parameters, bytes a decode step,
operations and bytes of the grouped matmul and of the two attention kernels)
the utilisation metrics divide by.

The equations, from the published ``config.json`` keys and the catalog's
``described_as``; every reading the keys do not settle is listed under
``assumed`` in the configuration file.  Streams ``X (S, n, E)``, n =
``mhc_expansion_rate`` = 4, ``X0[i] = embed(token)`` for every stream.  Every
layer has two sublayers F (attention, then feed-forward), each wrapped alike
(mHC)::

    x~      = RMSNorm_{nE}(vec X)
    [a|b|c] = x~ Phi                       Phi: nE x (n + n + n n)
    H_pre   = sigmoid(alpha_pre a + beta_pre)            (n)
    H_post  = 2 sigmoid(alpha_post b + beta_post)        (n)
    H_res   = Sinkhorn_iters(exp(alpha_res mat(c) + beta_res))   (n x n):
              iters x (divide rows by their sums, then columns by theirs)
    u       = sum_i H_pre[i] X[i]
    y       = F(RMSNorm_E(u))
    X       = clip(H_res X + H_post (x) y, +-hidden_clamp)

After the last layer ``x = sum_i X[i]``, the final RMSNorm, the untied head.

Attention F (GDLA), H = ``num_attention_heads`` = 64 signal + 16 noise heads
over ``num_key_value_heads`` = 16 key/value heads::

    c_q = RMSNorm(x W_qa);  [q_nope_h | q_rope_h] = c_q W_qb     128 | 64
    [c_kv | k_r] = x W_kva;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r)
    [k_nope_g | v_g] = c_kv W_kvb,g                  g < 16, 128 | 128
    g(h) = h // 4 for a signal head h < 64;  g(64 + j) = j for noise head j
    a_h = softmax((q_nope_h . k_nope_g(h) + RoPE(q_rope_h) . k_r)
                  / sqrt(head_dim) + mask) v_g(h)
    mask: causal; on a sliding layer also only positions p - W + 1 .. p
    lambda = sigmoid(x W_lambda)                     (S, 64)
    o_h = a_h - lambda_h a_(64 + h // 4)             h < 64
    out = (sigmoid(x W_gate) * concat_h o_h) W_o

RoPE is plain (base ``rope_theta``), interleaved pairs.  Published layer l is
FULL where ``l % sliding_window_period == period - 1``, else SLIDING with
window ``sliding_window``.  Feed-forward F: ``down(PolyNorm(x W_gate) * (x
W_up))`` with ``PolyNorm(z) = polynorm_output_scale * (sum_{i=1..3} w_i z^i /
sqrt(mean(z^(2i)) + eps) + clip(b, +-polynorm_bias_clamp))``; published
layers below ``n_dense_first_layers`` dense, the others routed: ``s =
sigmoid(x W_r)`` in f32, the ``experts_top_k`` largest, ``w = route_scale *
s_sel / sum s_sel``, ``y = shared(x) + sum_e w_e expert_e(x)``.

Written in jax.numpy in float32 under
``jax.default_matmul_precision("highest")``, with no kernel, no paged cache,
no batching and no absorption, and importing nothing from ``deepspeed_tpu``.
It is computed a BLOCK of ``_ROWS`` query rows at a time, a block through
every layer before the next block (causal: a block's keys are its own and
earlier blocks', whose expanded keys and values are kept, a sliding layer's
only as far back as a window reaches), a key/value head and ``_Q_ROWS``
queries at a time inside, the routed experts as tiles of (token, choice)
pairs laid out by expert on the host.  So the stream of a 49k-token request
(3.3 GB in f32) never exists whole, and beside the 7.86 GB of served weights
the largest arrays alive are the full layer's keys and values (0.8 GB).

Departures from the source, each by the configuration's own statement: only
the layers ``layers_held`` exist; only the experts this chip holds are
computed (the others would add their part on the chips that hold them) and
only the held eighth of the vocabulary exists; the multi-token-prediction
head is absent.
"""
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# the program's model, built from the configuration file
# ---------------------------------------------------------------------------
def build_model(config, overrides):
    """The program's ``MotifModel`` at the file's sizes.  ``overrides`` are
    the job's settings of the program, never a size."""
    from deepspeed_tpu.models.motif import MotifConfig, MotifModel

    if config["attention_cls"] != "gdla" or not config["diff_v2"] \
            or not config["elementwise_attn_output_gate"] \
            or config["headwise_attn_output_gate"] \
            or config["hidden_act"] != "poly_norm" \
            or not config["mhc_enabled"] or config["score_before_experts"] \
            or config["interleave_moe_layer_step"] != 1 \
            or config["sliding_window_pattern"] != "interleave" \
            or config["rope_scaling"]["apply_yarn_scaling"] \
            or config["swa_rope_theta"] != config["rope_theta"] \
            or config["tie_word_embeddings"] or config["k_ratio"] != 1 \
            or config["polynorm_output_scale_per_layer"]:
        raise ValueError(f"configuration {config['name']!r} is not the "
                         f"Motif layer this architecture file describes")
    return MotifModel(MotifConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        num_noise_heads=config["num_noise_heads"],
        head_dim=config["head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        experts_top_k=config["experts_top_k"],
        num_shared_experts=config["num_shared_experts"],
        n_dense_first_layers=config["n_dense_first_layers"],
        route_norm=bool(config["route_norm"]),
        route_scale=float(config["route_scale"]),
        score_func=config["score_func"],
        sliding_window=config["sliding_window"],
        sliding_window_period=config["sliding_window_period"],
        mhc_expansion_rate=config["mhc_expansion_rate"],
        mhc_sinkhorn_iters=config["mhc_sinkhorn_iters"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        polynorm_output_scale=float(config["polynorm_output_scale"]),
        polynorm_bias_clamp=float(config["polynorm_bias_clamp"]),
        hidden_clamp=float(config["hidden_clamp"]),
        max_position_embeddings=config["max_position_embeddings"],
        layers_held=tuple(config["layers_held"]),
        experts_held=(int(config["first_expert_held"]),
                      int(config["num_experts_held"])),
        dtype=jnp.dtype(config["assumed"]["compute_dtype"]).type,
        initializer_range=float(config["assumed"]["initializer_range"]),
        mhc_alpha_init=float(config["assumed"]["mhc_alpha_init"]),
        **overrides))


def init_params(model, seed):
    """The served weights: made on the device from the seed, in the dtype
    the configuration serves them in."""
    return model.init(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
def _is_full(config, i):
    per = config["sliding_window_period"]
    return config["layers_held"][i] % per == per - 1


def _is_dense(config, i):
    return config["layers_held"][i] < config["n_dense_first_layers"]


def head_order(config):
    """For each head of the equations (signal heads 0 .. 63, then noise
    heads) the index of the program's head that holds it: the program keeps
    key/value head g's signal heads and then its noise head side by side."""
    Hkv = config["num_key_value_heads"]
    signal = config["num_attention_heads"] - config["num_noise_heads"]
    per = signal // Hkv
    return np.array([h // per * (per + 1) + h % per for h in range(signal)]
                    + [j * (per + 1) + per for j in range(Hkv)])


def reference_weights(params, config):
    """The program's parameter tree -> what the reference reads, as held and
    only when asked (the jitted pieces upcast what they read): ``embed``,
    ``norm``, ``head``, and ``layer(i)`` = held layer i's matrices outside
    its feed-forward (``q_b``'s heads put back in the equations' order, the
    two sublayers' mixes under ``mhc``), and its feed-forward: ``dense``
    (``gate_up``, ``down``, ``poly``) or ``router``, ``shared`` and
    ``experts``, the three tensors that hold every routed layer's held
    experts with the index of this layer's first.  Only names and shapes of
    the program's tree are used."""
    held = int(config["num_experts_held"])
    order = head_order(config)
    H, D = config["num_attention_heads"], config["head_dim"]

    @jax.jit
    def cut(tree, index):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False),
            tree)

    @functools.lru_cache(maxsize=None)
    def layer(i):
        w = cut(params["layers"], jnp.int32(i))
        w["q_b"] = w["q_b"].reshape(-1, H, D)[:, order].reshape(-1, H * D)
        w["mhc"] = [{name[4:]: w[name][s] for name in
                     ("mhc_norm", "mhc_phi", "mhc_beta", "mhc_alpha")}
                    for s in range(2)]
        for name in ("mhc_norm", "mhc_phi", "mhc_beta", "mhc_alpha"):
            del w[name]
        dense = _is_dense(config, i)
        k = sum(_is_dense(config, j) == dense for j in range(i))
        if dense:
            w["dense"] = cut(params["dense"], jnp.int32(k))
        else:
            r = cut(params["routed"], jnp.int32(k))
            w["router"] = r["router"]
            w["shared"] = {"gate_up": r["shared_gate_up"],
                           "down": r["shared_down"],
                           "poly": r["shared_poly"]}
            w["experts"] = (params["experts"]["gate_up"],
                            params["experts"]["down"],
                            params["experts"]["poly"], k * held)
        return w

    return {"embed": params["embed"], "norm": params["norm"],
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
    return lambda a, b: keep(keep(a) @ keep(b.astype(jnp.float32)))


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight.astype(jnp.float32)


def _rope(x, positions, theta):
    """Interleaved RoPE: the pairs (x[2i], x[2i+1]) turn by
    ``position * theta^(-2i/d)``.  x: (S, ..., d); positions: (S,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angles = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def poly_norm(z, w, scale, clamp, eps):
    """PolyNorm over the last dim; w = [w1, w2, w3, b]."""
    w = w.astype(jnp.float32)
    out = sum(w[i] * z ** (i + 1) * jax.lax.rsqrt(
        jnp.mean(z ** (2 * (i + 1)), -1, keepdims=True) + eps)
        for i in range(3))
    return scale * (out + jnp.clip(w[3], -clamp, clamp))


def sinkhorn(m, iters):
    """(..., n, n) positive -> ``iters`` times rows divided by their sums,
    then columns by theirs."""
    for _ in range(iters):
        m = m / m.sum(-1, keepdims=True)
        m = m / m.sum(-2, keepdims=True)
    return m


def _static(config):
    """The hashable part of a configuration the jitted pieces close over."""
    return tuple((k, config[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "num_noise_heads",
        "head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "rms_norm_eps", "rope_theta", "mhc_expansion_rate",
        "mhc_sinkhorn_iters", "polynorm_output_scale", "polynorm_bias_clamp",
        "hidden_clamp", "experts_top_k", "route_norm", "route_scale")) \
        + (("lambda_zero", bool(config.get("control_lambda_zero"))),)


def _mhc_pre(X, p, c, mm):
    """X (S, n, E) -> u (S, E), H_post (S, n), H_res (S, n, n)."""
    S, n, E = X.shape
    flat = _rms_norm(X.reshape(S, n * E), p["norm"], c["rms_norm_eps"])
    abc = mm(flat, p["phi"])
    alpha, beta = p["alpha"].astype(jnp.float32), \
        p["beta"].astype(jnp.float32)
    h_pre = jax.nn.sigmoid(alpha[0] * abc[:, :n] + beta[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * abc[:, n:2 * n] + beta[n:2 * n])
    h_res = sinkhorn(jnp.exp((alpha[2] * abc[:, 2 * n:] + beta[2 * n:])
                             .reshape(S, n, n)), c["mhc_sinkhorn_iters"])
    return jnp.einsum("si,sie->se", h_pre, X), h_post, h_res


def _mhc_post(X, y, h_post, h_res, c):
    out = jnp.einsum("sij,sje->sie", h_res, X) + h_post[:, :, None] \
        * y[:, None, :]
    return jnp.clip(out, -c["hidden_clamp"], c["hidden_clamp"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _ref_keys(X, p, start, static, bits):
    """A block's part of one layer's keys and values: X (rows, n, E) ->
    k_nope (Hkv, rows, Dn), k_rope (rows, Dr), v (Hkv, rows, Dv)."""
    c = dict(static)
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        rows = X.shape[0]
        R, Hkv = c["kv_lora_rank"], c["num_key_value_heads"]
        Dn = c["head_dim"] - c["qk_rope_head_dim"]
        u, _, _ = _mhc_pre(X, p["mhc"][0], c, mm)
        h = _rms_norm(u, p["attn_norm"], c["rms_norm_eps"])
        kv = mm(h, p["kv_a"])
        c_kv = _rms_norm(kv[:, :R], p["kv_a_norm"], c["rms_norm_eps"])
        k_rope = _rope(kv[:, R:], start + jnp.arange(rows), c["rope_theta"])
        expanded = mm(c_kv, p["kv_b"]).reshape(rows, Hkv, -1) \
            .transpose(1, 0, 2)
        return expanded[..., :Dn], k_rope, expanded[..., Dn:]


_ROWS = 1024        # query rows a block: through every layer, then the next
_Q_ROWS = 256       # query rows whose (G, rows, keys) scores are alive at once
_KEY_BUCKET = 8192  # keys a block of query rows is given, rounded up
_TILE_ROWS = 64     # rows of one expert computed in one go
_HEAD_ROWS = 128    # rows of the head computed in one call


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _ref_attention(X, p, k_nope, k_rope, v, start, k_start, window, static,
                   bits):
    """The attention sublayer of a block: X (rows, n, E) at positions
    ``start ..``, against keys at positions ``k_start ..`` (rows of them
    past the block's last position are padding and masked).  A key/value
    head and ``_Q_ROWS`` queries at a time."""
    c = dict(static)
    keep = _kept(bits)
    mm = _matmul(keep)
    with jax.default_matmul_precision("highest"):
        rows = X.shape[0]
        H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
        D, Dr, Dv = c["head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
        signal = H - c["num_noise_heads"]
        per = signal // Hkv
        pos = start + jnp.arange(rows)
        u, h_post, h_res = _mhc_pre(X, p["mhc"][0], c, mm)
        h = _rms_norm(u, p["attn_norm"], c["rms_norm_eps"])
        c_q = _rms_norm(mm(h, p["q_a"]), p["q_a_norm"], c["rms_norm_eps"])
        q = mm(c_q, p["q_b"]).reshape(rows, H, D)
        q = jnp.concatenate([q[..., :D - Dr],
                             _rope(q[..., D - Dr:], pos, c["rope_theta"])],
                            -1) * D ** -0.5
        # by key/value head: its signal heads, then its noise head
        qg = keep(jnp.concatenate([q[:, :signal].reshape(rows, Hkv, per, D),
                                   q[:, signal:, None]], axis=2))
        k_nope, k_rope, v = keep(k_nope), keep(k_rope), keep(v)
        kpos = k_start + jnp.arange(k_rope.shape[0])
        n_blocks = rows // _Q_ROWS

        def block(args):
            qb, qpos = args             # (q_rows, Hkv, per + 1, D), (q_rows,)
            seen = kpos[None, :] <= qpos[:, None]
            if window is not None:
                seen = seen & (kpos[None, :] > qpos[:, None] - window)

            def head(args):
                # the rotary key is one array for every head: its part of
                # the scores is added, never copied beside each head's own
                qj, kj, vj = args
                s = keep(jnp.einsum("qgd,kd->gqk", qj[..., :D - Dr], kj)
                         + jnp.einsum("qgd,kd->gqk", qj[..., D - Dr:],
                                      k_rope))
                w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf),
                                   axis=-1)
                return keep(jnp.einsum("gqk,kv->qgv", keep(w), vj))

            return jax.lax.map(head, (qb.transpose(1, 0, 2, 3), k_nope, v))

        a = jax.lax.map(block, (
            qg.reshape(n_blocks, _Q_ROWS, Hkv, per + 1, D),
            pos.reshape(n_blocks, _Q_ROWS)))    # (nb, Hkv, q, per + 1, Dv)
        a = a.transpose(0, 2, 1, 3, 4).reshape(rows, Hkv, per + 1, Dv)
        lam = jax.nn.sigmoid(mm(h, p["lam"])).reshape(rows, Hkv, per, 1)
        if c["lambda_zero"]:
            lam = jnp.zeros_like(lam)
        o = (a[:, :, :per] - lam * a[:, :, per:]).reshape(rows, signal * Dv)
        y = mm(jax.nn.sigmoid(mm(h, p["gate"])) * o, p["o"])
        return _mhc_post(X, y, h_post, h_res, c)


def _poly_ffn(x, w, c, mm):
    inner = w["down"].shape[0]
    z = mm(x, w["gate_up"])
    return mm(poly_norm(z[:, :inner], w["poly"], c["polynorm_output_scale"],
                        c["polynorm_bias_clamp"], c["rms_norm_eps"])
              * z[:, inner:], w["down"])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _ref_ffn_pre(X, p, static, bits):
    """The feed-forward sublayer up to F: its mixes, F's input, and for a
    dense layer F itself, for a routed one the shared expert and the
    routing (weights (rows, k), ids (rows, k))."""
    c = dict(static)
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        u, h_post, h_res = _mhc_pre(X, p["mhc"][1], c, mm)
        h = _rms_norm(u, p["ffn_norm"], c["rms_norm_eps"])
        if "dense" in p:
            return h, h_post, h_res, _poly_ffn(h, p["dense"], c, mm), None
        scores = jax.nn.sigmoid(mm(h, p["router"]))
        weights, ids = jax.lax.top_k(scores, c["experts_top_k"])
        if c["route_norm"]:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return h, h_post, h_res, _poly_ffn(h, p["shared"], c, mm), \
            (weights * c["route_scale"], ids)


@functools.partial(jax.jit, static_argnums=(10, 11))
def _ref_add_experts(y, h, gate_up, down, poly, first, n_tiles, tile_expert,
                     rows, weight, static, bits):
    """``y + sum_e w_e expert_e(h)`` over tiles of ``_TILE_ROWS`` (token,
    choice) pairs that chose ONE held expert each: tile i reads the tokens
    ``rows[i]`` and expert ``tile_expert[i]``'s matrices (upcast from where
    they lie in the program's tensors, ``first`` on) and adds its result
    times ``weight[i]`` (zero on a tile's padding) to those tokens."""
    c = dict(static)
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        def tile(i, out):
            at = first + tile_expert[i]
            w = {"gate_up": jax.lax.dynamic_index_in_dim(
                     gate_up, at, keepdims=False),
                 "down": jax.lax.dynamic_index_in_dim(
                     down, at, keepdims=False),
                 "poly": jax.lax.dynamic_index_in_dim(
                     poly, at, keepdims=False)}
            return out.at[rows[i]].add(
                weight[i][:, None] * _poly_ffn(h[rows[i]], w, c, mm))

        return jax.lax.fori_loop(0, n_tiles, tile, y)


def _ref_routed(y, h, routing, experts, config, static, bits):
    """The held experts' part: the (token, choice) pairs on held experts
    laid out by expert on the host, each expert's padded to whole tiles."""
    weights, ids = routing
    first_held, held = int(config["first_expert_held"]), \
        int(config["num_experts_held"])
    S, k = ids.shape
    local = np.asarray(ids).reshape(-1) - first_held
    weights_host = np.asarray(weights).reshape(-1)
    mine = np.nonzero((local >= 0) & (local < held))[0]
    mine = mine[np.argsort(local[mine], kind="stable")]
    counts = np.bincount(local[mine], minlength=held)
    tiles = -(-counts // _TILE_ROWS)
    worst = S * k // _TILE_ROWS + held          # static: a block's worst
    rows = np.zeros((worst, _TILE_ROWS), np.int32)
    weight = np.zeros((worst, _TILE_ROWS), np.float32)
    tile_expert = np.zeros(worst, np.int32)
    at = pair = 0
    for e in range(held):
        pairs = mine[pair:pair + counts[e]]
        pair += counts[e]
        flat_rows = rows[at:at + tiles[e]].reshape(-1)
        flat_weight = weight[at:at + tiles[e]].reshape(-1)
        flat_rows[:len(pairs)] = pairs // k
        flat_weight[:len(pairs)] = weights_host[pairs]
        tile_expert[at:at + tiles[e]] = e
        at += tiles[e]
    gate_up, down, poly, first = experts
    return _ref_add_experts(y, h, gate_up, down, poly, jnp.int32(first),
                            jnp.int32(at), tile_expert, rows, weight, static,
                            bits)


@functools.partial(jax.jit, static_argnums=(4,))
def _ref_ffn_post(X, y, h_post, h_res, static):
    with jax.default_matmul_precision("highest"):
        return _mhc_post(X, y, h_post, h_res, dict(static))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _ref_head(X, norm, head, eps, n, bits):
    mm = _matmul(_kept(bits))
    with jax.default_matmul_precision("highest"):
        x = X.reshape(X.shape[0], n, -1).sum(axis=1)
        return mm(_rms_norm(x, norm, eps), head)


def _reach(window):
    """Blocks before its own that a block of a sliding layer can see."""
    return -(-(window - 1) // _ROWS)


@functools.partial(jax.jit, donate_argnums=0)
def _put(kept, block, at):
    """A block's keys and values into the layer's kept ones, in place."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(k, b, at, axis)
                 for k, b, axis in zip(kept, block, (1, 0, 1)))


def _keep(kept, block, b, window):
    """The keys and values a layer keeps, with block ``b``'s in: ``(k_nope,
    k_rope, v)`` and the position of their first row.  A full layer keeps
    every block in ONE buffer a bucket long (grown a bucket at a time and
    written in place: no second copy of 0.9 GB beside the first); a sliding
    layer the blocks a window reaches, padded to as many as it ever does.
    The padding stands behind the block's last position, where the mask
    hides it."""
    def padded(arrays, rows):
        return tuple(jnp.pad(a, [(0, rows - a.shape[axis]) if d == axis
                                 else (0, 0) for d in range(a.ndim)])
                     for a, axis in zip(arrays, (1, 0, 1)))

    if window is None:
        need = -(-(b + 1) * _ROWS // _KEY_BUCKET) * _KEY_BUCKET
        if kept is None:
            return padded(block, need), 0
        arrays, _ = kept
        if arrays[1].shape[0] < need:
            arrays = padded(arrays, need)
        return _put(arrays, block, jnp.int32(b * _ROWS)), 0
    first = max(0, b - _reach(window))
    earlier = [] if kept is None else kept[2]
    blocks = earlier[len(earlier) - (b - first):] + [block]
    arrays = tuple(jnp.concatenate([blk[i] for blk in blocks], axis=axis)
                   for i, axis in enumerate((1, 0, 1)))
    return padded(arrays, (_reach(window) + 1) * _ROWS), first * _ROWS, blocks


def reference_logits(weights, config, ids, rows=None, control_bits=None):
    """(1, S) token ids -> float32 logits: (1, S, vocab_size) with
    ``rows=None``, else (1, len(rows), vocab_size), the head applied to the
    positions ``rows`` and to no others.  The sequence is cut after the last
    judged row and padded to whole blocks of ``_ROWS`` inside (the harness
    pads every checked request to its longest; causal attention keeps what
    lies behind a row out of it); the last layer's attention and
    feed-forward are computed for the blocks that hold judged rows only.
    ``control_bits``: not the reference but its control, every matmul's
    inputs and result rounded to that many significand bits (4: about fp8,
    the nearest precision under the bf16 the configuration states), which
    the rule of ``served_check`` has to refuse.  The controls of the
    MECHANISMS are configurations (:func:`controls_of`)."""
    ids = np.asarray(ids, np.int32)
    assert ids.shape[0] == 1, "the reference takes one sequence at a time"
    n = ids.shape[1] if rows is None else int(np.max(rows)) + 1
    blocks = -(-n // _ROWS)
    padded = np.zeros(blocks * _ROWS, np.int32)
    padded[:min(n, ids.shape[1])] = ids[0, :n]
    L, static = config["num_hidden_layers"], _static(config)
    streams = config["mhc_expansion_rate"]
    first_kept = 0 if rows is None else int(np.min(rows)) // _ROWS
    layers = [weights["layer"](i) for i in range(L)]
    windows = [None if _is_full(config, i)
               or config["sliding_window"] >= config["max_position_embeddings"]
               else int(config["sliding_window"]) for i in range(L)]
    kept = [None] * L       # layer -> what _keep holds of earlier blocks
    out = {}
    for b in range(blocks):
        start = jnp.int32(b * _ROWS)
        x = weights["embed"][padded[b * _ROWS:(b + 1) * _ROWS]] \
            .astype(jnp.float32)
        X = jnp.tile(x[:, None, :], (1, streams, 1))
        for i, p in enumerate(layers):
            kept[i] = _keep(kept[i], _ref_keys(X, p, start, static,
                                               control_bits), b, windows[i])
            if i == L - 1 and b < first_kept:
                break                   # its keys were all that was needed
            (k_nope, k_rope, v), k_start = kept[i][:2]
            X = _ref_attention(X, p, k_nope, k_rope, v, start,
                               jnp.int32(k_start), windows[i], static,
                               control_bits)
            h, h_post, h_res, y, routing = _ref_ffn_pre(
                X, {k: v for k, v in p.items() if k != "experts"}, static,
                control_bits)
            if routing is not None:
                y = _ref_routed(y, h, routing, p["experts"], config, static,
                                control_bits)
            X = _ref_ffn_post(X, y, h_post, h_res, static)
        else:
            out[b] = X.reshape(_ROWS, -1)

    def head(x):
        return _ref_head(x, weights["norm"], weights["head"],
                         config["rms_norm_eps"], streams, control_bits)

    if rows is None:
        return jnp.concatenate(
            [head(out[b][i:i + _HEAD_ROWS]) for b in range(blocks)
             for i in range(0, _ROWS, _HEAD_ROWS)])[None, :ids.shape[1]]
    x = jnp.concatenate([out[b] for b in range(first_kept, blocks)])
    rows = np.asarray(rows) - first_kept * _ROWS
    take = np.resize(rows, -(-len(rows) // _HEAD_ROWS) * _HEAD_ROWS)
    heads = [head(x[take[i:i + _HEAD_ROWS]])
             for i in range(0, len(take), _HEAD_ROWS)]
    return jnp.concatenate(heads)[None, :len(rows)]


def controls_of(config):
    """The rule's controls: name -> (the configuration the control's forward
    is handed, ``control_bits``).  ``bits4``: the reference in the nearest
    precision under the configuration's bf16; and one control a MECHANISM,
    the reference with it taken out: lambda at zero (the signal heads
    alone), no window (every sliding layer sees everything), ``H_res``
    without Sinkhorn (``exp`` of its scores as they come).  A check that
    cannot tell the model from one of these guards nothing."""
    return {
        "bits4": (config, 4),
        "lambda_zero": (dict(config, control_lambda_zero=True), None),
        "no_window": (dict(
            config, sliding_window=config["max_position_embeddings"]), None),
        "no_sinkhorn": (dict(config, mhc_sinkhorn_iters=0), None),
    }


# ---------------------------------------------------------------------------
# the rule for served tokens
# ---------------------------------------------------------------------------
# The served path computes in bf16, the reference in f32; a routed model
# chooses discretely (``mistral4.py``, ``mellum.py``), so the rule keeps the
# routed cells' form: a share of rows that must lie near, and a bound in the
# row's own logit sigma that NO row may pass.  The numbers are measured on
# the chip under the cell's traffic (PERF.md, section 6, PR 46).
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
            "share": "routing chooses discretely, so under one row in a "
                     "hundred follows another expert than the reference's "
                     "and lies 4-21 spacings out: the program's smallest "
                     "share 0.9918 over ~12,700 rows of 8 runs on the chip "
                     "(0.9918-0.9972); the 4-bit control's 0.525 (worst row "
                     "56 spacings), lambda at zero 0.034, no window 0.0007, "
                     "H_res without Sinkhorn 0.015 (185-284 spacings) on "
                     "their one seed (PERF.md section 6, PR 46).  The limit "
                     "stands between 0.992 and 0.525 with room on both "
                     "sides; any matmul of the served path computed below "
                     "bf16, and each mechanism taken out, moves most rows "
                     "out",
            "every_row_sigma": "what a token picked blindly (~4 sigma under "
                               "at 27,520 ids), a broken head or a MECHANISM "
                               "taken out fails (the three mechanism "
                               "controls' worst rows 4.5-6.9 sigma under); "
                               "the program's worst row lay 0.50 sigma "
                               "under, the 4-bit control's 1.40: this bound "
                               "does not separate those two and is not "
                               "meant to, share does",
        },
        # rotary positions: no table to fill, so the longest checked
        # request rounded up to a block of query rows, not the cap
        "width": lambda longest: -(-int(longest) // _ROWS) * _ROWS,
    }


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _widths(config):
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    Dr, Dv = config["qk_rope_head_dim"], config["v_head_dim"]
    return {"E": config["hidden_size"], "H": H, "Hkv": Hkv,
            "signal": H - config["num_noise_heads"], "D": config["head_dim"],
            "Dn": config["head_dim"] - Dr, "Dr": Dr, "Dv": Dv,
            "Q": config["q_lora_rank"], "R": config["kv_lora_rank"],
            "n": config["mhc_expansion_rate"]}


def attention_params(config):
    """q_a, q_b, kv_a, kv_b, lambda, the gate, o, and the two inner norms."""
    w = _widths(config)
    E, out = w["E"], w["signal"] * w["Dv"]
    return E * w["Q"] + w["Q"] + w["Q"] * w["H"] * w["D"] \
        + E * (w["R"] + w["Dr"]) + w["R"] \
        + w["R"] * w["Hkv"] * (w["Dn"] + w["Dv"]) \
        + E * w["signal"] + E * out + out * E


def mhc_params(config):
    """One sublayer's mixes: the norm over the streams, Phi, beta, alpha."""
    w = _widths(config)
    wide, mixes = w["n"] * w["E"], 2 * w["n"] + w["n"] ** 2
    return wide + wide * mixes + mixes + 3


def ffn_params(config, width):
    """One PolyNorm feed-forward (dense, shared or an expert) of a width."""
    return 3 * config["hidden_size"] * width + 4


def layers_of(config):
    """(full layers, sliding layers, dense layers, routed layers) held."""
    L = config["num_hidden_layers"]
    full = sum(_is_full(config, i) for i in range(L))
    dense = sum(_is_dense(config, i) for i in range(L))
    return full, L - full, dense, L - dense


def _layer_params(config, dense, experts):
    """One held layer: attention, two sublayers' mixes, two norms, and its
    feed-forward: the dense one, or the router, the shared expert and
    ``experts`` routed experts."""
    E = config["hidden_size"]
    outside = attention_params(config) + 2 * mhc_params(config) + 2 * E
    if dense:
        return outside + ffn_params(config, config["intermediate_size"])
    return outside + E * config["num_experts"] \
        + (config["num_shared_experts"] + experts) \
        * ffn_params(config, config["moe_intermediate_size"])


def n_params(config):
    """Parameters this chip holds: the held layers with the held experts,
    the held vocabulary's embedding and head, the final norm.  The issue's
    table rounds to 3,928 M; the exact leaves (the inner norms, the mixes'
    beta and alpha, PolyNorm's four an expert) are counted here."""
    E = config["hidden_size"]
    _, _, dense, routed = layers_of(config)
    return dense * _layer_params(config, True, 0) \
        + routed * _layer_params(config, False, config["num_experts_held"]) \
        + 2 * config["vocab_size"] * E + E


def counters_are_of(config, program):
    """Whether a program's counters (``harness/roofline.programs``) come
    from a model of THIS configuration's sizes, as far as they can tell: it
    counts what its lanes attended by cache group, the Sinkhorn error of its
    mixes, and the held experts it could read over its routed layers.  A
    reader entered for one configuration gives nothing for another's run,
    where it would divide by the wrong sizes."""
    return any(k in program for k in ("attn_keys_full", "attn_pairs_full")) \
        and "mhc_sinkhorn_err_ppm" in program \
        and program.get("moe_expert_slots") \
        == config["num_experts_held"] * layers_of(config)[3]


def expert_bytes(config, weight_bytes=2):
    """One routed expert's three matrices, as held."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * weight_bytes


def kv_row_bytes(config, kv_bytes=2):
    """The latent row of one token in one layer, as cached values (the pool
    stores it padded to whole lanes: ``bytes`` in the configuration's
    file)."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * kv_bytes


def decode_step_bytes(config, *, keys_full, keys_window, weight_bytes,
                      kv_bytes, experts_touched=None):
    """Bytes one decode step has to move at the least: every weight outside
    the routed experts once (the head and the final norm with them; the
    embedding is read a row a lane and left out), the experts touched a
    routed layer (``experts_touched``; None: all held), and the latent rows
    its live lanes attend: ``keys_full`` rows (the lanes' contexts, summed)
    in every full layer, ``keys_window`` (a lane at most the window) in
    every sliding layer."""
    E = config["hidden_size"]
    full, sliding, _, routed = layers_of(config)
    held = config["num_experts_held"]
    touched = held if experts_touched is None else experts_touched
    outside = n_params(config) - config["vocab_size"] * E \
        - routed * held * ffn_params(config, config["moe_intermediate_size"])
    rows = (full * keys_full + sliding * keys_window) \
        * kv_row_bytes(config, kv_bytes)
    return outside * weight_bytes \
        + routed * touched * expert_bytes(config, weight_bytes) + rows


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


def prefill_attn_cost(config, *, pairs, queries, act_bytes=2):
    """Operations and bytes of ONE call of the rectangle attention kernel
    (``gdla_prefill_attn_<group>``: one layer of that kind) over ``pairs``
    (query, key) pairs that are causal and inside the window, of ``queries``
    query rows: QK^T over nope | rope and PV over those pairs only, all 80
    heads; a key/value head's rows are read once for each query head that
    shares it and each block of 1,024 query rows (the kernel's grid), the
    shared rotary key with them, every query and output row once."""
    w = _widths(config)
    flops = 2 * pairs * w["H"] * (w["D"] + w["Dv"])
    block_q = 1024                          # rect_attention's default tile
    moved = (pairs // block_q * w["H"] * (w["D"] + w["Dv"])
             + queries * w["H"] * (w["D"] + w["Dv"])) * act_bytes
    return flops, moved


def decode_attn_cost(config, *, keys, kv_bytes=2):
    """Operations and bytes of ONE call of the paged latent decode attention
    (``gdla_paged_decode_attn_<group>``: one layer of that kind) whose live
    lanes attend ``keys`` latent rows together (a sliding layer's lane at
    most its window: the engine's counter is capped so): every head's
    scores over latent | rope and its probabilities against the latent, and
    the rows read once, as cached values and not as the whole padded pages
    they are copied by."""
    w = _widths(config)
    return 2 * keys * w["H"] * (2 * w["R"] + w["Dr"]), \
        keys * kv_row_bytes(config, kv_bytes)


def _runs_of(config):
    """The held layers as runs of one kind, as the program traces them (a
    run is one call site of each kernel): ``[dense, full, layers]``."""
    kinds = [(_is_dense(config, i), _is_full(config, i))
             for i in range(config["num_hidden_layers"])]
    return [[*kind, len(list(run))] for kind, run in itertools.groupby(kinds)]


def attention_call_sites(config):
    """Cache group -> the layers each of its attention kernels' call sites
    runs."""
    runs = _runs_of(config)
    return {"full": [n for _, full, n in runs if full],
            "window": [n for _, full, n in runs if not full]}


def routed_call_sites(config, final=True):
    """The layers each call site of a grouped matmul runs.  ``final`` False:
    in a chunk program that is not a prompt's last.  There nothing reads
    the last layer's feed-forward (no logits are taken, the next chunk reads
    the cache), so where that layer is routed its experts' matmuls are not
    in the compiled program, though its router still counts its rows."""
    runs = _runs_of(config)
    if not final:
        runs[-1][2] -= 1
    return [n for dense, _, n in runs if not dense and n]
