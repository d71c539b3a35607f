"""Autoregressive generation for GPT-2 with a KV cache.

The reference snapshot has no generation utility (inference arrived in
later DeepSpeed); this is a TPU-first extension: the whole decode loop is
ONE `lax.scan` inside jit (static token count, no host round-trips), the
KV cache is a preallocated (L, B, H, S_max, D) pair updated with
`dynamic_update_slice`, and sampling is counter-based (one PRNG key per
step, folded from a base key).

The decode math consumes the SAME params pytree as GPT2LMHead — stacked
(scan_layers=True) or per-layer — and a parity test pins it to the
training forward (tests/unit/test_generation.py).
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def _dense(x, p):
    return x @ p["kernel"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _block_params(params, cfg):
    """Yield per-layer param trees; handles scan-stacked layouts."""
    if cfg.scan_layers:
        stacked = params["h"]["block"]
        return [jax.tree_util.tree_map(lambda l, i=i: l[i], stacked)
                for i in range(cfg.n_layer)]
    return [params[f"h_{i}"] for i in range(cfg.n_layer)]


def _split_heads(t, B, T, H, D):
    return t.reshape(B, T, H, D).transpose(0, 2, 1, 3)  # (B, H, T, D)


def _attn_core(q, keys, values, valid, p, out_dtype):
    """Masked attention shared by every decode surface: the contiguous
    KV cache here, the causal prefill, and the serving engine's paged
    pool (deepspeed_tpu/serving/engine.py).  q/keys/values: (B, H, Q, D)
    and (B, H, K, D); ``valid`` broadcasts against the (B, H, Q, K)
    score tensor.  Scores accumulate in f32 and masked positions score
    -1e30, which softmax turns into EXACT zeros — so a path that gathers
    a wider, padded key view (the paged pool) produces bit-identical
    outputs to one that attends a tight contiguous cache."""
    B, H, Q, D = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q, keys,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    s = jnp.where(valid, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(out_dtype)
    y = jnp.einsum("bhqk,bhkd->bhqd", probs, values)   # (B, H, Q, D)
    y = y.transpose(0, 2, 1, 3).reshape(B, Q, H * D)
    return _dense(y, p["c_proj"])


def _attn_decode(x, p, cache_k, cache_v, pos, cfg):
    """One-token attention against the cache. x: (B, 1, E); cache_k/v:
    (B, H, S_max, D); pos: scalar int32 current position."""
    B = x.shape[0]
    H, D = cfg.n_head, cfg.head_dim
    qkv = _dense(x, p["c_attn"])                       # (B, 1, 3E)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = (_split_heads(t, B, 1, H, D) for t in (q, k, v))
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, 0, pos, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, 0, pos, 0))
    # mask out the not-yet-written tail of the cache
    valid = jnp.arange(cache_k.shape[2]) <= pos        # (S_max,)
    out = _attn_core(q, cache_k, cache_v, valid[None, None, None, :], p,
                     x.dtype)
    return out, cache_k, cache_v


def _moe_ffn(x, mp, cfg):
    """Params-level MoE FFN for generation — the same dense top-k gating +
    stacked-expert einsums the training layer runs (moe/sharded_moe.py),
    deterministic (no jitter), gated with cfg.moe_capacity_factor exactly
    like the train=False forward (GPT-2's blocks do not set an eval
    capacity factor). x: (B, T, M).

    Capacity semantics: prefill gates the whole prompt per batch row
    exactly like the training forward; decode gates ONE token per step, so
    a decoded token never competes with its predecessors for expert slots
    (the min_capacity floor guarantees it a slot). Identical to the
    training forward whenever nothing drops; under capacity pressure
    decode keeps tokens the training pass would drop."""
    from deepspeed_tpu.moe.sharded_moe import top_k_gating

    dtype = x.dtype
    logits = x.astype(jnp.float32) @ mp["router"]["kernel"]    # (B, T, E)
    # single-token decode groups occupy at most one slot per chosen expert:
    # capacity=k is exact, and skips the min_capacity=4 floor that would
    # oversize the expert GEMMs 2-4x per generated token
    cap = cfg.moe_top_k if x.shape[1] == 1 else None
    combine, dispatch, _, _ = top_k_gating(
        logits, k=cfg.moe_top_k, capacity=cap,
        capacity_factor=cfg.moe_capacity_factor)
    ex = mp["experts"]
    E = cfg.moe_num_experts
    d = jnp.einsum("gsec,gsm->egcm", dispatch.astype(dtype), x)
    B, C = d.shape[1], d.shape[2]
    d = d.reshape(E, B * C, -1)
    h = jnp.einsum("enm,emf->enf", d, ex["w_in"].astype(dtype)) \
        + ex["b_in"].astype(dtype)[:, None, :]
    h = jax.nn.gelu(h, approximate=True)
    y = jnp.einsum("enf,efm->enm", h, ex["w_out"].astype(dtype)) \
        + ex["b_out"].astype(dtype)[:, None, :]
    y = y.reshape(E, B, C, -1)
    # dropped tokens get zero here and ride the residual, like training
    return jnp.einsum("egcm,gsec->gsm", y, combine.astype(dtype))


def _ffn(x, bp, cfg):
    """Dense-MLP or MoE feed-forward, keyed on the block's param names."""
    if "moe" in bp:
        return _moe_ffn(x, bp["moe"], cfg)
    mp = bp["mlp"]
    h = jax.nn.gelu(_dense(x, mp["c_fc"]), approximate=True)
    return _dense(h, mp["c_proj"])


class GPT2Decoder:
    """GPT-2 under the serving engine's decoder-block contract
    (``serving/decoder.py``), made of the functions above: learned
    positions in the embedding, ``c_attn`` split into equal Q, K and V
    heads, the shared masked core over the gathered page view, ``c_proj``,
    the GELU MLP, LayerNorm and the tied head.  Served from the tree
    :meth:`hold` states: the weights in ``cfg.dtype``, cast once by the
    engine and never in a program; LayerNorm's in f32."""

    stat_names = ()             # no block reports counters
    scan_layers = False         # the engine's loop; the programs as they were

    def __init__(self, cfg):
        assert not getattr(cfg, "moe_num_experts", 0), \
            "InferenceEngine serves GPT-2's dense blocks only: chunked " \
            "prefill changes its MoE capacity-gating semantics " \
            "(generation._moe_ffn gates whole prompts); use " \
            "models.generation.generate for a GPT-2 MoE"
        self.cfg = cfg
        self.dtype = cfg.dtype
        self.n_layer = cfg.n_layer

    def hold(self, params):
        """What ``_dense``, ``embed`` and the tied head cast, in the dtype
        they cast to; the LayerNorm leaves as given, for ``_ln`` reads
        them in f32.  Their ``.astype`` calls stay: no-ops on this tree,
        and ``generate`` shares them over a tree as given."""
        from deepspeed_tpu.serving.decoder import held_as

        return held_as(params, self.dtype, keep=lambda path: any(
            str(getattr(k, "key", "")).startswith("ln_") for k in path))

    def embed(self, params, tokens, positions):
        return params["wte"].astype(self.dtype)[tokens] \
            + params["wpe"].astype(self.dtype)[positions]

    def block(self, params, l, x, cache):
        cfg = self.cfg
        bp = jax.tree_util.tree_map(lambda a: a[l], params["h"]["block"]) \
            if cfg.scan_layers else params[f"h_{l}"]
        B, T, _ = x.shape
        H, D = cfg.n_head, cfg.head_dim
        h = _ln(x, bp["ln_1"], cfg.layer_norm_epsilon)
        qkv = _dense(h, bp["attn"]["c_attn"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = _split_heads(q, B, T, H, D)                  # (B, H, T, D)
        cache.write_heads(0, k.reshape(B * T, H, D))
        cache.write_heads(1, v.reshape(B * T, H, D))
        x = x + cache.attend_heads(q, H, bp["attn"])
        return x + _ffn(_ln(x, bp["ln_2"], cfg.layer_norm_epsilon), bp, cfg)

    def final_norm(self, params, x):
        return _ln(x, params["ln_f"], self.cfg.layer_norm_epsilon)

    def logits(self, params, xe):
        return _lm_logits(params, self.cfg, xe)


def _block_decode(x, bp, ck, cv, pos, cfg):
    a, ck, cv = _attn_decode(
        _ln(x, bp["ln_1"], cfg.layer_norm_epsilon), bp["attn"], ck, cv,
        pos, cfg)
    x = x + a
    h = _ln(x, bp["ln_2"], cfg.layer_norm_epsilon)
    x = x + _ffn(h, bp, cfg)
    return x, ck, cv


def _attn_prefill(x, p, cfg):
    """Causal attention over the whole prompt; returns (out, k, v) with
    k/v shaped (B, H, S0, D) for cache seeding."""
    B, S, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    qkv = _dense(x, p["c_attn"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = (_split_heads(t, B, S, H, D) for t in (q, k, v))
    mask = jnp.tril(jnp.ones((S, S), bool))
    return _attn_core(q, k, v, mask[None, None], p, x.dtype), k, v


def _lm_logits(params, cfg, xe):
    """Tied LM head over (B, E) final hidden states, MXU-alignment pad
    columns dropped so sampling never picks a pad id.  Shared by the
    prompt prefill, single-token decode, and the serving engine's paged
    decode/prefill (deepspeed_tpu/serving/engine.py) — one head, one
    dtype policy, bit-identical logits across every decode surface."""
    logits = jnp.einsum("be,ve->bv", xe, params["wte"].astype(cfg.dtype))
    return logits[:, :cfg.vocab_size].astype(jnp.float32)


def _prefill(params, cfg, tokens):
    """One batched forward over the (B, S0) prompt: returns the logits at
    the last prompt position and per-layer K/V for cache seeding."""
    S0 = tokens.shape[1]
    x = params["wte"].astype(cfg.dtype)[tokens] \
        + params["wpe"].astype(cfg.dtype)[None, :S0]
    ks, vs = [], []
    for bp in _block_params(params, cfg):
        a, k, v = _attn_prefill(
            _ln(x, bp["ln_1"], cfg.layer_norm_epsilon), bp["attn"], cfg)
        x = x + a
        h = _ln(x, bp["ln_2"], cfg.layer_norm_epsilon)
        x = x + _ffn(h, bp, cfg)
        ks.append(k)
        vs.append(v)
    x = _ln(x, params["ln_f"], cfg.layer_norm_epsilon)
    return _lm_logits(params, cfg, x[:, -1]), jnp.stack(ks), jnp.stack(vs)


def _forward_token(params, cfg, token, pos, caches_k, caches_v):
    """Embed one token, run all blocks against the cache, return logits.
    token: (B,) int32; caches: (L, B, H, S_max, D)."""
    wte = params["wte"]
    wpe = params["wpe"]
    x = wte.astype(cfg.dtype)[token][:, None, :] \
        + wpe.astype(cfg.dtype)[pos][None, None, :]    # (B, 1, E)
    blocks = _block_params(params, cfg)
    new_k, new_v = [], []
    for i, bp in enumerate(blocks):
        x, ck, cv = _block_decode(x, bp, caches_k[i], caches_v[i], pos, cfg)
        new_k.append(ck)
        new_v.append(cv)
    x = _ln(x, params["ln_f"], cfg.layer_norm_epsilon)
    return _lm_logits(params, cfg, x[:, 0]), \
        jnp.stack(new_k), jnp.stack(new_v)


def _sample(logits, key, temperature, top_k, top_p=0.0):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    use_k = top_k and top_k < logits.shape[-1]
    use_p = top_p and top_p < 1.0
    if use_k or use_p:
        # ONE descending sort serves both filters (this runs per decode
        # step inside the scan — no reason to sort twice)
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        if use_k:
            # top_k >= vocab filters nothing; clamping keeps the arg safe
            logits = jnp.where(
                logits < sorted_desc[:, top_k - 1][:, None], -1e30, logits)
        if use_p:
            # nucleus sampling: keep the smallest prefix of the sorted
            # distribution whose mass reaches top_p (the top token always
            # survives — its EXCLUSIVE cumulative mass is 0 < top_p).
            # With top_k also active, masked tokens carry ~0 probability
            # here, so the nucleus is computed within the top-k set.
            if use_k:
                sorted_desc = jnp.where(
                    sorted_desc < sorted_desc[:, top_k - 1][:, None],
                    -1e30, sorted_desc)
            probs = jax.nn.softmax(sorted_desc, axis=-1)
            exclusive = jnp.cumsum(probs, axis=-1) - probs
            keep = exclusive < top_p
            cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf),
                             axis=-1, keepdims=True)
            logits = jnp.where(logits >= cutoff, logits, -1e30)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def generate(model, params, input_ids, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: float = 0.0, rng=None, num_beams: int = 1,
             eos_token_id: Optional[int] = None):
    """Generate `max_new_tokens` continuations. input_ids: (B, S0) int.
    temperature 0 = greedy; top_k / top_p (nucleus) filter the sampling
    distribution and compose (top_k first); num_beams > 1 switches to
    beam search (deterministic — incompatible with sampling). Returns
    (B, S0 + max_new_tokens) int32.

    eos_token_id: rows that emit it stop — every later position repeats
    the eos id. The program stays fixed-shape (the scan always runs
    max_new_tokens steps; finished rows just carry eos), which is the
    TPU-friendly formulation of early stopping.

    The prompt is consumed by ONE batched causal forward (prefill) that
    seeds the KV cache; decode then scans one token at a time.
    """
    cfg = model.config
    input_ids = jnp.asarray(input_ids, jnp.int32)
    if max_new_tokens <= 0:
        return np.asarray(input_ids)
    # a sign/range bug here would otherwise mask EVERY logit and emit
    # plausible-shaped garbage (token 0 forever) with no error
    assert 0.0 <= (top_p or 0.0) <= 1.0, f"top_p must be in [0, 1]: {top_p}"
    assert top_k is None or top_k >= 0, f"top_k must be >= 0: {top_k}"
    assert temperature >= 0.0, f"temperature must be >= 0: {temperature}"
    if num_beams > 1:
        assert temperature == 0.0 and not top_k and not top_p \
            and rng is None, \
            "beam search is deterministic; drop temperature/top_k/top_p/rng"
        assert eos_token_id is None, \
            "beam search is fixed-length; eos_token_id is not supported " \
            "with num_beams > 1 (length-normalized eos-aware scoring is a " \
            "different search)"
        return generate_beam(model, params, input_ids, max_new_tokens,
                             num_beams=num_beams)
    B, S0 = input_ids.shape
    S_max = S0 + max_new_tokens
    assert S_max <= cfg.n_positions, \
        f"{S_max} exceeds n_positions={cfg.n_positions}"
    L, H, D = cfg.n_layer, cfg.n_head, cfg.head_dim
    caches_k = jnp.zeros((L, B, H, S_max, D), cfg.dtype)
    caches_v = jnp.zeros((L, B, H, S_max, D), cfg.dtype)
    key = rng if rng is not None else jax.random.PRNGKey(0)

    # cfg is a frozen (hashable) dataclass, so the decode program caches
    # per (config, shapes, sampling) — repeat generate() calls reuse the
    # compiled scan instead of re-tracing a fresh closure
    run = _decode_fn(cfg, S0, S_max, float(temperature), int(top_k or 0),
                     float(top_p or 0.0),
                     int(eos_token_id) if eos_token_id is not None else -1)
    out = run(params, input_ids, caches_k, caches_v, key)
    seq = jnp.concatenate([input_ids, jnp.transpose(out)], axis=1)
    return np.asarray(seq)


def generate_beam(model, params, input_ids, max_new_tokens: int,
                  num_beams: int = 4):
    """Beam-search decode: return the highest-log-probability continuation
    among `num_beams` beams per batch row. input_ids: (B, S0) int; returns
    (B, S0 + max_new_tokens) int32.

    Fixed-length search (no EOS concept in this API), whole loop in ONE
    jitted lax.scan: beams live as a (B*W) batch sharing the KV-cache
    machinery of greedy decode, and each step's top-W reselection reorders
    the caches by gathering along the beam dim. num_beams=1 is exactly
    greedy decode."""
    cfg = model.config
    input_ids = jnp.asarray(input_ids, jnp.int32)
    if max_new_tokens <= 0:
        return np.asarray(input_ids)
    B, S0 = input_ids.shape
    W = int(num_beams)
    assert W >= 1
    assert W <= model.config.vocab_size, \
        f"num_beams={W} exceeds vocab_size={model.config.vocab_size}; " \
        f"top-k reselection cannot produce more beams than tokens"
    S_max = S0 + max_new_tokens
    assert S_max <= cfg.n_positions, \
        f"{S_max} exceeds n_positions={cfg.n_positions}"
    run = _beam_fn(cfg, S0, S_max, W)
    seq = run(params, input_ids)
    return np.asarray(seq)


@functools.lru_cache(maxsize=32)
def _beam_fn(cfg, S0, S_max, W):
    T = S_max - S0

    def run(params, tokens_in):
        B = tokens_in.shape[0]
        logits0, pk, pv = _prefill(params, cfg, tokens_in)   # (B,V), (L,B,H,S0,D)
        logp0 = jax.nn.log_softmax(logits0, axis=-1)         # (B, V)
        V = logp0.shape[-1]
        # seed beams with the prompt's top-W continuations
        scores, first = jax.lax.top_k(logp0, W)              # (B, W)
        # tile caches to (L, B*W, H, S_max, D), beam-major within batch
        def tile(c):
            c = jnp.pad(c, ((0, 0), (0, 0), (0, 0), (0, S_max - S0), (0, 0)))
            c = jnp.repeat(c, W, axis=1)
            return c
        ck, cv = tile(pk), tile(pv)
        toks = jnp.zeros((B, W, T), jnp.int32)
        toks = toks.at[:, :, 0].set(first)
        flat = lambda x: x.reshape(B * W)

        def step(carry, pos):
            toks, scores, ck, cv, prev = carry
            logits, ck, cv = _forward_token(params, cfg, flat(prev), pos,
                                            ck, cv)          # (B*W, V)
            logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, W, V)
            cand = scores[:, :, None] + logp                 # (B, W, V)
            scores, idx = jax.lax.top_k(cand.reshape(B, W * V), W)
            parent = idx // V                                # (B, W)
            nxt = (idx % V).astype(jnp.int32)
            # reorder beam state by parent: tokens-so-far and KV caches
            toks = jnp.take_along_axis(toks, parent[:, :, None], axis=1)
            toks = toks.at[:, :, pos - S0 + 1].set(nxt)
            gather = (jnp.arange(B)[:, None] * W + parent).reshape(-1)
            ck = jnp.take(ck, gather, axis=1)
            cv = jnp.take(cv, gather, axis=1)
            return (toks, scores, ck, cv, nxt), None

        if T > 1:
            (toks, scores, _, _, _), _ = jax.lax.scan(
                step, (toks, scores, ck, cv, first),
                jnp.arange(S0, S_max - 1))
        best = jnp.argmax(scores, axis=-1)                   # (B,)
        out = jnp.take_along_axis(
            toks, best[:, None, None], axis=1)[:, 0]         # (B, T)
        return jnp.concatenate([tokens_in, out], axis=1)

    return jax.jit(run)


@functools.lru_cache(maxsize=32)
def _decode_fn(cfg, S0, S_max, temperature, top_k, top_p=0.0, eos=-1):
    def run(params, tokens_in, caches_k, caches_v, key):
        # batched prefill over the prompt seeds positions [0, S0)
        logits0, pk, pv = _prefill(params, cfg, tokens_in)
        caches_k = jax.lax.dynamic_update_slice(
            caches_k, pk, (0, 0, 0, 0, 0))
        caches_v = jax.lax.dynamic_update_slice(
            caches_v, pv, (0, 0, 0, 0, 0))
        first = _sample(logits0, jax.random.fold_in(key, S0 - 1),
                        temperature, top_k, top_p)
        done0 = first == eos if eos >= 0 else jnp.zeros_like(first, bool)

        def step(carry, pos):
            tok, done, ck, cv = carry
            logits, ck, cv = _forward_token(params, cfg, tok, pos, ck, cv)
            nxt = _sample(logits, jax.random.fold_in(key, pos),
                          temperature, top_k, top_p)
            if eos >= 0:
                # finished rows keep emitting eos; the cache still advances
                # (harmless — nothing attends past a row's eos in the
                # returned sequence)
                nxt = jnp.where(done, jnp.int32(eos), nxt)
                done = done | (nxt == eos)
            return (nxt, done, ck, cv), nxt

        # decode steps consume tokens at positions S0 .. S_max-2
        (_, _, _, _), rest = jax.lax.scan(
            step, (first, done0, caches_k, caches_v),
            jnp.arange(S0, S_max - 1))
        return jnp.concatenate([first[None], rest], axis=0)  # (new, B)

    return jax.jit(run)
