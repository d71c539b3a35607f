"""Latent (MLA) attention over the serving engine's raw cache rows, and the
small pieces the DeepSeek-V3 family's blocks share (RMSNorm, interleaved
RoPE, SwiGLU).  ONE function for every model of the family the engine
serves (``models/mistral4.py``, ``models/longcat_flash.py``,
``models/motif.py``): what differs between them is an argument, not a copy.

``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> H heads of (nope | rope);
``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, ``k_r = RoPE(k_r)``
shared by all heads; ``[k_nope | v] = c_kv W_kvb``.  WHAT IS CACHED is
``[c_kv | k_r]``: ``kv_lora_rank + qk_rope_head_dim`` values a token, one raw
row, no separate value.  Prefill expands keys and values from the gathered
latent rows and attends with the rectangle kernel; decode absorbs ``W_kvb``
into the query and the output and attends over the latent rows themselves.
Same mathematics.
"""
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.rect_attention import \
    rect_flash_attention


def _rope_interleaved(x, cos, sin):
    """Rotate the pairs (x[2i], x[2i+1]) of the last dim; cos/sin broadcast
    against (..., d / 2).  f32 in, f32 out."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1) \
        .reshape(x.shape)


def _rms_norm(x, weight, eps, scale=1.0):
    """RMSNorm in f32; ``scale``: a constant the result carries besides
    (folded in before the one rounding to x's dtype)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    y = y * weight.astype(jnp.float32)
    if scale != 1.0:
        y = y * scale
    return y.astype(x.dtype)


def _swiglu(x, p):
    gate_up = (x @ p["gate_up"]).astype(jnp.float32)
    inner = p["down"].shape[0]
    h = (jax.nn.silu(gate_up[..., :inner]) * gate_up[..., inner:]) \
        .astype(x.dtype)
    return h @ p["down"]


def latent_attention(cfg, p, x, cache, *, q_scale, cos, sin,
                     latent_scale=1.0, row=0, kv_heads=None,
                     prefill_name=None, decode_name=None):
    """One latent attention over x (B, T, E), through the engine's cache
    hook (``serving/decoder.py``), output projection included where ``p``
    holds one.

    ``cfg`` states the sizes under the family's names
    (``num_attention_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``, ``rms_norm_eps``,
    ``pallas_interpret``); ``p`` holds ``q_a``, ``q_a_norm``, ``q_b``,
    ``kv_a``, ``kv_a_norm``, ``kv_b`` and, where the heads are to be
    projected here, ``o`` (without it the heads come back side by side,
    (B, T, H * Dv): a model that does something to them first projects them
    itself).  What differs by model:

    ``q_scale``
        what the query is multiplied by, once, in f32: the softmax scale
        and whatever else the model puts on it; a scalar or (B, T).
    ``cos``, ``sin``
        the model's rotary table at the queries' positions, (B, T, Dr/2).
    ``latent_scale``
        a constant on ``c_kv`` after its norm (keys and values both carry
        it; the rotary key does not).  It is folded in BEFORE the row is
        cached, so prefill and decode read it alike.
    ``row``
        which of the block's raw cache rows this attention writes and
        reads (``cache.write_rows`` / ``attend_rows`` / ``view_rows``).
    ``kv_heads``
        how many key/value heads ``kv_b`` up-projects the latent to (None:
        one a query head): query head h reads key/value head
        ``h // (H // kv_heads)``, in both kernels, no key repeated.
    ``prefill_name``, ``decode_name``
        what the two kernels are called in the compiled program (None:
        their own names).

    The WINDOW is the cache group's (``cache.window``, None in a group that
    keeps everything): each query then sees its last ``window`` positions,
    of a view whose first row stands at ``cache.k_start``.

    Decode (one query a lane) attends through ``cache.attend_rows``: the
    engine reads each lane's filled pages where they lie where it can
    (``ops/transformer/paged_attention.py``) and the gathered view
    elsewhere; prefill gathers the view itself."""
    B, T, _ = x.shape
    H, R = cfg.num_attention_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pos = cache.positions                               # (B, T)

    c_q = _rms_norm(x @ p["q_a"], p["q_a_norm"], cfg.rms_norm_eps)
    q = (c_q @ p["q_b"]).reshape(B, T, H, Dn + Dr).astype(jnp.float32)
    q = q * (q_scale[..., None, None] if jnp.ndim(q_scale) else q_scale)
    q_nope = q[..., :Dn].astype(x.dtype)
    q_rope = _rope_interleaved(q[..., Dn:], cos[:, :, None],
                               sin[:, :, None]).astype(x.dtype)

    kv = x @ p["kv_a"]                                  # (B, T, R + Dr)
    c_kv = _rms_norm(kv[..., :R], p["kv_a_norm"], cfg.rms_norm_eps,
                     latent_scale)
    k_rope = _rope_interleaved(kv[..., R:].astype(jnp.float32), cos,
                               sin).astype(x.dtype)
    cache.write_rows(row, jnp.concatenate([c_kv, k_rope], axis=-1)
                     .reshape(B * T, R + Dr))

    Hkv = H if kv_heads is None else kv_heads
    w_kvb = p["kv_b"].reshape(R, Hkv, Dn + Dv)
    names = lambda name: {} if name is None else {"name": name}  # noqa: E731
    if T == 1:
        # decode, absorbed: scores over the latent rows themselves (a head
        # a key/value head keeps the products it always had, so that the
        # two models that state no ``kv_heads`` compile to what they did)
        if kv_heads is None:
            q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_kvb[..., :Dn])
        else:
            q_lat = jnp.einsum(
                "bkgd,ckd->bkgc", q_nope[:, 0].reshape(B, Hkv, H // Hkv, Dn),
                w_kvb[..., :Dn]).reshape(B, H, R)
        o_lat = cache.attend_rows(row, q_lat, q_rope[:, 0], R,
                                  **names(decode_name))
        if kv_heads is None:
            out = jnp.einsum("bhc,chv->bhv", o_lat, w_kvb[..., Dn:])
        else:
            out = jnp.einsum("bkgc,ckv->bkgv",
                             o_lat.reshape(B, Hkv, H // Hkv, R),
                             w_kvb[..., Dn:])
        out = out.reshape(B, 1, H * Dv)
    else:
        # prefill, expanded: one sequence, keys and values of every
        # cached position; the kernel reads none past the last query
        assert B == 1, "chunked prefill attends one sequence a program"
        latent = cache.view_rows(row)   # (1, S, R + Dr padded to lanes)
        S = latent.shape[1]
        windowed = cache.window is not None
        at = jnp.arange(S) + cache.k_start[0] if windowed else jnp.arange(S)
        seen = (at <= cache.maxpos[0])[:, None]
        rows = jnp.where(seen, latent[0], 0)
        # head-major straight out of the products; the rotary key is
        # one (S, Dr) array for all heads, never copied per head
        k_nope = jnp.einsum("sc,chd->hsd", rows[:, :R], w_kvb[..., :Dn])
        values = jnp.einsum("sc,chd->hsd", rows[:, :R], w_kvb[..., Dn:])
        out = rect_flash_attention(
            q_nope[0].transpose(1, 0, 2), k_nope, values, pos[0, 0],
            q_rope[0].transpose(1, 0, 2), rows[:, R:R + Dr],
            **({"k_start": cache.k_start[0], "window": cache.window}
               if windowed else {}),
            interpret=cfg.pallas_interpret, **names(prefill_name))
        out = out.transpose(1, 0, 2).reshape(1, T, H * Dv)
    return out @ p["o"] if "o" in p else out
