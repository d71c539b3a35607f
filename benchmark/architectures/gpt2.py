"""GPT-2 for the benchmark: how to build the program's model from a
configuration file, the plain reference the program is held to, and the
arithmetic (operations per token, bytes per decode step) the utilisation
metrics divide by.

The reference follows the published description (Radford et al. 2019 and
the ``openai-community/gpt2*`` config.json keys): learned token and position
embeddings, pre-LayerNorm blocks of causal multi-head attention and a
4x GELU(tanh) MLP, a final LayerNorm, and the output head tied to the token
embedding.  It is written in jax.numpy in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul runs
in bf16 passes otherwise), with no kernel, no cache, no remat and no scan,
and imports nothing from ``deepspeed_tpu``.  Departure from the source: none
in the mathematics; the program pads the vocabulary to a multiple of 128
rows, and the reference reads only the published ``vocab_size`` rows.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# the program's model, built from the configuration file
# ---------------------------------------------------------------------------
_PUBLISHED = {"activation_function": "gelu_new", "tie_word_embeddings": True}


def build_model(config, overrides):
    """The program's ``GPT2Model`` at the file's sizes.  ``overrides`` are
    the job's settings of the program (remat, scan_layers, ...), never a
    size."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    for key, want in _PUBLISHED.items():
        if config.get(key, want) != want:
            raise ValueError(f"configuration {config['name']!r}: {key}="
                             f"{config[key]!r}, this architecture has {want!r}")
    return GPT2Model(GPT2Config(
        vocab_size=config["vocab_size"], n_positions=config["n_positions"],
        n_embd=config["n_embd"], n_layer=config["n_layer"],
        n_head=config["n_head"],
        layer_norm_epsilon=config["layer_norm_epsilon"],
        dtype=jnp.dtype(config["assumed"]["compute_dtype"]).type,
        **overrides))


def init_params(model, seed):
    """The served weights: made on the device in one jitted call from the
    seed, float32 as ``InferenceEngine`` holds them."""
    ids = np.zeros((1, 8), np.int32)
    return jax.jit(model.init)(jax.random.PRNGKey(seed),
                               {"input_ids": ids, "labels": ids})


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
def reference_weights(params, config):
    """The program's parameter tree -> what the reference reads: ``wte``
    (published rows only), ``wpe``, ``ln_f`` and ``layer(l)``, a function
    that cuts layer ``l`` out of the stacked (scan) or listed blocks, in
    float32.  Only names and shapes of the program's tree are used."""
    stacked = "h" in params
    cut = jax.jit(lambda tree, l: jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False)
        .astype(jnp.float32), tree))

    def layer(l):
        if stacked:
            return cut(params["h"]["block"], jnp.int32(l))
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      params[f"h_{l}"])

    return {"wte": params["wte"][:config["vocab_size"]].astype(jnp.float32),
            "wpe": params["wpe"].astype(jnp.float32),
            "ln_f": jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), params["ln_f"]),
            "layer": layer}


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _ref_block(x, p, n_head, eps):
    """One pre-LN block over (B, S, E) float32."""
    with jax.default_matmul_precision("highest"):
        B, S, E = x.shape
        D = E // n_head
        h = _layer_norm(x, p["ln_1"], eps)
        qkv = h @ p["attn"]["c_attn"]["kernel"] + p["attn"]["c_attn"]["bias"]
        q, k, v = (t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
        a = a.transpose(0, 2, 1, 3).reshape(B, S, E)
        x = x + a @ p["attn"]["c_proj"]["kernel"] + p["attn"]["c_proj"]["bias"]
        h = _layer_norm(x, p["ln_2"], eps)
        h = _gelu_new(h @ p["mlp"]["c_fc"]["kernel"] + p["mlp"]["c_fc"]["bias"])
        return x + h @ p["mlp"]["c_proj"]["kernel"] + p["mlp"]["c_proj"]["bias"]


@functools.partial(jax.jit, static_argnums=(3,))
def _ref_head(x, ln_f, wte, eps):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(x, ln_f, eps) @ wte.T


def reference_logits(weights, config, ids):
    """(B, S) token ids -> (B, S, vocab_size) float32 logits."""
    ids = jnp.asarray(ids, jnp.int32)
    x = weights["wte"][ids] + weights["wpe"][None, :ids.shape[1]]
    for l in range(config["n_layer"]):
        x = _ref_block(x, weights["layer"](l), config["n_head"],
                       config["layer_norm_epsilon"])
    return _ref_head(x, weights["ln_f"], weights["wte"],
                     config["layer_norm_epsilon"])


def reference_loss(weights, config, ids):
    """Mean next-token cross entropy of (B, S) ids, float32."""
    logits = reference_logits(weights, config, ids)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.asarray(ids, jnp.int32)[:, 1:, None], axis=-1)
    return float(jnp.mean(nll))


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def n_params(config):
    """Parameters at the published sizes (tied head counted once)."""
    E, L = config["n_embd"], config["n_layer"]
    block = 12 * E * E + 13 * E          # 4 matrices, 4 biases, 2 LayerNorms
    return (config["vocab_size"] + config["n_positions"]) * E \
        + L * block + 2 * E


def train_flops_per_token(config, seq_len):
    """Floating-point operations one trained token requires, forward and
    backward (3 x forward, a multiply-add counted as 2), recomputation not
    counted: the four block matmuls (24 E^2 a layer forward), causal
    attention (QK^T and PV over the lower triangle: 2 x 2 x S/2 x E = 2 S E
    a layer forward) and the tied output head (2 E V) at the published
    vocabulary.  Embedding lookups, LayerNorm, GELU, softmax and biases are
    left out: they are under 1 % and are not matmul work."""
    E, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    forward = L * (24 * E * E + 2 * seq_len * E) + 2 * E * V
    return 3 * forward


def decode_step_bytes(config, *, lanes, context_positions, weight_bytes,
                      kv_bytes):
    """Bytes one decode step has to move at the least: every weight once
    (``weight_bytes`` each as held), and for each lane the keys and values
    of ``context_positions`` positions in every layer."""
    E, L = config["n_embd"], config["n_layer"]
    weights = n_params(config) * weight_bytes
    kv = lanes * context_positions * L * 2 * E * kv_bytes
    return weights + kv
