"""GPT-2 for the benchmark: how to build the program's model from a
configuration file, the plain reference the program is held to, the rule
its served tokens are held by, and the arithmetic (operations per token,
bytes per decode step) the utilisation metrics divide by.

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
    """The model's weights: made on the device in one jitted call from the
    seed, float32 as a trainer keeps them.  ``InferenceEngine`` holds and
    serves a bf16 copy of them (PR 33: every weight bf16, the LayerNorm
    leaves f32; ``assumed.served_weight_dtype`` of the configuration's
    file), and the plain reference reads this f32 tree."""
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


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _ref_block(x, p, n_head, eps, bits):
    """One pre-LN block over (B, S, E) float32."""
    keep = _kept(bits)

    def dense(h, layer):
        return keep(keep(h) @ keep(layer["kernel"]) + layer["bias"])

    with jax.default_matmul_precision("highest"):
        B, S, E = x.shape
        D = E // n_head
        qkv = dense(_layer_norm(x, p["ln_1"], eps), p["attn"]["c_attn"])
        q, k, v = (t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = keep(jnp.einsum("bhqd,bhkd->bhqk", q, k)) / np.sqrt(D)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        a = keep(jnp.einsum("bhqk,bhkd->bhqd",
                            keep(jax.nn.softmax(scores, axis=-1)), v))
        a = a.transpose(0, 2, 1, 3).reshape(B, S, E)
        x = x + dense(a, p["attn"]["c_proj"])
        h = _gelu_new(dense(_layer_norm(x, p["ln_2"], eps), p["mlp"]["c_fc"]))
        return x + dense(h, p["mlp"]["c_proj"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _ref_head(x, ln_f, wte, eps, bits):
    keep = _kept(bits)
    with jax.default_matmul_precision("highest"):
        return keep(_layer_norm(x, ln_f, eps)) @ keep(wte).T


# rows of the head computed in one call: one compiled shape whatever number
# of rows a request asks for, and 128 x vocab_size logits at a time
_HEAD_ROWS = 128


def reference_logits(weights, config, ids, rows=None, control_bits=None):
    """(B, S) token ids -> float32 logits: (B, S, vocab_size) with
    ``rows=None``, else (B, len(rows), vocab_size), the head applied to the
    positions ``rows`` and to no others, ``_HEAD_ROWS`` of them at a time.
    ``control_bits``: not the reference but its control, every matmul's
    inputs and result rounded to that many significand bits (4: about fp8,
    the nearest precision under the bf16 the configuration states), which
    the rule of ``served_check`` has to refuse."""
    ids = jnp.asarray(ids, jnp.int32)
    x = weights["wte"][ids] + weights["wpe"][None, :ids.shape[1]]
    for l in range(config["n_layer"]):
        x = _ref_block(x, weights["layer"](l), config["n_head"],
                       config["layer_norm_epsilon"], control_bits)

    def head(x):
        return _ref_head(x, weights["ln_f"], weights["wte"],
                         config["layer_norm_epsilon"], control_bits)

    if rows is None:
        return head(x)
    rows = np.asarray(rows)
    padded = np.resize(rows, -(-len(rows) // _HEAD_ROWS) * _HEAD_ROWS)
    blocks = [head(x[:, padded[i:i + _HEAD_ROWS]])
              for i in range(0, len(padded), _HEAD_ROWS)]
    return jnp.concatenate(blocks, axis=1)[:, :len(rows)]


def reference_loss(weights, config, ids):
    """Mean next-token cross entropy of (B, S) ids, float32."""
    logits = reference_logits(weights, config, ids)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.asarray(ids, jnp.int32)[:, 1:, None], axis=-1)
    return float(jnp.mean(nll))


# ---------------------------------------------------------------------------
# the rule for served tokens
# ---------------------------------------------------------------------------
# After chip_smoke.py's rule.  The served path computes in bf16 (8
# significand bits), the reference in f32, and with random weights the two
# best logits of a row are often one bf16 spacing apart, so tokens cannot be
# compared for equality.  Instead: under the reference's teacher-forced
# forward of the served sequence, every served token's logit lies within
# ``near_best_spacings`` spacings of bf16 (at the magnitude of the row's best
# logit: 2^-6 near 2.0) of the best logit of its row.  chip_smoke.py allows
# 2 against the program's own bf16 forward; against f32 the served logit and
# its rival each carry the error of 24 layers of bf16 activations as well,
# about one spacing each: the worst of some 5,600 served tokens over seven
# runs on the chip lay 2.12 under (PERF.md, section 6).  A wrong cache row,
# mask or position moves a logit by tenths, tens of spacings; a token picked
# blindly lies ~170 under; the control (``control_bits=4``) 10-20.
def served_check(config):
    """What the serving driver's check takes from this architecture: the
    numbers of ``drive_serve.judge_rows``' rule with the reason for each,
    and ``width(longest)``, the padded length at which a checked request of
    ``longest`` tokens is run through the reference."""
    return {
        "rule": {"near_best_spacings": 4.0, "share": 1.0,
                 "every_row_sigma": None},
        "why": {
            "near_best_spacings": "worst of ~5,600 served tokens on the "
                                  "chip 2.12, the control's least 10.5 "
                                  "(PERF.md section 6)",
            "share": "dense: no layer chooses discretely, so no row has a "
                     "reason to lie further out than the others",
            "every_row_sigma": "idle beside share 1.0: 4 spacings are 0.1 "
                               "of a row's logit sigma at these weights",
        },
        # learned positions: one padded shape for every request, the
        # published context; causal attention keeps the padding out of the
        # rows that count
        "width": lambda longest: config["n_positions"],
    }


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
