"""Model contract consumed by the engine.

The reference engine wraps a torch ``nn.Module`` (reference: runtime/engine.py:101).
The TPU engine is functional: a model is anything exposing

  - ``init(rng, batch) -> params``                (parameter pytree, fp32)
  - ``loss(params, batch, rng, train) -> (loss, metrics_dict)``
  - ``param_partition_spec(params) -> pytree of PartitionSpec``  (optional;
    tensor-parallel layout over the 'model' mesh axis — this build implements
    TP natively, unlike the reference which delegates to an external Megatron
    mpu, SURVEY §2.5)

``FlaxModel`` adapts a flax linen module + loss head to this contract.
"""
import functools
from typing import Any, Callable, Optional


def pad_to_multiple(n: int, multiple: int) -> int:
    """Ceil `n` to a multiple (MXU lane alignment for vocab dims); 0/None
    multiple returns n unchanged. Single source of truth for GPT2Config,
    BertConfig and the HF weight loader."""
    return -(-n // multiple) * multiple if multiple else n


class FlaxModel:
    """Adapter: flax linen module -> engine model contract.

    module.__call__(batch_inputs, train=...) must return model outputs;
    ``loss_head(outputs, batch) -> (scalar_loss, metrics)``.
    """

    def __init__(self, module, loss_head: Callable, input_key: str = "input",
                 partition_rules: Optional[Callable] = None):
        self.module = module
        self.loss_head = loss_head
        self.input_key = input_key
        self.partition_rules = partition_rules

    def init(self, rng, batch):
        variables = self.module.init(
            {"params": rng, "dropout": rng}, batch[self.input_key], train=False)
        return variables["params"]

    def loss(self, params, batch, rng, train=True):
        outputs = self.module.apply({"params": params}, batch[self.input_key],
                                    train=train, rngs={"dropout": rng})
        return self.loss_head(outputs, batch)

    def param_partition_spec(self, params):
        import jax
        from jax.sharding import PartitionSpec as P

        if self.partition_rules is None:
            return jax.tree_util.tree_map(lambda _: P(), params)
        return self.partition_rules(params)


def replicated_spec(params):
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(lambda _: P(), params)


def cross_entropy_loss(logits, labels, ignore_index: Optional[int] = None):
    """Token-level softmax cross entropy; returns (mean_loss, metrics)."""
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    logz = jnp.log(jnp.sum(jnp.exp(logits - jnp.max(logits, -1, keepdims=True)),
                           -1)) + jnp.max(logits, -1)
    # ignored labels (e.g. -100) are out of range: gather them at 0 and mask
    # (out-of-bounds take_along_axis fills NaN, and NaN*0 stays NaN)
    safe_labels = labels if ignore_index is None else \
        jnp.where(labels == ignore_index, 0, labels)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if ignore_index is not None:
        mask = (labels != ignore_index).astype(jnp.float32)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        loss = jnp.mean(nll)
    return loss, {"loss": loss}


def chunked_lm_cross_entropy(hidden, wte, labels, chunk_tokens: int = 2048,
                             ignore_index: Optional[int] = -100,
                             valid_vocab: Optional[int] = None):
    """Memory-efficient LM head + softmax cross entropy.

    Computes mean(-log softmax(hidden @ wte.T)[labels]) WITHOUT materializing
    the full (tokens, vocab) logits tensor: a lax.scan walks token chunks,
    and jax.checkpoint on the body makes the backward recompute each chunk's
    logits instead of saving them. Peak extra memory is O(chunk_tokens *
    vocab) instead of O(batch * seq * vocab) — the fp32 logits residual was
    the allocation that kept gpt2-350m from fitting batch 32 on one v5e chip
    (round-4 profile; the reference leans on fused CUDA softmax-xent kernels
    for the same reason, csrc/transformer/softmax_kernels.cu).

    Where the chunks are cut: on a mesh whose 'data' axis is larger than one
    and still the partitioner's to place (Auto), and divides the rows (dim 0
    of ``hidden``), each chip walks chunks of ITS OWN rows' tokens (a
    shard_map over 'data' alone; every other axis stays the partitioner's).
    Across 'data' then go one (sum of token losses, count of counted tokens)
    pair a chip, and backward the ``wte`` gradient, once a call. Chunks cut
    through the flattened global batch hold tokens of several chips, and the
    partitioner then moves every chunk's hidden rows or float32 logits
    across 'data' (gpt2-xl on four chips: 824 MB a chunk, forward and
    recomputation; PERF.md §6, PR 40). Everywhere else (no mesh, one device,
    rows the axis does not divide, 'data' Manual as inside the 1-bit Adam
    wire step, where the tokens are local already) the chunks are cut through
    the flattened batch. Either way the loss is the global sum over the
    global count, never a mean of the chips' means.

    hidden: (..., E) activations entering the LM head (already shifted);
    wte: (V, E) tied embedding; labels: (...) int targets aligned to hidden;
    valid_vocab: when wte carries MXU-alignment pad rows (V > true vocab),
    columns >= valid_vocab are masked out of the softmax so padding stays an
    invisible layout detail.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel import mesh as mesh_lib

    sums = functools.partial(
        _chunked_nll_sums, chunk_tokens=chunk_tokens,
        ignore_index=ignore_index, valid_vocab=valid_vocab)
    rows = P(mesh_lib.DATA_AXIS)
    chips = mesh_lib.auto_axis_size(mesh_lib.DATA_AXIS)
    if hidden.ndim > 1 and chips > 1 and hidden.shape[0] % chips == 0:
        # Nothing communicates inside the map: a chip gets its own copy of
        # wte and returns one (sum, count) pair, and the partitioner adds up
        # the pairs and, backward, the copies' gradients.
        nll_sum, cnt = jax.shard_map(
            lambda x, w, y: jnp.stack(sums(x, w[0], y))[None],
            in_specs=rows, out_specs=rows,
            axis_names={mesh_lib.DATA_AXIS}, check_vma=False,
        )(hidden, jnp.broadcast_to(wte, (chips, *wte.shape)), labels).sum(0)
    else:
        nll_sum, cnt = sums(hidden, wte, labels)
    loss = nll_sum / jnp.maximum(cnt, 1.0)
    return loss, {"loss": loss}


def _chunked_nll_sums(hidden, wte, labels, chunk_tokens, ignore_index,
                      valid_vocab):
    """(sum of the counted tokens' losses, their count), both float32, of
    :func:`chunked_lm_cross_entropy`: the scan over chunks of the flattened
    tokens it is handed."""
    import jax
    import jax.numpy as jnp

    E = hidden.shape[-1]
    x = hidden.reshape(-1, E)
    y = labels.reshape(-1)
    n = x.shape[0]
    chunk = max(1, min(chunk_tokens, n))
    pad = (-n) % chunk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        fill = ignore_index if ignore_index is not None else 0
        y = jnp.pad(y, (0, pad), constant_values=fill)
        if ignore_index is None:
            # no ignore label available: mask pad rows explicitly
            valid = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad))
    xs = x.reshape(-1, chunk, E)
    ys = y.reshape(-1, chunk)
    if ignore_index is not None:
        valids = (ys != ignore_index).astype(jnp.float32)
    else:
        valids = (valid if pad else jnp.ones_like(y, jnp.float32)).reshape(
            -1, chunk)

    def body(carry, inputs):
        nll_sum, cnt = carry
        xc, yc, mc = inputs
        logits = jax.lax.dot_general(
            xc, wte.astype(xc.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (chunk, V) f32
        if valid_vocab is not None and valid_vocab < wte.shape[0]:
            cols = jax.lax.iota(jnp.int32, wte.shape[0])
            logits = jnp.where(cols[None, :] < valid_vocab, logits, -1e9)
        m = jnp.max(logits, axis=-1)
        logz = jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)) + m
        safe = jnp.where(mc > 0, yc, 0)
        gold = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        nll = (logz - gold) * mc
        return (nll_sum + jnp.sum(nll), cnt + jnp.sum(mc)), None

    return jax.lax.scan(
        jax.checkpoint(body), (jnp.float32(0.0), jnp.float32(0.0)),
        (xs, ys, valids))[0]
