"""Activation checkpointing — recompute-instead-of-save, TPU-native.

Reference behavior: deepspeed/runtime/activation_checkpointing/
checkpointing.py:58-832 (CheckpointFunction with partitioned/CPU/contiguous
activations, model-parallel RNG tracker, configure()/is_configured()).

TPU formulation: `checkpoint(fn, *args)` wraps `jax.checkpoint` — XLA
rematerializes inside the jitted step, which subsumes the reference's manual
save/recompute machinery:
- partition_activations -> saved residuals inherit GSPMD shardings, so they
  are already partitioned across the mesh; the flag additionally selects the
  nothing-saveable policy (recompute everything, the most memory-lean);
- checkpoint_in_cpu -> offload saved residuals to host memory via
  jax.checkpoint policies (offload_dot_products...) where supported;
- contiguous_checkpointing -> no-op (XLA owns layout; accepted for config
  parity);
- model-parallel RNG: `model_parallel_rng(key)` folds the mesh 'model'
  coordinate into the key so dropout differs per TP shard, the analog of the
  reference's CudaRNGStatesTracker branch seeds (:148-263).
"""
from typing import Any, Optional

from deepspeed_tpu.utils.logging import logger

# module state (reference keeps the same globals, :40-56)
_CONFIG = {
    "partition_activations": False,
    "contiguous_checkpointing": False,
    "checkpoint_in_cpu": False,
    "synchronize": False,
    "profile": False,
    "num_checkpoints": None,
}
_CONFIGURED = False
_MPU = None
_NUM_LAYERS = None


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, checkpoint_in_cpu=None,
              synchronize=None, profile=None, num_checkpoints=None):
    """Reference analog: checkpointing.py:747-827. Accepts either explicit
    flags or a DeepSpeedConfig(-like) object / path with an
    activation_checkpointing section."""
    global _CONFIGURED, _MPU, _NUM_LAYERS
    _CONFIGURED = True
    _MPU = mpu_

    if deepspeed_config is not None:
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        cfg = deepspeed_config
        if isinstance(cfg, (str, dict)):
            cfg = DeepSpeedConfig(cfg, world_size=1)
        ac = getattr(cfg, "activation_checkpointing_config", None)
        if ac is not None:
            _CONFIG["partition_activations"] = ac.partition_activations
            _CONFIG["contiguous_checkpointing"] = \
                ac.contiguous_memory_optimization
            _CONFIG["checkpoint_in_cpu"] = ac.cpu_checkpointing
            _CONFIG["synchronize"] = ac.synchronize_checkpoint_boundary
            _CONFIG["profile"] = ac.profile
            _NUM_LAYERS = ac.number_checkpoints

    for key, val in [("partition_activations", partition_activations),
                     ("contiguous_checkpointing", contiguous_checkpointing),
                     ("checkpoint_in_cpu", checkpoint_in_cpu),
                     ("synchronize", synchronize), ("profile", profile)]:
        if val is not None:
            _CONFIG[key] = val
    if num_checkpoints is not None:
        _NUM_LAYERS = num_checkpoints
    if _CONFIG["contiguous_checkpointing"]:
        logger.info("contiguous_checkpointing: XLA owns buffer layout on "
                    "TPU; flag accepted for parity and otherwise ignored")
    if _CONFIG["contiguous_checkpointing"] and _NUM_LAYERS is None:
        raise ValueError(
            "contiguous_checkpointing requires num_checkpoints "
            "(reference checkpointing.py:816-818)")


def is_configured():
    return _CONFIGURED


def reset():
    """Reference analog: :691-703 (frees contiguous buffers there; clears
    config state here)."""
    global _CONFIGURED, _NUM_LAYERS
    _CONFIGURED = False
    _NUM_LAYERS = None
    for k, v in [("partition_activations", False),
                 ("contiguous_checkpointing", False),
                 ("checkpoint_in_cpu", False), ("synchronize", False),
                 ("profile", False)]:
        _CONFIG[k] = v


def partition_activations_in_checkpoint(flag):
    """Reference analog: :678-683."""
    _CONFIG["partition_activations"] = flag
    logger.info(f"**************Partition Activations {flag}************")


def set_num_layers(nlayers):
    global _NUM_LAYERS
    _NUM_LAYERS = nlayers


def _policy():
    import jax

    if _CONFIG["checkpoint_in_cpu"]:
        # save matmul outputs but offload them to host memory — the TPU
        # analog of cpu_checkpointing's activation host placement
        return jax.checkpoint_policies.offload_dot_products_with_no_batch_dims(
            "device", "pinned_host")
    if _CONFIG["partition_activations"]:
        return jax.checkpoint_policies.nothing_saveable
    # default matches torch checkpointing: save boundaries, recompute body
    return None


def checkpoint(function, *args):
    """Checkpoint a function call: outputs computed normally, intermediate
    activations rematerialized in backward (reference CheckpointFunction,
    :362-663). Differentiable; non-array args are captured statically."""
    import jax

    policy = _policy()
    wrapped = jax.checkpoint(function, policy=policy) if policy is not None \
        else jax.checkpoint(function)
    return wrapped(*args)


# ---------------------------------------------------------------------------
# model-parallel RNG (reference CudaRNGStatesTracker :148-263)
# ---------------------------------------------------------------------------
_MODEL_PARALLEL_RNG_TRACKER_NAME = "model-parallel-rng"


def model_parallel_rng(key, axis_name: str = "model"):
    """Per-TP-shard dropout key: fold the mesh coordinate into the key.
    Inside jit/shard_map with the axis bound, each model-parallel shard
    draws independent dropout masks (the reference tracker's
    model-parallel-rng branch seed = base + 2718 + rank, :238-248)."""
    import jax

    try:
        idx = jax.lax.axis_index(axis_name)
    except NameError:
        return key
    return jax.random.fold_in(key, 2718 + idx)


class RNGStatesTracker:
    """Named RNG streams over jax keys (reference :148-214). States are
    explicit keys rather than device RNG registers; `fork(name)` returns a
    fresh key from the named stream and advances it."""

    def __init__(self):
        self.states = {}

    def reset(self):
        self.states = {}

    def get_states(self):
        return dict(self.states)

    def set_states(self, states):
        self.states = dict(states)

    def add(self, name, seed):
        import jax

        if name in self.states:
            raise Exception(f"rng state {name} already exists")
        self.states[name] = jax.random.PRNGKey(seed)

    def fork(self, name=_MODEL_PARALLEL_RNG_TRACKER_NAME):
        import jax

        if name not in self.states:
            raise Exception(f"rng state {name} is not added")
        self.states[name], out = jax.random.split(self.states[name])
        return out


_RNG_TRACKER = RNGStatesTracker()


def get_rng_tracker():
    return _RNG_TRACKER


# torch-API alias (reference get_cuda_rng_tracker)
get_cuda_rng_tracker = get_rng_tracker


def model_parallel_seed(seed, model_parallel_rank=0):
    """Seed the default + model-parallel streams (reference
    model_parallel_cuda_manual_seed :224-263)."""
    offset = seed + 2718
    _RNG_TRACKER.reset()
    _RNG_TRACKER.add("default", seed)
    _RNG_TRACKER.add(_MODEL_PARALLEL_RNG_TRACKER_NAME,
                     offset + model_parallel_rank)


model_parallel_cuda_manual_seed = model_parallel_seed
