"""Device-mesh construction: the TPU replacement for NCCL process groups.

The reference builds torch.distributed process groups per parallel axis
(reference: deepspeed/runtime/pipe/topology.py:252-364, engine.py:69-85).  On
TPU the equivalent is ONE named-axis ``jax.sharding.Mesh`` over all chips:
collectives become sharding annotations (GSPMD) or explicit ``psum`` /
``ppermute`` over a named axis inside ``shard_map``.

Axis order is ('pipe', 'data', 'model') — model innermost so tensor-parallel
collectives ride the fastest ICI links, matching the reference's
PipeModelDataParallelTopology axis nesting (topology.py:246, model innermost).
"""
from typing import Optional

import numpy as np

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
# seq sits between data and model: sequence-parallel all_to_alls ride
# faster links than data-parallel gradient reductions, TP innermost still
AXIS_ORDER = (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS)


def resolve_mesh_shape(mesh_shape: dict, n_devices: int,
                       allow_partial: bool = False):
    """Fill in -1 axes; validate product == n_devices.

    A fully-specified mesh that uses only a subset of the devices is an
    error unless ``allow_partial`` — a config typo (stale axis sizes after
    scaling down) must fail at validation, not silently train on fewer
    chips. Tests/partial-pod runs opt in via ``mesh["allow_partial"]`` or
    an explicit devices list to build_mesh.
    """
    shape = {PIPE_AXIS: mesh_shape.get(PIPE_AXIS, 1),
             DATA_AXIS: mesh_shape.get(DATA_AXIS, -1),
             SEQ_AXIS: mesh_shape.get(SEQ_AXIS, 1),
             MODEL_AXIS: mesh_shape.get(MODEL_AXIS, 1)}
    fixed = 1
    free_axes = [a for a, s in shape.items() if s == -1]
    for a, s in shape.items():
        if s != -1:
            fixed *= s
    assert len(free_axes) <= 1, f"at most one mesh axis may be -1, got {shape}"
    if free_axes:
        assert n_devices % fixed == 0, \
            f"{n_devices} devices not divisible by fixed axes product {fixed}"
        shape[free_axes[0]] = n_devices // fixed
    total = shape[PIPE_AXIS] * shape[DATA_AXIS] * shape[SEQ_AXIS] \
        * shape[MODEL_AXIS]
    if allow_partial:
        assert total <= n_devices, \
            f"mesh {shape} needs {total} devices but {n_devices} available"
    else:
        assert total == n_devices, (
            f"mesh {shape} covers {total} of {n_devices} devices; set "
            f'mesh["allow_partial"] = true (or pass an explicit devices '
            f"list) to intentionally train on a subset")
    return shape


def build_mesh(mesh_shape: Optional[dict] = None, devices=None):
    """Build a Mesh with axes ('pipe','data','model').

    mesh_shape: {"pipe": P, "data": D, "model": M}; -1 = fill remaining.
    An explicit devices list always permits a subset mesh (the caller
    already chose the devices); otherwise subset meshes require
    mesh_shape["allow_partial"].
    """
    import jax
    from jax.sharding import Mesh

    mesh_shape = dict(mesh_shape or {})
    allow_partial = bool(mesh_shape.pop("allow_partial", False))
    if devices is None:
        devices = jax.devices()
    else:
        allow_partial = True
    shape = resolve_mesh_shape(mesh_shape, len(devices), allow_partial)
    total = shape[PIPE_AXIS] * shape[DATA_AXIS] * shape[SEQ_AXIS] \
        * shape[MODEL_AXIS]
    if total < len(devices):
        from deepspeed_tpu.utils.logging import logger

        logger.warning(
            f"mesh {shape} uses {total} of {len(devices)} devices — "
            f"{len(devices) - total} idle (intended for tests/partial "
            f"slices; check the config's mesh axes if not)")
    dev_array = np.asarray(devices[:total]).reshape(
        shape[PIPE_AXIS], shape[DATA_AXIS], shape[SEQ_AXIS],
        shape[MODEL_AXIS])
    return Mesh(dev_array, AXIS_ORDER)


def constrain(x, spec):
    """with_sharding_constraint that no-ops when no mesh is active or the
    referenced axes are absent/trivial — lets model code carry sharding
    annotations that only bind inside an engine's mesh context. Inside
    shard_map, axes the map handles manually (e.g. 'data' in the 1-bit Adam
    wire step) are dropped: the data is already device-local there, and
    with_sharding_constraint rejects specs naming manual axes."""
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    cleaned = _bound_spec(mesh, spec)
    if all(a is None for a in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, cleaned)


def _bound_spec(mesh, spec):
    """``spec`` without the axes that cannot bind in ``mesh``: absent from
    it, or not Auto — under shard_map the mapped axes are Manual and the
    rest become Explicit, and both with_sharding_constraint and a nested
    shard_map accept only Auto axes
    (checked up front — genuine spec errors like rank mismatch still
    surface from the caller's own jax call)."""
    from jax.sharding import PartitionSpec as P

    auto = mesh.auto_axes

    def keep(axis):
        if axis is None:
            return None
        axes = axis if isinstance(axis, tuple) else (axis,)
        kept = tuple(a for a in axes if a in mesh.shape and a in auto)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    return P(*(keep(a) for a in spec))


# The layout attention runs in, as a spec for constrain() / per_shard():
# batch over 'data', heads over 'model' and 'seq', whole sequences.  The
# models constrain q/k/v to it and the kernel dispatch maps its kernels
# over it — one definition, or a changed layout reshards at every call.
HEAD_SHARDED = (DATA_AXIS, (MODEL_AXIS, SEQ_AXIS), None, None)


def shards_evenly(shape, spec) -> bool:
    """Whether every dim of ``shape`` splits evenly over the axes ``spec``
    binds in the active mesh — what a shard_map over that spec needs."""
    import math

    import jax

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return True
    for n, axis in zip(shape, _bound_spec(mesh, spec)):
        axes = () if axis is None else \
            axis if isinstance(axis, tuple) else (axis,)
        if n % math.prod(mesh.shape[a] for a in axes):
            return False
    return True


def auto_axis_size(axis) -> int:
    """Size of ``axis`` in the active mesh where it is still Auto, the
    partitioner's to place; 1 where there is no mesh, the axis is absent, or
    a shard_map already maps it (Manual: the data is device-local there, and
    a second map over it cannot nest)."""
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or axis not in mesh.auto_axes:
        return 1
    return mesh.shape[axis]


def per_shard(fn, in_specs, out_spec):
    """``fn`` wrapped to run once per shard of the active mesh — for Mosaic
    (Pallas TPU) kernels, which GSPMD cannot partition: on a mesh of more
    than one device the lowering refuses them ("Mosaic kernels cannot be
    automatically partitioned") unless EVERY mesh axis is manual.  So all
    axes still Auto are mapped; the specs name the operands' full layout
    the way :func:`constrain` specs do, and an operand is whole along the
    axes its spec leaves out.  With no mesh, one device, or every axis
    manual already, ``fn`` is returned as it is."""
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.size == 1 or not mesh.auto_axes:
        return fn
    return jax.shard_map(
        fn, in_specs=tuple(_bound_spec(mesh, s) for s in in_specs),
        out_specs=_bound_spec(mesh, out_spec),
        axis_names=set(mesh.auto_axes), check_vma=False)


def data_sharding(mesh, *, extra_dims: int = 1):
    """NamedSharding for a batch: dim0 over 'data', rest replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (extra_dims - 1))))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def dp_size(mesh) -> int:
    return mesh.shape[DATA_AXIS]


def mp_size(mesh) -> int:
    return mesh.shape[MODEL_AXIS]


def pp_size(mesh) -> int:
    return mesh.shape[PIPE_AXIS]


def sp_size(mesh) -> int:
    return mesh.shape.get(SEQ_AXIS, 1)


def zero_merge_spec(spec, leaf, dp: int):
    """Merge ZeRO 'data'-axis sharding into an existing (TP) PartitionSpec.

    The reference flattens params and slices 1/N per rank
    (stage1.py:426, stage2.py:223-295).  The TPU-native formulation keeps
    leaves in natural shape and shards the largest dimension not already
    taken by TP that divides the data-parallel size; XLA then
    reduce-scatters grads into the shard and all-gathers updated params —
    same memory footprint, no bucket machinery.  Leaves too small to shard
    stay replicated (the reference's unpartitioned remainder).
    """
    from jax.sharding import PartitionSpec as P

    if dp == 1 or not hasattr(leaf, "shape") or leaf.ndim == 0:
        return spec
    used = set(a for a in spec if a is not None) if spec else set()
    if DATA_AXIS in used:
        return spec
    entries = list(spec) + [None] * (leaf.ndim - len(spec))
    best_dim, best = None, 0
    for d in range(leaf.ndim):
        if entries[d] is None and leaf.shape[d] % dp == 0 and leaf.shape[d] > best:
            best_dim, best = d, leaf.shape[d]
    if best_dim is None:
        return spec
    entries[best_dim] = DATA_AXIS
    return P(*entries)


def zero_partition_spec(pytree, mesh, stage: int, tp_specs=None):
    """Sharding specs implementing ZeRO state partitioning over the data
    axis, layered on top of optional tensor-parallel specs."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = dp_size(mesh)

    if tp_specs is None:
        tp_specs = jax.tree_util.tree_map(lambda _: P(), pytree)

    def spec_for(spec, leaf):
        if stage == 0:
            return NamedSharding(mesh, spec)
        return NamedSharding(mesh, zero_merge_spec(spec, leaf, dp))

    return jax.tree_util.tree_map(
        spec_for, tp_specs, pytree, is_leaf=lambda x: isinstance(x, P))
