"""Analytic + measured HBM accounting — the third accounting leg.

``comm_accounting`` prices bytes on the wire and ``bubble_accounting``
replays time; this module prices the resource that actually gates both —
device memory.  Two sides, cross-checked:

- **Analytic**: a pure shape/dtype per-component byte model (params /
  gradient accumulators / optimizer state / fp16 masters per ZeRO stage,
  gathered stage-3 weights with fwd→bwd persistence, ZB stash residuals,
  the serving KV block pool, quantization scratch).  No device, no jax
  array is touched, so the numbers are deterministic on any host and
  ``tools/mem_budget.py`` can gate peak-bytes regressions in tier-1
  exactly like ``comm_budgets.json`` gates wire bytes.
- **Measured**: what the compiler actually reserved, read from
  ``compiled.memory_analysis()`` (argument/output/temp/alias bytes) per
  registered step jit, plus the runtime's ``device.memory_stats()`` HBM
  watermark where the backend reports one.  Registration is the
  telemetry capture-by-shape idiom (``register_by_shape``): the shape
  structs are taken at first dispatch, the ``lower().compile()`` runs
  lazily at report time, and the compiled object is SHARED with the MFU
  ledger (:class:`telemetry.mfu.MfuAccounting`) — arming both costs ONE
  compile per jit and zero compiles on the step path.

This module is also THE normalizer for the backend-dependent probe
shapes: ``memory_analysis()`` has been an attribute object, a dict and
None across jax versions/backends, and ``memory_stats()`` is a dict on
TPU/GPU, ``None`` on CPU, and raises on some plugin backends — the same
treatment ``telemetry.mfu.normalize_cost_analysis`` gives
``cost_analysis()``.  The ad-hoc readers in the flops profiler,
``runtime/utils.see_memory_usage`` and ``utils/timer.memory_usage`` all
delegate here.

Consumers: ``engine.memory_report()`` on all three engines (training,
pipeline, serving), the ``memory`` section of ``telemetry_report()``,
``tools/mem_budget.py`` + ``tools/memory_budgets.json``, and the
``_arm_stash`` / ``_arm_stage3`` analytic-vs-measured cross-checks.
"""
import threading
from typing import Optional, Sequence

import numpy as np

from deepspeed_tpu.runtime.comm_accounting import LeafSpec  # noqa: F401
from deepspeed_tpu.runtime.quantization import (DEFAULT_BLOCK_SIZE,
                                                block_layout)
from deepspeed_tpu.utils.logging import logger

# byte fields of xla_extension.CompiledMemoryStats (and its dict twins)
_MEM_FIELDS = ("argument", "output", "temp", "alias", "generated_code")

# the default analytic-vs-measured tolerance: an analytic estimate more
# than 15% under the compiler's own number is a sizing hazard (budgets
# derived from it under-provision) and is warned about loudly
UNDERESTIMATE_TOLERANCE = 0.15


# ---------------------------------------------------------------------------
# normalizers — THE one place the per-backend probe variants are handled
# ---------------------------------------------------------------------------

def normalize_memory_analysis(compiled_or_stats):
    """``compiled.memory_analysis()`` → plain byte dict, whatever shape
    the backend hands back.

    Accepts a compiled object (``memory_analysis()`` is called on it), a
    stats object (``*_size_in_bytes`` attributes), a dict (either
    ``*_size_in_bytes`` or ``*_bytes`` keys), or None.  Returns::

        {"argument_bytes", "output_bytes", "temp_bytes", "alias_bytes",
         "generated_code_bytes", "peak_bytes", "modeled"}

    ``peak_bytes`` prefers the backend's own peak when it reports one
    (``peak_memory_in_bytes``, TPU), else derives the standard XLA
    footprint ``argument + output - alias + temp``.  ``modeled=False``
    (all fields None) when the backend reports nothing — callers report
    the gap honestly instead of crashing on a quirk.
    """
    stats = compiled_or_stats
    if hasattr(stats, "memory_analysis"):
        try:
            stats = stats.memory_analysis()
        except (AttributeError, NotImplementedError, RuntimeError) as e:
            return dict(_EMPTY_ANALYSIS, error=str(e))
    if stats is None:
        return dict(_EMPTY_ANALYSIS)

    def read(field):
        if isinstance(stats, dict):
            v = stats.get(f"{field}_size_in_bytes",
                          stats.get(f"{field}_bytes"))
        else:
            v = getattr(stats, f"{field}_size_in_bytes", None)
        return int(v) if v is not None else None

    out = {f"{f}_bytes": read(f) for f in _MEM_FIELDS}
    peak = stats.get("peak_memory_in_bytes") if isinstance(stats, dict) \
        else getattr(stats, "peak_memory_in_bytes", None)
    if peak is None and None not in (out["argument_bytes"],
                                     out["output_bytes"],
                                     out["alias_bytes"], out["temp_bytes"]):
        peak = (out["argument_bytes"] + out["output_bytes"]
                - out["alias_bytes"] + out["temp_bytes"])
    out["peak_bytes"] = int(peak) if peak is not None else None
    out["modeled"] = any(v is not None for v in out.values())
    return out


_EMPTY_ANALYSIS = {f"{f}_bytes": None for f in _MEM_FIELDS}
_EMPTY_ANALYSIS.update({"peak_bytes": None, "modeled": False})


def normalize_memory_stats(device_or_stats):
    """``device.memory_stats()`` → ``{"bytes_in_use",
    "peak_bytes_in_use", "bytes_limit"}`` or None.

    Accepts a device object (``memory_stats()`` is called; per-backend
    errors are swallowed), a stats dict, or None.  Returns None when the
    backend reports nothing (the CPU backend) — "no watermark" is a
    reportable fact, not an exception.
    """
    stats = device_or_stats
    if hasattr(stats, "memory_stats"):
        try:
            stats = stats.memory_stats()
        except Exception:  # lint: allow-broad-except — plugin backends
            # raise assorted RuntimeErrors for unimplemented stats; a
            # memory probe must never take down the caller
            stats = None
    if not isinstance(stats, dict) or not stats:
        return None
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        v = stats.get(key)
        out[key] = int(v) if v is not None else None
    return out


def device_memory_report(devices=None):
    """Per-device HBM snapshot: ``memory_stats`` watermark + headroom
    where the backend reports them, honest Nones where it doesn't.

    One entry per device: ``{"id", "kind", "platform", "bytes_in_use",
    "peak_bytes_in_use", "bytes_limit", "headroom_bytes"}``.  Cold-path
    builder — call it from reports, never from a step loop.
    """
    if devices is None:
        import jax

        devices = jax.local_devices()
    out = []
    for d in devices:
        stats = normalize_memory_stats(d) or {}
        entry = {
            "id": getattr(d, "id", None),
            "kind": getattr(d, "device_kind", None),
            "platform": getattr(d, "platform", None),
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        }
        if entry["bytes_limit"] and entry["bytes_in_use"] is not None:
            entry["headroom_bytes"] = \
                entry["bytes_limit"] - entry["bytes_in_use"]
        else:
            entry["headroom_bytes"] = None
        out.append(entry)
    return out


# ---------------------------------------------------------------------------
# analytic per-component model (pure shape math — no devices, no jax)
# ---------------------------------------------------------------------------

def bytes_of(shape: Sequence[int], dtype) -> int:
    """Bytes of one dense array of ``shape`` in ``dtype``."""
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def leaf_device_bytes(leaf) -> int:
    """Per-device bytes of one CONCRETE jax array (or any shaped value):
    the leaf's shard shape under its sharding × itemsize — exact, not
    modeled, because the placement is known.  Host/numpy leaves count
    their full shape (they are replicated by construction)."""
    shape = tuple(np.shape(leaf))
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and hasattr(sharding, "shard_shape"):
        try:
            shape = tuple(sharding.shard_shape(shape))
        except (ValueError, TypeError):
            pass
    dt = getattr(leaf, "dtype", None)
    if dt is None:
        dt = np.asarray(leaf).dtype
    return bytes_of(shape, dt)


def tree_device_bytes(tree) -> int:
    """Per-device bytes of a pytree of concrete arrays (0 for None/empty
    subtrees)."""
    import jax

    return sum(leaf_device_bytes(l)
               for l in jax.tree_util.tree_leaves(tree))


def _partitioned(leaf: LeafSpec, dp: int) -> bool:
    return (dp > 1 and leaf.shard_dim is not None
            and leaf.shape[leaf.shard_dim] % dp == 0)


def _leaves_bytes(leaves: Sequence[LeafSpec], dp: int, elem_bytes: int,
                  sharded: bool) -> int:
    """Per-device bytes of a param-shaped component: partitioned leaves
    divide by dp when the component is ZeRO-``sharded``; indivisible
    leaves stay whole either way (mesh.zero_merge_spec semantics)."""
    total = 0
    for leaf in leaves:
        n = leaf.elements
        if sharded and _partitioned(leaf, dp):
            n //= dp
        total += n * elem_bytes
    return total


def quantization_scratch_bytes(leaves: Sequence[LeafSpec], dp: int,
                               block_size: int = DEFAULT_BLOCK_SIZE) -> int:
    """Transient scratch of one quantized collective in flight: the int8
    payload + fp32 per-block scales of the LARGEST leaf (collectives
    serialize on the wire, so one quantize buffer is live at a time).
    0 when nothing is partitioned."""
    worst = 0
    for leaf in leaves:
        if not _partitioned(leaf, dp):
            continue
        _, nb, npad = block_layout(leaf.elements, block_size)
        worst = max(worst, npad * 1 + nb * 4)
    return worst


def kv_pool_bytes(n_layer: int, num_blocks: int, n_head: int,
                  block_size: int, head_dim: int, *,
                  kv_dtype="bfloat16", quantized: bool = False,
                  shards: int = 1, shared_blocks: int = 0,
                  shared_refs: int = 1) -> int:
    """Per-shard device bytes of the serving paged KV pool: k + v in the
    shapes of ``serving.kv_cache.pool_shapes`` with ``num_blocks/shards``
    blocks (int8 when quantized, else ``kv_dtype``) plus the two fp32
    per-(token, head) scale tensors int8 storage carries.  THE builder both
    ``PagedKVPool.stats()`` and the serving ``memory_report()`` price
    the pool through — byte-exact against the allocated arrays.

    Under prefix sharing (ISSUE 17), ``num_blocks`` may be the LOGICAL
    block demand of the workload: ``shared_blocks`` distinct blocks each
    mapped read-only by ``shared_refs`` requests are stored ONCE, so the
    physical pool shrinks by ``shared_blocks * (shared_refs - 1)`` —
    refcounted shared storage is never priced per reference.  The
    defaults (no sharing) price exactly the allocated arrays."""
    assert shared_blocks >= 0 and shared_refs >= 1, \
        (shared_blocks, shared_refs)
    physical = num_blocks - shared_blocks * (shared_refs - 1)
    assert physical > 0, (num_blocks, shared_blocks, shared_refs)
    assert physical % shards == 0, (physical, shards)
    bps = physical // shards
    store = 1 if quantized else np.dtype(kv_dtype).itemsize
    kv = 2 * n_layer * bps * n_head * block_size * head_dim * store
    scales = 2 * n_layer * bps * n_head * block_size * 4 if quantized else 0
    return kv + scales


def sparse_kv_blocks_per_seq(n_positions: int, block_size: int, *,
                             num_sliding_window_blocks: int,
                             num_global_blocks: int = 1) -> int:
    """RESIDENT pool blocks one sequence of ``n_positions`` tokens holds
    under a sliding-window + global-anchor sparse attention policy
    (serving/sparse_context.py) with window-expired reclamation: the
    ``num_global_blocks`` anchors stay pinned and only the trailing
    ``num_sliding_window_blocks`` window stays mapped — everything
    between has been returned to the allocator.  This is the
    active-page factor long-context pool sizing composes into
    :func:`kv_pool_bytes`: ``num_blocks ~= slots *
    sparse_kv_blocks_per_seq(...) + shards`` instead of ``slots *
    ceil(n_positions / block_size) + shards``.  Short sequences that
    never outgrow the window are priced at their dense footprint."""
    assert num_sliding_window_blocks >= 1 and num_global_blocks >= 0
    dense = -(-int(n_positions) // int(block_size))
    return min(dense, num_global_blocks + num_sliding_window_blocks)


def train_memory_report(leaves: Sequence[LeafSpec], dp: int, *,
                        zero_stage: int = 0,
                        compute_dtype="float32",
                        mixed_precision: Optional[bool] = None,
                        optimizer_slots: int = 2,
                        cpu_offload: bool = False,
                        quantized_gradients: bool = False,
                        block_size: int = DEFAULT_BLOCK_SIZE,
                        gathered_stage3_bytes: int = 0,
                        stash_bytes: int = 0,
                        extra_transient_bytes: int = 0) -> dict:
    """Analytic per-device HBM bytes of one training configuration —
    pure shape/mesh math, the memory twin of
    ``comm_accounting.volume_report``.

    Components (bytes per device):

    - ``params``: compute dtype; ZeRO-sharded at rest under stage 3.
    - ``grad_accum``: fp32 accumulators; sharded under stage >= 2; ZERO
      under cpu_offload (grads stream to the host per micro).
    - ``master``: fp32 master copies under mixed precision (defaults to
      ``compute_dtype != float32``); sharded under stage >= 1; on the
      host under cpu_offload.
    - ``optimizer_state``: ``optimizer_slots`` fp32 param-shaped slots
      (Adam m+v = 2); sharded under stage >= 1; host under offload.
    - transients: ``gathered_stage3`` (scheduled stage-3 weights live
      fwd→bwd — ``GatherPlan.gathered_bytes``), ``stash`` (ZB residual
      peak), ``quantization_scratch`` (qgZ quantize buffer), plus any
      ``extra_transient_bytes`` the caller prices.

    ``peak_bytes = persistent + transient`` is the number
    ``tools/mem_budget.py`` budgets and the measured watermark is judged
    against.
    """
    if mixed_precision is None:
        mixed_precision = np.dtype(compute_dtype).itemsize < 4
    compute_b = np.dtype(compute_dtype).itemsize
    components = {
        "params_bytes": _leaves_bytes(leaves, dp, compute_b,
                                      sharded=zero_stage >= 3),
        "grad_accum_bytes": 0 if cpu_offload else _leaves_bytes(
            leaves, dp, 4, sharded=zero_stage >= 2),
        "master_bytes": 0 if (cpu_offload or not mixed_precision)
        else _leaves_bytes(leaves, dp, 4, sharded=zero_stage >= 1),
        "optimizer_state_bytes": 0 if cpu_offload else
        optimizer_slots * _leaves_bytes(leaves, dp, 4,
                                        sharded=zero_stage >= 1),
    }
    transient = {
        "gathered_stage3_bytes": int(gathered_stage3_bytes),
        "stash_bytes": int(stash_bytes),
        "quantization_scratch_bytes": quantization_scratch_bytes(
            leaves, dp, block_size) if quantized_gradients else 0,
        "extra_transient_bytes": int(extra_transient_bytes),
    }
    persistent = sum(components.values())
    transient_total = sum(transient.values())
    return {
        "config": {
            "dp": dp, "zero_stage": zero_stage,
            "compute_dtype": np.dtype(compute_dtype).name,
            "mixed_precision": bool(mixed_precision),
            "optimizer_slots": optimizer_slots,
            "cpu_offload": bool(cpu_offload),
        },
        "components": components,
        "transient": transient,
        "persistent_bytes": persistent,
        "transient_bytes": transient_total,
        "peak_bytes": persistent + transient_total,
    }


# ---------------------------------------------------------------------------
# measured side: per-jit memory_analysis registry (capture-by-shape)
# ---------------------------------------------------------------------------

def register_by_shape(mem, name, jit_fn, args, mesh=None,
                      calls_per_step=1.0, expect_label=None):
    """The telemetry capture-by-shape idiom for the memory ledger: take
    a ``jax.ShapeDtypeStruct`` tree of the REAL dispatch args NOW
    (donated buffers still alive), record the EXACT per-device argument
    bytes from their live shard shapes, and register a lazy
    ``lower().compile()`` closure that only runs at report time.  No-op
    when ``mem``/``jit_fn`` is None or ``name`` is already registered.

    When the engine also arms MFU, pass the shared
    :class:`~deepspeed_tpu.telemetry.mfu.MfuAccounting` to
    ``MemoryAccounting(shared=...)`` and register the same names with
    both — the compiled object is cached once between the two ledgers.

    ``expect_label`` arms the analytic-vs-measured cross-check for this
    jit: the analytic side is the trace-level output footprint
    (``jax.eval_shape`` over the same shape structs, resolved lazily at
    report time — no trace on the step path) plus one argument-sized
    working-set allowance, and the measured side is ``temp + output``
    from ``memory_analysis()``.  The claim being checked is the one
    budgets rely on: a step jit's transient needs are its outputs plus
    at most an input-sized scratch — when XLA's own number exceeds that
    by >15%, the warning says the hand model under-provisions.  Use it
    only for jits the engine sizes a budget from (the micro step, the
    stage-3 staged forward, the ZB stash forwards, the serving decode)
    — reduction jits whose outputs are scalars would warn spuriously.
    """
    if mem is None or jit_fn is None or mem.has(name):
        return
    import jax

    from deepspeed_tpu.telemetry.mfu import shape_structs

    structs = shape_structs(args)
    argument_bytes = sum(leaf_device_bytes(l)
                         for l in jax.tree_util.tree_leaves(args))

    def make_compiled():
        if mesh is None:
            return jit_fn.lower(*structs).compile()
        with jax.set_mesh(mesh):
            return jit_fn.lower(*structs).compile()

    mem.register(name, make_compiled, calls_per_step=calls_per_step,
                 argument_bytes=argument_bytes)
    if expect_label:
        def analytic_transient_bytes():
            if mesh is None:
                out = jax.eval_shape(jit_fn, *structs)
            else:
                with jax.set_mesh(mesh):
                    out = jax.eval_shape(jit_fn, *structs)
            # per-device where the abstract outputs carry a sharding
            # (leaf_device_bytes applies shard_shape); jax versions
            # whose eval_shape drops out-shardings fall back to global
            # shapes — a LOOSER bound there (the guard still catches
            # gross underestimates; the tight per-device exactness
            # check is argument_delta, which is always shard-exact)
            out_bytes = sum(leaf_device_bytes(l)
                            for l in jax.tree_util.tree_leaves(out))
            return out_bytes + argument_bytes

        mem.expect(name, expect_label, analytic_transient_bytes,
                   field="transient_bytes")


class MemoryAccounting:
    """Per-jit measured-memory registry + cross-check ledger.

    ``shared`` is the engine's :class:`telemetry.mfu.MfuAccounting`:
    when the same jit name is registered with both, the compiled object
    comes from the MFU cache — ONE ``lower().compile()`` serves both the
    FLOPs and the bytes ledger.  All reads are lazy (report time); the
    step path only ever pays the registration no-op check.
    """

    def __init__(self, shared=None):
        self._shared = shared
        self._jits = {}      # name -> (make_compiled, calls/step, arg B)
        self._compiled = {}  # own compile cache (used when not shared)
        self._measured = {}  # name -> normalized analysis (lazy)
        self._expect = {}    # name -> expectation dict
        self._checked = {}   # name -> cross-check verdict
        self._lock = threading.Lock()

    def has(self, name):
        return name in self._jits

    def register(self, name, make_compiled, calls_per_step=1.0,
                 argument_bytes=None):
        with self._lock:
            if name not in self._jits:
                self._jits[name] = (make_compiled, float(calls_per_step),
                                    argument_bytes)

    def expect(self, name, label, analytic_bytes,
               field="output_bytes", tolerance=UNDERESTIMATE_TOLERANCE):
        """Record an arming-time analytic claim about one jit —
        ``_arm_stash`` / ``_arm_stage3`` call this with the peak bytes
        their budget checks were sized from.  ``analytic_bytes`` may be
        a zero-arg callable resolved lazily at cross-check time (so
        arming never pays the abstract eval twice).  The cross-check
        compares it against the measured ``field`` and warns loudly on a
        > ``tolerance`` underestimate."""
        self._expect[name] = {"label": label, "analytic": analytic_bytes,
                              "field": field, "tolerance": float(tolerance)}

    def _get_compiled(self, name):
        shared = self._shared
        if shared is not None and shared.has(name):
            return shared.compiled(name)
        if name not in self._compiled:
            self._compiled[name] = self._jits[name][0]()
        return self._compiled[name]

    def measured_memory(self):
        """{name: normalized memory_analysis + calls_per_step +
        analytic argument bytes} — compiled lazily on first call, cached
        after; one program's lowering failure reports its error string
        instead of poisoning the rest (the MFU ``costs()`` contract)."""
        with self._lock:
            jits = dict(self._jits)
        for name, (_make, calls, arg_bytes) in jits.items():
            if name in self._measured:
                continue
            try:
                entry = normalize_memory_analysis(self._get_compiled(name))
            except Exception as e:  # lint: allow-broad-except — one
                # program's lowering quirk must not kill the report
                entry = dict(_EMPTY_ANALYSIS,
                             error=f"{type(e).__name__}: {e}")
            entry["calls_per_step"] = calls
            entry["analytic_argument_bytes"] = arg_bytes
            if arg_bytes and entry.get("argument_bytes"):
                entry["argument_delta"] = \
                    entry["argument_bytes"] / arg_bytes - 1.0
            else:
                entry["argument_delta"] = None
            # the working set beyond the (exactly-priced) arguments —
            # what the transient cross-checks compare against
            out_b, tmp_b = entry.get("output_bytes"), entry.get("temp_bytes")
            entry["transient_bytes"] = (out_b or 0) + (tmp_b or 0) \
                if (out_b is not None or tmp_b is not None) else None
            self._measured[name] = entry
        return dict(self._measured)

    def has_expectation(self, name):
        return name in self._expect

    def cross_check(self, warn=True):
        """Resolve every armed expectation against the measured side.

        Returns ``{name: {"label", "analytic_bytes", "measured_bytes",
        "ratio", "underestimated"}}``.  A measured value more than
        ``tolerance`` over the analytic claim means the hand-derived
        budget model under-provisions — warned per jit (once), in the
        DISARM-warning voice: the budget sized from that estimate should
        not be trusted until re-derived."""
        measured = self.measured_memory()
        for name, exp in self._expect.items():
            if name in self._checked:
                continue
            entry = measured.get(name)
            if entry is None or entry.get(exp["field"]) is None:
                continue        # not dispatched / backend silent: retry
            analytic = exp["analytic"]
            if callable(analytic):
                try:
                    analytic = analytic()
                except Exception as e:  # lint: allow-broad-except — the
                    # measured side's contract applies here too: one
                    # program's abstract-eval quirk (dead mesh after an
                    # elastic restart, backend tracing bug) must not
                    # kill the whole memory report
                    self._checked[name] = {
                        "label": exp["label"], "field": exp["field"],
                        "analytic_bytes": None, "measured_bytes":
                            entry[exp["field"]], "ratio": None,
                        "underestimated": False,
                        "error": f"{type(e).__name__}: {e}",
                    }
                    continue
            got = entry[exp["field"]]
            ratio = got / analytic if analytic else None
            under = bool(analytic) and got > analytic * (1 + exp["tolerance"])
            self._checked[name] = {
                "label": exp["label"], "field": exp["field"],
                "analytic_bytes": int(analytic) if analytic else analytic,
                "measured_bytes": got, "ratio": ratio,
                "underestimated": under,
            }
            if under and warn:
                logger.warning(
                    "memory accounting: analytic model UNDERESTIMATES the "
                    "compiler for %s (%s) — measured %s = %d B vs analytic "
                    "%d B (> %.0f%% over); treat budgets sized from this "
                    "estimate (stash_budget / stage3_prefetch_budget) as "
                    "DISARMED until the model is re-derived",
                    name, exp["label"], exp["field"], got, int(analytic),
                    100 * exp["tolerance"])
        return dict(self._checked)


# ---------------------------------------------------------------------------
# report builder (cold path — graftlint flags calls from hot step fns)
# ---------------------------------------------------------------------------

def memory_report(*, analytic=None, accounting=None, devices=None,
                  extra=None):
    """Assemble the unified memory report every engine surface uses:

    - ``analytic``: the caller's component model (engine state bytes or
      :func:`train_memory_report` output);
    - ``measured``: per-jit ``memory_analysis`` + analytic-vs-measured
      deltas + expectation cross-checks, when a
      :class:`MemoryAccounting` is armed;
    - ``devices``: per-device ``memory_stats`` watermark + headroom.

    Pure host work, but O(registered jits) with lazy compiles on first
    call — a cold report builder, never for the step path.
    """
    report = {
        "armed": accounting is not None,
        "analytic": analytic,
        "devices": device_memory_report(devices),
    }
    if accounting is not None:
        report["measured"] = accounting.measured_memory()
        report["cross_check"] = accounting.cross_check()
    if extra:
        report.update(extra)
    return report
