"""Rule ``host-sync``: device round-trips where they stall the pipeline.

Two contexts, two severities of wrong:

**Traced functions** (anything jit- or shard_map-traced): a host sync on
a tracer either crashes at trace time (``float``/``.item()``) or — worse
— silently forces a transfer per call (``np.asarray`` on a concrete
array closed over the trace).  Flagged calls: ``.item()``,
``.block_until_ready()``, ``jax.device_get``, ``np.asarray``/``np.array``.
Traced functions are discovered by:

- Name/lambda arguments to ``jax.jit`` / ``jit`` / ``jax.shard_map`` /
  ``shard_map`` (incl. ``partial(jax.jit, ...)``) and ``@jit`` decorators;
- the repo idiom: every function DEFINED INSIDE a ``_make_*`` factory is
  trace-bound (the engine builds its jitted steps that way).

**Hot host loops**: in the engine files' step-driving methods
(train_batch / eval_batch / the schedule interpreters) and in benchmark
timed regions, a ``jax.device_get`` / ``.item()`` /
``.block_until_ready()`` INSIDE a Python loop serializes the device
against the host once per iteration — the async-dispatch overlap the
schedules depend on dies quietly.  The fix idiom: dispatch inside the
loop, fetch ONCE after it (``jax.device_get`` on the collected list), as
train_batch's loss reduction does.

``float()``/``int()`` and ``np.asarray`` are NOT flagged in host loops —
host-side math on host data is legitimate there; only true device syncs
are.
"""
import ast
import re

from ..core import Finding, Rule, call_name, register

# files whose step-driving loops are hot paths (repo-relative).  The
# serving engine/scheduler are held to the same bar as the training
# engines: a decode step may fetch its token batch ONCE (straight-line
# device_get after dispatch) but a device sync inside any per-slot /
# per-request loop serializes every running sequence against the host.
HOT_FILES = {
    "deepspeed_tpu/runtime/engine.py",
    "deepspeed_tpu/runtime/pipe/engine.py",
    "deepspeed_tpu/serving/engine.py",
    "deepspeed_tpu/serving/scheduler.py",
    "deepspeed_tpu/serving/kv_cache.py",
    "deepspeed_tpu/serving/reliability.py",
    "deepspeed_tpu/serving/fleet.py",
    "deepspeed_tpu/runtime/resilience/supervisor.py",
    "deepspeed_tpu/runtime/resilience/integrity.py",
    "deepspeed_tpu/runtime/resilience/transport.py",
    # the quantized wire (PR 18): pack/quantize kernels and the
    # collective bodies run inside every sync round's traced program —
    # a host sync in any of their loops stalls the optimizer wire
    "deepspeed_tpu/runtime/quantization.py",
    "deepspeed_tpu/runtime/custom_collectives.py",
    # sparse page attention (ISSUE 20): the per-lane LUT walk
    # (active_row / prefill_active_row) runs once per decode dispatch
    # over every running lane, and window-expired reclamation runs at
    # the same cadence — all pure numpy on host tables by contract
    "deepspeed_tpu/serving/sparse_context.py",
}
HOT_FN_RE = re.compile(
    r"^(train_batch|eval_batch|forward|backward|step"
    r"|_take_model_step\w*|_exec_\w+|_run_\w+"
    r"|serve\w*|submit|cancel|_decode_\w+|_prefill_\w+"
    r"|_on_new_token|_ensure_blocks|warmup"
    r"|alloc|free|table_row"
    # serving reliability layer (ISSUE 9): deadline sweeps, journal
    # hooks and drain/recover all run at step boundaries — a device
    # sync per live request there serializes the whole batch
    r"|_enforce_deadlines|_abort|recover|drain|request_drain"
    r"|on_\w+|record_\w+|commit|replay|predicted_\w+"
    # fleet router (ISSUE 11): the router step loop, placement and
    # migration/handoff paths run once per fleet step over every
    # replica — a device sync per replica/request there serializes
    # the whole fleet (the single batched handoff fetch is the ONLY
    # blessed device touch, straight-line in _handoff_tick)
    r"|_step_replica|_place|_eligible|_migrate\w*|_handoff_tick"
    r"|_on_failure|_mark_dead|_retire_drained|drain_replica"
    r"|has_work|export_request|import_request|adopt_running"
    # training supervisor (ISSUE 12): the supervised loop runs these
    # once per wall step — detection must stay pure host bookkeeping,
    # and the recovery paths may touch the device only through the
    # engine's own load/init entry points (a raw device sync in the
    # heartbeat/verdict tick would serialize every step against the
    # host even in the no-failure steady state)
    r"|tick|supervised_step|_heartbeat_tick|_verdict|_rollback"
    r"|_elastic_restart|_reseat_\w+"
    # numerical-integrity defense (ISSUE 13): observe_step runs once per
    # optimizer step on the supervised hot path (the sentinel values must
    # RIDE the engine's one batched fetch, never re-sync), and the
    # vote/dup-check entry points are allowed exactly ONE straight-line
    # fetch per cadence hit — a per-leaf or per-rank device_get loop
    # would serialize the whole state against the host
    r"|observe_step|decide|note_micro|state_vote|dup_check"
    r"|apply_chaos_faults|_integrity_tick|_skip_and_reseat"
    # transport seam + autoscaling (ISSUE 16): the heartbeat bus, ack
    # vote and result drain run once per wall/router step (transport.py
    # is all-host by contract — no jax import, ever), and the router's
    # transport/autoscale ticks are pure telemetry bookkeeping — a
    # device sync there stalls every replica's step clock
    r"|heartbeat_tick|vote_dead|poll_results|request|handoff"
    r"|_transport_tick|_autoscale_tick|_scale_up|_scale_down"
    r"|_record_scale"
    # prefix cache + speculative decode (ISSUE 17): the radix walk
    # (lookup/attach/insert), refcount bookkeeping and LRU reclaim run
    # at ADMISSION for every request, and the draft/verify tick runs
    # once per decode dispatch over every lane.  The COW split is
    # allowed exactly ONE device dispatch (the jitted _cow_copy_rows
    # program inside _cow_copy) and the verify tick ONE batched fetch —
    # a sync per tree node, per draft token or per lane would serialize
    # admission and decode against the host
    r"|prefix_\w+|_cow_copy\w*|_reclaim_\w+|warm_cow|cached_blocks"
    r"|_touch|_rank_slot|_prefix_probe|_draft_\w+|_spec_\w+"
    # 0/1 Adam wire (PR 18): the phase/wire selectors run once per
    # train_batch step (pure host bookkeeping on counters — a device
    # read there re-serializes the step clock the latch exists to
    # protect), and the sign pack/quantize kernels + collective
    # round-trip helpers execute inside every sync round's program
    r"|_zeroone_\w+|quantize_\w+|dequantize_\w+|pack_signs\w*"
    r"|unpack_signs\w*|sign_pack_layout|compressed_allreduce"
    # sparse page attention (ISSUE 20): the LUT→active-page walk and
    # window-expired free run per lane per decode step; a device sync
    # there serializes every running sequence against the host
    r"|active_row|prefill_active_row|window_expired_free)$")
# benchmark drivers: every loop is (or brackets) a timed region — a sync
# per iteration pollutes the measured step time with transfer latency
BENCH_FILES = {"benchmark/harness/drive_train.py",
               "benchmark/harness/drive_serve.py",
               "benchmark/tools/knee_sweep.py"}
# telemetry: the whole package is hot-path by contract (span emit runs
# once per instruction/step inside the engines' dispatch loops, and the
# armed-overhead bound is a tier-1 test) — every function is held to the
# bench-file bar: a device sync in ANY loop is a finding
TELEMETRY_FILES = {"deepspeed_tpu/telemetry/trace.py",
                   "deepspeed_tpu/telemetry/metrics.py",
                   "deepspeed_tpu/telemetry/mfu.py",
                   "deepspeed_tpu/telemetry/__init__.py"}

# cold-path builders: O(param-leaves) host work (tree flattening, shape
# math, spec construction) that belongs at arming/compile time.  A call
# from a hot step-driving function — even outside a loop — rebuilds the
# plan every step, so it is flagged anywhere inside a hot fn.  The
# memory-accounting report builders (ISSUE 15) are held to the same
# bar: a measured-memory read (memory_report / measured_memory /
# device_memory_report / train_memory_report) lazily COMPILES every
# registered jit on first call and walks whole state trees after —
# report-time work, never step-time.
COLD_BUILDER_NAMES = {"build_gather_plan", "_arm_stage3",
                      "_arm_quantized_collectives", "_build_shardings",
                      "memory_report", "measured_memory",
                      "device_memory_report", "train_memory_report",
                      "_analytic_memory_components",
                      "_arm_memory_accounting",
                      # 0/1 Adam arming + program-cache build (PR 18):
                      # blocker scans and the per-(phase, k) jit cache
                      # setup are arming/compile-time work — re-arming
                      # per step would rebuild the wire decision (and
                      # its WARNING spam) on every train_batch
                      "_arm_zeroone", "_arm_quantized_allreduce",
                      "_compile_zeroone",
                      # sparse-context arming (ISSUE 20): blocker scan
                      # + LUT compile happen once at engine build — a
                      # per-step re-arm would rebuild the (W, K) LUTs
                      # and re-emit the DISARMED warning every decode
                      "_arm_sparse_context", "_compile_luts"}

SYNC_METHOD_ATTRS = {"item", "block_until_ready"}
SYNC_FN_NAMES = {"device_get", "block_until_ready"}
NP_MATERIALIZERS = {"asarray", "array"}
NP_MODULES = {"np", "numpy", "onp"}
TRACE_WRAPPERS = {"jit", "shard_map", "pmap"}
LOOP_NODES = (ast.For, ast.AsyncFor, ast.While, ast.ListComp, ast.SetComp,
              ast.DictComp, ast.GeneratorExp)


def _attr_root_module(node):
    """'np' for np.asarray, 'jax' for jax.device_get, None otherwise."""
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
        return node.value.id
    return None


def _is_trace_wrapper(func):
    """True for jax.jit / jit / jax.shard_map / shard_map (as a call
    target), including partial(jax.jit, ...)."""
    name = call_name(func) if not isinstance(func, ast.Call) else None
    if name in TRACE_WRAPPERS:
        return True
    # partial(jax.jit, ...) used as decorator or wrapper
    if isinstance(func, ast.Call) and call_name(func) == "partial" \
            and func.args and call_name(func.args[0]) in TRACE_WRAPPERS:
        return True
    return False


def _collect_traced_nodes(tree):
    """Function/Lambda nodes whose bodies execute under a jax trace."""
    defs_by_name = {}
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(n.name, []).append(n)

    traced = []
    for n in ast.walk(tree):
        # jax.jit(fn, ...) / shard_map(fn, ...) with a Name or Lambda arg
        if isinstance(n, ast.Call) and _is_trace_wrapper(n.func) and n.args:
            target = n.args[0]
            if isinstance(target, ast.Lambda):
                traced.append(target)
            elif isinstance(target, ast.Name):
                traced.extend(defs_by_name.get(target.id, []))
        # decorators: @jax.jit / @jit / @partial(jax.jit, ...)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_trace_wrapper(dec) for dec in n.decorator_list):
                traced.append(n)
            # repo idiom: functions defined inside a _make_* factory are
            # the jit-traced step bodies
            if n.name.startswith("_make_"):
                for sub in ast.walk(n):
                    if sub is not n and isinstance(
                            sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                        traced.append(sub)
    return traced


def _sync_calls(tree, include_np):
    """(node, what) for host-sync calls in a subtree."""
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        func = n.func
        if isinstance(func, ast.Attribute):
            if func.attr in SYNC_METHOD_ATTRS and not n.args:
                yield n, f".{func.attr}()"
                continue
            root = _attr_root_module(func)
            if func.attr in SYNC_FN_NAMES and root in {"jax", None}:
                yield n, f"jax.{func.attr}"
                continue
            if include_np and func.attr in NP_MATERIALIZERS \
                    and root in NP_MODULES:
                yield n, f"{root}.{func.attr}"
        elif isinstance(func, ast.Name) and func.id in SYNC_FN_NAMES:
            yield n, func.id


@register
class HostSyncRule(Rule):
    name = "host-sync"
    description = ("host↔device sync (.item()/.block_until_ready()/"
                   "jax.device_get/np.asarray) inside a traced function "
                   "or a hot per-micro loop")

    def check(self, tree, source, path):
        findings = []
        seen = set()

        def add(node, what, ctx):
            key = (node.lineno, getattr(node, "col_offset", 0))
            if key in seen:
                return
            seen.add(key)
            findings.append(Finding(
                rule=self.name, path=path, line=node.lineno,
                col=getattr(node, "col_offset", 0),
                message=f"{what} {ctx}"))

        # --- traced-function context (any file) ------------------------
        for fn in _collect_traced_nodes(tree):
            for node, what in _sync_calls(fn, include_np=True):
                add(node, what,
                    "inside a jit/shard_map-traced function — this either "
                    "fails on a tracer or forces a per-call device sync; "
                    "move it outside the traced body")

        # --- hot-loop context (engine step paths + bench/telemetry) ----
        if path in HOT_FILES or path in BENCH_FILES \
                or path in TELEMETRY_FILES:
            hot_fns = []
            for n in ast.walk(tree):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and (path in BENCH_FILES
                             or path in TELEMETRY_FILES
                             or HOT_FN_RE.match(n.name)):
                    hot_fns.append(n)
            for fn in hot_fns:
                # cold-path builders called from a hot fn: the gather
                # plan / sharding spec would be rebuilt every step
                for n in ast.walk(fn):
                    if isinstance(n, ast.Call) \
                            and call_name(n) in COLD_BUILDER_NAMES:
                        add(n, f"{call_name(n)}()",
                            f"called inside hot step path {fn.name}() — "
                            f"plan/spec builders are O(param-leaves) host "
                            f"work; build once at arming time and reuse "
                            f"the cached plan")
                for n in ast.walk(fn):
                    if not isinstance(n, LOOP_NODES):
                        continue
                    bodies = []
                    if isinstance(n, (ast.For, ast.AsyncFor, ast.While)):
                        bodies.extend(n.body)
                    else:  # comprehensions: the element/key/value exprs
                        for name in ("elt", "key", "value"):
                            sub = getattr(n, name, None)
                            if sub is not None:
                                bodies.append(sub)
                    for b in bodies:
                        for node, what in _sync_calls(b, include_np=False):
                            add(node, what,
                                f"inside a per-iteration loop in "
                                f"{fn.name}() — one device round-trip per "
                                f"iteration; dispatch in the loop and "
                                f"fetch once after it (jax.device_get on "
                                f"the collected list)")
        return findings
