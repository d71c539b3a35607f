"""PipelineEngine — pipeline-parallel training over stage submeshes.

Reference behavior: deepspeed/runtime/pipe/engine.py:45-1169 (instruction
dispatch `_exec_schedule` :1148, train_batch :244, eval_batch :320, p2p via
2-rank broadcast groups).

TPU-native architecture: the full device mesh (pipe, data, model) is split
into one submesh per stage; each stage's params/optimizer state live only on
its submesh (pipeline memory scaling), with ZeRO sharding over the submesh's
'data' axis on top. The engine executes the SAME declarative instruction
schedules as the reference (runtime/pipe/schedule.py), but:

- SendActivation/RecvActivation/SendGrad/RecvGrad are `jax.device_put`
  transfers between adjacent submeshes (ICI neighbor copies — the analog of
  the reference's broadcast-pair p2p, pipe/p2p.py:31-58);
- ForwardPass/BackwardPass are per-stage jitted calls; the single-controller
  runtime dispatches them asynchronously, so stages on disjoint devices
  overlap exactly as the 1F1B schedule intends;
- BackwardPass recomputes the stage forward inside the jit (vjp-with-remat) —
  activation checkpointing per stage, matching the reference's
  activation-checkpoint-every-stage default;
- ReduceGrads is implicit: XLA inserts the data-axis psum inside the
  backward jit (the reference's bucketed allreduce, engine.py:852-868);
- ReduceTiedGrads sums accumulated tied-param grads across the stages in the
  tie group and redistributes, so identical optimizer updates keep tied
  copies in sync (reference module.py:405-418).

fp16 dynamic loss scaling runs host-side here (the schedule is host-driven
anyway): per-stage finite checks combine on host, overflow skips the step
and halves the scale (reference fp16/loss_scaler.py:79-170 semantics).
"""
import logging
import os
import pickle
from collections import deque
from typing import NamedTuple

import numpy as np

from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.pipe import schedule as sched_lib
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.pipe.topology import (PipelineParallelGrid,
                                                 PipeModelDataParallelTopology)
from deepspeed_tpu.utils.compile_cache import \
    disable_persistent_compile_cache
from deepspeed_tpu.utils.logging import log_dist, logger


class StageState(NamedTuple):
    params: object      # compute-dtype params for this stage's layers
    master: object      # fp32 master (None in fp32 mode)
    opt_state: object   # optimizer state over master
    accum: object       # fp32 grad accumulator


def _cached_stage_programs_halt(submeshes):
    """A program over more than one TPU whose devices leave out device 0
    halts its cores once it is read back from the persistent compile cache
    ("Invalid logical z: enhanced-barrier"; jax 0.9.0 and its libtpu, any
    such jit — tools/compile_cache_probe.py).  Every stage after the first
    is such a program, so a PipelineEngine with one compiles in the
    process."""
    return any(m.size > 1 and m.devices.flat[0].platform == "tpu"
               for m in submeshes[1:])


class _MfuJitProxy:
    """Transparent stage-jit wrapper for the compiled-program registry
    and the MFU/measured-memory ledgers: on FIRST dispatch it captures a
    ShapeDtypeStruct tree of the real args and registers a lazy
    lower+compile with telemetry/programs.py (always — the registry is
    the seam tools/graftlint/program_lint.py reads, and registration is
    a shape capture + dict insert, no compile) plus telemetry/mfu.py and
    runtime/memory_accounting.py when those ledgers are armed (the two
    share ONE compiled object per jit), then calls through.  Attribute
    access (``.lower`` for the HLO contract tests) passes through to the
    wrapped jit."""

    # __weakref__: jax.eval_shape / linear_util cache weakref their
    # callables (the stash-size estimate abstract-evals fwd_stash
    # through this proxy)
    __slots__ = ("fn", "name", "mfu", "mem", "mesh", "calls",
                 "programs", "contract", "_registered", "__weakref__")

    def __init__(self, fn, name, mfu, mesh, calls, mem=None,
                 programs=None, contract=None):
        self.fn = fn
        self.name = name
        self.mfu = mfu
        self.mem = mem
        self.mesh = mesh
        self.calls = calls
        self.programs = programs
        self.contract = contract
        self._registered = False

    def __call__(self, *args):
        if not self._registered:
            import jax

            # register only from a CONCRETE dispatch: under an abstract
            # evaluation (the stash-size estimate eval_shapes fwd_stash
            # through this proxy) the args are tracers with no
            # shardings — capturing them would re-lower the UNsharded
            # whole-stage program, inflating per-device cost/memory and
            # breaking the per-device HFU premise
            if not any(isinstance(l, jax.core.Tracer)
                       for l in jax.tree_util.tree_leaves(args)):
                self._registered = True
                from deepspeed_tpu.telemetry import (register_by_shape,
                                                     register_program)

                register_program(self.programs, self.name, self.fn, args,
                                 mesh=self.mesh, contract=self.contract,
                                 calls_per_step=self.calls)
                register_by_shape(self.mfu, self.name, self.fn, args,
                                  mesh=self.mesh,
                                  calls_per_step=self.calls)
                if self.mem is not None:
                    from deepspeed_tpu.runtime import \
                        memory_accounting as mem_acc

                    mem_acc.register_by_shape(
                        self.mem, self.name, self.fn, args,
                        mesh=self.mesh, calls_per_step=self.calls)
        return self.fn(*args)

    def __getattr__(self, item):
        return getattr(self.fn, item)


class PipelineEngine(DeepSpeedEngine):
    # per-stage params have no cross-stage 'data' replica to vote over —
    # _arm_integrity keeps the SENTINELS armed (they ride the host
    # loss/grad-norm this interpreter already fetches) and DISARM-warns
    # only the vote (ISSUE 13/16); inherited by any PipelineEngine
    # subclass, unlike a class-name check
    _integrity_armable = False
    """Training engine for PipelineModule models. Use train_batch/eval_batch;
    forward/backward/step are disabled (reference pipe/engine.py:1090-1098)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        assert isinstance(self.module, PipelineModule), \
            "PipelineEngine requires a PipelineModule model"
        # own program-registry namespace: pipe jits are per-(chunk, kind),
        # not the base engine's micro/apply programs (nothing registered
        # yet — base-engine registration happens at first dispatch)
        self._programs.engine = "pipe"
        if self.zero_optimization_stage() > 2:
            # stage-3 parameter partitioning (and its scheduled gather
            # plan) lives in the base engine: here each stage's params
            # are already stage-local on a submesh, and the per-chunk
            # jits have no cross-stage axis to gather over.  Downgrade
            # to stage 2 (optimizer + gradient sharding still apply)
            # instead of dying on an assert.
            log_dist(
                "PipelineEngine: ZeRO stage-3 scheduled gathers DISARMED "
                "— parameters are already partitioned per pipeline stage "
                "and the stage-3 gather plan has no cross-stage 'data' "
                "shard to gather; running ZeRO stage 2 (optimizer state "
                "+ gradient sharding over 'data')", ranks=[0],
                level=logging.WARNING)
            self._config.zero_config.stage = 2
            self._config.zero_optimization_stage = 2

        import jax

        self.num_stages = mesh_lib.pp_size(self.mesh)
        self.micro_batches = self.gradient_accumulation_steps()
        self._arm_schedule()
        self.num_chunks = self.num_stages * self.virtual_stages
        # the module partitions by CHUNK: with v=1 chunks == stages, with
        # interleaving each physical stage owns v non-contiguous chunks
        self.module.num_stages = self.num_chunks

        topo = PipeModelDataParallelTopology(
            num_pp=self.num_stages, num_mp=self.mp_world_size,
            num_dp=self.dp_world_size)
        self.grid = PipelineParallelGrid(topology=topo, rank=0,
                                         virtual_stages=self.virtual_stages)

        # one submesh per stage: mesh.devices is (pipe, data, seq, model)
        self._submeshes = []
        for s in range(self.num_stages):
            self._submeshes.append(
                jax.sharding.Mesh(self.mesh.devices[s],
                                  ("data", "seq", "model")))

        if _cached_stage_programs_halt(self._submeshes):
            disable_persistent_compile_cache(
                "PipelineEngine stages of more than one TPU halt their "
                "cores when read back from the cache (ROADMAP.md S4)")

        self.stage_states = None          # list[StageState] per CHUNK, lazy
        self._stage_shardings = None
        self._stage_jits = None
        self._compiled_schedule = None    # CompiledSchedule, lazy
        self._last_p2p_bytes = 0          # measured p2p volume, last batch
        self._p2p_edge_bytes = {}         # global chunk -> (act, grad) bytes
        # zb-h1 activation stashing (resolved lazily by _arm_stash once
        # shapes are known: the budget check needs per-micro stash bytes)
        self._stash_armed = False
        self._stash_blockers = []
        self._stash_bytes_per_chunk = None  # per-micro vjp-residual bytes

        if self.progressive_layer_drop is not None:
            # base engine injects pld_theta into flat batches; the pipeline
            # engine's per-stage jits never see the batch dict mid-stage
            log_dist(
                "PipelineEngine: progressive_layer_drop DISARMED — layers "
                "run undropped (theta would have to thread through every "
                "per-stage jit and re-partition stage compute; unsupported "
                "with pipeline parallelism — use the base engine for PLD)",
                ranks=[0], level=logging.WARNING)
            self.progressive_layer_drop = None
        # host-side loss scaling: the schedule is host-driven, so the shared
        # host DynamicLossScaler owns the policy (hysteresis, window, floor)
        if self.fp16_enabled():
            from deepspeed_tpu.runtime.fp16.loss_scaler import CreateLossScaler

            args_ls = dict(self._config.dynamic_loss_scale_args or {})
            args_ls.setdefault("init_scale",
                               self._config.initial_dynamic_scale)
            self._pipe_scaler = CreateLossScaler(
                static_loss_scale=self._config.loss_scale or 0,
                dynamic_scale_args=args_ls)
        else:
            from deepspeed_tpu.runtime.fp16.loss_scaler import LossScaler

            self._pipe_scaler = LossScaler(scale=1)
        self._host_skipped = 0

        log_dist(
            f"PipelineEngine: stages={self.num_stages} "
            f"micro_batches={self.micro_batches} dp={self.dp_world_size} "
            f"mp={self.mp_world_size}", ranks=[0])

    def _arm_schedule(self):
        """Resolve the requested pipeline schedule against its blockers.

        Sets self.pipe_schedule (effective), self.virtual_stages, and
        self._schedule_blockers. A blocked request falls back to plain
        1f1b with a DISARMED warning naming every blocker (the repo's
        armed-or-warns discipline, same as OneBitAdam/qgZ arming)."""
        from deepspeed_tpu.runtime.constants import (PIPELINE_SCHEDULE,
                                                     PIPELINE_VIRTUAL_STAGES)
        from deepspeed_tpu.runtime.pipe import schedule as sched_lib

        pcfg = self._config.pipeline
        requested = pcfg[PIPELINE_SCHEDULE]
        req_v = int(pcfg[PIPELINE_VIRTUAL_STAGES])
        S, gas = self.num_stages, self.micro_batches
        self.requested_schedule = requested
        blockers = []

        if requested == sched_lib.SCHEDULE_INTERLEAVED:
            if S < 2:
                blockers.append("pipe=1 (nothing to interleave)")
            if req_v < 2:
                blockers.append(f"virtual_stages={req_v} (needs >= 2)")
            if S >= 2 and gas % S != 0:
                blockers.append(
                    f"gradient_accumulation_steps={gas} not divisible by "
                    f"pipe={S} (the Megatron interleaving order requires it)")
            if req_v >= 2:
                why = self.module.validate_chunking(S, req_v)
                if why:
                    blockers.append(why)
        elif requested == sched_lib.SCHEDULE_ZB_H1:
            if S < 2:
                blockers.append("pipe=1 (no bubble to fill)")
            if self.module.has_tied_layers():
                blockers.append(
                    "tied layers present (deferred wgrads would interleave "
                    "with the cross-stage tied-grad reduction)")
            if req_v > 1:
                log_dist(
                    f"PipelineEngine: pipeline.virtual_stages={req_v} is "
                    f"ignored by the zb-h1 schedule (wgrad deferral fills "
                    f"the bubble instead of chunk interleaving)",
                    ranks=[0], level=logging.WARNING)
        elif req_v > 1:
            log_dist(
                f"PipelineEngine: pipeline.virtual_stages={req_v} has no "
                f"effect with schedule=1f1b; set schedule=interleaved",
                ranks=[0], level=logging.WARNING)

        if blockers:
            log_dist(
                f"PipelineEngine: schedule '{requested}' DISARMED — "
                f"falling back to 1f1b ({'; '.join(blockers)})",
                ranks=[0], level=logging.WARNING)
            self.pipe_schedule = sched_lib.SCHEDULE_1F1B
            self.virtual_stages = 1
        else:
            self.pipe_schedule = requested
            self.virtual_stages = req_v \
                if requested == sched_lib.SCHEDULE_INTERLEAVED else 1
        self._schedule_blockers = blockers

    # ------------------------------------------------------------------
    # disabled base API (reference pipe/engine.py:1090-1098)
    # ------------------------------------------------------------------
    def forward(self, *a, **k):
        raise RuntimeError("PipelineEngine: use train_batch()/eval_batch()")

    def backward(self, *a, **k):
        raise RuntimeError("PipelineEngine: use train_batch()/eval_batch()")

    def step(self, *a, **k):
        raise RuntimeError("PipelineEngine: use train_batch()/eval_batch()")

    @property
    def skipped_steps(self):
        return self._host_skipped

    def loss_scale(self):
        return self._pipe_scaler.cur_scale

    def is_first_stage(self):
        """True: the single controller owns every stage, including stage 0
        (reference semantics — 'does this rank host the first stage' — are
        per-rank; here one process IS all ranks, so both predicates hold
        and first/last-stage-only work like data loading and loss handling
        runs on this process)."""
        return True

    def is_last_stage(self):
        return True

    # ------------------------------------------------------------------
    # state construction
    # ------------------------------------------------------------------
    def _stage_zero_shardings(self, submesh, params_template):
        """NamedShardings for one stage: params take the layers' TP specs
        over the submesh 'model' axis (PP x TP — the reference's 3D grid,
        pipe/topology.py:246-249), master/opt/accum additionally
        ZeRO-sharded over the submesh 'data' axis."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        stage = self.zero_optimization_stage()
        dp = submesh.shape["data"]

        tp_spec = self.module.param_partition_spec(params_template)
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        param_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(submesh, s), tp_spec, is_leaf=is_p)
        if stage == 0:
            zero_spec = tp_spec
            zero = param_sh
        else:
            zero_spec = jax.tree_util.tree_map(
                lambda s, l: mesh_lib.zero_merge_spec(s, l, dp),
                tp_spec, params_template, is_leaf=is_p)
            zero = jax.tree_util.tree_map(
                lambda s: NamedSharding(submesh, s), zero_spec, is_leaf=is_p)

        # optimizer-state shardings (same policy as the base engine,
        # runtime/engine.py:_build_shardings): the optimizer declares its
        # state layout via state_spec; fallback matches param shapes
        rep = NamedSharding(submesh, P())
        opt_template = jax.eval_shape(self.optimizer.init_state,
                                      params_template)
        flat_opt, opt_def = jax.tree_util.tree_flatten(opt_template)
        if hasattr(self.optimizer, "state_spec"):
            spec_tree = self.optimizer.state_spec(zero_spec)
            spec_flat = jax.tree_util.tree_flatten(
                spec_tree, is_leaf=lambda x: x is None or isinstance(x, P))[0]
            assert len(spec_flat) == len(flat_opt)
            opt_sh_flat = [rep if s is None else NamedSharding(submesh, s)
                           for s in spec_flat]
        else:
            from deepspeed_tpu.runtime.utils import opt_shardings_by_shape

            zero_flat = jax.tree_util.tree_leaves(zero)
            shapes = [tuple(l.shape) for l in
                      jax.tree_util.tree_leaves(params_template)]
            opt_sh_flat = opt_shardings_by_shape(
                flat_opt, shapes, zero_flat, rep)
        opt_sh = opt_def.unflatten(opt_sh_flat)
        return param_sh, zero, opt_sh

    def _ensure_pipe_state(self, sample_micro):
        if self.stage_states is not None:
            return
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        # init full params on host once (layer by layer), then scatter each
        # stage's slice to its submesh
        init_rng, self._pipe_rng = jax.random.split(self._init_rng)
        # init on the HOST cpu backend: local_devices()[0] would be an
        # accelerator chip and the full fp32 model + a whole-model forward
        # would defeat per-stage memory scaling
        try:
            host_dev = jax.local_devices(backend="cpu")[0]
        except RuntimeError:  # pragma: no cover - cpu backend always exists
            host_dev = jax.local_devices()[0]
        with jax.default_device(host_dev):
            full_params = self.module.init(init_rng, sample_micro)
        full_params = jax.tree_util.tree_map(
            lambda l: np.asarray(jax.device_get(l), dtype=np.float32),
            full_params)
        parts = self.module.partition_layers(self.num_chunks)
        logger.info(f"pipeline partition boundaries: {parts} "
                    f"(chunks={self.num_chunks}, v={self.virtual_stages})")

        self.stage_states = []
        self._stage_shardings = []
        for s in range(self.num_chunks):
            submesh = self._chunk_mesh(s)
            keys = self.module.stage_param_keys(s)
            p32 = {k: full_params[k] for k in keys}
            rep, zero, opt_sh = self._stage_zero_shardings(submesh, p32)

            master = jax.tree_util.tree_map(
                lambda l, sh: jax.device_put(l, sh), p32, zero) \
                if self.mixed_precision else None
            params = jax.tree_util.tree_map(
                lambda l, sh: jax.device_put(
                    np.asarray(l, dtype=self.compute_dtype), sh), p32, rep)
            opt_src = master if self.mixed_precision else \
                jax.tree_util.tree_map(lambda l, sh: jax.device_put(l, sh),
                                       p32, zero)
            with jax.set_mesh(submesh):
                # out_shardings pins the declared layout — unconstrained,
                # XLA would pick its own and void the ZeRO partitioning
                opt_state = jax.jit(self.optimizer.init_state,
                                    out_shardings=opt_sh)(opt_src)
                accum = jax.tree_util.tree_map(
                    lambda l: jnp.zeros(l.shape, jnp.float32), p32)
                accum = jax.tree_util.tree_map(
                    lambda l, sh: jax.device_put(l, sh), accum, zero)
            self.stage_states.append(StageState(
                params=params, master=master, opt_state=opt_state,
                accum=accum))
            self._stage_shardings.append((rep, zero, opt_sh))
        self._build_stage_jits()
        self._arm_stash(sample_micro)
        n = sum(self.module.num_params(st.params) for st in self.stage_states)
        log_dist(f"Pipeline state initialized: {n/1e6:.1f}M params over "
                 f"{self.num_stages} stages x {self.virtual_stages} chunks "
                 f"(schedule={self.pipe_schedule})", ranks=[0])

    def _chunk_mesh(self, chunk):
        """Submesh of the physical stage owning global model chunk
        ``chunk`` (chunk q lives on stage q % pipe — grid.chunk_owner_
        stage; with v=1 this is the identity)."""
        return self._submeshes[self.grid.chunk_owner_stage(chunk)]

    def _build_stage_jits(self):
        import jax
        import jax.numpy as jnp

        module = self.module
        S = self.num_chunks
        gas = self.micro_batches
        zb = self.pipe_schedule == sched_lib.SCHEDULE_ZB_H1
        loss_fn = module.loss_fn
        # does any layer sow aux losses (MoE)? decided by module.init()
        self._module_has_aux = any(l.has_losses for l in module._layers)

        self._stage_jits = []
        for s in range(S):
            is_last = s == S - 1

            def fwd(params, x, rng, s=s):
                return module.forward_stage(params, x, s, rng, train=True)

            def fwd_aux(params, x, rng, s=s):
                # stage forward + stage-local sown aux losses (MoE load
                # balance): backward adds them to the objective directly
                return module.forward_stage(params, x, s, rng, train=True,
                                            return_aux=True)

            def fwd_loss(params, x, rng, batch, s=s):
                out, aux = module.forward_stage(params, x, s, rng,
                                                train=True, return_aux=True)
                loss, _ = loss_fn(out, batch)
                return loss, aux

            rep_sh, zero_sh, opt_sh = self._stage_shardings[s]

            def accum_add(accum, gp, zero_sh=zero_sh):
                # pin the ZeRO layout: without the constraint XLA is free to
                # re-lay-out the donated accumulator after the add
                return jax.tree_util.tree_map(
                    lambda a, g, sh: jax.lax.with_sharding_constraint(
                        a + g.astype(jnp.float32), sh),
                    accum, gp, zero_sh)

            # NOTE: closures bind loop-locals via default args — a bare
            # reference would late-bind to the LAST stage's function.
            # backward + gradient accumulation are ONE jit (donated accum):
            # the host-driven schedule pays one dispatch per BackwardPass
            # instead of two, and the grads never materialize outside the
            # accumulator.
            def bwd_last(params, accum, x, rng, batch, scale,
                         fwd_loss=fwd_loss, accum_add=accum_add):
                def scaled(params, x):
                    loss, aux = fwd_loss(params, x, rng, batch)
                    # reported loss includes the stage-local aux term so the
                    # two executors of a PipelineModule (this engine and the
                    # sequential base-engine path via module.loss) agree.
                    # Mid-stage aux terms enter gradients only — a truly
                    # global reported objective would need an extra host
                    # reduction per micro-batch.
                    with_aux = loss.astype(jnp.float32) + aux
                    return with_aux * scale / gas, with_aux

                # integer x (token ids reaching the last stage when pipe=1)
                # is not differentiable and its grad is never sent anywhere
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
                    (_, loss), grads = jax.value_and_grad(
                        scaled, argnums=(0, 1), has_aux=True)(params, x)
                    gp, gx = grads
                else:
                    (_, loss), gp = jax.value_and_grad(
                        scaled, argnums=0, has_aux=True)(params, x)
                    gx = jnp.zeros((), jnp.float32)
                return accum_add(accum, gp), gx, loss

            def bwd_mid(params, accum, x, rng, gy, scale, fwd_aux=fwd_aux,
                        accum_add=accum_add):
                def f(p, x):
                    y, aux = fwd_aux(p, x, rng)
                    return y, jnp.asarray(aux, jnp.float32)

                (_, aux), vjp = jax.vjp(f, params, x)
                # aux cotangent scale/gas: the stage-local aux losses enter
                # the objective with the same loss scaling as the last
                # stage's loss term
                gp, gx = vjp((gy, (scale / gas).astype(jnp.float32)))
                # raw aux returned so train_batch can report the FULL
                # objective (last-stage loss + every stage's aux)
                return accum_add(accum, gp), gx, aux

            def sqnorm(accum):
                total = jnp.float32(0.0)
                finite = jnp.asarray(True)
                for g in jax.tree_util.tree_leaves(accum):
                    g32 = g.astype(jnp.float32)
                    total += jnp.sum(jnp.square(g32))
                    finite &= jnp.all(jnp.isfinite(g32))
                return total, finite

            optimizer = self.optimizer
            mixed = self.mixed_precision
            cdtype = self.compute_dtype

            def apply_step(state: StageState, lr, inv_scale, clip_factor,
                           rep_sh=rep_sh, zero_sh=zero_sh, opt_sh=opt_sh):
                grads = jax.tree_util.tree_map(
                    lambda g: g * inv_scale * clip_factor, state.accum)
                target = state.master if mixed else state.params
                new_master, new_opt = optimizer.update(
                    grads, state.opt_state, target, lr=lr)
                new_opt = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, new_opt, opt_sh)
                # pin layouts: params keep the TP spec (replicated over
                # 'data' — the ZeRO all-gather happens here, reference
                # stage2.py:1556-1590), master stays ZeRO-sharded.
                # Unconstrained, XLA would leave params data-sharded and
                # re-gather on every forward.
                if mixed:
                    new_master = jax.tree_util.tree_map(
                        jax.lax.with_sharding_constraint, new_master, zero_sh)
                    new_params = jax.tree_util.tree_map(
                        lambda l, sh: jax.lax.with_sharding_constraint(
                            l.astype(cdtype), sh), new_master, rep_sh)
                else:
                    new_params = jax.tree_util.tree_map(
                        jax.lax.with_sharding_constraint, new_master, rep_sh)
                    new_master = None
                zero_accum = jax.tree_util.tree_map(
                    lambda l, sh: jax.lax.with_sharding_constraint(
                        jnp.zeros_like(l), sh), state.accum, zero_sh)
                return StageState(params=new_params, master=new_master,
                                  opt_state=new_opt, accum=zero_accum)

            def eval_fwd(params, x, rng, s=s):
                return module.forward_stage(params, x, s, rng, train=False)

            def eval_loss(params, x, rng, batch, s=s):
                out = module.forward_stage(params, x, s, rng, train=False)
                loss, _ = loss_fn(out, batch)
                return loss

            # --- zero-bubble split backward (ZB-H1, arXiv 2401.10241) ---
            # dgrad stays on the critical path (it unblocks the upstream
            # stage), wgrad is deferred into bubble slots; both recompute
            # the stage forward (per-stage remat, same as the fused
            # backward) under the SAME rng so dropout masks agree, and the
            # identical cotangents make dgrad+wgrad = the fused vjp.
            def bwd_last_dgrad(params, x, rng, batch, scale,
                               fwd_loss=fwd_loss):
                def scaled(x_):
                    loss, aux = fwd_loss(params, x_, rng, batch)
                    with_aux = loss.astype(jnp.float32) + aux
                    return with_aux * scale / gas, with_aux

                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
                    (_, loss), gx = jax.value_and_grad(
                        scaled, has_aux=True)(x)
                else:
                    _, loss = scaled(x)
                    gx = jnp.zeros((), jnp.float32)
                return gx, loss

            def bwd_last_wgrad(params, accum, x, rng, batch, scale,
                               fwd_loss=fwd_loss, accum_add=accum_add):
                def scaled(p):
                    loss, aux = fwd_loss(p, x, rng, batch)
                    return (loss.astype(jnp.float32) + aux) * scale / gas

                gp = jax.grad(scaled)(params)
                return accum_add(accum, gp)

            def bwd_mid_dgrad(params, x, rng, gy, scale, fwd_aux=fwd_aux):
                def f(x_):
                    y, aux = fwd_aux(params, x_, rng)
                    return y, jnp.asarray(aux, jnp.float32)

                (_, aux), vjp = jax.vjp(f, x)
                (gx,) = vjp((gy, (scale / gas).astype(jnp.float32)))
                return gx, aux

            def bwd_mid_wgrad(params, accum, x, rng, gy, scale,
                              fwd_aux=fwd_aux, accum_add=accum_add):
                def f(p):
                    y, aux = fwd_aux(p, x, rng)
                    return y, jnp.asarray(aux, jnp.float32)

                _, vjp = jax.vjp(f, params)
                (gp,) = vjp((gy, (scale / gas).astype(jnp.float32)))
                return accum_add(accum, gp)

            # --- zb-h1 + activation stashing ------------------------------
            # The forward runs ONCE per (chunk, micro) and returns its vjp
            # closure — a jax.tree_util.Partial whose array leaves are the
            # saved residuals (every checkpoint_name'd intermediate the
            # model's remat_policy would have kept, and then some): that
            # Partial IS the stash, crossing the jit boundary as a pytree.
            # dgrad evaluates the cotangent chain only (XLA DCEs the
            # param-transpose work), wgrad replays the chain into the
            # param grads — neither pass recomputes the forward, which is
            # exactly CostModel.stash()'s d = w = 1.  wgrad DONATES the
            # stash (and accum): the residual buffers free in place on the
            # dgrad->wgrad handoff instead of surviving to the end of the
            # batch.  rng/dropout consistency is free — there is only one
            # forward, so dgrad and wgrad share its masks by construction.
            def fwd_stash_mid(params, x, rng, fwd_aux=fwd_aux):
                def f(p, x_):
                    y, aux = fwd_aux(p, x_, rng)
                    return y, jnp.asarray(aux, jnp.float32)

                (y, aux), stash = jax.vjp(f, params, x)
                return y, aux, stash

            def fwd_stash_last(params, x, rng, batch, scale,
                               fwd_loss=fwd_loss):
                def scaled(p, x_):
                    loss, aux = fwd_loss(p, x_, rng, batch)
                    with_aux = loss.astype(jnp.float32) + aux
                    return with_aux * scale / gas, with_aux

                _, stash, loss = jax.vjp(scaled, params, x, has_aux=True)
                return loss, stash

            def bwd_dgrad_last_stash(stash):
                _, gx = stash(jnp.float32(1.0))
                return gx

            def bwd_dgrad_mid_stash(stash, gy, scale):
                _, gx = stash((gy, (scale / gas).astype(jnp.float32)))
                return gx

            def bwd_wgrad_last_stash(stash, accum, accum_add=accum_add):
                gp, _ = stash(jnp.float32(1.0))
                return accum_add(accum, gp)

            def bwd_wgrad_mid_stash(stash, accum, gy, scale,
                                    accum_add=accum_add):
                gp, _ = stash((gy, (scale / gas).astype(jnp.float32)))
                return accum_add(accum, gp)

            submesh = self._chunk_mesh(s)
            jits = {
                "fwd": jax.jit(fwd),
                "bwd_last": jax.jit(bwd_last, donate_argnums=(1,))
                if is_last else None,
                "bwd_mid": jax.jit(bwd_mid, donate_argnums=(1,)),
                "sqnorm": jax.jit(sqnorm),
                "apply_step": jax.jit(apply_step, donate_argnums=(0,)),
                "eval_fwd": jax.jit(eval_fwd),
                "eval_loss": jax.jit(eval_loss) if is_last else None,
                "mean_scalar": jax.jit(lambda ls: jnp.stack(ls).mean()),
                "mesh": submesh,
            }
            if zb:
                jits["bwd_dgrad"] = jax.jit(bwd_last_dgrad) if is_last \
                    else jax.jit(bwd_mid_dgrad)
                jits["bwd_wgrad"] = (
                    jax.jit(bwd_last_wgrad, donate_argnums=(1,)) if is_last
                    else jax.jit(bwd_mid_wgrad, donate_argnums=(1,)))
                # stash twins (compiled only if _arm_stash arms: jax.jit
                # wrappers are lazy).  dgrad must NOT donate the stash —
                # the deferred wgrad is its second consumer.
                jits["fwd_stash"] = jax.jit(
                    fwd_stash_last if is_last else fwd_stash_mid)
                jits["bwd_dgrad_stash"] = jax.jit(
                    bwd_dgrad_last_stash if is_last else bwd_dgrad_mid_stash)
                jits["bwd_wgrad_stash"] = jax.jit(
                    bwd_wgrad_last_stash if is_last else bwd_wgrad_mid_stash,
                    donate_argnums=(0, 1))
            tel = self._telemetry
            mem = self._memacct
            # every compute jit is proxied: the program registry is
            # always on (registration is a first-dispatch shape capture,
            # no compile — the disarmed step stays bit-identical with
            # zero extra compiles); MFU/memory ledgers ride the same
            # proxy only when armed.  fwd/bwd kinds run once per micro
            # per chunk, the reductions/apply once per optimizer step.
            per_micro = {"fwd", "fwd_stash", "bwd_last", "bwd_mid",
                         "bwd_dgrad", "bwd_wgrad", "bwd_dgrad_stash",
                         "bwd_wgrad_stash"}
            mfu = tel.mfu if tel is not None else None
            n_accum = len(jax.tree_util.tree_leaves(zero_sh))
            jits = {
                k: _MfuJitProxy(v, f"chunk{s}:{k}", mfu, submesh,
                                gas if k in per_micro else 1.0,
                                mem=mem, programs=self._programs,
                                contract=self._stage_jit_contract(
                                    k, is_last, n_accum))
                if (v is not None and k != "mesh") else v
                for k, v in jits.items()}
            self._stage_jits.append(jits)

    def _stage_jit_contract(self, kind, is_last, n_accum):
        """The HLO contract one stage-jit kind declares to the program
        registry (telemetry/programs.py): every compute jit is pure
        device work; a non-last forward's boundary activation leaves the
        stage in the compute dtype (an f32 boundary would double the p2p
        bytes pipeline_report() budgets per edge); backward kinds donate
        the grad accumulator; the zb-stash wgrad additionally donates the
        residual stash and writes every new-accum output into donated
        memory (no copy on the dgrad->wgrad handoff)."""
        import numpy as np

        contract = {"host_transfer_free": True}
        if kind == "fwd" and not is_last:
            short = {"float32": "f32", "bfloat16": "bf16",
                     "float16": "f16", "float64": "f64"}
            name = np.dtype(self.compute_dtype).name
            contract["boundary_dtypes"] = [short.get(name, name)]
        if kind in ("bwd_last", "bwd_mid", "bwd_wgrad"):
            contract["donates_argnums"] = (1,)
        if kind == "apply_step":
            contract["donates_argnums"] = (0,)
        if kind == "bwd_wgrad_stash":
            contract["donates_argnums"] = (0, 1)
            contract["outputs_aliased"] = n_accum
        return contract

    def _stash_bytes_estimate(self, sample_micro):
        """Per-chunk, per-micro stash bytes (the vjp-residual leaves of one
        fwd_stash call), by abstract evaluation — no device work.  Chains
        the chunk output shapes forward exactly as the executor does.
        Also records the FULL fwd_stash output footprint per chunk
        (stash + boundary activation/loss) in
        ``_stash_out_bytes_per_chunk`` — the analytic side of the
        memory-accounting cross-check against the compiled program's
        measured output+temp bytes."""
        import jax

        from deepspeed_tpu.runtime import memory_accounting as mem_acc

        def tree_bytes(tree):
            # the shared analytic primitive — one byte-pricing
            # implementation for both sides of the cross-check
            return sum(mem_acc.bytes_of(l.shape, l.dtype)
                       for l in jax.tree_util.tree_leaves(tree))

        C = self.num_chunks
        rng = jax.random.PRNGKey(0)
        scale = np.float32(1.0)
        x = self.module.input_fn(sample_micro)
        out, out_full = [], []
        for q in range(C):
            jits = self._stage_jits[q]
            # analytic transient bound per chunk: outputs (stash +
            # boundary activation/loss) + one argument-sized working set
            args_b = tree_bytes(self.stage_states[q].params) \
                + tree_bytes(x)
            with jax.set_mesh(self._chunk_mesh(q)):
                if q < C - 1:
                    x, _aux, stash = jax.eval_shape(
                        jits["fwd_stash"], self.stage_states[q].params,
                        x, rng)
                    extra = tree_bytes((x, _aux))
                else:
                    args_b += tree_bytes(sample_micro)
                    _loss, stash = jax.eval_shape(
                        jits["fwd_stash"], self.stage_states[q].params,
                        x, rng, sample_micro, scale)
                    extra = tree_bytes(_loss)
            out.append(tree_bytes(stash))
            out_full.append(out[-1] + extra + args_b)
        self._stash_out_bytes_per_chunk = out_full
        return out

    def _arm_stash(self, sample_micro):
        """Resolve zb-h1 activation stashing against its blockers.

        Sets self._stash_armed / self._stash_blockers /
        self._stash_bytes_per_chunk.  Armed, the executor runs the forward
        once per (chunk, micro) and the split backward consumes the stash;
        any blocker falls back to the remat split backward with DISARMED
        warnings naming it — including one warning PER STAGE whose
        analytic peak stash bytes exceed ``pipeline.stash_budget``."""
        from deepspeed_tpu.runtime.constants import (PIPELINE_STASH,
                                                     PIPELINE_STASH_BUDGET)
        from deepspeed_tpu.runtime.pipe import bubble_accounting as ba

        pcfg = self._config.pipeline
        requested = pcfg[PIPELINE_STASH]
        budget = int(pcfg[PIPELINE_STASH_BUDGET])
        self._stash_armed = False
        self._stash_blockers = []
        zb = self.pipe_schedule == sched_lib.SCHEDULE_ZB_H1
        if requested is False:
            return
        if not zb:
            if requested is True:
                # explicit request on a non-zb schedule warns; "auto" is
                # silently inert (stashing is a zb-h1 refinement)
                self._stash_blockers = [
                    f"effective schedule is '{self.pipe_schedule}' "
                    f"(stashing feeds the zb-h1 split backward; fused "
                    f"backwards already recompute exactly once)"]
                log_dist(
                    f"PipelineEngine: activation_stashing DISARMED — "
                    f"{self._stash_blockers[0]}",
                    ranks=[0], level=logging.WARNING)
            return
        blockers = []
        try:
            per_chunk = self._stash_bytes_estimate(sample_micro)
        except Exception as e:  # lint: allow-broad-except — stashing is an
            # optimization: any abstract-eval failure must DISARM it (and
            # name itself), never take down training
            per_chunk = None
            blockers.append(f"stash-size estimation failed "
                            f"({type(e).__name__}: {e})")
        self._stash_bytes_per_chunk = per_chunk
        if per_chunk is not None and budget > 0:
            rep = ba.simulate(sched_lib.compile_schedule(
                sched_lib.SCHEDULE_ZB_H1, self.micro_batches,
                self.num_stages, stash=True))
            for s, peak in enumerate(rep["peak_live_stash"]):
                need = peak * per_chunk[s]
                if need > budget:
                    why = (f"stage {s} needs {need} stash bytes at peak "
                           f"({peak} live micros x {per_chunk[s]} B) > "
                           f"pipeline.stash_budget={budget}")
                    blockers.append(why)
                    log_dist(
                        f"PipelineEngine: activation_stashing DISARMED on "
                        f"stage {s} — {why}; falling back to remat",
                        ranks=[0], level=logging.WARNING)
        self._stash_blockers = blockers
        self._stash_armed = not blockers
        if self._stash_armed and self._memacct is not None \
                and per_chunk is not None:
            # analytic-vs-measured cross-check (ISSUE 15): the same
            # residual estimate the stash_budget gate was sized from,
            # checked at report time against the compiled fwd_stash's
            # measured output+temp bytes — a >15% underestimate warns
            # that the budget under-provisions
            for q in range(self.num_chunks):
                self._memacct.expect(
                    f"chunk{q}:fwd_stash",
                    f"zb stash forward chunk {q}: vjp residuals "
                    f"({per_chunk[q]} B analytic, the stash_budget "
                    f"input) + boundary outputs",
                    self._stash_out_bytes_per_chunk[q],
                    field="transient_bytes")
        if self._stash_armed:
            import warnings

            # bwd_wgrad_stash's donated residuals that alias no output
            # draw XLA's 'donated buffers were not usable' warning at
            # lowering; that is the expected rendering of the stash
            # contract (buffer donors), not a lost alias.  Filter ONCE
            # here instead of paying a catch_warnings save/restore per
            # instruction in the dispatch hot loop.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
        if blockers and not any("stash_budget" in b for b in blockers):
            log_dist(
                f"PipelineEngine: activation_stashing DISARMED — "
                f"{'; '.join(blockers)}; falling back to remat",
                ranks=[0], level=logging.WARNING)
        # the compiled stream depends on the stash decision (wgrad slots
        # are timed at d = w = 1 and stash slots are emitted)
        self._compiled_schedule = None

    # ------------------------------------------------------------------
    # batch placement
    # ------------------------------------------------------------------
    def _put_stage(self, tree, stage_id, batch_dims=1):
        """Place arrays on a stage submesh, dim0 sharded over 'data'."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        submesh = self._submeshes[stage_id]

        def put(x):
            x = np.asarray(x)
            spec = P(*(["data"] + [None] * (x.ndim - 1))) if x.ndim >= 1 else P()
            return jax.device_put(x, NamedSharding(submesh, spec))

        return jax.tree_util.tree_map(put, tree)

    def _transfer(self, arr, to_stage, edge=None, kind=None):
        """Move an activation/grad tensor to an adjacent stage's submesh —
        the p2p edge (reference pipe/p2p.py:31-58). ``edge``/``kind`` tag
        the chunk boundary for the p2p volume accounting (edge q = the
        boundary between global chunks q and q+1)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if edge is not None:
            nbytes = int(arr.size) * arr.dtype.itemsize
            self._last_p2p_bytes += nbytes
            # first-seen payload per (edge, kind): the stable representative
            # for the analytic model (micros are shape-uniform slices of one
            # batch; see comm_accounting.pipe_p2p_bytes)
            self._p2p_edge_bytes.setdefault(edge, {}).setdefault(kind, nbytes)
        submesh = self._submeshes[to_stage]
        spec = P(*(["data"] + [None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(submesh, spec))

    # ------------------------------------------------------------------
    # schedule execution
    # ------------------------------------------------------------------
    def train_batch(self, data_iter=None, batch=None):
        """Run one full 1F1B-scheduled batch: gas micro-batches through all
        stages + optimizer step (reference pipe/engine.py:244-318)."""
        import jax

        micros = self._collect_micros(data_iter, batch)
        self._ensure_pipe_state(micros[0])
        if self._telemetry is not None:
            if self._mfu_n_params is None and self.stage_states is not None:
                self._mfu_n_params = sum(
                    int(l.size) for st in self.stage_states
                    for l in jax.tree_util.tree_leaves(st.params))
            self._note_mfu_workload(micros[0],
                                    micros_in_batch=self.micro_batches)
        self.tput_timer.start()

        losses, mid_auxes = self._exec_train_schedule(micros)
        self._chaos_poison_accum()

        # --- optimizer step (host-coordinated across stages) -----------
        tr = self._tracer
        _t0 = tr.begin() if tr is not None else 0.0
        lr = self._advance_lr()
        sq_total, all_finite = 0.0, True
        stats = []
        for s in range(self.num_chunks):
            with jax.set_mesh(self._chunk_mesh(s)):
                stats.append(self._stage_jits[s]["sqnorm"](
                    self.stage_states[s].accum))
        # one batched fetch for all chunks: a device_get per chunk would
        # serialize host<->device once per loop turn (graftlint host-sync)
        for sq, finite in jax.device_get(stats):
            sq_total += float(sq)
            all_finite &= bool(finite)

        scale = self._pipe_scaler.cur_scale
        if all_finite:
            # accum holds sum of scaled per-micro grads (each already /gas)
            inv_scale = 1.0 / scale
            gnorm = np.sqrt(sq_total) * inv_scale
            clip = self.gradient_clipping()
            clip_factor = min(1.0, clip / (gnorm + 1e-6)) if clip else 1.0
            for s in range(self.num_chunks):
                with jax.set_mesh(self._chunk_mesh(s)):
                    self.stage_states[s] = self._stage_jits[s]["apply_step"](
                        self.stage_states[s], np.float32(lr),
                        np.float32(inv_scale), np.float32(clip_factor))
            self._last_grad_norm = gnorm
        else:
            # overflow: drop grads; the shared scaler applies hysteresis
            self._host_skipped += 1
        self._pipe_scaler.update_scale(not all_finite)
        if not all_finite:
            log_dist(f"PipelineEngine: OVERFLOW, skipping step "
                     f"{self.global_steps + 1}, scale -> "
                     f"{self._pipe_scaler.cur_scale:g}", ranks=[0])
            import jax.numpy as jnp

            for s in range(self.num_chunks):
                with jax.set_mesh(self._chunk_mesh(s)):
                    st = self.stage_states[s]
                    # zeros_like, NOT a*0.0: accum holds Inf/NaN here and
                    # inf*0 = NaN would poison every subsequent step
                    zero = jax.tree_util.tree_map(jnp.zeros_like, st.accum)
                    self.stage_states[s] = st._replace(accum=zero)

        self.global_steps += 1
        self.micro_steps += self.micro_batches
        if tr is not None:
            tr.complete("optimizer_step", self._lane_train, _t0,
                        a0=self.global_steps)
            if not all_finite:
                tr.instant("overflow_skip", self._lane_train,
                           a0=self.global_steps)
        self.tput_timer.stop()
        # one reduction + one transfer instead of gas scalar fetches
        with jax.set_mesh(self._chunk_mesh(self.num_chunks - 1)):
            loss = float(jax.device_get(
                self._stage_jits[-1]["mean_scalar"](losses)))
        # mid-chunk aux losses (MoE load balance) join the reported
        # objective so train_batch returns the same number regardless of
        # stage count (the last chunk's own aux is already inside `loss`).
        # Per-chunk reductions dispatch async; ONE fetch collects them all.
        aux_means = []
        for s, auxes in enumerate(mid_auxes):
            if auxes:
                with jax.set_mesh(self._chunk_mesh(s)):
                    aux_means.append(self._stage_jits[s]["mean_scalar"](auxes))
        if aux_means:
            loss += float(np.sum(jax.device_get(aux_means)))
        self._last_loss = loss
        self._last_metrics = {
            "overflow": not all_finite,
            "grad_norm": getattr(self, "_last_grad_norm", 0.0),
            "loss_scale": scale, "loss": loss,
            "pipe_schedule": self.pipe_schedule,
            "pipe_p2p_bytes_per_step": self._last_p2p_bytes}
        mon = self._integrity
        if mon is not None and mon.sentinels_armed:
            # sentinels ride the values this interpreter ALREADY holds
            # on host — the batched sqnorm fetch above and the one loss
            # reduction: zero new device syncs (update_ratio stays
            # None; per-stage apply jits have no delta-norm outputs)
            mon.observe_step(self.global_steps, loss=loss,
                             grad_norm=float(self._last_grad_norm)
                             if all_finite else None,
                             update_ratio=None, overflow=not all_finite)
        self._observe_step_outcome(loss=loss, overflow=not all_finite)
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(self.global_steps)
        return loss

    def eval_batch(self, data_iter=None, batch=None):
        """Forward-only pipelined evaluation (reference pipe/engine.py:320)."""
        import jax

        micros = self._collect_micros(data_iter, batch)
        self._ensure_pipe_state(micros[0])
        C = self.num_chunks
        losses = []
        rng = jax.random.fold_in(self._pipe_rng, self.global_steps)
        # forward wavefront over model chunks (with interleaving the
        # activation hops back to stage 0 after each chunk group)
        for mb, micro in enumerate(micros):
            x = self._put_stage(self.module.input_fn(micro), 0)
            for q in range(C):
                jits = self._stage_jits[q]
                with jax.set_mesh(self._chunk_mesh(q)):
                    if q == C - 1:
                        batch_dev = self._put_stage(micro, self.num_stages - 1)
                        losses.append(jits["eval_loss"](
                            self.stage_states[q].params, x, rng, batch_dev))
                    else:
                        x = jits["eval_fwd"](self.stage_states[q].params, x, rng)
                        x = self._transfer(
                            x, self.grid.chunk_owner_stage(q + 1))
        # single batched fetch: per-loss device_get would sync once per micro
        out = float(np.mean(jax.device_get(losses)))
        if self._watchdog is not None:
            # eval between optimizer steps is progress, not a stalled step
            self._watchdog.heartbeat()
        return out

    def _collect_micros(self, data_iter, batch):
        gas = self.micro_batches
        if batch is not None:
            if isinstance(batch, dict):
                return [{k: v[i] for k, v in batch.items()} for i in range(gas)]
            return list(batch)
        assert data_iter is not None, "train_batch needs data_iter or batch"
        return [next(data_iter) for _ in range(gas)]

    def _ensure_compiled_schedule(self):
        if self._compiled_schedule is None:
            self._compiled_schedule = sched_lib.compile_schedule(
                self.pipe_schedule, self.micro_batches, self.num_stages,
                self.virtual_stages, stash=self._stash_armed)
        return self._compiled_schedule

    def _exec_train_schedule(self, micros):
        """Execute the compiled schedule's per-stage instruction streams
        with queue semantics (the single-controller analog of reference
        _exec_schedule, pipe/engine.py:1148-1161): stages advance round-
        robin one instruction at a time; a Recv blocks its stage until the
        matching Send ran. Device programs still overlap — dispatch is
        async, ordering here is host-side only. A stream set that can
        never unblock raises instead of hanging."""
        import jax

        compiled = self._ensure_compiled_schedule()
        S = self.num_stages
        C = self.num_chunks
        streams = compiled.streams
        nbuf = compiled.num_buffers

        # per-CHUNK buffer slots
        in_act = [[None] * nbuf[q] for q in range(C)]    # fwd input (saved)
        out_act = [[None] * nbuf[q] for q in range(C)]   # fwd output
        in_grad = [[None] * nbuf[q] for q in range(C)]   # recv'd dL/dout
        out_grad = [[None] * nbuf[q] for q in range(C)]  # computed dL/din
        micro_dev = [[None] * nbuf[q] for q in range(C)] # loaded micro
        # the COMPILED stream is the single source of truth: stash mode
        # only runs against a stream that emitted stash slots
        stashed = compiled.stash
        # stash slots (zb-h1 stashing): the forward's vjp residuals, live
        # from ForwardPass until BackwardWeightPass donates them away
        stash_buf = [[None] * n for n in compiled.num_stash_slots]
        act_q = [deque() for _ in range(C)]   # inbound acts per dest chunk
        grad_q = [deque() for _ in range(C)]  # inbound grads per dest chunk
        losses = []
        mid_auxes = [[] for _ in range(C)]    # per-micro aux, mid chunks
        base_rng = jax.random.fold_in(self._pipe_rng, self.global_steps)
        micro_rngs = [jax.random.fold_in(base_rng, i)
                      for i in range(self.micro_batches)]
        scale = np.float32(self._pipe_scaler.cur_scale)
        self._last_p2p_bytes = 0
        # telemetry: one lane per PHYSICAL stage, one span per executed
        # compiled instruction (chunk/micro in the args) — the exported
        # trace renders the schedule, and bubble_accounting.replay_trace
        # replays exactly these spans for the measured-vs-analytic
        # cross-check.  The batch-begin marker scopes a replay to the
        # LAST batch (streams of two batches would pipeline across the
        # optimizer step the simulator doesn't model).
        tr = self._tracer
        if tr is not None:
            tr_lanes = [tr.lane(f"stage{s}") for s in range(S)]
            for n in ("LoadMicroBatch", "ForwardPass", "BackwardPass",
                      "BackwardGradPass", "BackwardWeightPass",
                      "SendActivation", "RecvActivation", "SendGrad",
                      "RecvGrad"):
                tr.intern(n, args=("chunk", "micro"))
            tr.instant("pipe_batch_begin", self._lane_train,
                       a0=self.global_steps)

        def chunk_of(cmd, s):
            return getattr(cmd, "chunk_id", 0) * S + s

        def exec_cmd(cmd, s):
            q = chunk_of(cmd, s)
            buf = cmd.buffer_id
            mb = cmd.micro_id
            jits = self._stage_jits[q]
            st = self.stage_states[q]
            if isinstance(cmd, sched_lib.SendActivation):
                dest = q + 1
                act_q[dest].append(self._transfer(
                    out_act[q][buf], self.grid.chunk_owner_stage(dest),
                    edge=q, kind="act"))
                out_act[q][buf] = None
            elif isinstance(cmd, sched_lib.SendGrad):
                dest = q - 1
                grad_q[dest].append(self._transfer(
                    out_grad[q][buf], self.grid.chunk_owner_stage(dest),
                    edge=q - 1, kind="grad"))
                out_grad[q][buf] = None
            elif isinstance(cmd, sched_lib.LoadMicroBatch):
                micro = micros[mb]
                if q == 0:
                    in_act[q][buf] = self._put_stage(
                        self.module.input_fn(micro), 0)
                if q == C - 1:
                    micro_dev[q][buf] = self._put_stage(micro, S - 1)
            elif isinstance(cmd, sched_lib.RecvActivation):
                in_act[q][buf] = act_q[q].popleft()
            elif isinstance(cmd, sched_lib.RecvGrad):
                in_grad[q][buf] = grad_q[q].popleft()
            elif isinstance(cmd, sched_lib.ForwardPass):
                with jax.set_mesh(self._chunk_mesh(q)):
                    if stashed:
                        # forward runs ONCE: its vjp residuals are the
                        # stash; the saved input (and last-chunk labels)
                        # free here — the residuals supersede them
                        if q == C - 1:
                            loss, stash_buf[q][buf] = jits["fwd_stash"](
                                st.params, in_act[q][buf], micro_rngs[mb],
                                micro_dev[q][buf], scale)
                            losses.append(loss)
                            micro_dev[q][buf] = None
                        else:
                            out_act[q][buf], aux, stash_buf[q][buf] = \
                                jits["fwd_stash"](st.params, in_act[q][buf],
                                                  micro_rngs[mb])
                            if self._module_has_aux:
                                mid_auxes[q].append(aux)
                        in_act[q][buf] = None
                    elif q < C - 1:
                        out_act[q][buf] = jits["fwd"](
                            st.params, in_act[q][buf], micro_rngs[mb])
                    # last chunk w/o stash: loss computed in the backward
            elif isinstance(cmd, sched_lib.BackwardPass):
                with jax.set_mesh(self._chunk_mesh(q)):
                    if q == C - 1:
                        new_accum, gx, loss = jits["bwd_last"](
                            st.params, st.accum, in_act[q][buf],
                            micro_rngs[mb], micro_dev[q][buf], scale)
                        losses.append(loss)
                        micro_dev[q][buf] = None
                    else:
                        new_accum, gx, aux = jits["bwd_mid"](
                            st.params, st.accum, in_act[q][buf],
                            micro_rngs[mb], in_grad[q][buf], scale)
                        if self._module_has_aux:
                            mid_auxes[q].append(aux)
                    self.stage_states[q] = st._replace(accum=new_accum)
                    out_grad[q][buf] = gx
                in_act[q][buf] = None
                in_grad[q][buf] = None
            elif isinstance(cmd, sched_lib.BackwardGradPass):
                # zb dgrad: unblocks the upstream stage.  Stashed: consume
                # the forward's residuals (no recompute), keeping the stash
                # and in_grad LIVE for the deferred wgrad.  Remat: keeps
                # in_act and in_grad live and re-runs the forward.
                with jax.set_mesh(self._chunk_mesh(q)):
                    if stashed:
                        if q == C - 1:
                            gx = jits["bwd_dgrad_stash"](stash_buf[q][buf])
                        else:
                            gx = jits["bwd_dgrad_stash"](
                                stash_buf[q][buf], in_grad[q][buf], scale)
                    elif q == C - 1:
                        gx, loss = jits["bwd_dgrad"](
                            st.params, in_act[q][buf], micro_rngs[mb],
                            micro_dev[q][buf], scale)
                        losses.append(loss)
                    else:
                        gx, aux = jits["bwd_dgrad"](
                            st.params, in_act[q][buf], micro_rngs[mb],
                            in_grad[q][buf], scale)
                        if self._module_has_aux:
                            mid_auxes[q].append(aux)
                    out_grad[q][buf] = gx
            elif isinstance(cmd, sched_lib.BackwardWeightPass):
                with jax.set_mesh(self._chunk_mesh(q)):
                    if stashed:
                        # the wgrad jit DONATES the stash (+ accum): the
                        # residual buffers free in place here (XLA's
                        # unusable-donation warning for donor-only leaves
                        # is filtered once at _arm_stash time)
                        if q == C - 1:
                            new_accum = jits["bwd_wgrad_stash"](
                                stash_buf[q][buf], st.accum)
                        else:
                            new_accum = jits["bwd_wgrad_stash"](
                                stash_buf[q][buf], st.accum,
                                in_grad[q][buf], scale)
                        stash_buf[q][buf] = None
                    elif q == C - 1:
                        new_accum = jits["bwd_wgrad"](
                            st.params, st.accum, in_act[q][buf],
                            micro_rngs[mb], micro_dev[q][buf], scale)
                        micro_dev[q][buf] = None
                    else:
                        new_accum = jits["bwd_wgrad"](
                            st.params, st.accum, in_act[q][buf],
                            micro_rngs[mb], in_grad[q][buf], scale)
                    self.stage_states[q] = st._replace(accum=new_accum)
                in_act[q][buf] = None
                in_grad[q][buf] = None
            else:  # pragma: no cover
                raise AssertionError(f"unknown instruction {cmd}")

        pc = [0] * S
        while True:
            progressed, alldone = False, True
            for s in range(S):
                if pc[s] >= len(streams[s]):
                    continue
                alldone = False
                cmd = streams[s][pc[s]]
                if isinstance(cmd, sched_lib.RecvActivation) and \
                        not act_q[chunk_of(cmd, s)]:
                    continue                    # blocked on the producer
                if isinstance(cmd, sched_lib.RecvGrad) and \
                        not grad_q[chunk_of(cmd, s)]:
                    continue
                if tr is None:
                    exec_cmd(cmd, s)
                else:
                    _t0 = tr.begin()
                    exec_cmd(cmd, s)
                    tr.complete(cmd.name, tr_lanes[s], _t0,
                                a0=getattr(cmd, "chunk_id", 0),
                                a1=getattr(cmd, "micro_id", -1))
                pc[s] += 1
                progressed = True
            if alldone:
                break
            if not progressed:  # pragma: no cover - compiler-verified
                blocked = [s for s in range(S) if pc[s] < len(streams[s])]
                raise RuntimeError(
                    f"pipeline schedule '{compiled.name}' deadlocked; "
                    f"stages {blocked} blocked at "
                    f"{[streams[s][pc[s]] for s in blocked]}")
        self._reduce_tied_grads()
        return losses, mid_auxes

    def _reduce_tied_grads(self):
        """Sum tied-param grad accumulators across tie-group stages and
        redistribute so each member applies the identical update. Stays on
        device: peers' accum shards transfer over ICI (device_put to the
        target submesh) and sum inside a jitted add — no host round-trip."""
        import jax

        groups = self.module.tied_groups(self.num_chunks)
        for key, stages in groups.items():
            pkey = f"tied_{key}"
            # snapshot pre-reduction accums: summing in place would make
            # later targets double-count already-reduced members
            originals = {s: self.stage_states[s].accum[pkey] for s in stages}
            for target in stages:
                total = originals[target]
                with jax.set_mesh(self._chunk_mesh(target)):
                    for s in stages:
                        if s == target:
                            continue
                        peer = jax.tree_util.tree_map(
                            lambda l, ref: jax.device_put(l, ref.sharding),
                            originals[s], total)
                        total = jax.tree_util.tree_map(
                            lambda a, b: a + b, total, peer)
                accum = dict(self.stage_states[target].accum)
                accum[pkey] = total
                self.stage_states[target] = \
                    self.stage_states[target]._replace(accum=accum)

    # ------------------------------------------------------------------
    # analytic schedule/bubble reporting
    # ------------------------------------------------------------------
    def pipeline_report(self, costs=None):
        """Analytic pipeline execution report for the ACTIVE schedule: the
        tick simulation's per-stage idle fractions, aggregate bubble
        fraction, peak live activation buffers (bubble_accounting), the
        1f1b baseline for comparison, and the p2p transfer volume
        (measured bytes from the last train_batch; per-boundary payloads
        once one batch has run). Deterministic on CPU — no device work."""
        from deepspeed_tpu.runtime import comm_accounting as ca
        from deepspeed_tpu.runtime.pipe import bubble_accounting as ba

        from deepspeed_tpu.runtime.constants import (PIPELINE_STASH,
                                                     PIPELINE_STASH_BUDGET)

        compiled = self._ensure_compiled_schedule()
        report = ba.simulate(compiled, costs)
        report["requested_schedule"] = self.requested_schedule
        report["schedule_blockers"] = list(self._schedule_blockers)
        budget = int(self._config.pipeline[PIPELINE_STASH_BUDGET])
        stash_info = {
            "requested": self._config.pipeline[PIPELINE_STASH],
            "armed": self._stash_armed,
            "blockers": list(self._stash_blockers),
            "budget_bytes": budget or None,
            # arming needs shapes: before the first batch the decision is
            # still open and the report says so instead of guessing
            "resolved": self.stage_states is not None,
        }
        if self._stash_bytes_per_chunk is not None:
            stash_info["bytes_per_micro_per_chunk"] = \
                list(self._stash_bytes_per_chunk)
            if self._stash_armed:
                stash_info["peak_bytes_per_stage"] = [
                    peak * self._stash_bytes_per_chunk[s]
                    for s, peak in enumerate(report["peak_live_stash"])]
        report["stash"] = stash_info
        if self.pipe_schedule != sched_lib.SCHEDULE_1F1B:
            base = ba.bubble_report(
                sched_lib.SCHEDULE_1F1B, self.micro_batches,
                self.num_stages, costs=costs)
            report["baseline_1f1b_bubble_fraction"] = \
                base["bubble_fraction"]
        p2p = {"measured_bytes_per_step": self._last_p2p_bytes or None}
        if self._p2p_edge_bytes:
            # model the recorded per-boundary payloads as budgeted
            # collectives (comm_accounting idiom; joins comm_budgets.json
            # via tools/comm_budget.py's canonical configs)
            acts = [b.get("act", 0) for _, b in
                    sorted(self._p2p_edge_bytes.items())]
            grads = [b.get("grad", 0) for _, b in
                     sorted(self._p2p_edge_bytes.items())]
            p2p["analytic_bytes_per_step"] = ca.pipe_p2p_bytes(
                act_bytes_per_edge=acts, grad_bytes_per_edge=grads,
                micro_batches=self.micro_batches)
        report["p2p"] = p2p
        return report

    def measured_bubble_report(self, costs=None):
        """Measured-vs-analytic bubble cross-check from the telemetry
        trace (None when tracing is disarmed; raises before the first
        traced train_batch).

        ``analytic`` simulates the compiled plan; ``measured`` replays
        the instruction spans the interpreter actually recorded for the
        LAST batch through the same simulator
        (bubble_accounting.replay_trace) — faithful execution reproduces
        the analytic per-stage idle fractions exactly, and
        ``max_abs_idle_error`` is the tier-1-pinned drift bound.
        ``wall_clock`` is the honest wall-time lane utilization of the
        same spans (dispatch-bound on a CPU mesh; the transferable claim
        is the replay)."""
        from deepspeed_tpu.runtime.pipe import bubble_accounting as ba
        from deepspeed_tpu.telemetry import lane_utilization

        tr = self._tracer
        if tr is None:
            return None
        if tr.dropped:
            raise ValueError(
                f"telemetry trace ring dropped {tr.dropped} events — the "
                f"instruction stream is holey and a replay would wedge; "
                f"raise telemetry.trace_capacity (now {tr.capacity})")
        events = tr.events()
        # scope to the LAST batch: streams spanning two batches would
        # pipeline across the optimizer step the simulator doesn't model
        last_begin = 0
        for i, ev in enumerate(events):
            if ev["name"] == "pipe_batch_begin":
                last_begin = i
        events = events[last_begin:]
        compiled = self._ensure_compiled_schedule()
        measured = ba.replay_trace(events, compiled, costs)
        analytic = ba.simulate(compiled, costs)
        lanes = {f"stage{s}" for s in range(self.num_stages)}
        return {
            "analytic": analytic,
            "measured": measured,
            "wall_clock": lane_utilization(events, lanes=lanes),
            "max_abs_idle_error": max(
                abs(m - a) for m, a in zip(measured["idle_fraction"],
                                           analytic["idle_fraction"])),
        }

    def _analytic_memory_components(self):
        """Pipeline analytic memory: per-STAGE component bytes (each
        stage is a separate submesh, so the watermark that matters is
        the worst stage, not a sum across them), chunk states aggregated
        onto their owner stages, plus the ZB stash residual peak per
        stage when stashing is armed.  None before the first batch."""
        if self.stage_states is None:
            return None
        from deepspeed_tpu.runtime import memory_accounting as mem_acc
        from deepspeed_tpu.runtime.pipe import bubble_accounting as ba

        S = self.num_stages
        per_stage = [{"params_bytes": 0, "master_bytes": 0,
                      "optimizer_state_bytes": 0, "grad_accum_bytes": 0}
                     for _ in range(S)]
        for q, st in enumerate(self.stage_states):
            s = self.grid.chunk_owner_stage(q)
            per_stage[s]["params_bytes"] += \
                mem_acc.tree_device_bytes(st.params)
            per_stage[s]["master_bytes"] += \
                mem_acc.tree_device_bytes(st.master)
            per_stage[s]["optimizer_state_bytes"] += \
                mem_acc.tree_device_bytes(st.opt_state)
            per_stage[s]["grad_accum_bytes"] += \
                mem_acc.tree_device_bytes(st.accum)
        stash_peak = [0] * S
        if self._stash_armed and self._stash_bytes_per_chunk is not None:
            rep = ba.simulate(self._ensure_compiled_schedule())
            for s, peak in enumerate(rep["peak_live_stash"]):
                stash_peak[s] = peak * self._stash_bytes_per_chunk[s]
        stages = []
        for s in range(S):
            persistent = sum(per_stage[s].values())
            stages.append({
                "components": per_stage[s],
                "transient": {"stash_bytes": stash_peak[s]},
                "persistent_bytes": persistent,
                "peak_bytes": persistent + stash_peak[s],
            })
        worst = max(range(S), key=lambda s: stages[s]["peak_bytes"])
        return {
            "per_stage": stages,
            "persistent_bytes": stages[worst]["persistent_bytes"],
            "transient_bytes": stages[worst]["transient"]["stash_bytes"],
            # devices are per stage: the fleet watermark is the worst
            # stage's peak, not the sum over submeshes
            "peak_bytes": stages[worst]["peak_bytes"],
            "worst_stage": worst,
        }

    def telemetry_report(self):
        """Base unified report plus the pipeline sections: the analytic
        ``pipeline_report()`` and — once a traced batch has run — the
        measured-vs-analytic bubble cross-check."""
        report = super().telemetry_report()
        report["pipeline"] = self.pipeline_report()
        tr = self._tracer
        if tr is not None and not tr.dropped \
                and any(e["name"] == "pipe_batch_begin"
                        for e in tr.events()):
            report["pipeline"]["measured"] = self.measured_bubble_report()
        return report

    # ------------------------------------------------------------------
    # checkpointing (pipeline layout: per-stage state files)
    # ------------------------------------------------------------------
    def _layer_key_set(self):
        """Stage-count-independent universe of layer param keys: layer-
        granular files are keyed by these, so a checkpoint written at pp=N
        can be read at pp=M (reference pipe/module.py:536-567 writes
        layer_XX-model_states files for the same reason)."""
        return {layer.param_key for layer in self.module._layers
                if layer.param_key is not None}

    @staticmethod
    def _path_layer_key(path, layer_keys):
        import jax

        for p in path:
            if isinstance(p, jax.tree_util.DictKey) and str(p.key) in layer_keys:
                return str(p.key)
        return None

    def _stage_save_tree(self, st):
        """The persisted slice of a StageState. accum is excluded: steps only
        complete at accumulation boundaries, where it is zeros."""
        return {"params": st.params, "master": st.master,
                "opt_state": st.opt_state}

    def _chaos_poison_accum(self):
        """Pipeline variant of the chaos NaN-grad hook: the accumulator
        lives per stage, not on a single TrainState."""
        from deepspeed_tpu.runtime.resilience import chaos

        if chaos.active() is None or not chaos.consume_nan_grad_step():
            return
        import jax
        import jax.numpy as jnp

        for s in range(self.num_chunks):
            with jax.set_mesh(self._chunk_mesh(s)):
                st = self.stage_states[s]
                poisoned = jax.tree_util.tree_map(
                    lambda a: jnp.full_like(a, jnp.nan), st.accum)
                self.stage_states[s] = st._replace(accum=poisoned)

    def _assert_saveable(self):
        assert self.stage_states is not None, "no pipeline state to save"

    def _assert_loadable(self):
        assert self.stage_states is not None, \
            "run one batch (or _ensure_pipe_state) before load_checkpoint"

    def _resolve_ckpt_backend(self, backend):
        if backend not in (None, "auto", "npz", "npz-layer"):
            raise ValueError(
                f"pipeline checkpoints only support the layer-granular npz "
                f"backend; got backend={backend!r}")
        return "npz-layer"

    def _ckpt_host_snapshot(self, client_state, backend, copy_host=False):
        """Device->host transfer of every stage's persisted slice, plus
        the metadata — the foreground part of a commit; the writer below
        is pure filesystem work over this snapshot.  ``copy_host`` is
        moot here: device_get already yields host arrays owned by the
        snapshot (nothing mutates them in place)."""
        import jax

        from deepspeed_tpu.runtime.resilience import reshard

        host_states = [jax.device_get(self._stage_save_tree(st))
                       for st in self.stage_states]
        meta = {
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "skipped_steps": self._host_skipped,
            "cur_scale": self._pipe_scaler.cur_scale,
            "scaler_state": self._pipe_scaler.__dict__.copy(),
            "num_stages": self.num_stages,
            "virtual_stages": self.virtual_stages,
            "schedule": self.pipe_schedule,
            "partition": self.module.partition_layers(self.num_chunks),
            "layer_keys": sorted(self._layer_key_set()),
            "format": "layer-granular",
            "lr_scheduler": self.lr_scheduler.state_dict()
            if self.lr_scheduler is not None else None,
            "client_state": client_state,
            "dp_world_size": self.dp_world_size,
            reshard.TOPOLOGY_KEY: reshard.topology_manifest(self),
            reshard.DATA_POSITION_KEY: reshard.data_position(self),
        }
        return {"host_states": host_states, "meta": meta,
                "backend": "npz-layer"}

    def _write_snapshot_files(self, path, snap):
        """Pipeline payload: layer-granular layout — one file per layer
        param key, entries keyed by the leaf's tree path (identical no
        matter which stage owns the layer), plus a 'globals' file for
        layer-independent optimizer scalars (identical on every stage).
        Runs inside the atomic commit path (sync, or on the async commit
        thread): ``path`` is the tag temp dir and each write feeds the
        chaos fault-injection hooks."""
        import jax

        from deepspeed_tpu.runtime.checkpoint_utils import named_leaf_entry
        from deepspeed_tpu.runtime.resilience import chaos

        layer_keys = set(snap["meta"]["layer_keys"])
        per_layer = {}
        global_leaves = {}
        for host in snap["host_states"]:
            for p, leaf in jax.tree_util.tree_flatten_with_path(host)[0]:
                entry = named_leaf_entry(jax.tree_util.keystr(p), leaf)
                k = self._path_layer_key(p, layer_keys)
                if k is None:
                    global_leaves.update(entry)
                else:
                    per_layer.setdefault(k, {}).update(entry)
        for k, entries in per_layer.items():
            fname = os.path.join(path, f"{k}-states.npz")
            self._ckpt_savez(fname, **entries)
            chaos.file_written(fname)
        fname = os.path.join(path, "globals-states.npz")
        self._ckpt_savez(fname, **global_leaves)
        chaos.file_written(fname)
        fname = os.path.join(path, "metadata.pkl")
        with open(fname, "wb") as f:
            pickle.dump(snap["meta"], f)
        chaos.file_written(fname)
        log_dist(f"Wrote pipeline checkpoint payload "
                 f"({len(per_layer)} layer files)", ranks=[0])

    def _write_checkpoint_files(self, path, client_state, backend):
        backend = self._resolve_ckpt_backend(backend)
        self._write_snapshot_files(
            path, self._ckpt_host_snapshot(client_state, backend))
        return backend

    def _ckpt_state_snapshot(self):
        snap = super()._ckpt_state_snapshot()
        snap["stage_states"] = list(self.stage_states) \
            if self.stage_states is not None else None
        snap["pipe_scaler"] = dict(self._pipe_scaler.__dict__) \
            if getattr(self, "_pipe_scaler", None) is not None else None
        return snap

    def _ckpt_state_restore(self, snap):
        super()._ckpt_state_restore(snap)
        if snap.get("stage_states") is not None:
            self.stage_states = snap["stage_states"]
        if snap.get("pipe_scaler") is not None:
            self._pipe_scaler.__dict__.update(snap["pipe_scaler"])

    def _load_checkpoint_tag(self, load_dir, tag, load_module_strict=True,
                             load_optimizer_states=True,
                             load_lr_scheduler_states=True, elastic=False):
        import jax

        path = os.path.join(load_dir, str(tag))
        with open(os.path.join(path, "metadata.pkl"), "rb") as f:
            meta = pickle.load(f)
        assert meta.get("format") == "layer-granular", \
            "pre-round-4 per-stage pipeline checkpoints are not readable; " \
            "re-save with this version"
        assert self.stage_states is not None, \
            "run one batch (or _ensure_pipe_state) before load_checkpoint"
        layer_keys = self._layer_key_set()
        saved_keys = set(meta.get("layer_keys", []))
        if load_module_strict:
            assert saved_keys == layer_keys, \
                (f"checkpoint layers {sorted(saved_keys)} != module layers "
                 f"{sorted(layer_keys)}")

        from deepspeed_tpu.runtime.checkpoint_utils import named_leaf_lookup

        files = {}

        def lookup(k, name):
            fname = "globals-states.npz" if k is None else f"{k}-states.npz"
            if fname not in files:
                files[fname] = np.load(os.path.join(path, fname))
            return named_leaf_lookup(files[fname], name)

        # rebuild each (possibly re-partitioned) stage from the layer files:
        # every leaf of the fresh stage state is looked up by (layer key,
        # tree path), which is stage-layout independent
        new_states = []
        for st in self.stage_states:
            tpl = jax.device_get(self._stage_save_tree(st))
            leaves, treedef = jax.tree_util.tree_flatten_with_path(tpl)
            restored = [lookup(self._path_layer_key(p, layer_keys),
                               jax.tree_util.keystr(p))
                        for p, _ in leaves]
            host = jax.tree_util.tree_unflatten(treedef, restored)
            ref = self._stage_save_tree(st)
            dev = jax.tree_util.tree_map(
                lambda l, r: jax.device_put(l, r.sharding), host, ref)
            new_states.append(st._replace(
                params=dev["params"], master=dev["master"],
                opt_state=dev["opt_state"]))
        self.stage_states = new_states
        self.global_steps = meta["global_steps"]
        self.micro_steps = meta["micro_steps"]
        self._host_skipped = meta["skipped_steps"]
        self._pipe_scaler.cur_scale = meta["cur_scale"]
        for k, v in meta.get("scaler_state", {}).items():
            setattr(self._pipe_scaler, k, v)
        if load_lr_scheduler_states and self.lr_scheduler is not None \
                and meta.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        log_dist(f"Loaded pipeline checkpoint {path} (saved at "
                 f"{meta['num_stages']}x{meta.get('virtual_stages', 1)} "
                 f"chunks/{meta.get('schedule')}, now "
                 f"{self.num_stages}x{self.virtual_stages}/"
                 f"{self.pipe_schedule})", ranks=[0])
        return path, self._elastic_client_state(meta, elastic)
