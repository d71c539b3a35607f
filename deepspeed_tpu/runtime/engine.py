"""DeepSpeedEngine — TPU-native training engine.

API parity with the reference engine (reference: deepspeed/runtime/engine.py:101:
forward :810 / backward :871 / step :1016 / save_checkpoint :1489 /
load_checkpoint :1299), implemented functionally:

- ONE jitted micro-step (value_and_grad + fp32 grad accumulation) and one
  jitted apply-step (overflow check -> lax.cond{skip, update} -> loss-scale
  update), instead of per-parameter backward hooks and bucketed NCCL calls.
- Parallelism is a named-axis Mesh; data parallelism = batch sharded over
  'data' (XLA inserts the psum/reduce_scatter the reference does by hand in
  engine.py:852-868 and zero/stage2.py:740-821).
- ZeRO-1/2 = sharding specs on master weights / optimizer moments / gradient
  accumulator over the 'data' axis (see parallel/mesh.py:zero_partition_spec);
  XLA's SPMD partitioner emits reduce-scatter of grads into the shard and
  all-gather of updated params — the bucket/stream machinery of stage2.py
  disappears (SURVEY §7).
- fp16 master-weight flow: params live in compute dtype (fp16/bf16),
  fp32 master + moments inside the optimizer state (reference
  fp16/fused_optimizer.py:17).
"""
import logging
import os
import pickle
import time
import weakref
from typing import Any, NamedTuple, Optional

import numpy as np

from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.runtime.config import (ADAFACTOR_OPTIMIZER, ADAM_OPTIMIZER,
                                          ADAMW_OPTIMIZER, DeepSpeedConfig,
                                          LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
                                          SGD_OPTIMIZER,
                                          ZEROONE_ADAM_OPTIMIZER)
from deepspeed_tpu.runtime.constants import ROUTE_TRAIN
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader
from deepspeed_tpu.runtime.fp16.loss_scaler import (LossScaleState,
                                                    make_loss_scale_state,
                                                    update_loss_scale)
from deepspeed_tpu.runtime.lr_schedules import SCHEDULER_REGISTRY
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer

MEMORY_OPT_ALLREDUCE_SIZE = 500000000

FORWARD_MICRO_TIMER = "forward_microstep"
FORWARD_GLOBAL_TIMER = "forward"
BACKWARD_MICRO_TIMER = "backward_microstep"
BACKWARD_GLOBAL_TIMER = "backward"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"


class TrainState(NamedTuple):
    """Full training state — a single pytree, sharded per config."""
    step: Any             # i32: optimizer steps taken
    micro_step: Any       # i32: micro-batches in current accumulation window
    params: Any           # compute-dtype params (replicated over 'data', TP over 'model')
    opt_state: Any        # optimizer state incl. fp32 master (ZeRO-sharded)
    master: Any           # fp32 master params (None in pure-fp32 mode: params are master)
    accum: Any            # fp32 grad accumulator (ZeRO-2: sharded over 'data')
    scaler: Any           # LossScaleState or None
    skipped_steps: Any    # i32
    rng: Any              # PRNGKey


class DeepSpeedEngine:
    # subclasses whose state layout cannot support the cross-replica
    # integrity vote (ISSUE 13) override this to False — _arm_integrity
    # then arms sentinels-only and DISARM-warns the vote (a class flag,
    # not a name check, so SUBCLASSES inherit the block)
    _integrity_armable = True

    def __init__(self, args=None, model=None, optimizer=None,
                 model_parameters=None, training_data=None, lr_scheduler=None,
                 mpu=None, dist_init_required=None, collate_fn=None,
                 config_params=None, dont_change_device=False):
        import jax

        assert model is not None, "deepspeed_tpu.initialize requires a model"
        self.module = model
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.mpu = mpu
        self.global_steps = 0
        self.micro_steps = 0
        # samples the integrity ladder deliberately skipped (PaLM-style
        # rollback-and-skip, ISSUE 13): biases reshard.data_position so
        # the stream offset stays truthful; persisted with checkpoints
        self.samples_skipped = 0
        self.gradient_average = True
        self.warn_unscaled_loss = True

        if dist_init_required is None or dist_init_required:
            from deepspeed_tpu.utils.distributed import init_distributed

            init_distributed()

        # --- config -------------------------------------------------------
        config_file = getattr(args, "deepspeed_config", None) if args else None
        if config_file is None and args is not None:
            config_file = getattr(args, "deepscale_config", None)
        raw = config_params if config_params is not None else config_file
        assert raw is not None, \
            "DeepSpeed requires --deepspeed_config or config_params"
        if isinstance(raw, str):
            import json

            from deepspeed_tpu.runtime.config_utils import load_config_json

            raw_dict = load_config_json(raw)
        else:
            raw_dict = raw

        # mesh first: the config's world size is the data-parallel degree
        from deepspeed_tpu.runtime.config import get_mesh_shape

        self.mesh = mesh_lib.build_mesh(get_mesh_shape(raw_dict))
        self.dp_world_size = mesh_lib.dp_size(self.mesh)
        self.mp_world_size = mesh_lib.mp_size(self.mesh)
        self.sp_world_size = mesh_lib.sp_size(self.mesh)
        self._config = DeepSpeedConfig(raw_dict, world_size=self.dp_world_size)
        self._config.print_enabled = False

        self.local_dp_size = max(1, self.dp_world_size // jax.process_count())

        # --- precision ----------------------------------------------------
        import jax.numpy as jnp

        if self.fp16_enabled():
            self.compute_dtype = jnp.float16
        elif self.bf16_enabled() or self.amp_enabled():
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        self.mixed_precision = self.compute_dtype != jnp.float32

        # --- optimizer / scheduler / misc --------------------------------
        self.optimizer = self._configure_basic_optimizer()
        if self.zero_optimization_stage() > 0:
            # reference engine.py:694-700 gates client optimizers through
            # the ZeRO whitelist before partitioning their state
            from deepspeed_tpu.runtime.zero.utils import \
                assert_zero_supported_optimizer

            assert_zero_supported_optimizer(
                self.optimizer, self._config.zero_allow_untested_optimizer)
        self.lr_scheduler = self._configure_lr_scheduler()
        self.progressive_layer_drop = None
        if self.pld_enabled():
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self.pld_theta(), gamma=self.pld_gamma())

        # --- resilience ---------------------------------------------------
        res = self._config.resilience
        self._resilience = res
        self._consecutive_skips = 0
        self._last_ckpt_dir = None
        self._last_metrics = None
        # async checkpoint commit (resilience.async_commit): at most ONE
        # in flight; the background thread owns write+hash+fsync, the
        # training thread owns the rename (+ latest) via
        # _finalize_pending_commit
        self._pending_commit = None
        self._pending_commit_info = None
        self._ckpt_foreground_ms = 0.0
        self._ckpt_metrics = None
        # graceful preemption: the flag is set by request_preemption()
        # (signal-handler safe); the coordinated save + GracefulPreemption
        # raise happen at the next optimizer-step boundary
        self._preempt_requested = False
        self._preempt_poll_enabled = False
        # self-healing supervision (runtime/resilience/supervisor.py):
        # None until a TrainingSupervisor arms its hook points via
        # _arm_supervisor — one is-None check per step boundary
        self._supervisor = None
        self._watchdog = None
        if res.watchdog_enabled:
            from deepspeed_tpu.runtime.resilience.watchdog import \
                TrainingWatchdog

            self._watchdog = TrainingWatchdog(
                max_skipped_steps=res.watchdog_max_skipped_steps,
                max_nan_losses=res.watchdog_max_nan_losses,
                stall_timeout=res.watchdog_stall_timeout,
                default_action=res.watchdog_action)

        # --- telemetry (ISSUE 10) -----------------------------------------
        self._arm_telemetry()

        # --- memory accounting (ISSUE 15) ---------------------------------
        # after telemetry so the measured side can share its lazy compile
        # cache (one lower().compile() per jit serves MFU and memory)
        self._arm_memory_accounting()

        # --- numerical integrity (ISSUE 13) -------------------------------
        # after telemetry so the monitor can claim its tracer lane
        self._arm_integrity()

        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu() * self.dp_world_size,
            num_workers=1, steps_per_output=self.steps_per_print())

        self.training_dataloader = self.deepspeed_io(training_data) \
            if training_data is not None else None

        # --- state (lazy: built on first batch) --------------------------
        self.state: Optional[TrainState] = None
        self._state_shardings = None
        self._jit_micro = None
        self._jit_apply = None
        self._jit_fused = None
        self._jit_eval = None
        self._pending_state = None
        self._train_mode = True
        self._pending_loss = None
        # scheduled stage-3: the staged forward's vjp stash (gathered
        # weights + activations) awaiting its backward
        self._pending_s3_stash = None
        self.summary_writer = None
        if self.tensorboard_enabled() and jax.process_index() == 0:
            from deepspeed_tpu.utils.tb_writer import SummaryWriter

            # real TensorBoard event-file format (reference tensorboardX,
            # engine.py:157-158) — native writer, no tensorboard dep
            self.summary_writer = SummaryWriter(
                log_dir=os.path.join(
                    self.tensorboard_output_path() or ".",
                    self.tensorboard_job_name() or "DeepSpeedJobName"))

        seed = int(raw_dict.get("seed", 42))
        self._init_rng = jax.random.PRNGKey(seed)

        log_dist(
            f"DeepSpeedEngine: mesh={dict(self.mesh.shape)} "
            f"dtype={self.compute_dtype.__name__} zero_stage={self.zero_optimization_stage()} "
            f"micro_batch={self.train_micro_batch_size_per_gpu()} "
            f"gas={self.gradient_accumulation_steps()}", ranks=[0])

    # ------------------------------------------------------------------
    # config getters (parity with reference engine.py:212-406)
    # ------------------------------------------------------------------
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def steps_per_print(self):
        return self._config.steps_per_print

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bf16_enabled(self):
        return self._config.bf16_enabled

    def amp_enabled(self):
        return self._config.amp_enabled

    @property
    def _live_state(self):
        """The alive TrainState: between forward() and backward() the micro
        jit has donated self.state's buffers into the staged state, so
        mid-window readers (loss_scale, skipped_steps, eval) must look at
        the staged one.  The scaler/skip counters are identical in both —
        only apply moves them."""
        return self._pending_state if self._pending_state is not None \
            else self.state

    def loss_scale(self):
        if self.state is not None and self.state.scaler is not None:
            # host-synced at most once per optimizer step (the scale only
            # changes in apply): repeated reads — e.g. _report_progress at
            # steps_per_print boundaries plus user polling — must not each
            # pay a device round-trip
            cached = getattr(self, "_scale_cache", None)
            if cached is not None and cached[0] == self.global_steps:
                return cached[1]
            import jax

            val = float(jax.device_get(self._live_state.scaler.loss_scale))
            self._scale_cache = (self.global_steps, val)
            return val
        return self._config.loss_scale or self._config.initial_dynamic_scale

    def dynamic_loss_scale(self):
        return self._config.loss_scale == 0

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def zero_optimization(self):
        return self._config.zero_enabled

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def zero_cpu_offload(self):
        return self._config.zero_config.cpu_offload

    def zero_reduce_scatter(self):
        return self._config.zero_config.reduce_scatter

    def zero_overlap_comm(self):
        return self._config.zero_config.overlap_comm

    def zero_reduce_bucket_size(self):
        return self._config.zero_config.reduce_bucket_size

    def zero_allgather_bucket_size(self):
        return self._config.zero_config.allgather_bucket_size

    def zero_contiguous_gradients(self):
        return self._config.zero_config.contiguous_gradients

    def zero_elastic_checkpoint(self):
        return self._config.zero_config.elastic_checkpoint

    def zero_load_from_fp32_weights(self):
        return self._config.zero_config.load_from_fp32_weights

    def zero_quantized_gradients(self):
        return self._config.zero_config.quantized_gradients

    def zero_quantized_weights(self):
        return self._config.zero_config.quantized_weights

    def zero_hierarchical_allreduce(self):
        return self._config.zero_config.hierarchical_allreduce

    def allreduce_always_fp32(self):
        return self._config.allreduce_always_fp32

    def prescale_gradients(self):
        return self._config.prescale_gradients

    def gradient_predivide_factor(self):
        return self._config.gradient_predivide_factor

    def sparse_gradients_enabled(self):
        return self._config.sparse_gradients_enabled

    def postscale_gradients(self):
        return not self._config.prescale_gradients

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def memory_breakdown(self):
        return self._config.memory_breakdown

    def tensorboard_enabled(self):
        return self._config.tensorboard_enabled

    def tensorboard_output_path(self):
        return self._config.tensorboard_output_path

    def tensorboard_job_name(self):
        return self._config.tensorboard_job_name

    def optimizer_name(self):
        return self._config.optimizer_name

    def optimizer_params(self):
        return self._config.optimizer_params

    def optimizer_legacy_fusion(self):
        return self._config.optimizer_legacy_fusion

    def scheduler_name(self):
        return self._config.scheduler_name

    def scheduler_params(self):
        return self._config.scheduler_params

    def pld_enabled(self):
        return self._config.pld_enabled

    def pld_theta(self):
        return self._config.pld_theta

    def pld_gamma(self):
        return self._config.pld_gamma

    def elasticity_enabled(self):
        return self._config.elasticity_enabled

    def dump_state(self):
        return self._config.dump_state

    def get_global_grad_norm(self):
        return getattr(self, "_last_grad_norm", None)

    @property
    def skipped_steps(self):
        """Overflow-skipped step count; lives on-device in the train state.
        The device scalar is fetched at most once per optimizer step (the
        counter only moves in apply, which also bumps global_steps) and the
        host value is served from cache after that — the 1-bit freeze probe
        and _report_progress read this repeatedly without extra syncs.
        Checkpoint loads drop the cache explicitly."""
        if self.state is None:
            return 0
        key = (self.global_steps, getattr(self, "_host_skipped", 0))
        cached = getattr(self, "_skipped_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        import jax

        val = int(jax.device_get(self._live_state.skipped_steps)) \
            + getattr(self, "_host_skipped", 0)
        self._skipped_cache = (key, val)
        return val

    def get_lr(self):
        return [self._current_lr()]

    def get_mom(self):
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "get_mom"):
            return self.lr_scheduler.get_mom()
        return [getattr(self.optimizer, "beta1", 0.9)]

    # ------------------------------------------------------------------
    # wiring
    # ------------------------------------------------------------------
    def _configure_basic_optimizer(self):
        """Reference analog: engine.py:599-639."""
        if self.client_optimizer is not None:
            return self.client_optimizer
        name = self.optimizer_name()
        params = dict(self.optimizer_params() or {})
        if name is None:
            # default optimizer: Adam (reference requires one; we default sanely)
            name = ADAM_OPTIMIZER
        params.pop("torch_adam", None)
        max_grad_norm = params.pop("max_grad_norm", None)
        if max_grad_norm and not self._config.gradient_clipping:
            self._config.gradient_clipping = max_grad_norm
        if name in (ADAM_OPTIMIZER, ADAMW_OPTIMIZER):
            if self.zero_cpu_offload():
                # ZeRO-Offload: optimizer state + step on the host
                # (reference engine.py:599-614 picks DeepSpeedCPUAdam)
                from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam

                params.setdefault("adamw_mode", name == ADAMW_OPTIMIZER)
                return DeepSpeedCPUAdam(**params)
            from deepspeed_tpu.ops.adam.fused_adam import FusedAdam

            params.setdefault("adam_w_mode", name == ADAMW_OPTIMIZER)
            return FusedAdam(**params)
        if name == LAMB_OPTIMIZER:
            from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb

            return FusedLamb(**params)
        if name == ONEBIT_ADAM_OPTIMIZER:
            from deepspeed_tpu.ops.onebit.onebit_adam import OnebitAdam

            # wire compression (reference onebit_adam.py:104-228 compresses
            # BEFORE the network): with data parallelism and no ZeRO/pipe
            # sharding in the way, the train step runs under shard_map over
            # 'data' so gradients stay device-local and the only gradient
            # traffic after freeze_step is the bit-packed collective
            dp = self.dp_world_size
            wire_ok = (params.get("comm_backend_name", "xla") != "none"
                       and dp > 1
                       and self.zero_optimization_stage() == 0
                       and self.mesh.shape.get("pipe", 1) == 1)
            if wire_ok:
                params.setdefault("axis_name", "data")
                params.setdefault("axis_size", dp)
            elif dp > 1:
                # compression silently no-oping would defeat the user's
                # intent — name the blocking condition loudly
                blockers = []
                if self.zero_optimization_stage() != 0:
                    blockers.append(
                        f"zero_optimization.stage={self.zero_optimization_stage()}")
                if self.mesh.shape.get("pipe", 1) != 1:
                    blockers.append(f"pipe={self.mesh.shape.get('pipe')}")
                if params.get("comm_backend_name") == "none":
                    blockers.append("comm_backend_name='none'")
                log_dist(
                    "OneBitAdam: wire compression DISARMED — gradients move "
                    f"dense ({', '.join(blockers)}); the compressed "
                    "collective path requires zero stage 0 and pipe=1",
                    ranks=[0], level=logging.WARNING)
            return OnebitAdam(mesh=self.mesh, **params)
        if name == ZEROONE_ADAM_OPTIMIZER:
            from deepspeed_tpu.ops.onebit.zeroone_adam import ZeroOneAdam

            # 0/1 Adam (arxiv 2202.06009): the 1-bit wire one rung below
            # qgZ.  Armed exactly like the OneBitAdam wire above, plus the
            # stage-3 / CSR / offload blockers — the packed collective
            # owns the whole grad exchange, so anything else claiming the
            # wire disarms it loudly.
            if self._arm_zeroone(params):
                params.setdefault("axis_name", "data")
                params.setdefault("axis_size", self.dp_world_size)
                params.setdefault(
                    "intra_size",
                    self._arm_quantized_allreduce(self.dp_world_size,
                                                  params))
            return ZeroOneAdam(mesh=self.mesh, **params)
        if name == SGD_OPTIMIZER:
            from deepspeed_tpu.ops.adam.sgd import SGD

            return SGD(**params)
        raise ValueError(f"Unknown optimizer type {name!r}")

    def _configure_lr_scheduler(self):
        """Reference analog: engine.py:408-421."""
        if self.client_lr_scheduler is not None:
            return self.client_lr_scheduler
        name = self.scheduler_name()
        if name is None:
            return None
        assert name in SCHEDULER_REGISTRY, f"Unknown scheduler {name}"
        sched = SCHEDULER_REGISTRY[name](**(self.scheduler_params() or {}))
        return sched

    def _current_lr(self):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler.get_last_lr()[0] \
                if getattr(self.lr_scheduler, "_last_lr", None) else \
                self.lr_scheduler.lr_at(max(0, self.lr_scheduler.last_batch_iteration))
            return float(lr)
        return float(getattr(self.optimizer, "lr", 1e-3))

    def deepspeed_io(self, dataset, batch_size=None, route=ROUTE_TRAIN,
                     pin_memory=False, data_sampler=None, collate_fn=None,
                     num_local_io_workers=None):
        """Reference analog: engine.py:731-772."""
        import jax

        if batch_size is None:
            batch_size = self.train_micro_batch_size_per_gpu() * self.local_dp_size
        return DeepSpeedDataLoader(
            dataset, batch_size=batch_size,
            collate_fn=collate_fn or self.collate_fn,
            num_local_io_workers=num_local_io_workers or 0,
            data_sampler=data_sampler,
            data_parallel_world_size=jax.process_count(),
            data_parallel_rank=jax.process_index(),
            tput_timer=self.tput_timer)

    # ------------------------------------------------------------------
    # state construction
    # ------------------------------------------------------------------
    def _build_shardings(self, params_template):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        rep = NamedSharding(mesh, P())

        def ns(spec_tree):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), spec_tree,
                is_leaf=lambda x: isinstance(x, P))

        if hasattr(self.module, "param_partition_spec"):
            tp_spec = self.module.param_partition_spec(params_template)
        else:
            tp_spec = jax.tree_util.tree_map(lambda _: P(), params_template)

        stage = self.zero_optimization_stage()
        dp = self.dp_world_size
        zero_spec = jax.tree_util.tree_map(
            lambda s, l: mesh_lib.zero_merge_spec(s, l, dp) if stage > 0 else s,
            tp_spec, params_template, is_leaf=lambda x: isinstance(x, P))

        # stage 3 (extension; reference engine.py:720-722 caps at 2): the
        # COMPUTE params also live ZeRO-sharded over 'data' — XLA all-gathers
        # each weight at its use sites (fwd and, under remat, again in bwd),
        # exactly stage-3's gather-on-demand, expressed as one spec choice
        param_sh = ns(zero_spec) if stage >= 3 else ns(tp_spec)
        master_sh = ns(zero_spec) if self.mixed_precision else None
        # accum: ZeRO-2+ shards gradients; otherwise keep with param layout
        accum_sh = ns(zero_spec) if stage >= 2 else param_sh

        if self._offload:
            # optimizer state lives on host, gradients stream to it per
            # micro-batch — no device accumulator at all (1x params fp32 of
            # HBM back; the 13B-per-chip headline depends on it). Micro-step
            # grads come out ZeRO-sharded: out_shardings below makes XLA
            # reduce-scatter instead of all-reduce, and each process then
            # fetches only its own shard (reference stage2.py:876-958
            # updates only the local partition).
            # sparse_gradients (reference engine.py:187-193,1227-1265):
            # models may declare untied embedding tables whose gradients are
            # row-sparse; those leaves stream to the host as (row indices,
            # row values) with capacity = batch tokens instead of the dense
            # table, cutting offload D2H traffic by ~vocab/tokens. The flag
            # tree is static (model contract); row capacity binds per trace.
            self._offload_sparse_flags = None
            if self.sparse_gradients_enabled() \
                    and hasattr(self.module, "sparse_grad_spec"):
                self._offload_sparse_flags = \
                    self.module.sparse_grad_spec(params_template)
            zero_ns = ns(zero_spec)
            if self._offload_sparse_flags is not None:
                # grads out_shardings: sparse leaves become replicated
                # {indices, values} pairs; region layout (for the host
                # master/moment step) treats them as whole-buffer regions
                self._offload_grad_sh = jax.tree_util.tree_map(
                    lambda flag, s: {"csr_indices": rep, "csr_values": rep,
                                     "csr_dropped": rep}
                    if flag else s,
                    self._offload_sparse_flags, zero_ns)
                self._offload_region_sh = jax.tree_util.tree_map(
                    lambda flag, s: rep if flag else s,
                    self._offload_sparse_flags, zero_ns)
            else:
                self._offload_grad_sh = zero_ns
                self._offload_region_sh = zero_ns
            self._shardings = TrainState(
                step=rep, micro_step=rep, params=param_sh, opt_state=(),
                master=None, accum=(),
                scaler=(LossScaleState(rep, rep, rep, rep)
                        if self._use_loss_scaler() else None),
                skipped_steps=rep, rng=rep)
            self._batch_sharding_cache = {}
            self._arm_stage3(stage, dp, params_template)
            self._arm_quantized_collectives(stage, dp)
            return self._shardings
        # sparse_gradients under plain DP (reference engine.py:1227-1265
        # swaps the embedding-grad all-reduce for a sparse all-gather): the
        # micro step's gradient exchange runs under shard_map with 'data'
        # manual, flagged leaves move as (row indices, row values) at
        # capacity = local lookup tokens instead of the dense (vocab, dim)
        # table. Armed only where the dense accumulator layout survives:
        # stage <= 1 (stage 2 shards accum over 'data'), no pipe/seq axes.
        self._csr_dp_flags = None
        if (self.sparse_gradients_enabled()
                and hasattr(self.module, "sparse_grad_spec")
                and dp > 1 and stage <= 1
                and self.mesh.shape.get("pipe", 1) == 1
                and self.sp_world_size == 1):
            self._csr_dp_flags = self.module.sparse_grad_spec(params_template)
        opt_state_template = jax.eval_shape(self.optimizer.init_state, params_template)
        flat_opt, opt_def = jax.tree_util.tree_flatten(opt_state_template)
        if hasattr(self.optimizer, "state_spec"):
            # optimizer declares its state layout in terms of param specs
            # (None = replicated scalar) — exact per-param mapping
            spec_tree = self.optimizer.state_spec(zero_spec)
            spec_flat = jax.tree_util.tree_flatten(
                spec_tree, is_leaf=lambda x: x is None or isinstance(x, P))[0]
            assert len(spec_flat) == len(flat_opt), \
                f"optimizer state_spec leaves ({len(spec_flat)}) != state " \
                f"leaves ({len(flat_opt)})"
            opt_sh_flat = [rep if s is None else NamedSharding(mesh, s)
                           for s in spec_flat]
        else:
            from deepspeed_tpu.runtime.utils import opt_shardings_by_shape

            flat_param_sh = jax.tree_util.tree_leaves(ns(zero_spec))
            param_shapes = [tuple(l.shape)
                            for l in jax.tree_util.tree_leaves(params_template)]
            opt_sh_flat = opt_shardings_by_shape(
                flat_opt, param_shapes, flat_param_sh, rep)
        opt_sh = opt_def.unflatten(opt_sh_flat)

        self._shardings = TrainState(
            step=rep, micro_step=rep, params=param_sh, opt_state=opt_sh,
            master=master_sh, accum=accum_sh,
            scaler=(LossScaleState(rep, rep, rep, rep)
                    if self._use_loss_scaler() else None),
            skipped_steps=rep, rng=rep)
        self._batch_sharding_cache = {}
        self._arm_stage3(stage, dp, params_template)
        self._arm_quantized_collectives(stage, dp)
        return self._shardings

    def _arm_stage3(self, stage, dp, params_template):
        """Decide whether stage 3 runs the SCHEDULED gather path (ISSUE 8):
        a compile-time per-layer-block plan (runtime/zero/stage3.py) of
        quantized (int8 + fp32 scales) all-gathers, one per partitioned
        leaf per micro-step, with the gathered weight persisted fwd->bwd
        as a vjp residual and donated/freed at wgrad.  Disarmed, stage 3
        falls back to the implicit path — XLA inserts full-precision
        gathers at every use site (and again in a remat'd backward) —
        with every blocker named loudly (the qgZ/OneBit discipline)."""
        import warnings

        import jax
        from jax.sharding import NamedSharding

        from deepspeed_tpu.runtime.zero import stage3 as s3

        zc = self._config.zero_config
        self._s3_sched_armed = False
        self._s3_plan = None
        if stage != 3:
            return
        dims_tree = jax.tree_util.tree_map(
            _spec_data_dim, self._shardings.params,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        dims = jax.tree_util.tree_leaves(dims_tree,
                                         is_leaf=lambda x: x is None)
        names = _leaf_path_names(params_template)
        shapes = [tuple(l.shape)
                  for l in jax.tree_util.tree_leaves(params_template)]
        plan = s3.build_gather_plan(
            names, shapes, dims, dp,
            block_size=zc.quantization_block_size,
            param_dtype=str(np.dtype(self.compute_dtype)))
        self._s3_plan = plan
        self._s3_dims = dims_tree
        blockers = []
        if not zc.stage3_scheduled_gathers:
            blockers.append("zero_optimization.stage3_scheduled_gathers="
                            "false")
        if dp <= 1:
            blockers.append("data-parallel degree is 1 (nothing is "
                            "partitioned)")
        if self._offload:
            blockers.append("cpu_offload=true (params materialize through "
                            "the offload push, which has its own qwZ wire)")
        if self.mesh.shape.get("pipe", 1) != 1:
            blockers.append(f"pipe={self.mesh.shape.get('pipe')}")
        if self.sp_world_size != 1:
            blockers.append(f"seq={self.sp_world_size}")
        if not blockers and plan.n_gathered_leaves == 0:
            blockers.append("no parameter leaf is partitionable over "
                            "'data' (all too small/indivisible)")
        budget = zc.stage3_prefetch_budget
        if not blockers and not plan.within_budget(budget):
            blockers.append(
                f"gather plan needs {plan.gathered_bytes} B of gathered "
                f"weights live fwd->bwd, over stage3_prefetch_budget="
                f"{budget} B — raise the budget or accept the implicit "
                f"path's per-use gathers")
        if blockers:
            log_dist(
                "ZeRO stage-3: scheduled quantized gathers DISARMED — "
                f"falling back to XLA-implicit per-use all-gathers "
                f"({'; '.join(blockers)})", ranks=[0],
                level=logging.WARNING)
            return
        self._s3_sched_armed = True
        # the bwd jit donates the stash; gathered-weight residuals are
        # donor-only (they alias no output), which XLA reports once per
        # compile with this warning — expected, same as the zb-h1 stash
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        log_dist(
            f"ZeRO stage-3: scheduled quantized gathers armed — "
            f"{plan.n_gathered_leaves} leaves in {len(plan.blocks)} "
            f"layer blocks, {plan.wire_bytes_per_gather} B int8+scales "
            f"wire per gather, {plan.gathered_bytes} B gathered peak "
            f"(budget {budget or 'unbounded'})", ranks=[0])

    def stage3_report(self):
        """The compile-time gather plan's report (blocks, per-block bytes,
        peak gathered footprint) plus arming status — the numbers
        stage3_prefetch_budget is sized from.  None below stage 3 or
        before state build."""
        if getattr(self, "_s3_plan", None) is None:
            return None
        report = self._s3_plan.report()
        report["armed"] = bool(self._s3_sched_armed)
        report["prefetch_budget"] = \
            self._config.zero_config.stage3_prefetch_budget
        return report

    def _make_stage3_gather(self):
        """params(sharded) -> params(replicated) through the plan's
        quantized all-gathers, emitted in forward block order so XLA's
        latency-hiding scheduler prefetches block k+1's gather behind
        block k's compute.  Straight-through vjp: gradients flow back
        constrained onto the ZeRO shard (one reduce-scatter per leaf)."""
        import jax

        from deepspeed_tpu.runtime.custom_collectives import \
            quantized_all_gather

        dims = self._s3_dims
        mesh = self.mesh
        block = self._config.zero_config.quantization_block_size

        def gather(params):
            def one(dim, p):
                if dim is None:
                    return p
                return quantized_all_gather(
                    p, mesh, dim=dim, block_size=block,
                    out_dtype=p.dtype)

            return jax.tree_util.tree_map(one, dims, params,
                                          is_leaf=lambda x: x is None)

        return gather

    def _make_stage3_fwd(self):
        """Forward half of the staged stage-3 micro step: gather once,
        compute the loss, and return the vjp closure (a tree_util.Partial
        whose residuals INCLUDE the gathered weights) as the stash that
        crosses to the backward jit — the PR-6 ZB stash idiom.  The
        engine state is NOT donated here: it stays alive until backward
        commits it."""
        import jax
        import jax.numpy as jnp

        gas = self.gradient_accumulation_steps()
        model = self.module
        gather = self._make_stage3_gather()

        def s3_fwd(state: TrainState, batch):
            rng = jax.random.fold_in(state.rng,
                                     state.micro_step + state.step * 131071)
            scale = state.scaler.loss_scale if state.scaler is not None \
                else jnp.float32(1.0)

            def loss_fn(shards):
                full = gather(shards)
                loss, _ = model.loss(full, batch, rng, train=True)
                return loss.astype(jnp.float32) * scale / gas, loss

            _, vjp, loss = jax.vjp(loss_fn, state.params, has_aux=True)
            return loss, vjp

        return s3_fwd

    def _make_stage3_bwd(self):
        """Backward half: evaluate the stash into gradients (they arrive
        ZeRO-sharded through the gather's straight-through cotangent
        constraint — the accumulator add is collective-free) and commit
        the micro step.  Donates BOTH the state (in-place accum) and the
        stash, so the gathered weights free at wgrad instead of
        surviving to peak memory."""
        import jax
        import jax.numpy as jnp

        def s3_bwd(state: TrainState, stash):
            grads, = stash(jnp.float32(1.0))
            accum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), state.accum, grads)
            return state._replace(accum=accum,
                                  micro_step=state.micro_step + 1)

        return s3_bwd

    def _arm_quantized_collectives(self, stage, dp):
        """Decide whether the ZeRO++-style quantized collectives run
        (qgZ: int8 gradient reduce-scatter; qwZ: int8 offload param
        all-gather) and resolve the hierarchical intra-group size.  Asked-for
        compression silently no-oping would defeat the user's intent, so
        every blocker is named loudly (same discipline as the OneBitAdam
        wire arming above)."""
        import math

        import jax

        zc = self._config.zero_config
        self._qgz_armed = False
        self._qgz_intra = 0
        self._qwz_armed = False
        if zc.quantized_gradients:
            blockers = []
            if dp <= 1:
                blockers.append("data-parallel degree is 1")
            if stage != 2:
                blockers.append(
                    f"zero_optimization.stage={stage} (qgZ quantizes the "
                    f"stage-2 sharded-accumulator reduce-scatter)")
            if self._offload:
                blockers.append("cpu_offload=true (gradients stream D2H, "
                                "no collective to quantize)")
            if getattr(self, "_csr_dp_flags", None) is not None:
                blockers.append("sparse_gradients CSR exchange is armed")
            if self.mesh.shape.get("pipe", 1) != 1:
                blockers.append(f"pipe={self.mesh.shape.get('pipe')}")
            if self.sp_world_size != 1:
                blockers.append(f"seq={self.sp_world_size}")
            if blockers:
                log_dist(
                    "ZeRO qgZ: quantized_gradients DISARMED — gradients "
                    f"move dense ({', '.join(blockers)}); the quantized "
                    "reduce-scatter requires zero stage 2, no cpu_offload, "
                    "and pipe=seq=1", ranks=[0], level=logging.WARNING)
            else:
                self._qgz_armed = True
        if zc.quantized_weights:
            if self._offload and dp > 1:
                self._qwz_armed = True
            elif getattr(self, "_s3_sched_armed", False):
                # stage-3's scheduled gathers ARE the int8 weight wire:
                # the ask is satisfied, nothing to disarm
                log_dist(
                    "ZeRO qwZ: quantized_weights rides the stage-3 "
                    "scheduled gather plan (int8 blocks + fp32 scales per "
                    "micro-step)", ranks=[0])
            else:
                blocker = "cpu_offload=false (the int8 weight gather rides " \
                          "the offload parameter push or the stage-3 " \
                          "scheduled plan)" \
                    if not self._offload else "data-parallel degree is 1"
                log_dist(
                    f"ZeRO qwZ: quantized_weights DISARMED — parameters "
                    f"move in the compute dtype ({blocker})",
                    ranks=[0], level=logging.WARNING)
        if zc.hierarchical_allreduce and self._qgz_armed:
            k = zc.hierarchical_intra_size
            auto = k <= 0
            if auto:
                # auto: co-located ranks (consecutive on the 'data' axis)
                # form the intra group
                k = math.gcd(dp, jax.local_device_count())
            if 1 < k < dp and dp % k == 0:
                self._qgz_intra = k
            elif not auto:
                log_dist(
                    f"ZeRO qgZ: hierarchical_allreduce requested but "
                    f"hierarchical_intra_size={k} cannot form >=2 groups "
                    f"over the data axis ({dp}; needs 1 < k < {dp} with k "
                    f"dividing it); using the flat quantized all_to_all",
                    ranks=[0], level=logging.WARNING)
            # auto + degenerate (e.g. single host: every rank is intra)
            # falls back flat silently — nothing was misconfigured
        elif zc.hierarchical_allreduce:
            # the knob shapes the QUANTIZED exchange only — say so instead
            # of silently ignoring it
            why = "quantized_gradients is disarmed (see warning above)" \
                if zc.quantized_gradients else \
                "zero_optimization.quantized_gradients is not enabled"
            log_dist(
                f"ZeRO qgZ: hierarchical_allreduce has no effect — it "
                f"routes the quantized gradient exchange and {why}",
                ranks=[0], level=logging.WARNING)

    def _arm_zeroone(self, params):
        """Decide whether 0/1 Adam runs the packed 1-bit wire (the fused
        step under shard_map with 'data' manual, sync rounds moving only
        sign bits + per-block scales).  Asked-for compression silently
        no-oping would defeat the user's intent, so every blocker is
        named loudly — a disarmed ZeroOneAdam falls back to the generic
        optimizer path: dense (bias-correction-free) Adam whose variance
        never freezes and whose local rounds never skip."""
        dp = self.dp_world_size
        self._zeroone_armed = False
        blockers = []
        if params.get("comm_backend_name", "xla") == "none":
            blockers.append("comm_backend_name='none'")
        if dp <= 1:
            blockers.append("data-parallel degree is 1")
        if self.zero_optimization_stage() != 0:
            blockers.append(
                f"zero_optimization.stage={self.zero_optimization_stage()} "
                f"(stage >= 1 shards the accumulator; stage-3 scheduled "
                f"gathers own the parameter wire)")
        if self.mesh.shape.get("pipe", 1) != 1:
            blockers.append(f"pipe={self.mesh.shape.get('pipe')}")
        if self.zero_cpu_offload():
            blockers.append("cpu_offload=true (gradients stream D2H, no "
                            "collective to compress)")
        if self.sparse_gradients_enabled():
            blockers.append("sparse_gradients CSR exchange owns the "
                            "embedding-grad wire")
        if blockers:
            log_dist(
                "ZeroOneAdam: wire compression DISARMED — gradients move "
                f"dense and the variance never freezes "
                f"({', '.join(blockers)}); the 1-bit collective path "
                "requires dp>1, zero stage 0, pipe=1, no cpu_offload and "
                "no sparse_gradients",
                ranks=[0], level=logging.WARNING)
            return False
        self._zeroone_armed = True
        return True

    def _arm_quantized_allreduce(self, dp, params=None):
        """Resolve the quantized_all_reduce wire shape for the armed 0/1
        Adam path: flat vs hierarchical two-hop (the qgZ
        ``axis_index_groups`` machinery).  Returns the intra-group size
        (0 = flat) and records it for the comm accounting."""
        import math

        import jax

        params = params or {}
        zc = self._config.zero_config
        self._qar_armed = False
        self._qar_intra = 0
        if dp <= 1:
            log_dist(
                "quantized_all_reduce: DISARMED — data-parallel degree is "
                "1, the collective collapses to the local "
                "quantize/dequantize twin (no wire to shrink)",
                ranks=[0], level=logging.WARNING)
            return 0
        self._qar_armed = True
        k = int(params.get("intra_size", 0) or 0)
        if not k and zc.hierarchical_allreduce:
            k = zc.hierarchical_intra_size
            if k <= 0:
                # auto: co-located ranks (consecutive on the 'data' axis)
                # form the intra group, as for qgZ
                k = math.gcd(dp, jax.local_device_count())
        if 1 < k < dp and dp % k == 0:
            self._qar_intra = k
        elif k > 1:
            log_dist(
                f"quantized_all_reduce: hierarchical intra size {k} cannot "
                f"form >=2 groups over the data axis ({dp}; needs 1 < k < "
                f"{dp} with k dividing it); using the flat wire",
                ranks=[0], level=logging.WARNING)
        return self._qar_intra

    # ------------------------------------------------------------------
    # telemetry (deepspeed_tpu/telemetry/, ISSUE 10)
    # ------------------------------------------------------------------
    def _arm_telemetry(self):
        """Build the telemetry session (span tracer + metrics registry/
        stream + MFU accounting) when the ``telemetry`` config block asks
        for it.  Disarmed engines hold ``self._tracer = None`` — every
        instrumentation site is one attribute check, tracing is purely
        host-side, and the compiled programs are UNTOUCHED either way
        (bit-identical steps, zero extra compiles; pinned by tier-1
        tests).  Sub-knobs set while the master switch is off would
        silently observe nothing, so that DISARMED state warns loudly
        (the OneBitAdam/qgZ discipline)."""
        from deepspeed_tpu.runtime.constants import (
            TELEMETRY_ENABLED, TELEMETRY_METRICS_FSYNC,
            TELEMETRY_METRICS_JSONL, TELEMETRY_MFU, TELEMETRY_PEAK_TFLOPS,
            TELEMETRY_TRACE, TELEMETRY_TRACE_CAPACITY)

        tc = self._config.telemetry
        # the compiled-program registry is ALWAYS on: registration is a
        # shape capture + dict insert once per jit (no compile, no device
        # work), and it is the seam tools/graftlint/program_lint.py and
        # ROADMAP item 5's plan compiler read — telemetry arming only
        # gates the FLOP/memory ledgers below
        from deepspeed_tpu.telemetry import ProgramRegistry

        self._programs = ProgramRegistry("base")
        self._telemetry = None
        self._tracer = None
        self._chaos_observer = None
        self._lane_train = 0
        self._lane_ckpt = 0
        self._mfu_n_params = None
        self._mfu_tokens_per_step = None
        if not tc[TELEMETRY_ENABLED]:
            if tc[TELEMETRY_METRICS_JSONL]:
                log_dist(
                    "telemetry: DISARMED — telemetry.metrics_jsonl is set "
                    "but telemetry.enabled=false, so no trace, step stream "
                    "or MFU accounting will be produced; set "
                    "telemetry.enabled=true to arm it",
                    ranks=[0], level=logging.WARNING)
            return
        from deepspeed_tpu.telemetry import Telemetry

        self._telemetry = Telemetry(
            trace=tc[TELEMETRY_TRACE],
            trace_capacity=tc[TELEMETRY_TRACE_CAPACITY],
            metrics_jsonl=tc[TELEMETRY_METRICS_JSONL],
            metrics_fsync=tc[TELEMETRY_METRICS_FSYNC],
            mfu=tc[TELEMETRY_MFU],
            peak_tflops_per_device=tc[TELEMETRY_PEAK_TFLOPS])
        tr = self._telemetry.tracer
        self._tracer = tr
        if tr is not None:
            self._lane_train = tr.lane("train")
            self._lane_ckpt = tr.lane("ckpt")
            tr.intern("optimizer_step", args=("global_step",))
            tr.intern("overflow_skip", args=("global_step",))
            tr.intern("preempt", args=("global_step",))
            if self._watchdog is not None:
                # observe-only callback: returns None so the verdict
                # stays with the configured callbacks/default action
                self._watchdog.add_callback(self._telemetry_watchdog_cb)
            from deepspeed_tpu.runtime.resilience import chaos

            # the chaos observer list is PROCESS-GLOBAL: register a
            # weakref trampoline, not a bound method, so an abandoned
            # engine (a loop that builds one per attempt) stays
            # collectable and its __del__ can deregister cleanly
            ref = weakref.ref(self)

            def _chaos_obs(kind, detail=None):
                eng = ref()
                if eng is not None:
                    eng._telemetry_chaos_cb(kind, detail)

            self._chaos_observer = chaos.add_observer(_chaos_obs)
        log_dist(
            f"telemetry armed: trace={tc[TELEMETRY_TRACE]} "
            f"(capacity {tc[TELEMETRY_TRACE_CAPACITY]}), "
            f"metrics_jsonl={tc[TELEMETRY_METRICS_JSONL] or 'off'}, "
            f"mfu={tc[TELEMETRY_MFU]}", ranks=[0])

    def _telemetry_watchdog_cb(self, event):
        tr = self._tracer
        if tr is not None:
            tr.instant(f"watchdog_{event.kind}", self._lane_train,
                       a0=int(event.step))
        return None

    def _telemetry_chaos_cb(self, kind, detail=None):
        tr = self._tracer
        if tr is not None:
            tr.instant(f"chaos_{kind}", self._lane_train)

    def close_telemetry(self):
        """Release the telemetry session's process-global hooks (the
        chaos observer) and close the metrics-stream file handle.
        Idempotent; also runs at GC so loops that build many engines
        never accumulate observers or leak JSONL fds.  The session
        object stays readable — only the stream is closed."""
        obs = getattr(self, "_chaos_observer", None)
        if obs is not None:
            self._chaos_observer = None
            from deepspeed_tpu.runtime.resilience import chaos

            chaos.remove_observer(obs)
        tel = getattr(self, "_telemetry", None)
        if tel is not None:
            tel.close()

    def __del__(self):
        try:
            self.close_telemetry()
        except Exception:  # lint: allow-broad-except — interpreter
            # teardown can fail imports mid-GC; never raise from __del__
            pass

    @property
    def telemetry(self):
        """The armed Telemetry session, or None."""
        return self._telemetry

    def export_trace(self, path):
        """Write the retained trace as Chrome-trace-event JSON (loadable
        in chrome://tracing / Perfetto); None when tracing is disarmed."""
        tr = self._tracer
        if tr is None:
            return None
        return tr.export_chrome_trace(path)

    @property
    def program_registry(self):
        """The engine's compiled-program registry (always armed): every
        jit the engine has dispatched, with its declarative HLO contract.
        Read by ``python -m tools.graftlint --programs``."""
        return self._programs

    def _register_program(self, name, jit_fn, args, contract=None,
                          calls_per_step=1.0):
        """Register one jit with the always-on program registry (shape
        capture + dict insert; the lower().compile() is lazy and happens
        only when a lint/report pass reads the entry)."""
        from deepspeed_tpu.telemetry import register_program

        register_program(self._programs, name, jit_fn, args,
                         mesh=self.mesh, contract=contract,
                         calls_per_step=calls_per_step)

    def _register_mfu_jit(self, name, jit_fn, args, calls_per_step=1.0,
                          mem_label=None, program_name=None, contract=None):
        """Capture-by-shape registration of a dispatched jit with the MFU
        ledger AND the measured-memory ledger: a ShapeDtypeStruct tree of
        the REAL dispatch args is taken once (first dispatch; donated
        buffers still alive) and the lower+compile+cost/memory_analysis
        runs lazily at report time — never on the step path, never inside
        a recompile-guard window.  The two ledgers share one compiled
        object per name (``MemoryAccounting(shared=...)``), so arming
        both costs ONE compile per jit.  ``mem_label`` additionally arms
        the analytic-vs-measured transient cross-check for jits the
        engine makes a budget claim about.  The program registry is fed
        FIRST and unconditionally (``program_name`` names the program
        when one MFU slot covers several compiled variants, e.g. the 0/1
        Adam per-(phase, k) fused programs; ``contract`` declares the
        entry's HLO contract for tools/graftlint/program_lint.py)."""
        self._register_program(program_name or name, jit_fn, args,
                               contract=contract,
                               calls_per_step=calls_per_step)
        tel = self._telemetry
        if tel is None:
            return
        from deepspeed_tpu.telemetry import register_by_shape

        register_by_shape(tel.mfu, name, jit_fn, args, mesh=self.mesh,
                          calls_per_step=calls_per_step)
        if self._memacct is not None:
            from deepspeed_tpu.runtime import memory_accounting as mem_acc

            mem_acc.register_by_shape(
                self._memacct, name, jit_fn, args, mesh=self.mesh,
                calls_per_step=calls_per_step, expect_label=mem_label)

    def _note_mfu_workload(self, batch, micros_in_batch=1):
        """Record the 6ND inputs once: parameter count (from the live
        state) and tokens per optimizer step (largest integer leaf of the
        dispatched batch × the accumulation factor not already in its
        shape)."""
        if self._telemetry is None or self._mfu_tokens_per_step is not None:
            return
        import jax

        if self.state is not None:
            self._mfu_n_params = sum(
                int(l.size)
                for l in jax.tree_util.tree_leaves(self.state.params))
        tokens = 0
        for leaf in jax.tree_util.tree_leaves(batch):
            dt = getattr(leaf, "dtype", None)
            if dt is not None and np.issubdtype(np.dtype(dt), np.integer):
                tokens = max(tokens, int(np.prod(np.shape(leaf))))
        if tokens:
            self._mfu_tokens_per_step = tokens * max(1, micros_in_batch)

    def _mfu_report(self):
        tel = self._telemetry
        from deepspeed_tpu.telemetry import model_flops_per_step

        devs = self.mesh.devices.reshape(-1)
        model_flops = None
        if self._mfu_n_params and self._mfu_tokens_per_step:
            model_flops = model_flops_per_step(self._mfu_n_params,
                                               self._mfu_tokens_per_step)
        rep = tel.mfu.report(
            step_time_s=tel.step_time_s(), n_devices=int(len(devs)),
            model_flops=model_flops,
            device_kind=getattr(devs[0], "device_kind", None))
        rep["n_params"] = self._mfu_n_params
        rep["tokens_per_step"] = self._mfu_tokens_per_step
        return rep

    def telemetry_report(self):
        """ONE observability report: consolidates the legacy builders —
        ``_last_metrics`` (per-step scalars), ``comm_volume_report()``
        (analytic wire bytes), and on subclasses ``pipeline_report()`` /
        ``serving_report()`` — behind a single dict WITHOUT replacing
        them, plus the telemetry-only sections: the metrics-registry
        snapshot, the trace summary, and the measured-vs-analytic
        MFU/HFU ledger (``mfu``, populated from
        ``compiled.cost_analysis()``)."""
        report = {
            "engine": type(self).__name__,
            "global_steps": self.global_steps,
            "telemetry_armed": self._telemetry is not None,
            "last_metrics": dict(self._last_metrics)
            if isinstance(self._last_metrics, dict) else self._last_metrics,
        }
        if self.state is not None:
            report["comm"] = self.comm_volume_report()
        if self._supervisor is not None:
            # recovery accounting (ISSUE 12): incident ledger, MTTR,
            # downtime spans, goodput-samples-per-wall-step
            report["recovery"] = self._supervisor.report()
        if self._integrity is not None:
            # numerical-integrity accounting (ISSUE 13): anomaly/vote
            # ledger, detection latency, false-positive counters
            report["integrity"] = self._integrity.report()
        # memory leg (ISSUE 15): analytic components always; measured
        # per-jit memory_analysis + device watermarks when armed
        report["memory"] = self.memory_report()
        tel = self._telemetry
        if tel is None:
            return report
        report["metrics"] = tel.registry.snapshot()
        if tel.tracer is not None:
            report["trace"] = tel.tracer.summary()
        if tel.mfu is not None:
            report["mfu"] = self._mfu_report()
        return report

    # ------------------------------------------------------------------
    # memory accounting (runtime/memory_accounting.py, ISSUE 15)
    # ------------------------------------------------------------------
    def _arm_memory_accounting(self):
        """Arm the measured side of the HBM accounting when telemetry is
        on: every step jit registers capture-by-shape with a
        :class:`runtime.memory_accounting.MemoryAccounting` whose
        ``memory_analysis()`` reads run lazily at report time, sharing
        the MFU channel's compile cache (one compile per jit, zero on
        the step path, zero for a disarmed engine — the compiled
        programs are untouched either way).  The analytic component
        model in ``memory_report()`` works armed or not; with
        ``telemetry.enabled`` on but ``telemetry.memory`` off the
        measured side is DISARMED with a loud warning, because budgets
        sized from the analytic model alone are exactly the unchecked
        estimates this channel exists to catch."""
        from deepspeed_tpu.runtime.constants import (TELEMETRY_ENABLED,
                                                     TELEMETRY_MEMORY)

        tc = self._config.telemetry
        self._memacct = None
        self._mem_stats_available = None   # None = probe on first step
        self._lane_mem = 0
        if not tc[TELEMETRY_ENABLED]:
            return
        if not tc[TELEMETRY_MEMORY]:
            log_dist(
                "memory accounting: DISARMED — telemetry.memory=false; "
                "memory_report() will carry the analytic component model "
                "only, with no measured memory_analysis() cross-check and "
                "no per-step HBM gauges", ranks=[0],
                level=logging.WARNING)
            return
        from deepspeed_tpu.runtime import memory_accounting as mem_acc

        self._memacct = mem_acc.MemoryAccounting(
            shared=self._telemetry.mfu if self._telemetry else None)
        if self._tracer is not None:
            self._lane_mem = self._tracer.lane("mem")
            self._tracer.intern("hbm_in_use", args=("bytes", "peak"))

    def _analytic_memory_components(self):
        """Analytic per-device HBM bytes of the live train state, by
        component — EXACT shard shapes (each leaf's ``shard_shape`` under
        its real sharding), not a modeled partition factor.  None before
        the first batch builds the state."""
        if self.state is None:
            return None
        from deepspeed_tpu.runtime import memory_accounting as mem_acc

        state = self.state
        components = {
            "params_bytes": mem_acc.tree_device_bytes(state.params),
            "grad_accum_bytes": mem_acc.tree_device_bytes(state.accum),
            "master_bytes": mem_acc.tree_device_bytes(state.master),
            "optimizer_state_bytes":
                mem_acc.tree_device_bytes(state.opt_state),
            "scaler_bytes": mem_acc.tree_device_bytes(state.scaler),
        }
        zc = self._config.zero_config
        transient = {
            # scheduled stage-3: gathered weights persist fwd->bwd as
            # vjp residuals — the plan's peak is live on top of the
            # sharded-at-rest state (the stage3_prefetch_budget number)
            "gathered_stage3_bytes":
                self._s3_plan.gathered_bytes
                if getattr(self, "_s3_sched_armed", False) else 0,
            "quantization_scratch_bytes": 0,
        }
        if getattr(self, "_qgz_armed", False):
            leaves, _ = self._comm_leaf_specs()
            transient["quantization_scratch_bytes"] = \
                mem_acc.quantization_scratch_bytes(
                    leaves, self.dp_world_size,
                    zc.quantization_block_size)
        persistent = sum(components.values())
        transient_total = sum(transient.values())
        return {
            "components": components,
            "transient": transient,
            "persistent_bytes": persistent,
            "transient_bytes": transient_total,
            "peak_bytes": persistent + transient_total,
        }

    def memory_report(self):
        """The memory leg of the accounting trio: analytic per-component
        state bytes (exact shard shapes), measured per-jit
        ``memory_analysis()`` with analytic-vs-measured deltas and the
        arming-time cross-checks, and the per-device ``memory_stats()``
        watermark + headroom where the backend reports one.  Cold
        report builder — first call compiles each registered jit's
        shape-struct lowering (shared with the MFU ledger)."""
        from deepspeed_tpu.runtime import memory_accounting as mem_acc

        return mem_acc.memory_report(
            analytic=self._analytic_memory_components(),
            accounting=self._memacct,
            devices=list(self.mesh.devices.reshape(-1)),
            extra={"engine": type(self).__name__})

    def _memory_step_gauges(self):
        """Per-step ``mem`` gauges: HBM in-use/peak from
        ``memory_stats()`` where the backend reports it.  The first step
        probes ONE device; backends with no stats (CPU) disable the path
        for the rest of the run, so the steady-state cost on an
        unsupported backend is a single attribute check."""
        if self._memacct is None or self._mem_stats_available is False:
            return
        from deepspeed_tpu.runtime import memory_accounting as mem_acc

        devices = self.mesh.devices.reshape(-1)
        if self._mem_stats_available is None:
            self._mem_stats_available = \
                mem_acc.normalize_memory_stats(devices[0]) is not None
            if not self._mem_stats_available:
                return
        in_use = peak = 0
        for d in devices:
            stats = mem_acc.normalize_memory_stats(d)
            if stats is None:
                continue
            in_use += stats.get("bytes_in_use") or 0
            peak = max(peak, stats.get("peak_bytes_in_use") or 0)
        reg = self._telemetry.registry
        reg.gauge("mem_bytes_in_use").set(in_use)
        reg.gauge("mem_peak_bytes_in_use").set(peak)
        if self._tracer is not None:
            self._tracer.instant("hbm_in_use", self._lane_mem,
                                 a0=in_use, a1=peak)

    def _use_loss_scaler(self):
        return self.fp16_enabled()

    @property
    def _offload(self):
        return getattr(self.optimizer, "needs_host_state", False)

    def _ensure_state_offload(self, batch):
        """ZeRO-Offload state: device params/accum, HOST fp32 master +
        optimizer moments (reference stage2.py:349-365 cpu_offload branch)."""
        import jax
        import jax.numpy as jnp

        t0 = time.time()
        dev_batch = self._shard_batch(batch)
        init_rng, state_rng = jax.random.split(self._init_rng)
        params_template = jax.eval_shape(
            lambda r, b: self.module.init(r, b), init_rng, dev_batch)
        self._build_shardings(jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32),
            params_template))
        param_sh = self._shardings.params

        # init on host, keep fp32 master there, push compute params down
        try:
            host_dev = jax.local_devices(backend="cpu")[0]
        except RuntimeError:  # pragma: no cover
            host_dev = jax.local_devices()[0]
        with jax.default_device(host_dev):
            params_f32 = self.module.init(init_rng, batch)
        # np.array(copy=True): device_get of an already-fp32 CPU array is a
        # zero-copy READ-ONLY view, and the host Adam updates masters in
        # place (bf16/fp16 configs hid this — their dtype cast forced a
        # writable copy; fp32 offload crashed)
        host_master = jax.tree_util.tree_map(
            lambda l: np.array(jax.device_get(l), dtype=np.float32,
                               copy=True),
            params_f32)
        self._host_master_flat, self._host_treedef = \
            jax.tree_util.tree_flatten(host_master)
        self._host_opt = self.optimizer.init_state(host_master)

        with jax.set_mesh(self.mesh):
            params = jax.tree_util.tree_map(
                lambda l, sh: jax.device_put(
                    np.asarray(l, dtype=self.compute_dtype), sh),
                host_master, param_sh)
        # host-side fp32 gradient accumulators (only this process's shard
        # regions are ever written/read) + in-flight async fetches
        self._host_grad_accum = None
        self._pending_fetches = []
        self._offload_regions_cache = None

        # scaler value lives in device state (the micro fn reads loss_scale
        # in jit); the update POLICY runs host-side via the shared
        # DynamicLossScaler — one implementation of hysteresis, not three
        scaler = None
        self._host_scaler = None
        if self._use_loss_scaler():
            from deepspeed_tpu.runtime.fp16.loss_scaler import CreateLossScaler

            args = dict(self._config.dynamic_loss_scale_args or {})
            args.setdefault("init_scale", self._config.initial_dynamic_scale)
            self._host_scaler = CreateLossScaler(
                static_loss_scale=self._config.loss_scale or 0,
                dynamic_scale_args=args)
            scaler = make_loss_scale_state(self._host_scaler.cur_scale)
        self._host_skipped = 0

        self.state = TrainState(
            params=params, opt_state=(), master=None, accum=(),
            **self._replicated_scalars(scaler, state_rng))
        n_params = sum(l.size for l in self._host_master_flat)
        log_dist(
            f"Initialized ZeRO-Offload state: {n_params/1e6:.1f}M params "
            f"(fp32 master + moments on host, "
            f"{'AVX' if getattr(self.optimizer, 'using_native', False) else 'numpy'} "
            f"Adam) in {time.time()-t0:.1f}s", ranks=[0])

    def _ensure_state(self, batch):
        if self.state is not None:
            return
        if self._offload:
            return self._ensure_state_offload(batch)
        import jax
        import jax.numpy as jnp

        t0 = time.time()
        dev_batch = self._shard_batch(batch)
        init_rng, state_rng = jax.random.split(self._init_rng)

        params_template = jax.eval_shape(
            lambda r, b: self.module.init(r, b), init_rng, dev_batch)
        # master template in fp32, compute params in compute dtype
        self._build_shardings(
            jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), params_template))

        param_sh = self._shardings.params
        master_sh = self._shardings.master

        def init_fn(rng, b):
            params_f32 = jax.tree_util.tree_map(
                lambda l: l.astype(jnp.float32), self.module.init(rng, b))
            return params_f32

        with jax.set_mesh(self.mesh):
            init_jit = jax.jit(init_fn,
                               out_shardings=master_sh if self.mixed_precision else param_sh)
            params_f32 = init_jit(init_rng, dev_batch)

            if self.mixed_precision:
                cast_jit = jax.jit(
                    lambda p: jax.tree_util.tree_map(
                        lambda l: l.astype(self.compute_dtype), p),
                    out_shardings=param_sh)
                params = cast_jit(params_f32)
                master = params_f32
            else:
                params = params_f32
                master = None

            opt_init_jit = jax.jit(self.optimizer.init_state,
                                   out_shardings=self._shardings.opt_state)
            opt_state = opt_init_jit(master if self.mixed_precision else params)

            accum_template = master if self.mixed_precision else params
            accum_jit = jax.jit(
                lambda p: jax.tree_util.tree_map(
                    lambda l: jnp.zeros(l.shape, jnp.float32), p),
                out_shardings=self._shardings.accum)
            accum = accum_jit(accum_template)

        scaler = None
        if self._use_loss_scaler():
            args = self._config.dynamic_loss_scale_args or {}
            if self._config.loss_scale and self._config.loss_scale > 0:
                scaler = make_loss_scale_state(self._config.loss_scale)
            else:
                scaler = make_loss_scale_state(
                    args.get("init_scale", self._config.initial_dynamic_scale),
                    delayed_shift=args.get("delayed_shift", 1))

        self.state = TrainState(
            params=params, opt_state=opt_state, master=master, accum=accum,
            **self._replicated_scalars(scaler, state_rng))
        n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        log_dist(f"Initialized model state: {n_params/1e6:.1f}M params "
                 f"in {time.time()-t0:.1f}s", ranks=[0])

    def _replicated_scalars(self, scaler, state_rng):
        """The scalar fields of a fresh TrainState, committed to the mesh's
        replicated sharding like every jit output.  Uncommitted, they give
        the first dispatch an input type no later one has (the whole step
        program then compiles twice), and multi-process checkpointing can
        only serialize globally-addressable arrays."""
        import jax
        import jax.numpy as jnp

        rep = mesh_lib.replicated(self.mesh)

        def put(x):     # a buffer each: the step jit donates the state
            return jax.device_put(x, rep)

        return dict(step=put(jnp.int32(0)), micro_step=put(jnp.int32(0)),
                    scaler=put(scaler), skipped_steps=put(jnp.int32(0)),
                    rng=put(state_rng))

    def _shard_batch(self, batch):
        """Host batch -> device arrays with dim0 sharded over 'data'."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh

        dp = self.dp_world_size

        def put(x):
            x = np.asarray(x)
            if x.ndim == 0:
                # scalars (e.g. pld_theta) replicate
                return jax.device_put(x, NamedSharding(mesh, P()))
            if x.shape[0] % max(1, dp // jax.process_count()) != 0:
                raise ValueError(
                    f"Batch dim0={x.shape[0]} is not divisible by the local "
                    f"data-parallel degree; feed "
                    f"train_micro_batch_size_per_gpu*local_dp = "
                    f"{self.train_micro_batch_size_per_gpu() * self.local_dp_size} rows")
            # dim1 (sequence) shards over 'seq' when a seq axis exists:
            # Ulysses-style sequence parallelism (parallel/ulysses.py)
            seq = ["seq"] if self.sp_world_size > 1 and x.ndim >= 2 else []
            if seq and x.shape[1] % self.sp_world_size != 0:
                raise ValueError(
                    f"Batch dim1 (sequence)={x.shape[1]} is not divisible by "
                    f"the 'seq' mesh axis size {self.sp_world_size}; pad the "
                    f"sequence so each seq-parallel rank gets equal tokens")
            sh = NamedSharding(mesh, P(*(["data"] + seq
                                         + [None] * (x.ndim - 1 - len(seq)))))
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sh, x)
            return jax.device_put(x, sh)

        return jax.tree_util.tree_map(put, batch)

    # ------------------------------------------------------------------
    # jitted steps
    # ------------------------------------------------------------------
    def _scaler_hparams(self):
        args = self._config.dynamic_loss_scale_args or {}
        return dict(
            scale_window=args.get("scale_window", 1000),
            min_scale=args.get("min_scale", 1.0),
            delayed_shift=args.get("delayed_shift", 1),
            dynamic=self.dynamic_loss_scale())

    def _make_micro_fn(self):
        import jax
        import jax.numpy as jnp

        gas = self.gradient_accumulation_steps()
        model = self.module

        csr_exchange = self._make_csr_grad_exchange() \
            if getattr(self, "_csr_dp_flags", None) is not None else None
        qgz_exchange = self._make_quantized_grad_exchange() \
            if getattr(self, "_qgz_armed", False) else None
        s3_gather = self._make_stage3_gather() \
            if getattr(self, "_s3_sched_armed", False) else None

        def micro(state: TrainState, batch):
            rng = jax.random.fold_in(state.rng, state.micro_step + state.step * 131071)
            scale = state.scaler.loss_scale if state.scaler is not None \
                else jnp.float32(1.0)

            if csr_exchange is not None:
                grads, loss = csr_exchange(state.params, batch, rng, scale)
            elif qgz_exchange is not None:
                grads, loss = qgz_exchange(state.params, batch, rng, scale)
            else:
                def loss_fn(params):
                    # scheduled stage-3: ONE planned quantized gather per
                    # partitioned leaf; its output is a vjp residual, so
                    # the backward reuses it instead of regathering
                    full = s3_gather(params) if s3_gather is not None \
                        else params
                    loss, metrics = model.loss(full, batch, rng, train=True)
                    return loss.astype(jnp.float32) * scale / gas, (loss, metrics)

                grads, (loss, metrics) = jax.grad(loss_fn, has_aux=True)(state.params)
            accum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), state.accum, grads)
            new_state = state._replace(accum=accum, micro_step=state.micro_step + 1)
            return new_state, loss

        return micro

    def _sparse_row_capacity(self, batch):
        """CSR row capacity from batch SHAPES (trace-time ints): the model's
        sparse_grad_tokens, falling back to the total integer-leaf size.
        Zero capacity would silently zero every sparse gradient, so it
        raises instead — shared by the offload D2H stream and the DP wire."""
        import jax
        import jax.numpy as jnp

        model = self.module
        if hasattr(model, "sparse_grad_tokens"):
            tokens = int(model.sparse_grad_tokens(batch))
        else:
            tokens = sum(
                int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(batch)
                if jnp.issubdtype(jnp.asarray(l).dtype, jnp.integer))
        if tokens <= 0:
            raise ValueError(
                "sparse_gradients: cannot size the CSR row capacity — the "
                "batch has no integer leaves and the model does not define "
                "sparse_grad_tokens(batch); truncating rows would silently "
                "corrupt gradients")
        return tokens

    def _make_csr_grad_exchange(self):
        """Gradient computation + exchange with 'data' manual: sparse-flagged
        leaves skip the dense psum and all-gather CSR rows instead (row
        capacity = local lookup tokens, from the model's sparse_grad_tokens
        or the batch's integer-leaf sizes); dense leaves pmean as GSPMD
        would. Returns (grads mesh-averaged dense, loss pmean'd) — from the
        accumulator onward nothing downstream changes.

        Reference swaps the allreduce for sparse all-gather in
        deepspeed/runtime/engine.py:1227-1265; the traffic win is proved by
        an HLO byte test (tests/unit/test_csr.py)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.runtime.csr_tensor import CSRTensor

        mesh = self.mesh
        gas = self.gradient_accumulation_steps()
        model = self.module
        flags = self._csr_dp_flags
        dp = self.dp_world_size
        pspec = self._onebit_state_spec().params

        def body(params, batch, rng, scale):
            rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))

            def loss_fn(p):
                loss, _ = model.loss(p, batch, rng, train=True)
                return loss.astype(jnp.float32) * scale / gas, loss

            grads, loss = jax.grad(loss_fn, has_aux=True)(params)
            # static row capacity from LOCAL batch shapes (trace-time ints)
            tokens = self._sparse_row_capacity(batch)

            def exchange(flag, g):
                if not flag:
                    return jax.lax.pmean(g, "data")
                # nonzero rows <= local lookup tokens by construction, so
                # capacity cannot drop gradient rows
                cap = min(tokens, g.shape[0])
                csr = CSRTensor.from_dense(g, max_rows=cap)
                idx = jax.lax.all_gather(csr.indices, "data")   # (dp, cap)
                vals = jax.lax.all_gather(csr.values, "data")
                flat_idx = idx.reshape(-1)
                valid = flat_idx >= 0
                flat_vals = vals.reshape((-1,) + vals.shape[2:])
                flat_vals = jnp.where(
                    valid[:, None] if flat_vals.ndim == 2 else valid,
                    flat_vals, 0)
                dense = jnp.zeros(g.shape, flat_vals.dtype)
                return dense.at[jnp.maximum(flat_idx, 0)].add(flat_vals) / dp

            grads = jax.tree_util.tree_map(exchange, flags, grads)
            return grads, jax.lax.pmean(loss, "data")

        def run(params, batch, rng, scale):
            batch_spec = jax.tree_util.tree_map(
                lambda x: P() if x.ndim == 0 else
                P(*(["data"] + [None] * (x.ndim - 1))), batch)
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(pspec, batch_spec, P(), P()),
                out_specs=(pspec, P()),
                axis_names={"data"}, check_vma=False)(params, batch, rng,
                                                      scale)

        return run

    def _accum_data_dims(self):
        """Per-leaf dim the ZeRO accumulator spec shards over 'data' (None =
        replicated leaf).  Drives which gradient leaves ride the quantized
        reduce-scatter and where their shard lands."""
        import jax
        from jax.sharding import NamedSharding

        return jax.tree_util.tree_map(
            _spec_data_dim, self._shardings.accum,
            is_leaf=lambda x: isinstance(x, NamedSharding))

    def _make_quantized_grad_exchange(self):
        """Gradient computation + exchange with 'data' manual: the stage-2
        reduce-scatter becomes quantize -> all_to_all -> local reduce ->
        dequantize (the ZeRO++ qgZ shape, custom_collectives.
        quantized_reduce_scatter), optionally hierarchical.  Shardable
        leaves come back as the device's fp32 accumulator shard (out_specs
        put 'data' on the same dim the ZeRO accum spec shards), so the
        downstream accum add is collective-free; leaves too small to shard
        pmean densely as GSPMD would.

        Wire bytes drop ~4x vs the fp32 reduce-scatter (int8 + per-block
        fp32 scales at block 128) — asserted analytically by
        comm_volume_report() and tests/unit/test_quantization.py."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.runtime.custom_collectives import \
            quantized_reduce_scatter

        mesh = self.mesh
        gas = self.gradient_accumulation_steps()
        model = self.module
        dp = self.dp_world_size
        block = self._config.zero_config.quantization_block_size
        intra = getattr(self, "_qgz_intra", 0)
        state_spec = self._onebit_state_spec()
        pspec = state_spec.params
        grads_out_spec = state_spec.accum
        dims = self._accum_data_dims()

        def body(params, batch, rng, scale):
            rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))

            def loss_fn(p):
                loss, _ = model.loss(p, batch, rng, train=True)
                return loss.astype(jnp.float32) * scale / gas, loss

            grads, loss = jax.grad(loss_fn, has_aux=True)(params)

            def exchange(dim, g):
                if dim is None:
                    return jax.lax.pmean(g, "data")
                return quantized_reduce_scatter(
                    g, "data", dim=dim, block_size=block, intra_size=intra)

            # is_leaf: a None dim means "replicated leaf", not an empty
            # subtree — without it tree_map drops the entry entirely
            grads = jax.tree_util.tree_map(exchange, dims, grads,
                                           is_leaf=lambda x: x is None)
            return grads, jax.lax.pmean(loss, "data")

        def run(params, batch, rng, scale):
            batch_spec = jax.tree_util.tree_map(
                lambda x: P() if x.ndim == 0 else
                P(*(["data"] + [None] * (x.ndim - 1))), batch)
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(pspec, batch_spec, P(), P()),
                out_specs=(grads_out_spec, P()),
                axis_names={"data"}, check_vma=False)(params, batch, rng,
                                                      scale)

        return run

    def _make_micro_offload_fn(self):
        """Offload micro step: no device accumulator — gradients are an
        OUTPUT (fp32, ZeRO-sharded via out_shardings), streamed to the host
        which owns accumulation + the Adam step."""
        import jax
        import jax.numpy as jnp

        gas = self.gradient_accumulation_steps()
        model = self.module

        sparse_flags = getattr(self, "_offload_sparse_flags", None)

        def micro(state: TrainState, batch):
            rng = jax.random.fold_in(state.rng,
                                     state.micro_step + state.step * 131071)

            def loss_fn(params):
                loss, metrics = model.loss(params, batch, rng, train=True)
                scale = state.scaler.loss_scale if state.scaler is not None \
                    else 1.0
                return loss.astype(jnp.float32) * scale / gas, loss

            grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
            if sparse_flags is not None:
                from deepspeed_tpu.runtime.csr_tensor import CSRTensor

                # row capacity (static per trace): an embedding grad has
                # nonzero rows only for looked-up ids, so (indices, values)
                # @ capacity rows beat the dense (vocab, dim) table on the
                # D2H wire. Models declare their lookup-token count via
                # sparse_grad_tokens(batch); the fallback counts every
                # integer leaf, which over-reserves when labels/masks ride
                # along (correct, just a smaller saving).
                tokens = self._sparse_row_capacity(batch)

                def maybe_csr(flag, g):
                    if not flag:
                        return g
                    cap = min(tokens, g.shape[0])
                    csr = CSRTensor.from_dense(g, max_rows=cap)
                    # capacity under-report (e.g. a wrong
                    # sparse_grad_tokens) would silently DROP gradient
                    # rows; the overflow count travels with the leaf and
                    # the host consume raises on it
                    nnz = jnp.sum(jnp.any(g != 0, axis=tuple(
                        range(1, g.ndim))).astype(jnp.int32))
                    return {"csr_indices": csr.indices,
                            "csr_values": csr.values,
                            "csr_dropped": jnp.maximum(nnz - cap, 0)}

                grads = jax.tree_util.tree_map(maybe_csr, sparse_flags,
                                               grads)
            new_state = state._replace(micro_step=state.micro_step + 1)
            return new_state, loss, grads

        return micro

    # ------------------------------------------------------------------
    # offload host-side gradient streaming
    # ------------------------------------------------------------------
    def _offload_regions(self):
        """Unique addressable (leaf_index, numpy_index, owned) regions of
        the ZeRO grad sharding — the slices of each full-shape array this
        process holds. `owned` is True on exactly ONE process per distinct
        region (the lowest process index holding it): cross-process
        reductions like the gradient norm must count a region once even
        when a leaf stays replicated over 'data' (zero_merge_spec leaves
        non-divisible leaves replicated). Cached; layouts are static."""
        if self._offload_regions_cache is not None:
            return self._offload_regions_cache
        import jax

        my_proc = jax.process_index()
        regions = []
        sh_flat = jax.tree_util.tree_leaves(self._offload_region_sh)
        for i, (master, sh) in enumerate(zip(self._host_master_flat,
                                             sh_flat)):
            imap = sh.devices_indices_map(tuple(master.shape))
            owner = {}
            for d, idx in imap.items():
                key = tuple((s.start, s.stop, s.step) for s in idx)
                owner[key] = min(owner.get(key, d.process_index),
                                 d.process_index)
            seen = set()
            for d in sh.addressable_devices:
                idx = imap[d]
                key = tuple((s.start, s.stop, s.step) for s in idx)
                if key in seen:
                    continue
                seen.add(key)
                regions.append((i, idx, owner[key] == my_proc))
        self._offload_regions_cache = regions
        return regions

    @staticmethod
    def _is_csr_leaf(x):
        return isinstance(x, dict) and "csr_indices" in x

    def _start_grad_fetch(self, grads):
        """Kick off async D2H copies of this process's grad shards; returns
        the per-master-leaf list (dense arrays or CSR {indices, values}
        pairs) for later consumption. The copy overlaps the next
        micro-batch's device compute (reference stage2.py:876-958 overlaps
        D2H on a side stream the same way)."""
        import jax

        flat = jax.tree_util.tree_flatten(grads, is_leaf=self._is_csr_leaf)[0]
        for leaf in flat:
            arrs = (list(leaf.values()) if self._is_csr_leaf(leaf)
                    else [leaf])
            for a in arrs:
                for s in a.addressable_shards:
                    s.data.copy_to_host_async()
        return flat

    def _consume_grad_fetch(self, flat):
        """Accumulate a fetched micro-batch's local grad shards into the
        host fp32 buffers (allocated lazily, full-shape; only this
        process's regions are ever touched). CSR leaves scatter-add their
        valid rows into the full-shape buffer."""
        if self._host_grad_accum is None:
            self._host_grad_accum = [np.zeros(m.shape, np.float32)
                                     for m in self._host_master_flat]
        for buf, leaf in zip(self._host_grad_accum, flat):
            if self._is_csr_leaf(leaf):
                dropped = int(np.asarray(leaf["csr_dropped"]))
                if dropped:
                    raise RuntimeError(
                        f"sparse_gradients: CSR capacity too small — "
                        f"{dropped} nonzero gradient rows were dropped; "
                        f"fix the model's sparse_grad_tokens(batch) to "
                        f"report the true lookup-token count")
                idx = np.asarray(leaf["csr_indices"])
                vals = np.asarray(leaf["csr_values"], dtype=np.float32)
                valid = idx >= 0
                np.add.at(buf, idx[valid], vals[valid])
                continue
            seen = set()
            for s in leaf.addressable_shards:
                key = tuple((sl.start, sl.stop, sl.step) for sl in s.index)
                if key in seen:
                    continue
                seen.add(key)
                buf[s.index] += np.asarray(s.data, dtype=np.float32)

    def _drain_pending_fetches(self):
        for flat in self._pending_fetches:
            self._consume_grad_fetch(flat)
        self._pending_fetches = []

    def _replicate_host_leaves(self, leaves):
        """Fill non-local regions of full-shape host fp32 arrays from peer
        processes: local regions go up ZeRO-sharded, one on-device gather
        replicates, and the full array comes back down. Checkpoint-save
        path only; leaves cycles through (master, m, v) so the grad-shard
        layout tree is tiled over it."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh_flat = jax.tree_util.tree_leaves(self._offload_region_sh)
        rep = NamedSharding(self.mesh, P())
        if not hasattr(self, "_jit_replicate"):
            # one cached identity: jit retraces per shape, not per call
            self._jit_replicate = jax.jit(lambda x: x, out_shardings=rep)
        out = []
        with jax.set_mesh(self.mesh):
            for j, arr in enumerate(leaves):
                gsh = sh_flat[j % len(sh_flat)]
                imap = gsh.devices_indices_map(tuple(arr.shape))
                arrs = [jax.device_put(
                            np.ascontiguousarray(arr[imap[d]]), d)
                        for d in gsh.addressable_devices]
                ga = jax.make_array_from_single_device_arrays(
                    tuple(arr.shape), gsh, arrs)
                full = self._jit_replicate(ga)
                out.append(np.asarray(jax.device_get(full),
                                      dtype=np.float32))
        return out

    def _qwz_leaf_meta(self):
        """Static per-leaf plan for the quantized (qwZ) parameter push.

        A leaf rides the int8 gather when its offload sharding is a pure
        'data' split on one dim (TP-mixed leaves keep the dense path — they
        are exotic under offload and the flat int8 layout assumes shard ==
        data coordinate).  Cached; layouts are static."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu.runtime import quantization as qz

        if getattr(self, "_qwz_meta", None) is not None:
            return self._qwz_meta
        dp = self.dp_world_size
        block = self._config.zero_config.quantization_block_size
        sh_flat = jax.tree_util.tree_leaves(self._offload_region_sh)
        metas = []
        for master, gsh in zip(self._host_master_flat, sh_flat):
            spec_axes = [(a if isinstance(a, tuple) else (a,))
                         for a in gsh.spec if a is not None]
            flat_axes = [x for axes in spec_axes for x in axes]
            if flat_axes != ["data"] or master.ndim == 0:
                metas.append(None)
                continue
            d = [i for i, a in enumerate(gsh.spec) if a is not None][0]
            s_d = master.shape[d]
            if s_d % dp != 0:
                metas.append(None)
                continue
            nloc = master.size // dp
            bs, nb, npad = qz.block_layout(nloc, block)
            metas.append({
                "dim": d, "shard_rows": s_d // dp, "nloc": nloc,
                "bs": bs, "nb": nb, "npad": npad,
                "q_sh": NamedSharding(self.mesh, P("data")),
            })
        self._qwz_meta = metas
        return metas

    def _build_param_gather(self):
        """The jitted shard->replicated parameter materialization for the
        offload step.  Dense leaves are an identity whose out_shardings make
        XLA all-gather the compute-dtype shards; qwZ leaves arrive as flat
        int8 blocks + fp32 scales, are FORCED replicated while still int8
        (the sharding constraint pins the all-gather to the 1-byte payload)
        and dequantize locally afterwards."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        dp = self.dp_world_size
        compute_dtype = self.compute_dtype
        param_sh_flat = jax.tree_util.tree_leaves(self._shardings.params)
        leaf_shapes = [tuple(m.shape) for m in self._host_master_flat]
        metas = self._qwz_leaf_meta() if self._qwz_armed \
            else [None] * len(leaf_shapes)
        rep = NamedSharding(mesh, P())

        def gather(dense_arrs, q_arrs, s_arrs):
            outs = [None] * len(metas)
            di = qi = 0
            for i, meta in enumerate(metas):
                if meta is None:
                    outs[i] = dense_arrs[di]
                    di += 1
                    continue
                q = jax.lax.with_sharding_constraint(q_arrs[qi], rep)
                s = jax.lax.with_sharding_constraint(s_arrs[qi], rep)
                qi += 1
                rows = (q.reshape(dp, meta["nb"], meta["bs"])
                        .astype(jnp.float32)
                        * s.reshape(dp, meta["nb"])[:, :, None])
                rows = rows.reshape(dp, meta["npad"])[:, :meta["nloc"]]
                shape = leaf_shapes[i]
                d = meta["dim"]
                # pieces were flattened host-side with dim d moved to the
                # front, so shard rows stack contiguously along that dim
                moved = (shape[d],) + shape[:d] + shape[d + 1:]
                full = rows.reshape((shape[d],) + moved[1:])
                outs[i] = jnp.moveaxis(full, 0, d).astype(compute_dtype)
            return outs

        return jax.jit(gather, out_shardings=param_sh_flat)

    def _push_local_params(self):
        """Upload this process's updated master slices and all-gather to the
        replicated/TP param layout on device — H2D traffic is O(params/dp)
        per process, the gather rides ICI.  With zero_optimization.
        quantized_weights (qwZ, ZeRO++ arxiv 2306.10209 §4.1) eligible
        leaves upload and gather as blockwise int8 + fp32 scales instead of
        the compute dtype, shrinking both the H2D copy and the on-wire
        all-gather ~2-4x; dequantization to the compute dtype happens
        replicated, after the gather."""
        import jax

        from deepspeed_tpu.runtime import quantization as qz

        dtype_name = str(jax.numpy.dtype(self.compute_dtype))
        sh_flat = jax.tree_util.tree_leaves(self._offload_region_sh)
        metas = self._qwz_leaf_meta() if self._qwz_armed \
            else [None] * len(self._host_master_flat)
        block = self._config.zero_config.quantization_block_size
        dense_arrs, q_arrs, s_arrs = [], [], []
        for master, gsh, meta in zip(self._host_master_flat, sh_flat,
                                     metas):
            imap = gsh.devices_indices_map(tuple(master.shape))
            if meta is None:
                pieces = {}
                for d in gsh.addressable_devices:
                    idx = imap[d]
                    key = tuple((s.start, s.stop, s.step) for s in idx)
                    if key not in pieces:
                        pieces[key] = self.optimizer.cast_to(
                            [master[idx]], dtype_name)[0]
                arrs = [jax.device_put(pieces[tuple(
                            (s.start, s.stop, s.step) for s in imap[d])], d)
                        for d in gsh.addressable_devices]
                dense_arrs.append(jax.make_array_from_single_device_arrays(
                    tuple(master.shape), gsh, arrs))
                continue
            npad, nb = meta["npad"], meta["nb"]
            rows = meta["shard_rows"]
            d_dim = meta["dim"]
            pieces = {}
            for dev in gsh.addressable_devices:
                coord = imap[dev][d_dim].start // rows
                if coord not in pieces:
                    # flatten with the sharded dim leading so the gathered
                    # rows stack contiguously (the gather jit's layout)
                    pieces[coord] = qz.quantize_blockwise_np(
                        np.moveaxis(master[imap[dev]], d_dim, 0), block)
            q_parts, s_parts = [], []
            for dev in gsh.addressable_devices:
                coord = imap[dev][d_dim].start // rows
                qp, sp = pieces[coord]
                q_parts.append(jax.device_put(qp, dev))
                s_parts.append(jax.device_put(sp, dev))
            q_arrs.append(jax.make_array_from_single_device_arrays(
                (self.dp_world_size * npad,), meta["q_sh"], q_parts))
            s_arrs.append(jax.make_array_from_single_device_arrays(
                (self.dp_world_size * nb,), meta["q_sh"], s_parts))
        if self._jit_param_gather is None:
            self._jit_param_gather = self._build_param_gather()
        with jax.set_mesh(self.mesh):
            new_flat = self._jit_param_gather(dense_arrs, q_arrs, s_arrs)
        new_params = jax.tree_util.tree_unflatten(self._host_treedef,
                                                  new_flat)
        self.state = self.state._replace(params=new_params)

    def _make_apply_fn(self):
        import jax
        import jax.numpy as jnp

        clip = self.gradient_clipping()
        scaler_hp = self._scaler_hparams()
        optimizer = self.optimizer
        mixed = self.mixed_precision
        compute_dtype = self.compute_dtype
        # integrity sentinels (ISSUE 13): a build-time Python flag, so a
        # disarmed engine compiles the EXACT pre-integrity program
        # (bit-identical, zero extra compiles — tier-1 pin); an armed one
        # adds the global grad norm + update/param-norm ratio as extra
        # jit outputs riding the existing metrics dict
        sentinels = self._integrity is not None \
            and self._integrity.sentinels_armed

        def _tree_norm(tree):
            return jnp.sqrt(sum(
                jnp.sum(jnp.square(l.astype(jnp.float32)))
                for l in jax.tree_util.tree_leaves(tree)))

        def apply(state: TrainState, lr):
            scale = state.scaler.loss_scale if state.scaler is not None else jnp.float32(1.0)
            # overflow check on raw accumulated (scaled) grads
            finite = jnp.asarray(True)
            for g in jax.tree_util.tree_leaves(state.accum):
                finite &= jnp.all(jnp.isfinite(g))
            overflow = ~finite

            def do_update(st):
                grads = jax.tree_util.tree_map(lambda g: g / scale, st.accum)
                if clip and clip > 0:
                    gnorm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
                    factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
                elif sentinels:
                    # the sentinel wants the global norm even unclipped
                    gnorm = _tree_norm(grads)
                else:
                    gnorm = jnp.float32(0.0)
                master = st.master if mixed else st.params
                new_master, new_opt = optimizer.update(
                    grads, st.opt_state, master, lr=lr)
                extras = gnorm
                if sentinels:
                    delta = jax.tree_util.tree_map(
                        lambda n, o: n.astype(jnp.float32)
                        - o.astype(jnp.float32), new_master, master)
                    extras = (gnorm, _tree_norm(delta)
                              / (_tree_norm(master) + 1e-12))
                if mixed:
                    new_params = jax.tree_util.tree_map(
                        lambda l: l.astype(compute_dtype), new_master)
                    return st._replace(params=new_params, master=new_master,
                                       opt_state=new_opt, step=st.step + 1), extras
                return st._replace(params=new_master, opt_state=new_opt,
                                   step=st.step + 1), extras

            def skip_update(st):
                zero = jnp.float32(0.0)
                return st._replace(skipped_steps=st.skipped_steps + 1,
                                   step=st.step + 1), \
                    ((zero, zero) if sentinels else zero)

            new_state, extras = jax.lax.cond(overflow, skip_update, do_update, state)
            gnorm = extras[0] if sentinels else extras
            if state.scaler is not None:
                new_scaler = update_loss_scale(new_state.scaler, overflow, **scaler_hp)
                new_state = new_state._replace(scaler=new_scaler)
            zero_accum = jax.tree_util.tree_map(jnp.zeros_like, new_state.accum)
            new_state = new_state._replace(accum=zero_accum, micro_step=jnp.int32(0))
            metrics = {"overflow": overflow, "grad_norm": gnorm,
                       "loss_scale": scale}
            if sentinels:
                metrics["update_ratio"] = extras[1]
            return new_state, metrics

        return apply

    # ------------------------------------------------------------------
    # 1-bit Adam wire-compressed path (shard_map over 'data')
    # ------------------------------------------------------------------
    def _onebit_wire(self) -> bool:
        """True when the optimizer asked for on-the-wire gradient compression
        (OnebitAdam with axis_name set): the fused step then runs under
        shard_map with 'data' manual, so gradients stay device-local and the
        only gradient-sized traffic after freeze_step is the bit-packed
        collective (reference onebit_adam.py:104-228 compresses before the
        network; the GSPMD path would psum densely first).  ZeroOneAdam
        carries axis_name too but owns its own phase-compiled path —
        see _zeroone_wire below."""
        return (getattr(self.optimizer, "axis_name", None) is not None
                and getattr(self.optimizer, "name", "")
                != ZEROONE_ADAM_OPTIMIZER
                and not self._offload)

    def _onebit_frozen(self) -> bool:
        """Static freeze phase for program selection, keyed on OPTIMIZER
        steps (engine steps minus scale-skipped steps — the reference's
        count, onebit_adam.py freeze_step semantics). The skipped count is
        a device scalar: it is read back once per train_batch during warmup
        only, and the phase latches True so the post-freeze steady state
        never syncs."""
        if getattr(self, "_onebit_frozen_latch", False):
            return True
        # skipped >= 0, so while engine steps alone cannot reach the
        # boundary there is nothing to read — keeps warmup free of
        # host-device syncs until the freeze is actually reachable
        if self.global_steps + 1 <= self.optimizer.freeze_step:
            return False
        # canonical counter (device counter + host-offload skips) — do not
        # re-implement the read inline, the two would drift
        skipped = self.skipped_steps \
            if self.state is not None and self.fp16_enabled() else 0
        frozen = (self.global_steps - skipped + 1) > self.optimizer.freeze_step
        if frozen:
            self._onebit_frozen_latch = True
        return frozen

    def _make_onebit_tail(self, frozen):
        """Shared optimizer tail for the wire path: overflow check ->
        compressed/warmup update -> scaler. Runs inside shard_map with 'data'
        manual. `accum` may be device-local (fused path) or replicated
        (forward/backward/step path) — both are valid 1-bit inputs."""
        import jax
        import jax.numpy as jnp

        optimizer = self.optimizer
        mixed = self.mixed_precision
        compute_dtype = self.compute_dtype
        scaler_hp = self._scaler_hparams()

        def tail(st, accum, lr):
            scale = st.scaler.loss_scale if st.scaler is not None \
                else jnp.float32(1.0)
            bad = jnp.float32(0.0)
            for g in jax.tree_util.tree_leaves(accum):
                bad += jnp.sum((~jnp.isfinite(g)).astype(jnp.float32))
            bad = jax.lax.psum(bad, "data")
            overflow = bad > 0

            def do_update(s2):
                master = s2.master if mixed else s2.params
                new_master, new_opt = optimizer.update(
                    accum, s2.opt_state, master, lr=lr, scale=scale,
                    frozen=frozen)
                if mixed:
                    new_params = jax.tree_util.tree_map(
                        lambda l: l.astype(compute_dtype), new_master)
                    return s2._replace(params=new_params, master=new_master,
                                       opt_state=new_opt, step=s2.step + 1)
                return s2._replace(params=new_master, opt_state=new_opt,
                                   step=s2.step + 1)

            def skip_update(s2):
                return s2._replace(skipped_steps=s2.skipped_steps + 1,
                                   step=s2.step + 1)

            new_state = jax.lax.cond(overflow, skip_update, do_update, st)
            if st.scaler is not None:
                new_scaler = update_loss_scale(new_state.scaler, overflow,
                                               **scaler_hp)
                new_state = new_state._replace(scaler=new_scaler)
            zero_accum = jax.tree_util.tree_map(jnp.zeros_like,
                                                new_state.accum)
            new_state = new_state._replace(accum=zero_accum,
                                           micro_step=jnp.int32(0))
            metrics = {"overflow": overflow,
                       "grad_norm": jnp.float32(0.0),
                       "loss_scale": scale}
            return new_state, metrics

        return tail

    def _onebit_state_spec(self):
        """State specs for the wire shard_map: partial-auto shard_map
        in_specs may ONLY name manual axes ('data'); auto axes (TP 'model',
        'pipe') are dropped — GSPMD keeps their placement implicitly."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def manual_only(axis):
            if axis is None:
                return None
            axes = axis if isinstance(axis, tuple) else (axis,)
            kept = tuple(a for a in axes if a == "data")
            if not kept:
                return None
            return kept if len(kept) > 1 else kept[0]

        return jax.tree_util.tree_map(
            lambda s: P(*(manual_only(a) for a in s.spec)), self._shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))

    def _make_onebit_fused(self, frozen):
        """Full train step (gas micro-batches + 1-bit update) with 'data'
        manual: per-device gradients never see a dense collective."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        gas = self.gradient_accumulation_steps()
        model = self.module
        tail = self._make_onebit_tail(frozen)
        state_spec = self._onebit_state_spec()

        def fused(state, stacked_batch, lr):
            batch_spec = jax.tree_util.tree_map(
                lambda x: P(*([None, "data"] + [None] * (x.ndim - 2))),
                stacked_batch)

            def body(st, local_batch, lr):
                scale = st.scaler.loss_scale if st.scaler is not None \
                    else jnp.float32(1.0)

                def micro(carry, b):
                    accum, i = carry
                    rng = jax.random.fold_in(
                        st.rng, i + st.step * 131071)
                    rng = jax.random.fold_in(
                        rng, jax.lax.axis_index("data"))

                    def loss_fn(params):
                        loss, _ = model.loss(params, b, rng, train=True)
                        return loss.astype(jnp.float32) * scale / gas, loss

                    grads, loss = jax.grad(loss_fn, has_aux=True)(st.params)
                    accum = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), accum, grads)
                    return (accum, i + 1), loss

                (accum, _), losses = jax.lax.scan(
                    micro, (st.accum, st.micro_step), local_batch)
                new_state, metrics = tail(st, accum, lr)
                metrics["loss"] = jax.lax.pmean(losses.mean(), "data")
                return new_state, metrics

            metrics_spec = {"overflow": P(), "grad_norm": P(),
                            "loss_scale": P(), "loss": P()}
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(state_spec, batch_spec, P()),
                out_specs=(state_spec, metrics_spec),
                axis_names={"data"}, check_vma=False)(state, stacked_batch, lr)

        return fused

    def _make_onebit_apply(self, frozen):
        """Optimizer step for the forward/backward/step path: accum arrived
        mesh-averaged from the GSPMD micro steps (identical per device), so
        the update still runs under shard_map for the bit-packed collective."""
        import jax
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        tail = self._make_onebit_tail(frozen)
        state_spec = self._onebit_state_spec()

        def apply_(state, lr):
            metrics_spec = {"overflow": P(), "grad_norm": P(),
                            "loss_scale": P()}
            return jax.shard_map(
                lambda st, lr: tail(st, st.accum, lr), mesh=mesh,
                in_specs=(state_spec, P()),
                out_specs=(state_spec, metrics_spec),
                axis_names={"data"}, check_vma=False)(state, lr)

        return apply_

    def _compile_onebit(self):
        import jax

        sh = self._shardings
        if self.gradient_clipping():
            # global-norm clipping needs the dense mean gradient — exactly
            # the collective the wire path exists to avoid (cross terms make
            # ||mean(g_i)|| incomputable from local norms). Refusing beats
            # silently training differently at dp>1 than at dp=1.
            raise ValueError(
                "gradient_clipping is incompatible with the 1-bit Adam "
                "wire-compression path (post-freeze there is no dense "
                "gradient to clip). Disable clipping, or set optimizer "
                "params comm_backend_name='none' to keep the dense path.")
        self._jit_micro = jax.jit(self._make_micro_fn(), donate_argnums=(0,),
                                  out_shardings=(sh, None))
        self._onebit_fused_fns = {b: self._make_onebit_fused(b)
                                  for b in (False, True)}
        self._onebit_apply_fns = {b: self._make_onebit_apply(b)
                                  for b in (False, True)}
        self._onebit_fused_jits = {}
        self._onebit_apply_jits = {}

    # ------------------------------------------------------------------
    # 0/1 Adam wire path (shard_map over 'data', per-phase programs)
    # ------------------------------------------------------------------
    def _zeroone_wire(self) -> bool:
        """True when ZeroOneAdam asked for the packed 1-bit wire
        (axis_name armed by _arm_zeroone): the train step then compiles
        one program per cadence phase — warmup (dense pmean + Adam),
        local (accumulate only, ZERO cross-device collectives) and sync
        (the quantized_all_reduce packed wire + lr*k update)."""
        return (getattr(self.optimizer, "name", "")
                == ZEROONE_ADAM_OPTIMIZER
                and getattr(self.optimizer, "axis_name", None) is not None
                and not self._offload)

    def _zeroone_phase(self):
        """(phase, k_round) for the NEXT optimizer step — host-side
        program selection, a pure function of the completed-optimizer-
        step count (zeroone_cadence), so an elastic resume re-derives
        the phase from restored counters.  Keyed on OPTIMIZER steps
        (engine steps minus scale-skipped steps) like _onebit_frozen;
        the latch only skips the device-counter read while the freeze
        boundary is provably unreachable."""
        opt = self.optimizer
        if not getattr(self, "_zeroone_frozen_latch", False) and \
                self.global_steps + 1 <= opt.var_freeze_step:
            return "warmup", 1
        skipped = self.skipped_steps \
            if self.state is not None and self.fp16_enabled() else 0
        phase, k = opt.cadence(self.global_steps - skipped)
        if phase != "warmup":
            self._zeroone_frozen_latch = True
        return phase, k

    def _make_zeroone_tail(self, phase, k):
        """Optimizer tail for the 0/1 Adam wire path, one per (phase,
        k_round).  Local rounds skip the overflow psum entirely — the
        contract is ZERO cross-device collectives — so non-finite
        gradients ride the per-device accumulator until the sync round's
        check (which scans the accumulator too) catches them, skips the
        update and drops the poisoned round's accumulation."""
        import jax
        import jax.numpy as jnp

        optimizer = self.optimizer
        mixed = self.mixed_precision
        compute_dtype = self.compute_dtype
        scaler_hp = self._scaler_hparams()

        def tail(st, accum, lr):
            scale = st.scaler.loss_scale if st.scaler is not None \
                else jnp.float32(1.0)

            if phase == "local":
                master = st.master if mixed else st.params
                _, new_opt = optimizer.update(
                    accum, st.opt_state, master, lr=lr, scale=scale,
                    phase="local", k_round=k)
                new_state = st._replace(opt_state=new_opt,
                                        step=st.step + 1)
                zero_accum = jax.tree_util.tree_map(
                    jnp.zeros_like, new_state.accum)
                new_state = new_state._replace(accum=zero_accum,
                                               micro_step=jnp.int32(0))
                metrics = {"overflow": jnp.asarray(False),
                           "grad_norm": jnp.float32(0.0),
                           "loss_scale": scale}
                return new_state, metrics

            bad = jnp.float32(0.0)
            for g in jax.tree_util.tree_leaves(accum):
                bad += jnp.sum((~jnp.isfinite(g)).astype(jnp.float32))
            if phase == "sync":
                # local rounds never checked: anything non-finite they
                # accumulated must trip the scaler here
                for a in jax.tree_util.tree_leaves(
                        st.opt_state.local_accum):
                    bad += jnp.sum((~jnp.isfinite(a)).astype(jnp.float32))
            bad = jax.lax.psum(bad, "data")
            overflow = bad > 0

            def do_update(s2):
                master = s2.master if mixed else s2.params
                new_master, new_opt = optimizer.update(
                    accum, s2.opt_state, master, lr=lr, scale=scale,
                    phase=phase, k_round=k)
                if mixed:
                    new_params = jax.tree_util.tree_map(
                        lambda l: l.astype(compute_dtype), new_master)
                    return s2._replace(params=new_params,
                                       master=new_master,
                                       opt_state=new_opt, step=s2.step + 1)
                return s2._replace(params=new_master, opt_state=new_opt,
                                   step=s2.step + 1)

            def skip_update(s2):
                new = s2._replace(skipped_steps=s2.skipped_steps + 1,
                                  step=s2.step + 1)
                if phase == "sync":
                    # the round's accumulation is poisoned — drop it, or
                    # every later sync re-trips on the same non-finite
                    new_opt = s2.opt_state._replace(
                        local_accum=jax.tree_util.tree_map(
                            jnp.zeros_like, s2.opt_state.local_accum))
                    new = new._replace(opt_state=new_opt)
                return new

            new_state = jax.lax.cond(overflow, skip_update, do_update, st)
            if st.scaler is not None:
                new_scaler = update_loss_scale(new_state.scaler, overflow,
                                               **scaler_hp)
                new_state = new_state._replace(scaler=new_scaler)
            zero_accum = jax.tree_util.tree_map(jnp.zeros_like,
                                                new_state.accum)
            new_state = new_state._replace(accum=zero_accum,
                                           micro_step=jnp.int32(0))
            metrics = {"overflow": overflow,
                       "grad_norm": jnp.float32(0.0),
                       "loss_scale": scale}
            return new_state, metrics

        return tail

    def _make_zeroone_fused(self, phase, k):
        """Full train step (gas micro-batches + 0/1 Adam tail) with
        'data' manual.  Local-round programs contain NO cross-device
        collective at all — the loss metric is the device-local mean
        (the next sync round reports the true global loss); warmup/sync
        pmean it as usual."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        gas = self.gradient_accumulation_steps()
        model = self.module
        tail = self._make_zeroone_tail(phase, k)
        state_spec = self._onebit_state_spec()

        def fused(state, stacked_batch, lr):
            batch_spec = jax.tree_util.tree_map(
                lambda x: P(*([None, "data"] + [None] * (x.ndim - 2))),
                stacked_batch)

            def body(st, local_batch, lr):
                scale = st.scaler.loss_scale if st.scaler is not None \
                    else jnp.float32(1.0)

                def micro(carry, b):
                    accum, i = carry
                    rng = jax.random.fold_in(
                        st.rng, i + st.step * 131071)
                    rng = jax.random.fold_in(
                        rng, jax.lax.axis_index("data"))

                    def loss_fn(params):
                        loss, _ = model.loss(params, b, rng, train=True)
                        return loss.astype(jnp.float32) * scale / gas, loss

                    grads, loss = jax.grad(loss_fn, has_aux=True)(st.params)
                    accum = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), accum, grads)
                    return (accum, i + 1), loss

                (accum, _), losses = jax.lax.scan(
                    micro, (st.accum, st.micro_step), local_batch)
                new_state, metrics = tail(st, accum, lr)
                loss = losses.mean()
                if phase != "local":
                    loss = jax.lax.pmean(loss, "data")
                metrics["loss"] = loss
                return new_state, metrics

            metrics_spec = {"overflow": P(), "grad_norm": P(),
                            "loss_scale": P(), "loss": P()}
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(state_spec, batch_spec, P()),
                out_specs=(state_spec, metrics_spec),
                axis_names={"data"}, check_vma=False)(state, stacked_batch,
                                                      lr)

        return fused

    def _make_zeroone_apply(self, phase, k):
        """Optimizer step for the forward/backward/step path: accum
        arrived mesh-averaged from the GSPMD micro steps (identical per
        device), so the update still runs under shard_map for the packed
        collective and the per-device residual state."""
        import jax
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        tail = self._make_zeroone_tail(phase, k)
        state_spec = self._onebit_state_spec()

        def apply_(state, lr):
            metrics_spec = {"overflow": P(), "grad_norm": P(),
                            "loss_scale": P()}
            return jax.shard_map(
                lambda st, lr: tail(st, st.accum, lr), mesh=mesh,
                in_specs=(state_spec, P()),
                out_specs=(state_spec, metrics_spec),
                axis_names={"data"}, check_vma=False)(state, lr)

        return apply_

    def _compile_zeroone(self):
        import jax

        sh = self._shardings
        if self.gradient_clipping():
            # same incompatibility as the 1-bit path: global-norm clipping
            # needs the dense mean gradient the wire exists to avoid
            raise ValueError(
                "gradient_clipping is incompatible with the 0/1 Adam "
                "wire-compression path (sync rounds never materialize a "
                "dense gradient to clip). Disable clipping, or set "
                "optimizer params comm_backend_name='none' to keep the "
                "dense path.")
        self._jit_micro = jax.jit(self._make_micro_fn(), donate_argnums=(0,),
                                  out_shardings=(sh, None))
        # per-(phase, k_round) program caches, built lazily — k doubles on
        # the cadence schedule, so only a handful of programs ever compile
        self._zeroone_fused_jits = {}
        self._zeroone_apply_jits = {}

    def _fused_callable(self):
        if getattr(self, "_zeroone_fused_jits", None) is not None:
            import jax

            phase, k = self._zeroone_phase()
            if (phase, k) not in self._zeroone_fused_jits:
                self._zeroone_fused_jits[(phase, k)] = jax.jit(
                    self._make_zeroone_fused(phase, k), donate_argnums=(0,),
                    out_shardings=(self._shardings, None))
            return self._zeroone_fused_jits[(phase, k)]
        if getattr(self, "_onebit_fused_fns", None):
            import jax

            frozen = self._onebit_frozen()
            if frozen not in self._onebit_fused_jits:
                self._onebit_fused_jits[frozen] = jax.jit(
                    self._onebit_fused_fns[frozen], donate_argnums=(0,),
                    out_shardings=(self._shardings, None))
            return self._onebit_fused_jits[frozen]
        return self._jit_fused

    def _apply_callable(self):
        if getattr(self, "_zeroone_apply_jits", None) is not None:
            import jax

            phase, k = self._zeroone_phase()
            if (phase, k) not in self._zeroone_apply_jits:
                self._zeroone_apply_jits[(phase, k)] = jax.jit(
                    self._make_zeroone_apply(phase, k), donate_argnums=(0,),
                    out_shardings=(self._shardings, None))
            return self._zeroone_apply_jits[(phase, k)]
        if getattr(self, "_onebit_apply_fns", None):
            import jax

            frozen = self._onebit_frozen()
            if frozen not in self._onebit_apply_jits:
                self._onebit_apply_jits[frozen] = jax.jit(
                    self._onebit_apply_fns[frozen], donate_argnums=(0,),
                    out_shardings=(self._shardings, None))
            return self._onebit_apply_jits[frozen]
        return self._jit_apply

    # ------------------------------------------------------------------
    # program-registry contracts (telemetry/programs.py): the HLO claims
    # each compiled variant must keep, read by program_lint's autopilot
    # ------------------------------------------------------------------
    def _micro_program_contract(self):
        """Contract of the per-micro jit: pure device work, donated
        state; under qgZ (stages 1/2) the gradient exchange it carries
        rides the s8 wire within the analytic per-micro budget."""
        contract = {"host_transfer_free": True, "donates_argnums": (0,)}
        if getattr(self, "_qgz_armed", False) \
                and self.zero_optimization_stage() != 3:
            contract.update(
                wire_dtype="s8",
                comm_budget_key="grad_exchange_bytes_per_step",
                # resolved lazily at lint time: the analytic report needs
                # built state, and the per-step figure covers gas micros
                comm_budget_bytes=lambda: (
                    self.comm_volume_report()["grad_exchange_bytes_per_step"]
                    / max(1, self.gradient_accumulation_steps())))
        return contract

    def _optimizer_wire_sync_contract(self):
        """The 0/1 Adam sync-round wire contract: packed u8/s8 payloads
        plus fp32 block scales; total payload within the analytic
        sync-round budget × dp/(dp-1) ring slack (HLO counts gathered
        OUTPUT bytes), scalar overflow/loss syncs (<= 8 elements)
        excluded."""
        dp = self.dp_world_size

        def budget():
            ow = self.comm_volume_report(refresh=True)["optimizer_wire"]
            return ow["sync_round_bytes"] * dp / max(1, dp - 1) + 1

        return {
            "wire_dtype": ("u8", "s8"),
            "comm_budget_key": "optimizer_wire.sync_round_bytes",
            "comm_budget_bytes": budget,
            "comm_small_op_cutoff": 8,
        }

    def _fused_program_spec(self):
        """(program_name, contract) of the fused-train-step variant the
        NEXT dispatch runs — 0/1 Adam and 1-bit Adam compile one program
        per (phase, k)/frozen state, each with its own wire contract.
        The rng key / step scalars pass through a lax.cond unaliased
        (out_shardings suppresses their buffer-donor entries too), hence
        the donation floor."""
        base = {"host_transfer_free": True, "donates_argnums": (0,),
                "donation_min_elements": 4}
        if self._zeroone_wire():
            phase, k = self._zeroone_phase()
            contract = dict(base)
            if phase == "local":
                # skipped round: NO cross-device collective at all —
                # zero wire bytes is what makes the k-round amortization
                # in comm_accounting honest
                contract["collective_free"] = True
            elif phase == "sync":
                contract.update(self._optimizer_wire_sync_contract())
            return f"zeroone_fused:{phase}_k{k}", contract
        if getattr(self, "_onebit_fused_fns", None):
            frozen = self._onebit_frozen()
            contract = dict(base)
            if frozen:
                # post-freeze 1-bit wire: bit-packed signs + fp32 scales
                contract["wire_dtype"] = ("u8", "s8")
            return f"onebit_fused:{'frozen' if frozen else 'warmup'}", \
                contract
        return "fused_train_step", base

    def _apply_program_spec(self):
        """(program_name, contract) of the optimizer-apply variant the
        NEXT dispatch runs (micro-accumulation path).  Donation floor as
        in :meth:`_fused_program_spec` — the rng key rides the cond
        unaliased."""
        base = {"donates_argnums": (0,), "donation_min_elements": 4}
        if self._zeroone_wire():
            phase, k = self._zeroone_phase()
            contract = dict(base)
            if phase == "local":
                contract["collective_free"] = True
            elif phase == "sync":
                contract.update(self._optimizer_wire_sync_contract())
            return f"zeroone_apply:{phase}_k{k}", contract
        if getattr(self, "_onebit_apply_fns", None):
            frozen = self._onebit_frozen()
            contract = dict(base)
            if frozen:
                contract["wire_dtype"] = ("u8", "s8")
            return f"onebit_apply:{'frozen' if frozen else 'warmup'}", \
                contract
        return "apply_step", base

    def _compile(self):
        if self._jit_micro is not None:
            return
        import jax

        if self._zeroone_wire():
            self._compile_zeroone()
            return

        if self._onebit_wire():
            self._compile_onebit()
            return

        sh = self._shardings
        if self._offload:
            # apply runs on host (CPU Adam); the jitted micro step returns
            # this micro-batch's gradients reduce-SCATTERED over 'data'
            # (out_shardings = zero spec) so each process fetches only its
            # own shard; accumulation happens host-side, overlapped with the
            # next micro-batch's device compute
            self._jit_micro = jax.jit(
                self._make_micro_offload_fn(), donate_argnums=(0,),
                out_shardings=(sh, None, self._offload_grad_sh))
            self._jit_param_gather = None  # built on first step
            return
        micro = self._make_micro_fn()
        apply_ = self._make_apply_fn()

        # donate_argnums on the micro step: params/opt_state/master pass
        # through unchanged and alias input buffers, and the fp32
        # accumulator updates in place — without donation every micro-batch
        # copies the full TrainState (transient 2x peak HBM).  The staged
        # forward()/backward() contract still holds (backward commits the
        # staged state); the cost is that a forward whose result is
        # DISCARDED (no backward) consumes the engine state — callers that
        # want a grad-free forward must use engine.eval()/eval_loss, which
        # never touch the train state.
        self._jit_micro = jax.jit(micro, donate_argnums=(0,),
                                  out_shardings=(sh, None))
        self._jit_apply = jax.jit(apply_, donate_argnums=(0,), out_shardings=(sh, None))

        # scheduled stage-3 staged API: the micro step splits into a
        # non-donating forward (returns the vjp stash) and a backward
        # that donates state + stash — gathered weights free at wgrad
        self._jit_s3_fwd = None
        self._jit_s3_bwd = None
        if getattr(self, "_s3_sched_armed", False):
            self._jit_s3_fwd = jax.jit(self._make_stage3_fwd())
            # no out_shardings: the output TrainState inherits the input
            # shardings (accum add is shard-local through the gather's
            # cotangent constraint), and jax 0.4.37 drops the HLO
            # buffer_donor table — the stash-donation contract — when
            # out_shardings is given alongside donate_argnums
            self._jit_s3_bwd = jax.jit(self._make_stage3_bwd(),
                                       donate_argnums=(0, 1))

        gas = self.gradient_accumulation_steps()

        def fused(state, stacked_batch, lr):
            def body(st, b):
                st, loss = micro(st, b)
                return st, loss

            state, losses = jax.lax.scan(body, state, stacked_batch)
            state, metrics = apply_(state, lr)
            metrics["loss"] = losses.mean()
            return state, metrics

        self._jit_fused = jax.jit(fused, donate_argnums=(0,), out_shardings=(sh, None))

    # ------------------------------------------------------------------
    # public training API (reference semantics)
    # ------------------------------------------------------------------
    def flops_profiler_enabled(self):
        return self._config.flops_profiler_config.enabled

    def flops_profiler_profile_step(self):
        return self._config.flops_profiler_config.profile_step

    def _maybe_profile(self, dev_batch):
        """Print the flops profile at profile_step (reference
        engine.py:817-847 triggers the profiler the same way)."""
        cfg = self._config.flops_profiler_config
        if not cfg.enabled or getattr(self, "_profiled", False):
            return
        if self.global_steps + 1 < cfg.profile_step:
            return
        self._profiled = True
        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler

        prof = FlopsProfiler(engine=self)
        prof.profile_params(self.state.params)
        comm_report = self.comm_volume_report()
        prof.profile_comm(comm_report if comm_report["grad_path_modeled"]
                          else None)
        micro = self._make_micro_offload_fn() if self._offload \
            else self._make_micro_fn()
        import jax

        with jax.set_mesh(self.mesh):
            prof.profile_fn(micro, self.state, dev_batch, n_timing_runs=3)
        prof.print_model_profile(profile_step=cfg.profile_step,
                                 module_depth=cfg.module_depth,
                                 top_modules=cfg.top_modules,
                                 detailed=cfg.detailed)

    # ------------------------------------------------------------------
    # analytic comm-volume accounting (runtime/comm_accounting.py)
    # ------------------------------------------------------------------
    def _comm_leaf_specs(self):
        """(LeafSpec list, qwZ-eligibility list) for the current state:
        name, shape and the 'data'-sharded dim of every parameter leaf."""
        import jax

        from deepspeed_tpu.runtime import comm_accounting as ca

        if self._offload:
            sh_tree = self._offload_region_sh
        else:
            sh_tree = self._shardings.accum

        from jax.sharding import NamedSharding

        dims = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            _spec_data_dim, sh_tree,
            is_leaf=lambda x: isinstance(x, NamedSharding)),
            is_leaf=lambda x: x is None)
        names = _leaf_path_names(self.state.params)
        shapes = [tuple(l.shape)
                  for l in jax.tree_util.tree_leaves(self.state.params)]
        leaves = [ca.LeafSpec(name=n, shape=s, shard_dim=dim)
                  for n, s, dim in zip(names, shapes, dims)]
        qwz_ok = [m is not None for m in self._qwz_leaf_meta()] \
            if (self._offload and getattr(self, "_qwz_armed", False)) \
            else [False] * len(leaves)
        return leaves, qwz_ok

    def comm_volume_report(self, refresh=False):
        """Analytic per-step communication volume of the ACTIVE config:
        the exact bytes each device sends, per collective and per optimizer
        step, computed from shapes/dtypes/mesh alone — deterministic on CPU
        (no device or HLO needed), so quantized-collective byte wins are
        assertable in tier-1 tests.

        Covers the ZeRO gradient exchange (dense reduce-scatter/all-reduce
        or the qgZ quantized all_to_alls, x gradient-accumulation steps)
        and the per-step weight materialization: the stage-1/2
        compute-dtype all-gather, the offload push (int8+scales under
        qwZ), and stage 3 — scheduled (one quantized gather per
        partitioned leaf per micro-step) or implicit (dense compute-dtype
        gathers at every use site, counted TWICE per micro for the
        remat'd-backward refetch; the baseline's
        ``implicit_param_gather_bytes_per_step`` prices the same so the
        scheduled path is judged against an honest yardstick).  The 0/1
        Adam wire IS modeled (``optimizer_wire`` section, byte-exact
        against quantization.sign_pack_layout, sync rounds amortized
        over the local-step round).  Not modeled: the CSR-sparse and
        1-bit (OneBitAdam) wire paths (proved by HLO byte tests in
        tests/unit/test_csr.py / test_onebit.py).

        Requires built state — call forward/train_batch/init_from_batch
        first."""
        assert self.state is not None, \
            "call forward/train_batch once (or init_from_batch) before " \
            "comm_volume_report"
        # the 0/1 Adam wire is phase-dependent (dense warmup -> packed
        # sync rounds amortized over k): a cached report from another
        # (phase, k) would misprice the wire, so it invalidates itself
        zeroone_key = self._zeroone_phase() if self._zeroone_wire() else None
        if not refresh and getattr(self, "_comm_report", None) is not None \
                and getattr(self, "_comm_report_zeroone", None) == zeroone_key:
            return self._comm_report
        from deepspeed_tpu.runtime import comm_accounting as ca

        zc = self._config.zero_config
        dp = self.dp_world_size
        stage = self.zero_optimization_stage()
        compute = np.dtype(self.compute_dtype).name
        leaves, qwz_ok = self._comm_leaf_specs()
        qwz_armed = getattr(self, "_qwz_armed", False)

        gas = self.gradient_accumulation_steps()
        s3_sched = getattr(self, "_s3_sched_armed", False)
        if stage == 3 and dp > 1:
            # scheduled: one quantized gather per micro; implicit: XLA
            # gathers per use site — fwd plus the remat'd-bwd refetch
            gathers_per_step = gas if s3_sched else 2 * gas
        else:
            gathers_per_step = 1
        report = ca.volume_report(
            leaves, dp,
            gas=gas,
            quantized_gradients=getattr(self, "_qgz_armed", False),
            quantized_weights=qwz_armed or s3_sched,
            quantized_weights_mask=qwz_ok if qwz_armed else None,
            block_size=zc.quantization_block_size,
            intra_size=getattr(self, "_qgz_intra", 0),
            param_dtype=compute,
            gather_params=dp > 1 and (self._offload
                                      or stage in (1, 2, 3)),
            param_gathers_per_step=gathers_per_step,
            implicit_param_gathers_per_step=(
                2 * gas if stage == 3 and dp > 1 else None))
        report["config"].update({"zero_stage": stage,
                                 "compute_dtype": compute})
        # the accounting models the dense/quantized ZeRO exchange; when the
        # active gradient path is actually CSR-sparse or the 1-bit wire the
        # dense numbers would overstate traffic 10-100x, so the report says
        # so and the per-step metric is withheld (those paths' wins are
        # proved by HLO byte tests instead)
        report["grad_path_modeled"] = not (
            getattr(self, "_csr_dp_flags", None) is not None
            or getattr(self, "_offload_sparse_flags", None) is not None
            or self._onebit_wire())
        if zeroone_key is not None:
            # the 0/1 Adam wire IS modeled (byte-exact against
            # sign_pack_layout): replace the dense grad-exchange pricing
            # with the phase-honest wire figure — dense pmean during
            # warmup, packed sync bytes amortized over the round after
            phase, k_round = zeroone_key
            opt = self.optimizer
            ow = ca.zeroone_volume_report(
                leaves, dp, bits=opt.bits,
                block_size=(opt.quantization_block_size
                            or ca.DEFAULT_BLOCK_SIZE),
                intra_size=opt.intra_size, local_steps_k=k_round, gas=gas)
            ow["phase"] = phase
            report["optimizer_wire"] = ow
            report["grad_path_modeled"] = True
            grad_bytes = ow["warmup_grad_exchange_bytes_per_step"] \
                if phase == "warmup" \
                else ow["amortized_grad_exchange_bytes_per_step"]
            report["grad_exchange_bytes_per_step"] = grad_bytes
            report["total_bytes_per_step"] = \
                grad_bytes + report["param_gather_bytes_per_step"]
            base = report["baseline"]["fp32_grad_exchange_bytes_per_step"]
            report["grad_reduction_vs_fp32"] = \
                base / grad_bytes if grad_bytes else None
        self._comm_report = report
        self._comm_report_zeroone = zeroone_key
        return report

    def _comm_bytes_per_step(self):
        """Cached total for the per-step metrics dict; None when the active
        gradient path is one the accounting does not model (CSR, 1-bit) —
        consumers must not see a dense number for a compressed wire."""
        if self.state is None:
            return None
        report = self.comm_volume_report()
        return report["total_bytes_per_step"] \
            if report["grad_path_modeled"] else None

    def _annotate_comm(self, metrics):
        """Copy a step's metrics dict and attach comm_bytes_per_step (plus
        the dense-vs-quantized parameter-gather split) when the accounting
        models the active wire path."""
        metrics = dict(metrics)
        comm = self._comm_bytes_per_step()
        if comm is not None:
            metrics["comm_bytes_per_step"] = comm
            report = self.comm_volume_report()
            metrics["param_gather_bytes_per_step"] = \
                report["param_gather_bytes_per_step"]
            metrics["param_gather_dense_bytes_per_step"] = \
                report["param_gather_dense_bytes_per_step"]
            metrics["param_gather_quantized_bytes_per_step"] = \
                report["param_gather_quantized_bytes_per_step"]
            ow = report.get("optimizer_wire")
            if ow is not None:
                # the 0/1 Adam wire, amortized over its round; 'phase' is
                # the phase the NEXT step will run (the report prices the
                # steady state around this step, not one micro-history)
                metrics["optimizer_wire_bytes_per_step"] = \
                    metrics["comm_bytes_per_step"] \
                    - report["param_gather_bytes_per_step"]
                metrics["optimizer_wire_sync_round_bytes"] = \
                    ow["sync_round_bytes"]
                metrics["optimizer_wire_k_round"] = \
                    ow["config"]["local_steps_k"]
                metrics["optimizer_wire_phase"] = ow["phase"]
        return metrics

    def train(self, mode=True):
        """torch-parity module mode (reference engine is an nn.Module):
        in eval mode forward() computes the loss WITHOUT gradients —
        inference pays forward cost only, not backward+accum."""
        self._train_mode = bool(mode)
        return self

    def eval(self):
        return self.train(False)

    def forward(self, batch):
        """Compute the micro-batch loss (grads are computed alongside and
        committed by backward(), keeping one-fwd-one-bwd cost parity).
        In eval mode (engine.eval()) this is a grad-free forward.

        The micro step donates the engine state into the staged result, so
        every train-mode forward() MUST be committed by backward() — a
        grad-free/discardable forward is engine.eval() + forward (or
        eval_loss), which never touches the train state."""
        if not self._train_mode:
            return self.eval_loss(batch)
        if self._pending_state is not None \
                or self._pending_s3_stash is not None:
            # fail here with the real story, not deep in XLA with a cryptic
            # "buffer was donated" once the dead state is passed back in
            raise RuntimeError(
                "forward() called twice without backward(): the micro step "
                "donates the engine state into the staged result, so each "
                "train-mode forward must be committed by backward() before "
                "the next one; use engine.eval()/eval_loss for grad-free "
                "forwards")
        if self.state is not None and _tree_has_deleted(self.state,
                                                       first_only=True):
            # a failed donated micro execution invalidated the state with
            # nothing staged (JAX deletes donated inputs at dispatch even
            # when the computation errors) — retrying cannot work; say how
            # to recover instead of surfacing XLA buffer errors
            raise RuntimeError(
                "engine state buffers were donated by a failed micro step; "
                "restore with load_checkpoint(..., auto_resume=True) "
                "before continuing")
        if self.wall_clock_breakdown():
            self.timers(FORWARD_MICRO_TIMER).start()
        if self.progressive_layer_drop is not None:
            # theta rides the batch as a traced scalar (reference injects it
            # as module kwargs, engine.py:823-824)
            batch = dict(batch)
            batch["pld_theta"] = np.float32(
                self.progressive_layer_drop.get_theta())
        self._ensure_state(batch)
        self._compile()
        dev_batch = self._shard_batch(batch)
        self._maybe_profile(dev_batch)
        import jax

        gas = self.gradient_accumulation_steps()
        self._note_mfu_workload(dev_batch, micros_in_batch=gas)
        tr = self._tracer
        _t0 = tr.begin() if tr is not None else 0.0
        with jax.set_mesh(self.mesh):
            if getattr(self, "_jit_s3_fwd", None) is not None:
                # scheduled stage-3: the forward does NOT donate the state
                # — it stays alive; what stages is the vjp stash, whose
                # residuals hold the once-gathered weights for backward
                n_gathered = getattr(
                    getattr(self, "_s3_plan", None), "n_gathered_leaves",
                    None)
                self._register_mfu_jit(
                    "s3_fwd", self._jit_s3_fwd, (self.state, dev_batch),
                    gas, mem_label="stage-3 staged forward: gathered "
                    "weights + vjp residuals (fwd->bwd stash) — the "
                    "footprint stage3_prefetch_budget bounds",
                    contract={
                        # the staged forward gathers each partitioned
                        # leaf EXACTLY once, on the s8 wire (fp32 gathers
                        # are the tiny per-block scales, < 64 elements in
                        # the plan's block geometry)
                        "host_transfer_free": True,
                        "wire_dtype": "s8",
                        "wire_min_elements": 64,
                        "expect_op_counts":
                            [("all-gather", "s8", n_gathered)]
                            if n_gathered else None,
                    })
                loss, self._pending_s3_stash = \
                    self._jit_s3_fwd(self.state, dev_batch)
                self._pending_loss = loss
                if tr is not None:
                    tr.complete("forward_micro", self._lane_train, _t0)
                if self.wall_clock_breakdown():
                    self.timers(FORWARD_MICRO_TIMER).stop()
                return loss
            self._register_mfu_jit(
                "micro_step", self._jit_micro, (self.state, dev_batch),
                gas, mem_label="micro step: donated-in-place train state "
                "+ staged loss + activations",
                contract=self._micro_program_contract())
            if self._offload:
                new_state, loss, grads = self._jit_micro(self.state,
                                                         dev_batch)
                self._pending_grads = grads
            else:
                new_state, loss = self._jit_micro(self.state, dev_batch)
        # torch-parity semantics: gradients land when backward() commits the
        # staged state (the donated input buffers now live inside it).
        self._pending_state = new_state
        self._pending_loss = loss
        if tr is not None:
            tr.complete("forward_micro", self._lane_train, _t0)
        if self.wall_clock_breakdown():
            self.timers(FORWARD_MICRO_TIMER).stop()
        return loss

    def __call__(self, batch):
        return self.forward(batch)

    def backward(self, loss=None, allreduce_gradients=True):
        """Commit the gradients of the last forward (reference engine.py:871).

        In the functional engine the grads were already accumulated by
        forward(); backward() validates call order and handles timing.
        """
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_MICRO_TIMER).start()
        tr = self._tracer
        _t0 = tr.begin() if tr is not None else 0.0
        if self._pending_s3_stash is not None:
            # scheduled stage-3: evaluate the stash (gradients land
            # sharded through the gather's cotangent constraint) and
            # donate it — the gathered weights free here, at wgrad
            import jax

            gas = self.gradient_accumulation_steps()
            self._register_mfu_jit(
                "s3_bwd", self._jit_s3_bwd,
                (self.state, self._pending_s3_stash), gas,
                contract={
                    # the backward reuses the stash residuals: ZERO
                    # all-gathers (one would be a remat refetch), and the
                    # stash (argnum 1) is donated — freed at wgrad, not
                    # held to the end of the batch
                    "host_transfer_free": True,
                    "forbid_collectives": ("all-gather",),
                    "donates_argnums": (1,),
                })
            with jax.set_mesh(self.mesh):
                self.state = self._jit_s3_bwd(self.state,
                                              self._pending_s3_stash)
            self._pending_s3_stash = None
            self.micro_steps += 1
            if tr is not None:
                tr.complete("backward_micro", self._lane_train, _t0)
            if self.wall_clock_breakdown():
                self.timers(BACKWARD_MICRO_TIMER).stop()
            return loss
        assert self._pending_state is not None, \
            "backward() called without a preceding forward()"
        self.state = self._pending_state
        self._pending_state = None
        if self._offload:
            # kick off the async D2H of this micro's local grad shards, then
            # consume the PREVIOUS micro's (its copy overlapped this one's
            # compute). Keeping at most one fetch in flight bounds device
            # memory to one grad tree — gas in-flight trees would cost more
            # HBM than the accumulator this path removed.
            _tg = tr.begin() if tr is not None else 0.0
            fetch = self._start_grad_fetch(self._pending_grads)
            self._pending_grads = None
            self._drain_pending_fetches()
            self._pending_fetches.append(fetch)
            if tr is not None:
                # the host-visible half of the offload gradient exchange
                # (device→host shard stream; the collective half is in-jit)
                tr.complete("grad_exchange_d2h", self._lane_train, _tg)
        self.micro_steps += 1
        if tr is not None:
            tr.complete("backward_micro", self._lane_train, _t0)
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_MICRO_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self):
        return self.micro_steps % self.gradient_accumulation_steps() == 0

    def step(self):
        """Optimizer step at accumulation boundaries (reference engine.py:1016)."""
        if self.wall_clock_breakdown():
            self.timers(STEP_MICRO_TIMER).start()
        assert self._pending_state is None \
            and self._pending_s3_stash is None, \
            "step() called between forward() and backward()"
        if self.is_gradient_accumulation_boundary():
            self._chaos_poison_accum()
            self._take_model_step()
        if self.wall_clock_breakdown():
            self.timers(STEP_MICRO_TIMER).stop()

    def _take_model_step_offload(self):
        """Host-driven step, shard-local: each process updates ONLY the
        master/moment regions backing its own ZeRO grad shards (reference
        stage2.py:876-958,1525-1536), then pushes just those slices back —
        the replicated params materialize via one on-device all-gather over
        ICI instead of a full H2D upload per process."""
        import jax

        tr = self._tracer
        _t0 = tr.begin() if tr is not None else 0.0
        lr = self._advance_lr()
        state = self.state
        self._drain_pending_fetches()
        if self._host_grad_accum is None:  # zero micro-batches ran
            self._host_grad_accum = [np.zeros(m.shape, np.float32)
                                     for m in self._host_master_flat]
        regions = self._offload_regions()
        scale = self._host_scaler.cur_scale \
            if self._host_scaler is not None else 1.0
        finite = all(
            np.isfinite(self._host_grad_accum[i][idx]).all()
            for i, idx, _ in regions)
        clip = self.gradient_clipping()
        # norm counts only owned regions: a leaf replicated over 'data'
        # appears on every process and must not be summed N_proc times
        local_sq = sum(
            float((self._host_grad_accum[i][idx].astype(np.float64) ** 2)
                  .sum()) for i, idx, owned in regions if owned) \
            if (clip or finite) else 0.0
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            stats = multihost_utils.process_allgather(
                np.asarray([local_sq, 0.0 if finite else 1.0]))
            total_sq = float(stats[:, 0].sum())
            finite = float(stats[:, 1].sum()) == 0.0
        else:
            total_sq = local_sq

        if finite:
            gnorm = float(np.sqrt(total_sq)) / scale
            clip_factor = min(1.0, clip / (gnorm + 1e-6)) if clip else 1.0
            masters = [self._host_master_flat[i][idx]
                       for i, idx, _ in regions]
            grads = [self._host_grad_accum[i][idx] for i, idx, _ in regions]
            ms = [self._host_opt["m"][i][idx] for i, idx, _ in regions]
            vs = [self._host_opt["v"][i][idx] for i, idx, _ in regions]
            # region lists are VIEWS into the full host arrays: the kernel
            # updates them in place. The temp state dict's step increment is
            # discarded; the persistent counter advances once below.
            # ds_adam_step divides grads by grad_scale: fold unscale + clip
            self.optimizer.step(
                masters, grads, {"step": self._host_opt["step"],
                                 "m": ms, "v": vs},
                lr=lr, grad_scale=scale / clip_factor)
            self._host_opt["step"] += 1
            self._push_local_params()
            self._last_grad_norm = gnorm
        else:
            self._host_skipped += 1
            self._last_grad_norm = 0.0
        for i, idx, _ in regions:
            self._host_grad_accum[i][idx] = 0.0
        new_scale = scale
        if self._host_scaler is not None:
            self._host_scaler.update_scale(not finite)
            new_scale = self._host_scaler.cur_scale
        if not finite:
            log_dist(f"ZeRO-Offload: OVERFLOW, skipping step "
                     f"{self.global_steps + 1}, scale -> {new_scale:g}",
                     ranks=[0])

        import jax.numpy as jnp

        # fresh scalars take the replicated mesh sharding: host-local
        # SingleDeviceSharding scalars cannot be checkpointed multi-process
        put_rep = lambda x: jax.device_put(x, mesh_lib.replicated(self.mesh))
        scaler = self.state.scaler
        if scaler is not None and new_scale != scale:
            scaler = jax.tree_util.tree_map(
                put_rep, make_loss_scale_state(new_scale))
        self.state = self.state._replace(
            micro_step=put_rep(jnp.int32(0)),
            step=self.state.step + 1, scaler=scaler)
        self.global_steps += 1
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        if tr is not None:
            tr.complete("optimizer_step", self._lane_train, _t0,
                        a0=self.global_steps)
            if not finite:
                tr.instant("overflow_skip", self._lane_train,
                           a0=self.global_steps)
        self._last_metrics = self._annotate_comm(
            {"overflow": not finite,
             "grad_norm": getattr(self, "_last_grad_norm", 0.0),
             "loss_scale": scale})
        mon = self._integrity
        if mon is not None and mon.sentinels_armed:
            # sentinels ride the offload step's HOST values: the grad
            # norm was just computed on host for clipping, overflow is
            # the host finite check — the loss is the one scalar fetch,
            # on a path that already streams every gradient through
            # host memory (update_ratio stays None: the host kernel
            # updates masters in place, a before/after norm would add
            # a full extra pass over the master shards)
            observe_loss = None if self._pending_loss is None else \
                float(jax.device_get(self._pending_loss))
            mon.observe_step(self.global_steps, loss=observe_loss,
                             grad_norm=self._last_grad_norm if finite
                             else None,
                             update_ratio=None, overflow=not finite)
        self._observe_step_outcome(loss=self._pending_loss,
                                   overflow=not finite)
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(self.global_steps)

    def _take_model_step(self):
        if self._offload:
            return self._take_model_step_offload()
        lr = self._advance_lr()
        import jax
        import jax.numpy as jnp

        tr = self._tracer
        _t0 = tr.begin() if tr is not None else 0.0
        with jax.set_mesh(self.mesh):
            apply_fn = self._apply_callable()
            apply_name, apply_contract = self._apply_program_spec()
            self._register_mfu_jit("apply_step", apply_fn,
                                   (self.state, jnp.float32(lr)),
                                   program_name=apply_name,
                                   contract=apply_contract)
            new_state, metrics = apply_fn(self.state, jnp.float32(lr))
        self.state = new_state
        self.global_steps += 1
        if tr is not None:
            tr.complete("optimizer_step", self._lane_train, _t0,
                        a0=self.global_steps)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        self._last_metrics = metrics = self._annotate_comm(metrics)
        self._last_grad_norm = metrics["grad_norm"]
        overflow = None
        observe_loss = self._pending_loss
        mon = self._integrity
        if mon is not None:
            # integrity sentinels ride the step's ONE batched fetch; the
            # watchdog downstream gets the HOST loss value, never a
            # second device transfer of what this fetch already paid for
            fetched = jax.device_get((metrics["overflow"],
                                      self._pending_loss,
                                      metrics["grad_norm"],
                                      metrics["update_ratio"]))
            overflow = bool(fetched[0])
            observe_loss = None if fetched[1] is None else float(fetched[1])
            mon.observe_step(
                self.global_steps, loss=observe_loss,
                grad_norm=float(fetched[2]),
                update_ratio=float(fetched[3]), overflow=overflow)
        if self.fp16_enabled():
            # overflow must be visible when it happens (reference
            # fused_optimizer.py logs every skipped step); one small scalar
            # fetch on the already-host-driven non-fused path
            if overflow is None:
                overflow = bool(jax.device_get(metrics["overflow"]))
            if overflow:
                if tr is not None:
                    # loss-scale event: the scaler halves on this skip
                    tr.instant("overflow_skip", self._lane_train,
                               a0=self.global_steps)
                log_dist(
                    f"OVERFLOW! Skipping step {self.global_steps}; "
                    f"reducing loss scale to "
                    f"{float(jax.device_get(new_state.scaler.loss_scale)):g}",
                    ranks=[0])
        elif self._watchdog is not None and overflow is None:
            overflow = bool(jax.device_get(metrics["overflow"]))
        self._observe_step_outcome(loss=observe_loss,
                                   overflow=overflow)
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(self.global_steps)
            self._write_monitor({"lr": lr,
                                 "loss_scale": float(metrics["loss_scale"]),
                                 "grad_norm": float(metrics["grad_norm"])})

    def _advance_lr(self):
        if self.lr_scheduler is not None:
            return float(self.lr_scheduler.step())
        return self._current_lr()

    def train_batch(self, data_iter=None, batch=None):
        """Fused full-batch step: gas micro-batches + optimizer step in ONE jit
        (lax.scan over microbatches).  The fast path used for benchmarks."""
        gas = self.gradient_accumulation_steps()
        if batch is None:
            assert data_iter is not None
            micros = [next(data_iter) for _ in range(gas)]
            batch = _stack_batches(micros)
        if self.progressive_layer_drop is not None:
            batch = dict(batch)
            batch["pld_theta"] = np.full(
                (gas,), self.progressive_layer_drop.get_theta(), np.float32)
        self._ensure_state(_first_micro(batch))
        self._compile()
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.runtime.resilience import chaos as _chaos

        if _chaos.active() is not None:
            # silent-corruption chaos (ISSUE 13): an armed spike_loss
            # plan scales THIS batch host-side — finite anomalous data
            batch = _chaos.maybe_spike_batch(batch, self.global_steps + 1)
        if self._integrity is not None:
            # cache a host reference to the step's first micro for the
            # duplicate-compute sentinel (O(1), no copy, no device work)
            self._integrity.note_micro(_first_micro(batch))
        if self._offload:
            # apply runs on host: micro-loop on device; each micro's grad
            # shards D2H-copy asynchronously while the NEXT micro computes
            # (host-side accumulation of micro i overlaps device compute of
            # micro i+1 — the reference's migration-stream overlap,
            # stage2.py:876-958)
            self._maybe_profile(self._shard_batch(_first_micro(batch)))
            self.tput_timer.start()
            tr = self._tracer
            _t0 = tr.begin() if tr is not None else 0.0
            losses = []
            prev_fetch = None
            with jax.set_mesh(self.mesh):
                for i in range(gas):
                    dev_micro = self._shard_batch(_micro_at(batch, i))
                    self._note_mfu_workload(dev_micro, micros_in_batch=gas)
                    self._register_mfu_jit(
                        "micro_offload", self._jit_micro,
                        (self.state, dev_micro), gas,
                        contract={"host_transfer_free": True,
                                  "donates_argnums": (0,)})
                    self.state, loss, grads = self._jit_micro(self.state,
                                                              dev_micro)
                    fetch = self._start_grad_fetch(grads)
                    losses.append(loss)
                    if prev_fetch is not None:
                        self._consume_grad_fetch(prev_fetch)
                    prev_fetch = fetch
            if prev_fetch is not None:
                self._consume_grad_fetch(prev_fetch)
            self.micro_steps += gas
            self._pending_loss = jnp.mean(jnp.stack(losses))
            if tr is not None:
                tr.complete("train_batch_micros", self._lane_train, _t0,
                            a0=gas)
            self._chaos_poison_accum()
            self._take_model_step_offload()  # reports progress itself
            self.tput_timer.stop()
            # mean over micro-batches, matching the fused path's metric
            return self._pending_loss
        dev = self._shard_stacked_batch(batch)
        self._maybe_profile(self._shard_batch(_first_micro(batch)))
        lr = self._advance_lr()

        self._chaos_poison_accum()
        self.tput_timer.start()
        self._note_mfu_workload(dev)
        tr = self._tracer
        _t0 = tr.begin() if tr is not None else 0.0
        with jax.set_mesh(self.mesh):
            fused_fn = self._fused_callable()
            fused_name, fused_contract = self._fused_program_spec()
            self._register_mfu_jit(
                "fused_train_step", fused_fn,
                (self.state, dev, jnp.float32(lr)),
                mem_label="fused train step: donated-in-place state + "
                "step metrics + per-micro activations",
                program_name=fused_name, contract=fused_contract)
            new_state, metrics = fused_fn(self.state, dev, jnp.float32(lr))
        self.state = new_state
        self.global_steps += 1
        if tr is not None:
            # the fused jit carries micro fwd/bwd, the grad exchange AND
            # the optimizer step in one dispatch — one span per step
            tr.complete("fused_train_step", self._lane_train, _t0,
                        a0=self.global_steps)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        self.micro_steps += gas
        self._last_metrics = metrics = self._annotate_comm(metrics)
        self._last_grad_norm = metrics["grad_norm"]
        self.tput_timer.stop()
        # the fused path never syncs host-side; the per-step scalars are
        # only fetched when a watchdog or the integrity monitor is armed
        # — and then as ONE batched device_get (the integrity sentinels
        # RIDE the existing fetch; no second host sync per step)
        overflow = None
        observe_loss = None
        mon = self._integrity
        if mon is not None:
            fetched = jax.device_get((metrics["overflow"], metrics["loss"],
                                      metrics["grad_norm"],
                                      metrics["update_ratio"]))
            overflow = bool(fetched[0])
            # the watchdog's NaN check downstream gets the HOST value —
            # handing it the device array would force a SECOND per-step
            # transfer of the loss this fetch just paid for
            observe_loss = float(fetched[1])
            mon.observe_step(self.global_steps, loss=observe_loss,
                             grad_norm=float(fetched[2]),
                             update_ratio=float(fetched[3]),
                             overflow=overflow)
        elif self._watchdog is not None:
            overflow = bool(jax.device_get(metrics["overflow"]))
            observe_loss = metrics["loss"]
        self._observe_step_outcome(loss=observe_loss, overflow=overflow)
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(self.global_steps)
        return metrics["loss"]

    def eval_loss(self, batch):
        import jax

        self._ensure_state(batch)
        if self._jit_eval is None:
            model = self.module

            def ev(state, b):
                loss, metrics = model.loss(state.params, b, state.rng, train=False)
                return loss

            self._jit_eval = jax.jit(ev)
        with jax.set_mesh(self.mesh):
            # _live_state: a validation loss mid-accumulation must read the
            # staged (alive) state, not the donated committed one
            dev_b = self._shard_batch(batch)
            self._register_program("eval_loss", self._jit_eval,
                                   (self._live_state, dev_b),
                                   contract={"host_transfer_free": True})
            loss = self._jit_eval(self._live_state, dev_b)
        if self._watchdog is not None:
            # a long validation loop between optimizer steps is progress,
            # not a stalled step
            self._watchdog.heartbeat()
        return loss

    def _shard_stacked_batch(self, batch):
        """Batch with leading (gas, batch...) dims: shard dim1 over data."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh

        def put(x):
            x = np.asarray(x)
            seq = ["seq"] if self.sp_world_size > 1 and x.ndim >= 3 else []
            sh = NamedSharding(mesh, P(*([None, "data"] + seq
                                         + [None] * (x.ndim - 2 - len(seq)))))
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sh, x)
            return jax.device_put(x, sh)

        return jax.tree_util.tree_map(put, batch)

    @property
    def watchdog(self):
        """The TrainingWatchdog (None unless resilience.watchdog.enabled);
        register callbacks via engine.watchdog.add_callback(cb)."""
        return self._watchdog

    def consecutive_skipped_steps(self):
        """Current run of overflow-skipped optimizer steps (resets to 0 on
        any successful step).  Tracked on every host-synced step path, and
        on the fused device path whenever the watchdog is enabled."""
        return self._consecutive_skips

    def _observe_step_outcome(self, loss=None, overflow=None):
        """Shared post-step resilience bookkeeping for every step path:
        maintains the consecutive-skip streak, mirrors recovery progress
        (scale + streak) into _last_metrics, and feeds the watchdog.  On an
        abort verdict an emergency checkpoint is written before the
        WatchdogAlarm propagates."""
        # async checkpoint commit: publish (rename + latest) at the first
        # step boundary after the background seal lands — the commit
        # becomes visible without waiting for the next save/wait call
        if self._pending_commit is not None:
            if self._supervisor is not None:
                # supervised runs hold the commit-failure contract: a
                # failed seal/publish (disk full, kill mid-commit) must
                # not become a step crash the ladder answers with a
                # rollback — the atomic layout guarantees no torn tag
                # became visible, so training continues and the failure
                # is counted (the previous PUBLISHED tag stays the
                # rollback target)
                try:
                    self._finalize_pending_commit(wait=False)
                except Exception as e:  # lint: allow-broad-except —
                    # see contract above; unsupervised runs keep the
                    # raise-at-step-boundary behavior
                    self._supervisor.on_commit_failed(e)
            else:
                self._finalize_pending_commit(wait=False)
        from deepspeed_tpu.runtime.resilience import chaos

        if chaos.active() is not None:
            # silent-corruption chaos (ISSUE 13): armed bit flips land on
            # the just-committed state at the step boundary — AFTER this
            # step's sentinel fetch, so detection starts next step (or at
            # this boundary's vote)
            from deepspeed_tpu.runtime.resilience import \
                integrity as integrity_mod

            integrity_mod.apply_chaos_faults(self)
        if self._integrity is not None and self._supervisor is None:
            # unsupervised escalation: without a TrainingSupervisor there
            # is no rollback ladder, so a confirmed corrupt verdict
            # becomes a watchdog event (abort -> emergency checkpoint,
            # stamped integrity-suspect by the open anomaly window)
            verdict = self._integrity.decide(self, self.global_steps)
            if verdict is not None:
                if self._watchdog is not None:
                    from deepspeed_tpu.runtime.resilience.watchdog import \
                        WatchdogAlarm

                    try:
                        self._watchdog.observe_integrity(self.global_steps,
                                                         verdict)
                    except WatchdogAlarm as alarm:
                        self._emergency_checkpoint(alarm.event)
                        raise
                else:
                    logger.warning(
                        f"integrity: corrupt verdict at step "
                        f"{self.global_steps} with no supervisor and no "
                        f"watchdog armed — nothing will recover this run; "
                        f"verdict: {verdict}")
        if overflow is not None:
            self._consecutive_skips = \
                self._consecutive_skips + 1 if overflow else 0
            # published only when actually observed: the fused train_batch
            # path skips the overflow fetch without a watchdog (stays
            # host-async), and a frozen 0 would read as "no skips ever"
            if isinstance(self._last_metrics, dict):
                metrics = dict(self._last_metrics)
                metrics["consecutive_skips"] = self._consecutive_skips
                self._last_metrics = metrics
        if self._ckpt_metrics is not None and \
                isinstance(self._last_metrics, dict) \
                and "ckpt_commit_ms_foreground" not in self._last_metrics:
            metrics = dict(self._last_metrics)
            metrics.update(self._ckpt_metrics)
            metrics["ckpt_commit_pending"] = \
                int(self._pending_commit is not None)
            self._last_metrics = metrics
        if self._supervisor is not None:
            # supervised-step hook point: restart-count/backoff ladder
            # state rides _last_metrics (and, below, the telemetry step
            # stream) — pure host dict work, nothing on the device path
            self._supervisor.on_engine_step(self)
        if self._telemetry is not None:
            # `mem` lane gauges: HBM in-use/peak watermark per step where
            # the backend reports memory_stats (no-op after one probe on
            # backends that don't — the CPU mesh)
            self._memory_step_gauges()
            # step-aligned telemetry boundary: step_time histogram + one
            # JSONL record of this step's metrics (journal idiom — flush
            # per emit, a crash tears at most the final line)
            self._telemetry.on_step(
                self.global_steps,
                self._last_metrics
                if isinstance(self._last_metrics, dict) else None)
        if self._watchdog is not None:
            from deepspeed_tpu.runtime.resilience.watchdog import \
                WatchdogAlarm

            try:
                self._watchdog.observe_step(self.global_steps, loss=loss,
                                            overflow=bool(overflow))
            except WatchdogAlarm as alarm:
                self._emergency_checkpoint(alarm.event)
                raise
        self._maybe_preempt()

    # ------------------------------------------------------------------
    # numerical integrity (runtime/resilience/integrity.py, ISSUE 13)
    # ------------------------------------------------------------------
    def _arm_integrity(self):
        """Arm the silent-corruption defense when ``resilience.
        integrity.enabled`` asks for it, or warn DISARMED naming every
        blocker.  Armed engines compute the step sentinels (loss, global
        grad norm, update/param-norm ratio) INSIDE the step jits and
        fetch them with the existing one-per-step batched device read —
        no new host syncs; host-stepped paths (ZeRO-Offload, the pipe
        interpreter) feed the loss/grad-norm values they already hold on
        host instead; the cross-replica vote / duplicate-compute
        jits compile lazily on their cadence, never on the step path.
        Disarmed engines hold ``self._integrity = None``: the compiled
        step programs are UNTOUCHED (bit-identical, zero extra compiles
        — tier-1 pin)."""
        self._integrity = None
        res = self._resilience
        if not res.integrity_enabled:
            return
        from deepspeed_tpu.runtime.resilience.integrity import (
            IntegrityConfig, IntegrityMonitor)

        blockers = []
        if self._onebit_wire():
            blockers.append(
                "1-bit Adam wire compression (the shard_map'd update "
                "tail has no per-leaf norm outputs; error-feedback "
                "state is deliberately rank-local, which the vote would "
                "misread as corruption)")
        if blockers:
            log_dist(
                f"numerical-integrity defense DISARMED — "
                f"{'; '.join(blockers)}; silent corruption in this "
                f"configuration is only caught by the NaN/overflow "
                f"watchdog", ranks=[0], level=logging.WARNING)
            return
        cfg = IntegrityConfig.from_resilience(res)
        dp = self.dp_world_size
        vote_armed = True
        vote_gathered = False
        vote_blockers = []
        if dp <= 1:
            vote_blockers.append(
                "dp=1 (a single replica has nobody to disagree with)")
        if not self._integrity_armable:
            vote_blockers.append(
                "PipelineEngine (per-stage params have no cross-stage "
                "'data' replica to vote over; sentinels ride the host "
                "loss/grad-norm the pipe interpreter already fetches)")
        if self._offload:
            vote_blockers.append(
                "cpu_offload=true (the optimizer steps on HOST master "
                "shards and re-pushes device params every step — a "
                "device vote would checksum state the next push "
                "overwrites; sentinels ride the host grad-norm/loss "
                "the streaming path already computes)")
        if vote_blockers:
            vote_armed = False
            log_dist(
                f"integrity cross-replica vote DISARMED — "
                f"{'; '.join(vote_blockers)}; sentinels-only (anomalies "
                f"roll back without a culprit rank)",
                ranks=[0], level=logging.WARNING)
        elif self.zero_optimization_stage() >= 3:
            # stage 3: params are ZeRO-sharded at rest, so the vote
            # all_gather-assembles them inside the cadence jit and each
            # rank folds its OWN assembled copy — asymmetric gather/
            # assembly divergence splits the digest table (the mode a
            # stage-3 forward feeds straight into the matmuls); a shard
            # corrupted at rest assembles identically everywhere and
            # stays the sentinels' case
            vote_gathered = True
        # the dup check replays one micro with REPLICATED params; under
        # stage 3 the param in_specs are 'data'-sharded, so the replayed
        # loss would see shard-shaped weights — gathered mode keeps it off
        dup_armed = vote_armed and not vote_gathered \
            and cfg.dup_check_every_steps > 0
        self._integrity = IntegrityMonitor(
            cfg, dp, sentinels_armed=True, vote_armed=vote_armed,
            dup_armed=dup_armed, vote_gathered=vote_gathered,
            tracer=self._tracer)
        log_dist(
            f"numerical-integrity defense armed: sentinels "
            f"(z>{cfg.z_threshold:g} over a {cfg.window}-step window), "
            f"cross-replica vote="
            f"{('on (gathered)' if vote_gathered else 'on') if vote_armed else 'off'}, "
            f"duplicate-compute check="
            f"{'every %d steps' % cfg.dup_check_every_steps if dup_armed else 'off'}",
            ranks=[0])

    # ------------------------------------------------------------------
    # self-healing supervision (runtime/resilience/supervisor.py, ISSUE 12)
    # ------------------------------------------------------------------
    def _arm_supervisor(self, supervisor):
        """Arm the supervised-step hook points for a TrainingSupervisor,
        or warn DISARMED naming every blocker.  Armed supervision is
        purely host-side observation at step boundaries — the compiled
        device programs are untouched (bit-identical steps, zero extra
        compiles; pinned by tier-1 tests).  Blockers are the things the
        recovery ladder cannot work without: a committed-tag directory
        and the atomic commit discipline (a torn tag is not a rollback
        target).  A missing elasticity config disarms only the
        elastic-restart rung — retry and rollback stay armed — but
        warns, because lost capacity then aborts instead of resharding."""
        self._supervisor = None
        blockers = []
        if not getattr(supervisor, "save_dir", None):
            blockers.append(
                "no save_dir — rollback and elastic restart need a "
                "committed-tag directory")
        if not self._resilience.atomic_checkpoints:
            blockers.append(
                "resilience.atomic_checkpoints is disabled — a torn tag "
                "could become the rollback target")
        if blockers:
            log_dist(
                f"self-healing supervision DISARMED — "
                f"{'; '.join(blockers)}; steps run unsupervised (no "
                f"retry, rollback or elastic restart)",
                ranks=[0], level=logging.WARNING)
            return False
        from deepspeed_tpu.elasticity import elasticity_enabled

        if not elasticity_enabled(self._config._param_dict):
            log_dist(
                "supervisor elastic restart DISARMED — no elasticity "
                "config, so a lost host cannot reshard onto the "
                "survivors (compute_elastic_config has no valid world "
                "set) and lost capacity aborts the run; transient retry "
                "and coordinated rollback stay armed",
                ranks=[0], level=logging.WARNING)
        self._supervisor = supervisor
        log_dist("self-healing supervision armed: heartbeat detection + "
                 "retry/rollback/elastic-restart ladder", ranks=[0])
        return True

    # ------------------------------------------------------------------
    # graceful preemption (topology-elastic restart, ISSUE 7)
    # ------------------------------------------------------------------
    def request_preemption(self):
        """Ask for a graceful shutdown: at the next optimizer-step
        boundary the engine writes a synchronous, atomically committed
        ``preempt_step<N>`` checkpoint (multi-host coordinated via the
        all_agree discipline) and raises
        :class:`~deepspeed_tpu.runtime.resilience.watchdog.GracefulPreemption`.
        Signal-handler safe: only sets a flag."""
        self._preempt_requested = True
        self._preempt_poll_enabled = True

    def install_preemption_handler(self, signals=None):
        """Route SIGTERM (the preemption notice on TPU pods) into
        :meth:`request_preemption`.  Call it on EVERY process of a
        multi-host run — the per-step preemption poll is a collective
        (coordination.any_flag), so a host that never armed it would
        leave peers waiting in the agreement.  Any previously installed
        Python-level handler is CHAINED, not replaced — a process that
        also hosts a serving engine (or any client SIGTERM hook) keeps
        every handler (``signal.signal`` alone is last-wins).  Main
        thread only (a Python signal-handler constraint)."""
        import signal as signal_mod

        from deepspeed_tpu.runtime.resilience.watchdog import \
            chain_signal_handlers

        sigs = chain_signal_handlers(self.request_preemption, signals)
        self._preempt_poll_enabled = True
        log_dist(f"preemption handler installed for "
                 f"{[signal_mod.Signals(s).name for s in sigs]}", ranks=[0])

    def _maybe_preempt(self):
        """Step-boundary preemption poll: OR the local request flag with
        an armed chaos ``preempt_after_steps`` plan, agree across hosts
        (any rank's signal preempts everyone), then save + raise.  The
        collective poll only runs once preemption is armed on this host
        — an idle multi-host run pays nothing."""
        import jax

        from deepspeed_tpu.runtime.resilience import chaos

        want = self._preempt_requested
        if chaos.active() is not None and chaos.consume_preempt_step():
            want = True
        if jax.process_count() > 1:
            if not (self._preempt_poll_enabled or chaos.active() is not None):
                return
            from deepspeed_tpu.runtime.resilience.coordination import \
                any_flag

            want = any_flag(want)
        if not want:
            return
        self._preempt_requested = True  # latch (peer-initiated preempts)
        if self._tracer is not None:
            self._tracer.instant("preempt", self._lane_train,
                                 a0=self.global_steps)
        tag, save_dir = self._preempt_checkpoint()
        from deepspeed_tpu.runtime.resilience.watchdog import \
            GracefulPreemption

        raise GracefulPreemption(
            f"graceful preemption at step {self.global_steps}"
            + (f": committed checkpoint tag {tag!r} under {save_dir}"
               if tag else " (no checkpoint directory known; state NOT "
                          "saved)"),
            tag=tag, save_dir=save_dir)

    def _preempt_checkpoint(self):
        """The forced pre-shutdown save: synchronous (the process is
        about to exit — a background commit thread would die with it),
        atomic, ``latest``-updating (unlike watchdog emergency tags this
        state is HEALTHY, so restarts should resume from it), with the
        exact data position in client_state so the restart neither
        replays nor skips samples.  Returns ``(tag, save_dir)``."""
        from deepspeed_tpu.runtime.resilience import reshard

        # the run's own checkpoint dir FIRST (opposite of the watchdog's
        # emergency preference): the preempt tag holds healthy state and
        # updates `latest`, so it must land where restarts actually look;
        # the emergency dir is only the fallback for never-saved runs
        save_dir = self._last_ckpt_dir \
            or self._resilience.watchdog_emergency_dir
        if not save_dir:
            logger.warning(
                "graceful preemption: no prior save_checkpoint dir and no "
                "resilience.watchdog.emergency_checkpoint_dir configured; "
                "shutting down WITHOUT a checkpoint")
            return None, None
        tag = f"preempt_step{self.global_steps}"
        self.save_checkpoint(
            save_dir, tag=tag,
            client_state={"data_position": reshard.data_position(self)},
            manifest_meta={"preempt": True}, async_commit=False)
        log_dist(f"graceful preemption: committed {tag!r} under "
                 f"{save_dir}", ranks=[0])
        return tag, save_dir

    def _emergency_checkpoint(self, event=None):
        """Final checkpoint before a watchdog abort tears the run down."""
        import jax

        from deepspeed_tpu.runtime.resilience.watchdog import EVENT_STALL

        if self._tracer is not None:
            self._tracer.instant("emergency_checkpoint", self._lane_ckpt,
                                 a0=self.global_steps)

        if event is not None and event.kind == EVENT_STALL \
                and jax.process_count() > 1:
            # stall detection is host-local wall clock: peers may not have
            # fired, and the collective save below would deadlock against
            # their training-step collectives.  Overflow/NaN streaks derive
            # from globally-reduced values, so every host aborts together.
            logger.warning(
                "watchdog abort (stall): skipping emergency checkpoint on a "
                "multi-process run — stall verdicts are host-local and the "
                "collective save would hang peers")
            return
        save_dir = self._resilience.watchdog_emergency_dir \
            or self._last_ckpt_dir
        if not save_dir:
            logger.warning(
                "watchdog abort: skipping emergency checkpoint (no prior "
                "save_checkpoint dir and no resilience.watchdog."
                "emergency_checkpoint_dir configured)")
            return
        try:
            # save_latest=False + the manifest flag: the aborting state may
            # itself be the problem (NaN params on a non-fp16 divergence),
            # so restarts must prefer the last healthy checkpoint — the
            # emergency tag is kept for postmortem and as a last resort.
            # async_commit=False: the process is about to die on the
            # WatchdogAlarm — a background commit thread would die with
            # it, so the final snapshot commits synchronously.
            # data_position in client_state: the postmortem restart must
            # know the exact sample offset, or it replays/skips data
            from deepspeed_tpu.runtime.resilience import reshard

            self.save_checkpoint(save_dir,
                                 tag=f"emergency_step{self.global_steps}",
                                 save_latest=False,
                                 client_state={"data_position":
                                               reshard.data_position(self)},
                                 manifest_meta={"emergency": True},
                                 async_commit=False)
        except Exception as e:
            # best-effort by definition: whatever the save raises, the
            # caller must still see the WatchdogAlarm, not a ckpt error
            logger.error(f"emergency checkpoint failed: "
                         f"{type(e).__name__}: {e}")

    def _chaos_poison_accum(self):
        """Test hook: replace the grad accumulator with NaN when a chaos
        nan_grads plan is armed (no-op in production)."""
        from deepspeed_tpu.runtime.resilience import chaos

        if chaos.active() is None or not chaos.consume_nan_grad_step():
            return
        if self._offload and getattr(self, "_host_grad_accum", None):
            for acc in self._host_grad_accum:
                acc.fill(np.nan)
            return
        import jax
        import jax.numpy as jnp

        poisoned = jax.tree_util.tree_map(
            lambda a: jnp.full_like(a, jnp.nan), self.state.accum)
        self.state = self.state._replace(accum=poisoned)

    def _report_progress(self, step):
        lr = self._current_lr()
        scale = self.loss_scale() if self.fp16_enabled() else 1
        log_dist(f"step={step}, skipped={self.skipped_steps}, lr={lr:g}, "
                 f"scale={scale:g}", ranks=[0])

    def _write_monitor(self, scalars: dict):
        if self.summary_writer is None:
            return
        for tag, v in scalars.items():
            self.summary_writer.add_scalar(f"Train/Samples/{tag}", float(v),
                                           self.global_steps)
        self.summary_writer.flush()

    def _checkpoint_tag_validation(self, tag):
        """Cross-process consistency check on the checkpoint tag
        (reference engine.py:1472-1487: min/max allreduce of the tag hash;
        a rank writing under a different tag corrupts the layout)."""
        mode = getattr(self._config, "checkpoint_tag_validation_mode", "WARN")
        import jax

        if mode == "IGNORE" or jax.process_count() == 1:
            return
        import hashlib

        from jax.experimental import multihost_utils

        digest = int.from_bytes(
            hashlib.sha256(str(tag).encode()).digest()[:4], "big")
        arr = np.asarray([digest], dtype=np.int64)
        gathered = multihost_utils.process_allgather(arr)
        lo, hi = gathered.min(), gathered.max()
        if int(lo) != int(hi):
            msg = (f"checkpoint tag {tag!r} is not consistent across "
                   f"processes (hash min {lo} != max {hi})")
            if mode == "FAIL":
                raise AssertionError(msg)
            logger.warning(msg)

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:1279-1597; layout kept similar)
    # ------------------------------------------------------------------
    def _resolve_ckpt_backend(self, backend):
        """Concrete payload backend for None/'auto' requests: orbax when
        available (sharded write with NO host gather — npz would
        materialize the full TrainState on process 0; a 10B state OOMs
        the host), npz as the tiny/portable fallback."""
        if backend in (None, "auto"):
            try:
                import orbax.checkpoint  # noqa: F401

                return "orbax"
            except ImportError:  # pragma: no cover - orbax is baked in
                return "npz"
        return backend

    def _ckpt_host_snapshot(self, client_state, backend, copy_host=False):
        """Everything the payload writer needs, resident on HOST memory and
        owned by the snapshot (device_get'd / copied), so writing can
        happen on a background thread while training donates and mutates
        the live state.  Device transfers and host-replication collectives
        all happen HERE (the foreground), never in the writer.
        ``copy_host=True`` (async commits) additionally copies mutable
        host-optimizer buffers; the sync path writes before the next step
        can mutate them, so it skips the copy."""
        import jax

        snap = {"backend": backend, "client_state": client_state,
                "num_leaves": len(jax.tree_util.tree_leaves(self.state)),
                "flat": None, "off_leaves": None, "opt_step": None}
        if backend == "npz" and jax.process_index() == 0:
            host_state = jax.device_get(self.state)
            snap["flat"], _ = jax.tree_util.tree_flatten(host_state)
        if self._offload:
            # shard-local stepping means each process's host arrays are
            # only authoritative on its own regions: reassemble full
            # arrays via a device round-trip before rank 0 writes them
            off_leaves = (self._host_master_flat + self._host_opt["m"]
                          + self._host_opt["v"])
            if jax.process_count() > 1:
                off_leaves = self._replicate_host_leaves(off_leaves)
            if copy_host:
                # the host Adam steps these buffers in place; a background
                # writer must see the snapshot-time values
                off_leaves = [np.array(l, copy=True) for l in off_leaves]
            snap["off_leaves"] = off_leaves
            snap["opt_step"] = self._host_opt["step"]
        from deepspeed_tpu.runtime.resilience import reshard

        snap["meta"] = {
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "dp_world_size": self.dp_world_size,
            "backend": backend,
            "lr_scheduler": self.lr_scheduler.state_dict()
            if self.lr_scheduler is not None else None,
            "client_state": client_state,
            "num_leaves": snap["num_leaves"],
            reshard.TOPOLOGY_KEY: reshard.topology_manifest(self),
            reshard.DATA_POSITION_KEY: reshard.data_position(self),
        }
        return snap

    def _write_snapshot_files(self, path, snap):
        """Write one snapshot's payload files into ``path`` — filesystem
        work only (safe on the async commit thread).  Each file write is
        followed by a chaos hook so fault-injection tests can
        kill/corrupt the write at any point."""
        import jax

        from deepspeed_tpu.runtime.checkpoint_utils import leaves_to_npz_dict
        from deepspeed_tpu.runtime.resilience import chaos

        if snap["flat"] is not None:
            fname = os.path.join(path, "model_states.npz")
            self._ckpt_savez(fname, **leaves_to_npz_dict(snap["flat"]))
            chaos.file_written(fname)
        if jax.process_index() == 0:
            if snap["off_leaves"] is not None:
                fname = os.path.join(path, "offload_states.npz")
                self._ckpt_savez(fname,
                                 **leaves_to_npz_dict(snap["off_leaves"]),
                                 opt_step=snap["opt_step"])
                chaos.file_written(fname)
            fname = os.path.join(path, "metadata.pkl")
            with open(fname, "wb") as f:
                pickle.dump(snap["meta"], f)
            chaos.file_written(fname)

    def _write_checkpoint_files(self, path, client_state, backend):
        """Write every payload file of one checkpoint tag into ``path``
        (the temp dir on the atomic path).  Returns the backend used."""
        from deepspeed_tpu.runtime.resilience import chaos

        backend = self._resolve_ckpt_backend(backend)
        if backend == "orbax":
            import orbax.checkpoint as ocp

            ckptr = ocp.StandardCheckpointer()
            ckptr.save(os.path.join(os.path.abspath(path), "orbax_state"),
                       self.state)
            ckptr.wait_until_finished()
            chaos.file_written(os.path.join(path, "orbax_state"))
        self._write_snapshot_files(
            path, self._ckpt_host_snapshot(client_state, backend))
        return backend

    def _ckpt_snapshot_writer(self, client_state, backend):
        """(backend, write_fn) for an ASYNC commit: every device->host
        transfer and mutable-host copy happens NOW on the training
        thread; ``write_fn(path)`` then only touches the filesystem.
        ``backend`` must already be resolved and npz-family (the orbax
        writer gathers from live device state — the arming gate keeps it
        synchronous)."""
        snap = self._ckpt_host_snapshot(client_state, backend,
                                        copy_host=True)
        return backend, lambda path: self._write_snapshot_files(path, snap)

    def _assert_saveable(self):
        assert self.state is not None, \
            "nothing to save; train state not built"
        assert self._pending_state is None \
            and self._pending_s3_stash is None, \
            "save_checkpoint between forward() and backward(): the micro " \
            "step donated the committed state's buffers (or a stage-3 " \
            "stash is in flight) — commit the in-flight micro-batch with " \
            "backward() first"
        if _tree_has_deleted(self.state):
            raise RuntimeError(
                "cannot checkpoint: the train state's buffers were donated "
                "by a failed micro step; restore a previous checkpoint "
                "(load_checkpoint(..., auto_resume=True)) instead of "
                "saving the dead state")

    def _assert_loadable(self):
        assert self.state is not None, \
            "call forward/train_batch once (or init_from_batch) before " \
            "load_checkpoint"

    def _ckpt_savez(self, fname, **arrays):
        """np.savez for checkpoint payloads.  On the atomic path the bytes
        are sha256'd concurrently with the write so the manifest pass does
        not have to re-read and re-hash the file."""
        if self._resilience.atomic_checkpoints:
            from deepspeed_tpu.runtime.resilience.atomic import savez_hashed

            # commit-path helper: callers are the chaos-hooked snapshot
            # writers targeting the atomic temp dir
            savez_hashed(fname, **arrays)  # graftlint: disable=raw-ckpt-write
        else:
            # the sanctioned legacy (resilience.atomic_checkpoints=false)
            # in-place layout — unprotected by design, documented as such
            np.savez(fname, **arrays)  # graftlint: disable=raw-ckpt-write

    def _checkpoint_manifest_meta(self, tag):
        """World/step metadata recorded in the tag manifest (human- and
        tooling-readable without unpickling the payload).  The "backend"
        key is filled in by save_checkpoint once the payload write has
        resolved it.  "topology" + "data_position" make the tag
        topology-elastic: any mesh can read what layout wrote it and
        where the sample stream stood (resilience/reshard.py)."""
        from deepspeed_tpu.runtime.resilience import reshard

        meta = {
            "tag": str(tag),
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "world": {
                "dp": self.dp_world_size,
                "mp": self.mp_world_size,
                "sp": self.sp_world_size,
            },
            reshard.TOPOLOGY_KEY: reshard.topology_manifest(self),
            reshard.DATA_POSITION_KEY: reshard.data_position(self),
        }
        if self._integrity is not None:
            # integrity stamp (ISSUE 13): a tag committed INSIDE an
            # unresolved anomaly window holds bytes that verify but
            # numbers that are suspect — auto-resume and the supervisor's
            # rollback-target selection both fall back past it
            meta["integrity_clean"] = bool(self._integrity.clean())
        return meta

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True, backend=None, manifest_meta=None,
                        async_commit=None):
        """backend: None/'auto' (orbax when multi-process — sharded write
        without gathering, the fix for replicate-on-save OOM), 'npz'
        (single-file), or 'orbax' (sharded; supports world-size-elastic
        restore via orbax's sharding-aware load).  manifest_meta: extra
        keys merged into the tag manifest (atomic path only).

        With resilience.atomic_checkpoints (default on) the tag is written
        into a temp dir with a checksum manifest, fsync'd, atomically
        renamed into place, and only then is the ``latest`` pointer
        updated — a crash at any point leaves the previous checkpoint
        intact and loadable.

        async_commit (None = resilience.async_commit): snapshot the state
        to host HERE, then run the payload write + streaming hash + fsync
        on a background commit thread; only the atomic rename +
        latest-pointer update stay on the training thread (they run at
        the next step boundary once the seal lands, or in wait_pending_
        commit()).  Returns with the tag NOT yet visible; durability
        semantics and back-pressure are documented in
        docs/tutorials/fault_tolerance.md."""
        import time as _time

        import jax

        t0 = _time.perf_counter()
        # back-pressure: at most one commit in flight — a still-running
        # previous commit is finalized (waiting on its seal) first
        self._finalize_pending_commit(wait=True)
        self._assert_saveable()
        client_state = client_state or {}
        if tag is None:
            tag = f"global_step{self.global_steps}"
        self._checkpoint_tag_validation(tag)
        res = self._resilience
        self._last_ckpt_dir = save_dir
        want_async = res.async_commit if async_commit is None \
            else bool(async_commit)
        if want_async:
            want_async = self._arm_async_commit(backend)
        if want_async:
            backend_r = self._resolve_ckpt_backend(backend)
            meta = self._checkpoint_manifest_meta(tag)
            meta.update(manifest_meta or {})
            meta["backend"] = backend_r
            from deepspeed_tpu.runtime.resilience.atomic import (
                FollowerCommit, PendingCommit, atomic_tag)

            backend_r, write_fn = self._ckpt_snapshot_writer(client_state,
                                                             backend_r)
            hb = self._ckpt_commit_heartbeat()
            if jax.process_count() > 1 and jax.process_index() != 0:
                # npz-family backends write payload on process 0 only;
                # peers hold a placeholder so every rank runs the same
                # finalize choreography (all_agree phases) in lockstep
                self._pending_commit = FollowerCommit().start()
            else:
                commit = atomic_tag(save_dir, tag, meta=meta,
                                    update_latest=save_latest,
                                    fsync=res.fsync)
                self._pending_commit = PendingCommit(
                    commit, write_fn, heartbeat=hb).start()
            self._pending_commit_info = {
                "save_dir": save_dir, "tag": str(tag),
                "backend": backend_r,
                # the supervisor's published-tag tracking (ISSUE 13
                # async-cadence satellite): only a PUBLISHED tag is a
                # rollback target, and its integrity stamp was fixed at
                # commit time, not publish time
                "global_steps": int(meta.get("global_steps",
                                             self.global_steps)),
                "integrity_clean": bool(meta.get("integrity_clean", True)),
            }
            self._ckpt_foreground_ms = (_time.perf_counter() - t0) * 1000.0
            self._publish_ckpt_metrics()
            if self._tracer is not None:
                self._tracer.complete("ckpt_async_submit", self._lane_ckpt,
                                      t0, a0=self.global_steps)
            log_dist(f"Async checkpoint commit in flight for tag {tag!r} "
                     f"(snapshot took "
                     f"{self._ckpt_foreground_ms:.1f} ms foreground; "
                     f"write+hash+fsync on the commit thread)", ranks=[0])
            return True

        if not res.atomic_checkpoints:
            # legacy in-place layout (crash window: torn tag, stale latest)
            path = os.path.join(save_dir, str(tag))
            os.makedirs(path, exist_ok=True)
            backend = self._write_checkpoint_files(path, client_state,
                                                   backend)
            if save_latest and jax.process_index() == 0:
                from deepspeed_tpu.runtime.resilience.atomic import \
                    write_latest

                write_latest(save_dir, tag, fsync=False)
            if jax.process_index() == 0 and res.keep_checkpoint_tags > 0:
                from deepspeed_tpu.runtime.resilience.atomic import gc_tags

                gc_tags(save_dir, res.keep_checkpoint_tags,
                        protect={str(tag)})
            log_dist(f"Saved checkpoint {path} (backend={backend}, "
                     f"non-atomic)", ranks=[0])
            if self._watchdog is not None:
                self._watchdog.heartbeat()
            self._ckpt_foreground_ms = (_time.perf_counter() - t0) * 1000.0
            self._publish_ckpt_metrics()
            return True

        from deepspeed_tpu.runtime.resilience.atomic import atomic_tag, \
            gc_tags

        meta = self._checkpoint_manifest_meta(tag)
        meta.update(manifest_meta or {})
        commit = atomic_tag(save_dir, tag, meta=meta,
                            update_latest=save_latest, fsync=res.fsync)
        if jax.process_count() > 1:
            # every process writes its shards into the same temp dir on the
            # shared FS; process 0 commits (manifest + rename) after a
            # barrier so no shard write races the rename.  Every phase
            # follows the coordination.all_agree discipline: swallow the
            # local error, agree on success flags, only then proceed or
            # raise — so no rank can leave peers wedged in a collective.
            from deepspeed_tpu.runtime.resilience.coordination import \
                all_agree

            def _agree(err, phase):
                agreed, n_failed = all_agree(err is None)
                if agreed:
                    return
                if err is not None:
                    raise err
                raise RuntimeError(
                    f"checkpoint {phase} for tag {tag!r} failed on "
                    f"{n_failed} peer process(es); "
                    f"tag aborted, previous checkpoint left intact")

            # process 0 alone creates the temp dir (its __enter__ rmtree's
            # any stale .tmp- from a prior crash); peers wait for the
            # agreement so that cleanup can never delete shards a peer has
            # already started writing
            enter_err = None
            if jax.process_index() == 0:
                try:
                    commit.__enter__()
                except BaseException as e:
                    enter_err = e
            _agree(enter_err, "temp-dir setup")
            write_err = None
            try:
                # peer makedirs sits INSIDE the agreed phase: a rank-local
                # mkdir failure must feed the agreement, not raise past it
                if jax.process_index() != 0:
                    os.makedirs(commit.tmp, exist_ok=True)
                backend = self._write_checkpoint_files(commit.tmp,
                                                       client_state, backend)
            except BaseException as e:
                write_err = e
            try:
                # the agreement doubles as the payload barrier: no shard
                # write can race the commit below
                _agree(write_err, "write")
            except BaseException as e:
                if jax.process_index() == 0:
                    commit.__exit__(type(e), e, e.__traceback__)
                raise
            commit_err = None
            if jax.process_index() == 0:
                try:
                    commit.meta["backend"] = backend
                    commit.__exit__(None, None, None)
                except BaseException as e:
                    commit_err = e
            _agree(commit_err, "commit")
        else:
            with commit as tmp:
                backend = self._write_checkpoint_files(tmp, client_state,
                                                       backend)
                commit.meta["backend"] = backend
        if jax.process_index() == 0 and res.keep_checkpoint_tags > 0:
            gc_tags(save_dir, res.keep_checkpoint_tags, protect={str(tag)})
        log_dist(f"Saved checkpoint {os.path.join(save_dir, str(tag))} "
                 f"(backend={backend}, atomic)", ranks=[0])
        if self._watchdog is not None:
            # a large fsync'd save legitimately takes minutes; don't let
            # the stall detector read it as a hung step
            self._watchdog.heartbeat()
        # a synchronous commit is ALL foreground — the honest comparison
        # number for the async path's rename-only foreground
        self._ckpt_foreground_ms = (_time.perf_counter() - t0) * 1000.0
        self._publish_ckpt_metrics()
        if self._tracer is not None:
            self._tracer.complete("ckpt_sync_commit", self._lane_ckpt, t0,
                                  a0=self.global_steps)
        return True

    def _ckpt_commit_heartbeat(self):
        """Heartbeat callable handed to the background commit thread:
        feeds the TrainingWatchdog (a slow disk is progress, not a
        stall) and — when tracing is armed — drops one instant event per
        fsync'd file on the ``ckpt`` lane, so the commit thread's
        progress renders in the exported trace."""
        wd_beat = self._watchdog.heartbeat if self._watchdog is not None \
            else None
        tr = self._tracer
        if wd_beat is None and tr is None:
            return None
        lane = self._lane_ckpt

        def beat():
            if wd_beat is not None:
                wd_beat()
            if tr is not None:
                tr.instant("ckpt_commit_beat", lane)

        return beat

    def _arm_async_commit(self, backend):
        """True when the async commit path can carry this save; otherwise
        warn DISARMED (naming every blocker) and fall back to the
        synchronous commit."""
        blockers = []
        if not self._resilience.atomic_checkpoints:
            blockers.append(
                "resilience.atomic_checkpoints=false (the legacy in-place "
                "layout has no seal/publish split to defer)")
        if self._resolve_ckpt_backend(backend) == "orbax":
            blockers.append(
                "orbax backend (its sharded writer gathers from live "
                "device state; backend='npz' snapshots to host first)")
        if blockers:
            log_dist(
                f"DeepSpeedEngine: async checkpoint commit DISARMED — "
                f"{'; '.join(blockers)}; committing synchronously",
                ranks=[0], level=logging.WARNING)
            return False
        return True

    def _publish_ckpt_metrics(self):
        """Mirror commit-path health into _last_metrics (satellite of the
        _last_metrics idiom): ckpt_commit_ms_foreground is the training-
        thread time of the last save (snapshot + rename legs for async,
        the whole commit for sync), ckpt_commit_pending flags an
        in-flight background seal."""
        self._ckpt_metrics = {
            "ckpt_commit_ms_foreground":
                round(getattr(self, "_ckpt_foreground_ms", 0.0), 3),
            "ckpt_commit_pending": int(self._pending_commit is not None),
        }
        if isinstance(self._last_metrics, dict):
            metrics = dict(self._last_metrics)
            metrics.update(self._ckpt_metrics)
            self._last_metrics = metrics

    def _finalize_pending_commit(self, wait=True):
        """Foreground leg of an async commit: the atomic rename +
        latest-pointer-last, then retention GC.  With wait=False (the
        per-step opportunistic call) an unfinished seal is left in
        flight.  Returns True when a commit was published.

        Multi-process follows the coordination.all_agree discipline:
        every rank waits for its local seal, all agree on success,
        process 0 alone publishes, and all agree again — a failed write
        on any rank aborts the tag everywhere with the previous
        checkpoint intact."""
        import time as _time

        import jax

        pending = self._pending_commit
        if pending is None:
            return False
        multi = jax.process_count() > 1
        if not wait:
            ready = pending.ready()
            if multi:
                # the publish involves collectives: every rank must take
                # it at the same step, so readiness itself is agreed
                from deepspeed_tpu.runtime.resilience.coordination import \
                    all_agree

                ready, _ = all_agree(ready)
            if not ready:
                return False
        info = self._pending_commit_info
        res = self._resilience
        t0 = _time.perf_counter()
        try:
            if multi:
                from deepspeed_tpu.runtime.resilience.coordination import \
                    all_agree

                pending.wait()
                agreed, n_failed = all_agree(pending.error is None)
                if not agreed:
                    if pending.error is not None:
                        pending.finalize()  # raises the local error
                    raise RuntimeError(
                        f"async checkpoint write for tag "
                        f"{info['tag']!r} failed on {n_failed} peer "
                        f"process(es); tag aborted, previous checkpoint "
                        f"left intact")
                commit_err = None
                try:
                    pending.finalize()  # FollowerCommit no-ops off-leader
                except BaseException as e:
                    commit_err = e
                agreed, n_failed = all_agree(commit_err is None)
                if commit_err is not None:
                    raise commit_err
                if not agreed:
                    raise RuntimeError(
                        f"async checkpoint publish for tag "
                        f"{info['tag']!r} failed on {n_failed} peer "
                        f"process(es)")
            else:
                pending.finalize()
        finally:
            self._pending_commit = None
            self._pending_commit_info = None
            self._ckpt_foreground_ms = \
                getattr(self, "_ckpt_foreground_ms", 0.0) \
                + (_time.perf_counter() - t0) * 1000.0
            self._publish_ckpt_metrics()
            if self._tracer is not None:
                self._tracer.complete("ckpt_publish", self._lane_ckpt, t0)
        from deepspeed_tpu.runtime.resilience import chaos
        from deepspeed_tpu.runtime.resilience.atomic import gc_tags

        # kill window between rename and GC: the tag is already durable
        # and visible — chaos proves auto-resume lands on it
        chaos.point("before_gc")
        if jax.process_index() == 0 and res.keep_checkpoint_tags > 0:
            gc_tags(info["save_dir"], res.keep_checkpoint_tags,
                    protect={info["tag"]})
        if self._watchdog is not None:
            self._watchdog.heartbeat()
        if self._supervisor is not None:
            # published-tag notification (ISSUE 13 async-cadence
            # satellite): the supervisor tracks only PUBLISHED tags as
            # rollback targets — a sealed-but-unpublished commit is not
            # durable-visible and must never be a recovery destination
            self._supervisor.on_commit_published(dict(info))
        log_dist(f"Committed async checkpoint "
                 f"{os.path.join(info['save_dir'], info['tag'])} "
                 f"(backend={info['backend']}, atomic)", ranks=[0])
        return True

    def wait_pending_commit(self):
        """Block until any in-flight async checkpoint commit is fully
        published (rename + latest + GC); True if one was.  Re-raises a
        failed background write (previous checkpoint left intact)."""
        return self._finalize_pending_commit(wait=True)

    def pending_commit(self):
        """True while an async checkpoint commit is still in flight."""
        return self._pending_commit is not None

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True, auto_resume=None,
                        elastic=None):
        """Restore from ``load_dir``.

        tag=None loads the ``latest``-pointed tag.  With
        ``auto_resume=True`` (or resilience.auto_resume in ds_config) and
        ``tag=None``, the directory is scanned newest-first and
        corrupt/partial tags — failed manifest verification OR a
        load-time error — are skipped transparently until the newest
        intact checkpoint loads; returns (None, {}) when nothing intact
        exists.  An explicitly named tag is never second-guessed: it
        loads, or raises CheckpointCorrupt (never loads bad bytes
        silently, never substitutes a different tag).

        ``elastic=True`` makes a cross-topology restore explicit: the
        checkpoint's topology manifest is diffed against the live mesh
        (resilience/reshard.py), resharding actions are logged, schedule
        features the new topology drops DISARM-warn, the elastic batch
        config is verified against compute_elastic_config, and the
        returned client_state gains the reshard report + the exact data
        position (``data_position`` / ``micro_batches_to_skip``) so the
        sample stream resumes without replay.  Auto-resume is always
        elastic — a restart is exactly when the mesh may have changed."""
        from deepspeed_tpu.runtime.resilience import atomic as atomic_lib
        from deepspeed_tpu.runtime.resilience.atomic import CheckpointCorrupt

        # an in-flight async commit must land (or fail) before its tag can
        # be a resume candidate — and before a restore invalidates the
        # snapshot's meaning
        self._finalize_pending_commit(wait=True)
        res = self._resilience
        # a resumed run that aborts before its first save still has a
        # checkpoint home: the watchdog's emergency fallback dir
        self._last_ckpt_dir = self._last_ckpt_dir or load_dir
        if tag is not None:
            # an explicitly named tag is never second-guessed: it loads or
            # it raises; the newest-first scan is for tag=None only
            auto_resume = False
        elif auto_resume is None:
            auto_resume = res.auto_resume
        if auto_resume:
            # a restart is exactly when the topology may have changed;
            # elastic=False opts out explicitly
            return self._auto_resume_load(load_dir, load_module_strict,
                                          load_optimizer_states,
                                          load_lr_scheduler_states,
                                          elastic=elastic is not False)

        if tag is None:
            tag = atomic_lib.read_latest(load_dir)
            if tag is None:
                logger.warning(f"No 'latest' file at {load_dir}; nothing loaded")
                return None, {}
        if res.verify_on_load:
            import jax

            # leader-only verify + agreed verdict: N hosts re-hashing the
            # same multi-GB manifest multiplies load I/O by N, and a
            # rank-local verify failure must fail EVERY rank together —
            # one rank raising while peers enter the collective restore
            # would wedge the job (same discipline as save/auto-resume)
            from deepspeed_tpu.runtime.resilience.coordination import \
                all_agree

            if jax.process_index() == 0:
                ok, reason = atomic_lib.verify_tag(os.path.join(load_dir,
                                                                str(tag)))
            else:
                ok, reason = True, "verification failed on process 0"
            ok, _ = all_agree(ok)
            if not ok:
                raise CheckpointCorrupt(
                    f"checkpoint tag {tag!r} under {load_dir} failed "
                    f"verification: {reason}. Pass auto_resume=True to fall "
                    f"back to the newest intact checkpoint.")
        return self._load_checkpoint_tag(load_dir, tag, load_module_strict,
                                         load_optimizer_states,
                                         load_lr_scheduler_states,
                                         elastic=bool(elastic))

    def _auto_resume_load(self, load_dir, load_module_strict,
                          load_optimizer_states, load_lr_scheduler_states,
                          elastic=True):
        """Newest-first scan that falls back past corrupt/unloadable tags.

        Multi-process: process 0 alone selects each candidate (so every
        host attempts the SAME tag — per-host selection could send hosts
        into collective restores on different directories, a deadlock)
        and broadcasts it; after each attempt all hosts agree on success
        before returning, falling back together otherwise.  A failed
        attempt rolls the engine back to its pre-attempt state."""
        import jax

        from deepspeed_tpu.runtime.resilience import atomic as atomic_lib

        from deepspeed_tpu.runtime.resilience.coordination import \
            TAG_BCAST_BYTES, all_agree, broadcast_tag

        res = self._resilience
        multi = jax.process_count() > 1
        leader = jax.process_index() == 0
        cands = iter(atomic_lib.resume_candidates(load_dir)) \
            if (leader or not multi) else iter(())
        last_err = None
        while True:
            cand = None
            for c in cands:  # leader-side: next candidate passing verify
                if multi and len(str(c).encode()) > TAG_BCAST_BYTES:
                    logger.warning(f"auto-resume: skipping tag {c!r} "
                                   f"(name exceeds the {TAG_BCAST_BYTES}-"
                                   f"byte broadcast buffer)")
                    continue
                ok, reason = atomic_lib.verify_tag(
                    os.path.join(load_dir, c),
                    check_checksums=res.verify_on_load)
                if ok:
                    cand = c
                    break
                logger.warning(f"auto-resume: skipping tag {c!r} ({reason})")
            if multi:
                cand = broadcast_tag(cand)
            if cand is None:
                break
            # errors that cannot be tag-specific must fail loudly, not be
            # caught below as "corrupt tag" — the blanket catch would
            # reject every intact checkpoint and silently 'start fresh'
            # (state-built status is identical on every rank, so this
            # raises everywhere together)
            self._assert_loadable()
            snap = self._ckpt_state_snapshot()
            # any Exception means "this tag is bad" — the narrow whitelist
            # would let an unforeseen error (orbax XlaRuntimeError, tree
            # mismatch TypeError) escape without the rollback below, and on
            # multi-host without the agreement, wedging peers in the
            # collective (same discipline as the save path)
            err = None
            try:
                result = self._load_checkpoint_tag(
                    load_dir, cand, load_module_strict,
                    load_optimizer_states, load_lr_scheduler_states,
                    elastic=elastic)
            except Exception as e:
                err = e
            ok, _ = all_agree(err is None)
            if ok:
                return result
            # roll back everything _load_checkpoint_tag may have half-set:
            # "starting fresh" must not mean "corrupt params, stale opt"
            self._ckpt_state_restore(snap)
            if err is not None:
                last_err = err
                logger.warning(f"auto-resume: tag {cand!r} failed to load "
                               f"({type(err).__name__}: {err}); falling "
                               f"back to an older checkpoint")
            else:
                last_err = last_err or RuntimeError("peer load failure")
                logger.warning(f"auto-resume: a peer process failed to "
                               f"load tag {cand!r}; falling back together")
        if last_err is not None:
            logger.warning(f"auto-resume: no loadable checkpoint under "
                           f"{load_dir}; starting fresh")
        else:
            logger.warning(f"auto-resume: no checkpoint under "
                           f"{load_dir}; starting fresh")
        return None, {}

    def _ckpt_state_snapshot(self):
        """References/copies of everything _load_checkpoint_tag mutates
        (device state is immutable, so references suffice; host-side
        mutables are copied)."""
        import copy

        return {
            "state": self.state,
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "samples_skipped": self.samples_skipped,
            "onebit_latch": getattr(self, "_onebit_frozen_latch", False),
            "zeroone_latch": getattr(self, "_zeroone_frozen_latch", False),
            "host_master": getattr(self, "_host_master_flat", None),
            "host_opt": dict(self._host_opt)
            if getattr(self, "_host_opt", None) is not None else None,
            "host_skipped": getattr(self, "_host_skipped", None),
            "host_scale": self._host_scaler.cur_scale
            if getattr(self, "_host_scaler", None) is not None else None,
            "lr_sched": copy.deepcopy(self.lr_scheduler.state_dict())
            if self.lr_scheduler is not None else None,
        }

    def _discard_staged_micro(self):
        """Drop any in-flight forward() staging.  A recovery load must not
        leave a stale staged state behind: the next forward() would refuse
        ('called twice without backward') and backward() would commit
        pre-failure buffers over the freshly loaded checkpoint."""
        self._pending_state = None
        self._pending_loss = None
        self._pending_grads = None
        self._pending_s3_stash = None
        if getattr(self, "_pending_fetches", None):
            self._pending_fetches = []

    def _ckpt_state_restore(self, snap):
        # a rollback can land on the same global_steps with different
        # device counters — the host-side sync caches must not serve stale
        self._skipped_cache = None
        self._scale_cache = None
        self._discard_staged_micro()
        self.state = snap["state"]
        self.global_steps = snap["global_steps"]
        self.micro_steps = snap["micro_steps"]
        self.samples_skipped = snap["samples_skipped"]
        self._onebit_frozen_latch = snap["onebit_latch"]
        self._zeroone_frozen_latch = snap.get("zeroone_latch", False)
        if snap["host_master"] is not None:
            self._host_master_flat = snap["host_master"]
        if snap["host_opt"] is not None:
            self._host_opt.clear()
            self._host_opt.update(snap["host_opt"])
        if snap["host_skipped"] is not None:
            self._host_skipped = snap["host_skipped"]
        if snap["host_scale"] is not None:
            self._host_scaler.cur_scale = snap["host_scale"]
        if snap["lr_sched"] is not None and self.lr_scheduler is not None:
            self.lr_scheduler.load_state_dict(snap["lr_sched"])

    def _reset_misshaped_compression_state(self, host_state, ckpt_path):
        """Guard the npz restore against per-device compression state
        written on a different data axis.  The 1-bit/0-1 wire optimizers
        keep error-feedback residuals and a local-round accumulator with
        a leading (axis_size,) dim; a dp-change resume cannot remap old
        per-device error memories onto the new mesh, and device_put-ing
        the old-shaped arrays under the new shardings would silently
        misshape the TrainState (every jit retraces, then fails deep in
        shard_map).  Those leaves reset to zeros with a DISARMED warning
        — residuals are error *memory* and re-accumulate within a few
        rounds; any OTHER shape mismatch still fails loudly."""
        import jax

        _COMP_LEAVES = ("worker_error", "server_error", "local_accum")
        cur_flat = jax.tree_util.tree_flatten_with_path(self.state)[0]
        treedef = jax.tree_util.tree_structure(self.state)
        loaded = jax.tree_util.tree_leaves(host_state)
        out, reset = [], []
        for ((kpath, cur), old) in zip(cur_flat, loaded):
            name = jax.tree_util.keystr(kpath)
            if tuple(np.shape(old)) == tuple(cur.shape):
                out.append(old)
                continue
            if any(c in name for c in _COMP_LEAVES):
                out.append(np.zeros(cur.shape, np.asarray(old).dtype))
                reset.append(f"{name} {np.shape(old)} -> {cur.shape}")
            else:
                raise ValueError(
                    f"checkpoint at {ckpt_path} holds leaf {name} with "
                    f"shape {np.shape(old)} but the current engine "
                    f"expects {tuple(cur.shape)} — saved under a "
                    f"different config; re-save with the current version")
        if reset:
            log_dist(
                f"elastic resume: per-device compression state DISARMED "
                f"for this load — {len(reset)} error-feedback/accumulator "
                f"leaves were written on a different data axis and reset "
                f"to zero (they re-accumulate within a few rounds): "
                f"{'; '.join(reset[:4])}"
                + ("; ..." if len(reset) > 4 else ""),
                ranks=[0], level=logging.WARNING)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _load_checkpoint_tag(self, load_dir, tag, load_module_strict=True,
                             load_optimizer_states=True,
                             load_lr_scheduler_states=True, elastic=False):
        import jax

        # imported here (not in the npz branch) because the offload restore
        # below needs it regardless of which backend saved the model state
        from deepspeed_tpu.runtime.checkpoint_utils import npz_dict_to_leaves

        path = os.path.join(load_dir, str(tag))
        with open(os.path.join(path, "metadata.pkl"), "rb") as f:
            meta = pickle.load(f)
        assert self.state is not None, \
            "call forward/train_batch once (or init_from_batch) before load_checkpoint"
        treedef = jax.tree_util.tree_structure(self.state)
        if meta.get("backend") == "orbax":
            import orbax.checkpoint as ocp

            # sharding-aware restore: orbax repartitions to the CURRENT
            # shardings, so world-size changes (elastic) need no gather
            sh_tree = jax.tree_util.tree_unflatten(
                treedef, jax.tree_util.tree_leaves(self._shardings))
            template = jax.tree_util.tree_map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                  sharding=s),
                self.state, sh_tree)
            ckptr = ocp.StandardCheckpointer()
            self.state = ckptr.restore(
                os.path.join(os.path.abspath(path), "orbax_state"),
                target=template)
        else:
            data = np.load(os.path.join(path, "model_states.npz"))
            flat = npz_dict_to_leaves(data)
            assert len(flat) == meta["num_leaves"]
            cur_leaves = len(jax.tree_util.tree_leaves(self.state))
            if len(flat) != cur_leaves:
                raise ValueError(
                    f"checkpoint at {path} holds {len(flat)} state leaves "
                    f"but this engine's TrainState has {cur_leaves} — the "
                    f"checkpoint was saved by an older engine revision or "
                    f"under a different config (e.g. pre-round-4 offload "
                    f"states carried a device grad accumulator); re-save "
                    f"with the current version")
            host_state = jax.tree_util.tree_unflatten(treedef, flat)
            host_state = self._reset_misshaped_compression_state(host_state,
                                                                 path)
            # re-shard onto the current mesh: elastic by construction — the
            # full arrays repartition to any world size (reference
            # stage1.py:1197-1255)
            sh_flat = jax.tree_util.tree_leaves(self._shardings)
            dev_flat = [jax.device_put(l, s) for l, s in
                        zip(jax.tree_util.tree_leaves(host_state), sh_flat)]
            self.state = jax.tree_util.tree_unflatten(treedef, dev_flat)

        if self._offload:
            off = np.load(os.path.join(path, "offload_states.npz"))
            leaves = npz_dict_to_leaves(off)
            n = len(self._host_master_flat)
            assert len(leaves) == 3 * n
            # np.array(copy=True): loaded npz views can be read-only and
            # the host Adam updates these buffers in place
            self._host_master_flat = [np.array(l, copy=True)
                                      for l in leaves[:n]]
            self._host_opt["m"] = [np.array(l, copy=True)
                                   for l in leaves[n:2 * n]]
            self._host_opt["v"] = [np.array(l, copy=True)
                                   for l in leaves[2 * n:]]
            self._host_opt["step"] = int(off["opt_step"])
            # host-side skip counter: meta holds device + host total; the
            # device part restored with the state leaves above
            device_skips = int(jax.device_get(self.state.skipped_steps))
            self._host_skipped = max(
                0, int(meta.get("skipped_steps", 0)) - device_skips)
            if self._host_scaler is not None and self.state.scaler is not None:
                self._host_scaler.cur_scale = float(
                    jax.device_get(self.state.scaler.loss_scale))

        self.global_steps = meta["global_steps"]
        self.micro_steps = meta["micro_steps"]
        # skipped-data bias (ISSUE 13 rollback-and-skip): restore the
        # stream offset the tag recorded — a resume must fast-forward
        # past both the trained AND the deliberately skipped samples
        from deepspeed_tpu.runtime.resilience import reshard as _reshard

        self.samples_skipped = int(
            (meta.get(_reshard.DATA_POSITION_KEY) or {})
            .get("samples_skipped", 0))
        # the 1-bit freeze phase latches on optimizer steps; a rollback to a
        # pre-freeze tag must re-derive it from the restored counters, not
        # keep serving the compressed program through what is warmup again
        self._onebit_frozen_latch = False
        self._zeroone_frozen_latch = False
        # loaded device counters invalidate the host-side sync caches (the
        # loaded tag may share global_steps with the pre-load state), and
        # any staged micro-batch from before the load is dead weight
        self._skipped_cache = None
        self._scale_cache = None
        self._discard_staged_micro()
        # skipped_steps restores with the device state (a TrainState leaf)
        if load_lr_scheduler_states and self.lr_scheduler is not None \
                and meta.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        log_dist(f"Loaded checkpoint {path} (saved at dp={meta['dp_world_size']}, "
                 f"now dp={self.dp_world_size})", ranks=[0])
        if self._watchdog is not None:
            # mid-run restores can take minutes; not a stalled step
            self._watchdog.heartbeat()
        return path, self._elastic_client_state(meta, elastic)

    def _elastic_client_state(self, meta, elastic):
        """client_state returned by a load, with the elastic reshard
        report + exact data position attached when the load was elastic.
        A non-elastic cross-topology load still works (the payloads are
        topology-independent) but gets one info line pointing at
        elastic=True instead of the full plan."""
        from deepspeed_tpu.runtime.resilience import reshard

        client = dict(meta.get("client_state") or {})
        if elastic:
            report = reshard.elastic_load_report(meta, self)
            client["elastic_reshard"] = report
            client.setdefault(reshard.DATA_POSITION_KEY,
                              meta.get(reshard.DATA_POSITION_KEY))
        else:
            saved = (meta.get(reshard.TOPOLOGY_KEY) or {})
            if saved.get("dp") not in (None, self.dp_world_size):
                log_dist(
                    f"checkpoint was written at dp={saved.get('dp')}, now "
                    f"dp={self.dp_world_size}; pass elastic=True to "
                    f"load_checkpoint for the verified reshard plan + "
                    f"data-position resume", ranks=[0])
        return client

    def init_from_batch(self, batch):
        """Explicitly build train state from a sample batch (e.g. before
        load_checkpoint without training first)."""
        self._ensure_state(batch)
        self._compile()


def _tree_has_deleted(tree, first_only=False):
    """True if (any of / the first of) the pytree's jax arrays has had its
    buffer deleted — the donated-then-failed signature.  ``first_only``
    keeps the per-micro-step check O(1): donation invalidates every donated
    input at dispatch, so one leaf is representative."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        is_deleted = getattr(leaf, "is_deleted", None)
        if callable(is_deleted):
            try:
                if is_deleted():
                    return True
            except Exception:  # pragma: no cover - defensive: liveness
                return True    # probe failing means the buffer is unusable
            if first_only:
                return False
    return False


def _leaf_path_names(tree):
    """'/'-joined pytree path of every leaf, in flatten order — the leaf
    naming shared by the stage-3 gather plan (block grouping) and the
    comm-accounting leaf specs, so the two can never drift."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = []
    for path, _leaf in flat:
        parts = [str(getattr(p, "key", getattr(p, "idx",
                                               getattr(p, "name", p))))
                 for p in path]
        names.append("/".join(parts) or "param")
    return names


def _spec_data_dim(sh):
    """Dim index a NamedSharding's PartitionSpec puts 'data' on (None =
    replicated over the data axis)."""
    for d, axis in enumerate(sh.spec):
        axes = axis if isinstance(axis, tuple) else (axis,)
        if axis is not None and "data" in axes:
            return d
    return None


def _stack_batches(micros):
    return {k: np.stack([np.asarray(m[k]) for m in micros]) for k in micros[0]} \
        if isinstance(micros[0], dict) else np.stack([np.asarray(m) for m in micros])


def _first_micro(batch):
    return _micro_at(batch, 0)


def _micro_at(batch, i):
    if isinstance(batch, dict):
        return {k: v[i] for k, v in batch.items()}
    return batch[i]
