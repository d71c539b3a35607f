"""Drives a training job (``"driver": "train"`` in its traffic file)
through ``deepspeed_tpu.initialize`` -> ``engine.train_batch``.  Knows
nothing of a particular cell: sizes come from the configuration file, the
job from the traffic file."""
import math
import time

import deepspeed_tpu
from deepspeed_tpu.serving import CompilationCounter

from harness import device as device_lib
from harness import traffic as traffic_lib
from harness.profiler import TracedStretch, span

# The engine computes in bf16 (8 significand bits) from f32 master weights;
# the reference computes in f32.  At the initial weights the logits are
# small (|logit| < 4), each is off by a few 2^-9 relative roundings that are
# independent over 4 x 1023 positions, and the loss is a mean over them: the
# two losses agree to about 1e-5 relative (measured on the chip: PERF.md,
# section 6).  The tolerance is 20 times that; int8 or fp8 arithmetic, or a
# term left out, misses it by orders of magnitude.
LOSS_RTOL = 2e-4


def _steps(engine, batches, start_index, *, n=None, deadline=None,
           traced=False, clock=time.perf_counter):
    """Optimizer steps enqueued back to back as a training loop does, each
    loss fetched one step late, so the host is never more than one step
    ahead of the device.  Stops after ``n`` steps or at the first step that
    would begin after ``deadline``; returns when the last step begun has
    finished on the device."""
    losses, dispatch_s, pending, i = [], [], None, 0
    t0 = clock()
    while (n is None or i < n) and (deadline is None or clock() < deadline):
        batch = batches[(start_index + i) % len(batches)]
        t = clock()
        with span("bench:train_batch", traced):
            loss = engine.train_batch(batch=batch)
        dispatch_s.append(clock() - t)
        if pending is not None:
            with span("bench:fetch_loss", traced):
                losses.append(float(pending))
        pending = loss
        i += 1
    if pending is not None:
        with span("bench:fetch_loss", traced):
            pending.block_until_ready()
            losses.append(float(pending))
    return {"steps": i, "seconds": clock() - t0, "losses": losses,
            "dispatch_s": dispatch_s}


def run(cell, devices, *, seed, seconds, trace, process_start, log):
    arch, config, job = cell.architecture(), cell.config, cell.traffic
    chips = len(devices)
    stages = {"imports": time.perf_counter() - process_start}
    model = arch.build_model(config, job["model_overrides"])
    micro, gas = int(job["micro_batch_per_chip"]), \
        int(job["gradient_accumulation"])
    ds_config = dict(job["ds_config"],
                     train_batch_size=micro * gas * chips,
                     train_micro_batch_size_per_gpu=micro,
                     gradient_accumulation_steps=gas,
                     mesh={"data": chips, "model": 1, "pipe": 1,
                           "allow_partial": True},
                     seed=seed, steps_per_print=10 ** 9)
    engine = deepspeed_tpu.initialize(model=model, config_params=ds_config)[0]
    batches, tokens_per_step = traffic_lib.train_batches(
        job, config["vocab_size"], seed, chips)

    stages["engine_and_batches"] = time.perf_counter() - process_start
    # correctness, outside the window: the engine's loss at its initial
    # weights against the plain reference's, on a seeded sample of rows
    rows = batches[0]["input_ids"][0][:int(job["reference_rows"])]
    if len(rows) % chips:
        raise ValueError(f"reference_rows gives {len(rows)} rows, which "
                         f"{chips} chips do not divide")
    engine_loss = float(engine.eval_loss({"input_ids": rows, "labels": rows}))
    weights = arch.reference_weights(engine.state.master, config)
    reference_loss = arch.reference_loss(weights, config, rows)
    del weights
    loss_rel_err = abs(engine_loss - reference_loss) / abs(reference_loss)
    stages["state_and_reference_check"] = time.perf_counter() - process_start

    warm = _steps(engine, batches, 0, n=int(job["warmup_steps"]))
    setup_s = stages["warmup_steps"] = time.perf_counter() - process_start

    with CompilationCounter() as compiles:
        done = warm["steps"]
        reduction = None
        if trace:
            head = _steps(engine, batches, done,
                          deadline=time.perf_counter() + 0.3 * seconds)
            done += head["steps"]
            stretch = TracedStretch(cell.name)
            stretch.start()
            try:
                mid = _steps(engine, batches, done,
                             n=int(job["trace_steps"]), traced=True)
            finally:
                stretch.stop()
            done += mid["steps"]
            tail = _steps(engine, batches, done,
                          deadline=time.perf_counter() + 0.7 * seconds)
            timed = [head, tail]        # the traced steps are not timed
            phases = [head, mid, tail]
        else:
            timed = phases = [_steps(
                engine, batches, done,
                deadline=time.perf_counter() + seconds)]
    if trace:
        reduction = stretch.reduce()
    steps = sum(p["steps"] for p in timed)
    tokens_per_s = tokens_per_step * steps \
        / sum(p["seconds"] for p in timed)

    losses = warm["losses"] + [l for p in phases for l in p["losses"]]
    n_batches = len(batches)
    non_finite = sum(not math.isfinite(l) for l in losses)
    # the batches repeat: the loss of the first batch falls between its
    # first visit (initial weights) and its last
    revisits = losses[::n_batches]
    checks = {
        "loss_matches_reference": loss_rel_err <= LOSS_RTOL,
        "losses_finite": non_finite == 0,
        "loss_fell_on_repeated_batch":
            len(revisits) > 1 and revisits[-1] < revisits[0],
        "no_compile_in_window": compiles.count == 0,
    }
    log({"losses": losses, "engine_loss_initial": engine_loss,
         "reference_loss_initial": reference_loss,
         "loss_rel_err": loss_rel_err, "loss_rtol": LOSS_RTOL,
         "setup_reached_at_s": stages,
         "steps_in_window": sum(p["steps"] for p in phases),
         "checks": checks})

    return {
        "correct": all(checks.values()),
        "attempted": sum(p["steps"] for p in phases),
        "failed": non_finite,
        "compared": {"loss_rel_err": [loss_rel_err, LOSS_RTOL],
                     "losses_not_finite": [non_finite, 0],
                     "compiles_in_window": [compiles.count, 0]},
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "observed": {
            "tokens_per_s": tokens_per_s,
            "flops_per_token": arch.train_flops_per_token(
                config, int(job["seq_len"])),
            "chips": chips,
            # None for a device the table does not hold (the rehearsal's CPU)
            "peaks": device_lib.PEAKS.get(devices[0].device_kind),
            "dispatch_ms": [1e3 * s for p in phases for s in p["dispatch_s"]],
            "compiles_in_window": compiles.count,
            "memory_peak_bytes": device_lib.memory_peak_bytes(devices),
            "trace": reduction,
            "spans": {}, "counters": {},
        },
    }
