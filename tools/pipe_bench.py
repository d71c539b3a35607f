"""Pipeline execution cost evidence.

Measures, on the virtual 8-device CPU mesh (or real chips when present):

1. step-time table: the SAME tiny GPT-2 trained monolithic (pipe=1) vs
   pipe=2 and pipe=4, fixed global batch and gas — what pipelining costs
   or buys end to end;
2. host dispatch overhead per instruction: the interpreter's per-
   instruction enqueue cost, measured by timing a no-op jitted dispatch
   per stage submesh and counting the schedule's instructions — on real
   TPUs dispatch is async, so this bounds the host-side serialization the
   schedule overlap has to hide;
3. the ANALYTIC bubble fraction of the selected schedule next to the
   measured step time, from runtime/pipe/bubble_accounting's tick
   simulation (both the equal-f/b model behind the classic
   (S-1)/(M+S-1) formula and the default f=1,b=2 model) — so a
   BENCH_NOTES schedule comparison is one command.

Usage:     python tools/pipe_bench.py [--steps 8] [--gas 4] \
               [--schedule 1f1b|interleaved|zb-h1] [--virtual-stages 2]
Prints one JSON line per configuration; paste into BENCH_NOTES.md.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

_CPU_MODE = "--real-tpu" not in sys.argv
if _CPU_MODE:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--gas", type=int, default=4)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--embd", type=int, default=64)
    p.add_argument("--schedule", default="1f1b",
                   choices=["1f1b", "interleaved", "zb-h1"],
                   help="pipeline schedule for the pipe>1 configs")
    p.add_argument("--virtual-stages", type=int, default=1,
                   help="model chunks per stage (interleaved schedule)")
    p.add_argument("--untied-head", action="store_true",
                   help="untie the LM head from the embedding (zb-h1 is "
                        "blocked by tied weights)")
    p.add_argument("--cost-model", default="remat",
                   choices=["remat", "stash"],
                   help="analytic cost model: 'remat' prices each zb split "
                        "pass with its own forward recompute (d=w=1.5); "
                        "'stash' prices the activation-stashing engine "
                        "(d=w=1, forward runs once) and requires the "
                        "schedule to be compiled WITH stash slots — "
                        "implies pipeline.activation_stashing")
    p.add_argument("--stash-budget", type=int, default=0,
                   help="pipeline.stash_budget bytes per stage (0 = "
                        "unbounded); over-budget stages DISARM stashing")
    p.add_argument("--real-tpu", action="store_true")
    args = p.parse_args()
    if args.cost_model == "stash" and args.schedule != "zb-h1":
        p.error("--cost-model stash requires --schedule zb-h1 (only the "
                "zb split backward consumes a stash; fused schedules "
                "already recompute exactly once)")

    if _CPU_MODE:
        jax.config.update("jax_num_cpu_devices", 8)
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
    from deepspeed_tpu.runtime.pipe import bubble_accounting as ba

    n_dev = len(jax.devices())
    cfg = GPT2Config(vocab_size=256, n_positions=args.seq, n_embd=args.embd,
                     n_layer=args.layers, n_head=4, dtype=jnp.float32,
                     loss_chunk_tokens=0)
    gas, micro = args.gas, 1
    rng = np.random.default_rng(0)

    def run(pipe):
        dp = n_dev // pipe
        # keep the GLOBAL batch fixed across configs (micro grows as dp
        # shrinks) so step times compare equal work, as documented
        micro_p = micro * pipe
        global_bs = micro_p * gas * dp
        ds = {"train_batch_size": global_bs,
              "train_micro_batch_size_per_gpu": micro_p,
              "gradient_accumulation_steps": gas,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "mesh": {"pipe": pipe, "data": dp},
              "pipeline": {"schedule": args.schedule if pipe > 1 else "1f1b",
                           "virtual_stages": args.virtual_stages
                           if pipe > 1 else 1,
                           # the flag picks the VARIANT measured+priced:
                           # 'remat' forces the recompute split backward
                           # even though the engine's default is auto-stash
                           "activation_stashing": args.cost_model == "stash",
                           "stash_budget": args.stash_budget},
              "steps_per_print": 10 ** 9}
        model = gpt2_pipeline_module(cfg, partition_method="uniform",
                                     untied_head=args.untied_head) \
            if pipe > 1 else GPT2Model(cfg)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                                   config_params=ds)
        ids = rng.integers(0, 256, (gas, micro_p * dp, args.seq))
        batch = {"input_ids": ids, "labels": ids.copy()}
        loss = engine.train_batch(batch=batch)       # compile
        float(jax.device_get(loss))
        if pipe > 1 and args.cost_model == "stash" \
                and not engine._ensure_compiled_schedule().stash:
            # refuse BEFORE the timed loop: stash accounting against a
            # remat stream would price work the engine is not doing
            print(f"ERROR: --cost-model stash, but the compiled "
                  f"'{engine.pipe_schedule}' schedule carries no stash "
                  f"slots (stashing DISARMED: "
                  f"{'; '.join(engine._stash_blockers) or 'schedule fell back'}); "
                  f"fix the blockers (e.g. --untied-head, a larger "
                  f"--stash-budget) or use --cost-model remat",
                  file=sys.stderr, flush=True)
            sys.exit(2)
        t0 = time.time()
        for _ in range(args.steps):
            loss = engine.train_batch(batch=batch)
        float(jax.device_get(loss))
        step_ms = (time.time() - t0) / args.steps * 1000.0

        out = {"pipe": pipe, "dp": dp, "gas": gas,
               "global_batch": global_bs, "step_ms": round(step_ms, 2)}
        if pipe > 1:
            # schedule shape: EXACT per-stage compiled instruction streams
            # (first/last stages omit recv/send legs, so stage 0 x pipe
            # would overcount); host enqueue cost timed against each
            # stage's actual submesh device
            compiled = engine._ensure_compiled_schedule()
            sim = engine.pipeline_report()
            sim_eq = engine.pipeline_report(
                costs=ba.CostModel.equal_fwd_bwd())
            n_instr = sim["total_instructions"]
            devs = [m.devices.flat[0] for m in engine._submeshes] \
                if hasattr(engine, "_submeshes") else [jax.devices()[0]]
            reps = 200 // len(devs)
            noop = jax.jit(lambda x: x)   # placement follows the input
            noops = []
            for d in devs:
                x = jax.device_put(np.zeros((1,), np.float32), d)
                noop(x)                                   # compile/warm
                noops.append((noop, x))
            t0 = time.time()
            for _ in range(reps):
                for noop, x in noops:
                    noop(x)
            enqueue_us = (time.time() - t0) / (reps * len(devs)) * 1e6
            out.update({
                "schedule": engine.pipe_schedule,
                "virtual_stages": engine.virtual_stages,
                "cost_model": args.cost_model,
                "instructions_per_step": n_instr,
                "enqueue_us_per_dispatch": round(enqueue_us, 1),
                "host_dispatch_ms_per_step":
                    round(n_instr * enqueue_us / 1000.0, 2),
                "analytic_bubble_fraction":
                    round(sim["bubble_fraction"], 3),
                "analytic_bubble_fraction_equal_fb":
                    round(sim_eq["bubble_fraction"], 3),
                "analytic_makespan": round(sim["makespan"], 2),
                "ideal_1f1b_bubble_fraction":
                    round(ba.ideal_1f1b_bubble(gas, pipe), 3),
                "p2p_bytes_per_step":
                    sim["p2p"]["measured_bytes_per_step"],
                "peak_live_buffers": sim["peak_live_buffers"],
            })
            if compiled.stash:
                # the memory bill next to the analytic bubble: what the
                # stashing win costs in held residual bytes per stage
                out.update({
                    "stash_armed": True,
                    "stash_peak_bytes_per_stage":
                        sim["stash"]["peak_bytes_per_stage"],
                    "stash_bytes_per_micro_per_chunk":
                        sim["stash"]["bytes_per_micro_per_chunk"],
                    "peak_live_stash": sim["peak_live_stash"],
                })
            elif engine.pipe_schedule == "zb-h1":
                out["stash_armed"] = False
        print(json.dumps(out), flush=True)
        return step_ms

    base = run(1)
    for pipe in (2, 4):
        ms = run(pipe)
        print(json.dumps({"pipe": pipe, "relative_to_pipe1":
                          round(ms / base, 3)}), flush=True)


if __name__ == "__main__":
    main()
