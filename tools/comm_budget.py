#!/usr/bin/env python
"""Comm-volume regression guard.

Computes the analytic bytes/step (runtime/comm_accounting.py — pure
shape/mesh math, no devices, deterministic on CPU) for a table of canonical
configurations and compares each against the checked-in budget in
``tools/comm_budgets.json``.  A config whose bytes/step grew more than 10%
over its budget FAILS: someone fattened a ZeRO collective (dropped the
quantization, widened a dtype, added a gather) without re-justifying the
budget.

Run directly, or via tests/unit/test_comm_budget.py so regressions fail the
suite without a separate CI system (same pattern as check_no_bare_except).

  python tools/comm_budget.py            # check against the budget table
  python tools/comm_budget.py --update   # rewrite the budget table

Exit status 0 = within budget, 1 = violations (printed per config).
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from deepspeed_tpu.runtime import comm_accounting as ca  # noqa: E402

BUDGET_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "comm_budgets.json")
GROWTH_TOLERANCE = 0.10

# GPT-2 350M-ish decoder shapes (the benchmark's gpt2-350m): embeddings + 24
# blocks of qkv/proj/mlp + layernorms.  Shapes only — no model is built.
_H, _L, _V, _S = 1024, 24, 50304, 1024
GPT2ISH = (
    [("wte", (_V, _H)), ("wpe", (_S, _H))]
    + [(f"h{i}/{name}", shape) for i in range(_L) for name, shape in [
        ("qkv", (_H, 3 * _H)), ("attn_out", (_H, _H)),
        ("mlp_in", (_H, 4 * _H)), ("mlp_out", (4 * _H, _H)),
        ("ln1", (_H,)), ("ln2", (_H,)),
    ]]
)
MLP16 = [("w1", (16, 16)), ("b1", (16,)), ("w2", (16, 4)), ("b2", (4,))]


def _leaves(shapes, dp):
    return [ca.LeafSpec(name=n, shape=s,
                        shard_dim=ca.zero_shard_dim(s, dp))
            for n, s in shapes]


# pipeline p2p boundary: one micro-batch of activations crossing a stage
# boundary of the gpt2-350m-ish model (micro=1, seq x hidden)
_P2P_ELEMS = _S * _H

CONFIGS = {
    "gpt2-350m-ish/dp8/stage2/dense-bf16": dict(
        shapes=GPT2ISH, dp=8, quantized_gradients=False),
    "gpt2-350m-ish/dp8/stage2/qgz": dict(
        shapes=GPT2ISH, dp=8, quantized_gradients=True),
    "gpt2-350m-ish/dp8/stage2/qgz-hier4": dict(
        shapes=GPT2ISH, dp=8, quantized_gradients=True, intra_size=4),
    "gpt2-350m-ish/dp8/stage2/qgz-qwz": dict(
        shapes=GPT2ISH, dp=8, quantized_gradients=True,
        quantized_weights=True),
    "gpt2-350m-ish/dp256/stage2/qgz-hier8": dict(
        shapes=GPT2ISH, dp=256, quantized_gradients=True, intra_size=8),
    # ZeRO stage-3 parameter gathers (ISSUE 8).  The implicit path lets
    # XLA gather each partitioned weight at every use site — with a
    # remat'd backward that is TWO bf16 gathers per micro-step; the
    # scheduled path gathers ONCE per micro as int8 blocks + fp32
    # scales (~3.9x less gather wire).  Both are budgeted so neither a
    # regression to double-gathering nor a dequantized wire can land
    # silently.
    "gpt2-350m-ish/dp8/stage3/implicit-bf16-remat": dict(
        shapes=GPT2ISH, dp=8, param_gathers=2),
    "gpt2-350m-ish/dp8/stage3/scheduled-int8": dict(
        shapes=GPT2ISH, dp=8, quantized_weights=True, param_gathers=1),
    # 0/1 Adam optimizer wire (runtime/custom_collectives.
    # quantized_all_reduce): synced rounds move packed sign bits + fp32
    # block scales, local rounds move ZERO bytes, and one synced round
    # stands in for local_steps_k optimizer steps — the amortized figure
    # is the budget, and the qgz yardstick key gates the acceptance
    # bound (amortized <= 1/4 of the qgZ int8 wire, test_comm_budget)
    "gpt2-350m-ish/dp8/zeroone-1bit/flat-k2": dict(
        shapes=GPT2ISH, dp=8, zeroone=True, local_steps_k=2),
    "gpt2-350m-ish/dp8/zeroone-1bit/hier4-k2": dict(
        shapes=GPT2ISH, dp=8, zeroone=True, local_steps_k=2, intra_size=4),
    "mlp16/dp8/stage2/dense": dict(shapes=MLP16, dp=8,
                                   quantized_gradients=False),
    "mlp16/dp8/stage2/qgz": dict(shapes=MLP16, dp=8,
                                 quantized_gradients=True),
    # pipeline p2p (send/recv per micro per chunk boundary, bf16
    # activations): interleaved v=2 pays (S*v-1)/(S-1) x the 1f1b volume —
    # the boundary-crossing cost of the ~1/v bubble win, budgeted so it
    # cannot silently grow further
    "gpt2-350m-ish/pipe2/gas8/p2p-1f1b": dict(
        pipe=2, gas=8, boundary_elems=_P2P_ELEMS),
    "gpt2-350m-ish/pipe4/gas8/p2p-1f1b": dict(
        pipe=4, gas=8, boundary_elems=_P2P_ELEMS),
    "gpt2-350m-ish/pipe4/gas8/p2p-interleaved-v2": dict(
        pipe=4, gas=8, boundary_elems=_P2P_ELEMS, virtual_stages=2),
    # serving decode (one continuous-batching token step, batch=8).
    # Batch-axis sharding is collective-FREE by placement (every decode op
    # is slot-uniform; the serving HLO contract pins the compiled program
    # to 0 bytes) — budgeted at 0 so any collective sneaking into the
    # decode path fails here too.  The tensor-parallel alternative pays
    # 2 activation all-reduces per layer + the logits all-reduce per
    # TOKEN; keeping it in the table makes the trade legible.
    "serving/gpt2-350m-ish/decode-b8/batch-sharded-dp8": dict(
        serving=True, batch=8, tp=1),
    "serving/gpt2-350m-ish/decode-b8/tensor-sharded-tp8": dict(
        serving=True, batch=8, tp=8),
}


def compute_volumes():
    """{config name: {total/grad/param/inter bytes per step}}."""
    out = {}
    for name, cfg in CONFIGS.items():
        if cfg.get("serving"):
            colls = ca.serving_decode_collectives(
                _L, _H, _V, cfg["batch"], tp=cfg.get("tp", 1),
                act_dtype=cfg.get("act_dtype", "bfloat16"))
            out[name] = {
                "total_bytes_per_step":
                    sum(c.bytes_per_step for c in colls),
                "decode_allreduce_bytes_per_step":
                    sum(c.bytes_per_step for c in colls
                        if c.op == "all-reduce"),
            }
            continue
        if cfg.get("zeroone"):
            # every leaf rides the wire (params replicated, stage 0):
            # shard_dim is irrelevant to the packed all-reduce
            rep = ca.zeroone_volume_report(
                [ca.LeafSpec(name=n, shape=s, shard_dim=None)
                 for n, s in cfg["shapes"]],
                cfg["dp"], bits=cfg.get("bits", 1),
                block_size=cfg.get("block_size", 128),
                intra_size=cfg.get("intra_size", 0),
                local_steps_k=cfg.get("local_steps_k", 1))
            out[name] = {
                "total_bytes_per_step":
                    rep["amortized_grad_exchange_bytes_per_step"],
                "sync_round_bytes": rep["sync_round_bytes"],
                "local_round_bytes": rep["local_round_bytes"],
                "qgz_int8_wire_bytes_per_step":
                    rep["baseline"]["qgz_int8_wire_bytes_per_step"],
            }
            continue
        if "pipe" in cfg:
            colls = ca.pipe_p2p_collectives(
                cfg["boundary_elems"], cfg["gas"], stages=cfg["pipe"],
                virtual_stages=cfg.get("virtual_stages", 1),
                act_dtype=cfg.get("act_dtype", "bfloat16"))
            out[name] = {
                "total_bytes_per_step":
                    sum(c.bytes_per_step for c in colls),
                "p2p_act_bytes_per_step":
                    sum(c.bytes_per_step for c in colls
                        if c.name.startswith("p2p_act")),
                "p2p_grad_bytes_per_step":
                    sum(c.bytes_per_step for c in colls
                        if c.name.startswith("p2p_grad")),
            }
            continue
        dp = cfg["dp"]
        report = ca.volume_report(
            _leaves(cfg["shapes"], dp), dp,
            gas=cfg.get("gas", 1),
            quantized_gradients=cfg.get("quantized_gradients", False),
            quantized_weights=cfg.get("quantized_weights", False),
            block_size=cfg.get("block_size", 128),
            intra_size=cfg.get("intra_size", 0),
            param_dtype=cfg.get("param_dtype", "bfloat16"),
            param_gathers_per_step=cfg.get("param_gathers", 1))
        out[name] = {
            "total_bytes_per_step": report["total_bytes_per_step"],
            "grad_exchange_bytes_per_step":
                report["grad_exchange_bytes_per_step"],
            "param_gather_bytes_per_step":
                report["param_gather_bytes_per_step"],
            "inter_bytes_per_step": report["inter_bytes_per_step"],
        }
    return out


def check_budgets(volumes, budgets, tolerance=GROWTH_TOLERANCE):
    """Violations as (config, key, actual, budget) tuples.  A config or key
    missing from the budget table is itself a violation — new configs must
    check in a budget, not dodge the guard."""
    violations = []
    for name, vols in volumes.items():
        if name not in budgets:
            violations.append((name, "<missing from budget table>", None,
                               None))
            continue
        for key, actual in vols.items():
            budget = budgets[name].get(key)
            if budget is None:
                violations.append((name, f"{key} <missing>", actual, None))
            elif actual > budget * (1 + tolerance):
                violations.append((name, key, actual, budget))
    return violations


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--update", action="store_true",
                   help="rewrite tools/comm_budgets.json from current code")
    p.add_argument("--budget-file", default=BUDGET_PATH)
    args = p.parse_args(argv)

    volumes = compute_volumes()
    if args.update:
        with open(args.budget_file, "w") as f:
            json.dump(volumes, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.budget_file} ({len(volumes)} configs)")
        return 0

    if not os.path.exists(args.budget_file):
        print(f"FAIL: no budget table at {args.budget_file}; run "
              f"--update and commit it")
        return 1
    with open(args.budget_file) as f:
        budgets = json.load(f)
    violations = check_budgets(volumes, budgets)
    if violations:
        for name, key, actual, budget in violations:
            if budget is None:
                print(f"FAIL {name}: {key}")
            else:
                print(f"FAIL {name}: {key} = {actual} bytes/step exceeds "
                      f"budget {budget} by "
                      f"{100 * (actual / budget - 1):.1f}% "
                      f"(>{100 * GROWTH_TOLERANCE:.0f}% allowed)")
        print(f"{len(violations)} comm-budget violation(s). If the growth "
              f"is intentional, run tools/comm_budget.py --update and "
              f"justify the new budget in the PR.")
        return 1
    for name, vols in sorted(volumes.items()):
        print(f"ok {name}: {vols['total_bytes_per_step']} bytes/step")
    return 0


if __name__ == "__main__":
    sys.exit(main())
