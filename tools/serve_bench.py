#!/usr/bin/env python
"""Serving benchmark: scheduling policies + adversarial traffic mixes.

Traffic modes (``--traffic``):

- ``steady`` (default) — the PR 5 A/B: the SAME engine under the
  ``continuous`` vs ``static`` scheduler policies over a mixed
  prompt/output-length workload with staggered arrivals.  Gate:
  continuous >= 1.3x tokens per slot-step.
- ``bursty`` — thundering-herd arrivals (bursts of `--burst` requests
  every `--burst-gap` steps) on the continuous engine; reports how far
  p95 TTFT degrades vs steady arrivals of the same workload.
- ``overload`` — 2x-capacity arrivals with per-request deadlines, run
  TWICE: SLO shedding ARMED vs DISARMED (the reliability layer's
  graceful-degradation A/B).  Latencies run on a STEP clock (1.0/step)
  so the comparison is deterministic; the guard mirrors tier-1
  ``test_overload_shedding_guard``: armed p95 TTFT <= 2x SLO and armed
  goodput >= 0.75x a steady-state baseline, while DISARMED shows the
  congestion collapse (TTFT blow-up + wasted decoded tokens).
- ``shared-prefix`` — every prompt shares a long system-prompt prefix
  (ROADMAP item 3's workload), served twice: radix prefix cache
  DISARMED vs ARMED.  Gate: >= 2x fewer prefill tokens computed with
  the cache (the r02 mode's 744 duplicated tokens mostly eliminated).
- ``spec-decode`` — the steady mixed workload served twice: plain
  one-token decode vs self-speculative draft-k/verify-once.  Greedy
  acceptance is bit-honest, so token totals must match; the win is
  fewer decode dispatches (tokens-per-verify > 1).
- ``replica-failure`` — the fleet A/B (``--fleet K`` replicas behind
  the SLO-aware router, ISSUE 11): the SAME traffic twice on a step
  clock, once undisturbed and once with chaos hard-killing 1 of K
  replicas mid-run (``--kill-step``).  The router's circuit breaker
  marks it dead and migrates its journal-live requests onto survivors;
  the guard is that EVERY request still completes (zero lost) and the
  reported p95-TTFT / goodput ratios are the measured price of losing
  1/K of the fleet.
- ``long-context`` — sparse page attention A/B (ISSUE 20): book-length
  prompts (``--lc-len`` tokens in ``--lc-block``-token pool blocks)
  plus chatty shorts, served dense vs under a sliding-window +
  global-anchor SparseContext (``--lc-window-blocks``/``--lc-globals``)
  with window-expired page reclamation and chunked-prefill fairness
  (``--lc-fairness``).  Guards: >= 4x fewer pages gathered per
  dispatched lane, ZERO XLA compilations in the sparse timed region,
  short-request p95 TTFT (step clock) no worse than dense, window
  frees observed.
- ``diurnal`` — the autoscaling A/B (ISSUE 16): a quiet->peak->quiet
  arrival profile served twice on the step clock — once by a STATIC
  fleet provisioned for the peak (``--fleet K`` replicas the whole
  run) and once by an autoscaled fleet that starts at 1 replica, grows
  on queue depth through the peak and drains back down through the
  tail.  The honest efficiency number is goodput per REPLICA-step
  (useful tokens / sum of alive replicas over steps — the bill you pay
  for provisioned capacity, busy or idle); the guard is that the
  autoscaler scales up AND back down, loses zero requests, and beats
  the static-peak fleet on goodput per replica-step.

Two throughput views everywhere:

- ``tokens_per_slot_step`` — generated tokens per dispatched decode
  lane: the deterministic hardware-time proxy (each decode step costs
  one fixed-shape program execution regardless of live lanes).  The
  overload mode further splits it into GOODPUT (finished requests'
  tokens only) — the honest number once work can be shed/expired.
- ``tokens_per_s`` — wall clock, for context (host-dispatch-bound on
  the CPU toy model).

  python tools/serve_bench.py [--traffic MODE] [--json out.json]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _r(x, nd=4):
    """round() that is total over the metrics report's None slots."""
    return None if x is None else round(x, nd)


class StepClock:
    """Deterministic latency clock for the overload A/B: 1.0 per
    serving step, advanced by the driver."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def build_toy(n_embd, n_layer, vocab):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    cfg = GPT2Config(vocab_size=vocab, n_positions=128, n_embd=n_embd,
                     n_layer=n_layer, n_head=max(2, n_embd // 16),
                     dtype=jnp.float32, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, vocab, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    return model, params


def make_workload(n_requests, vocab, seed):
    """Mixed lengths: short interactive answers interleaved with long
    completions — the shape that makes drain-to-slowest expensive."""
    import numpy as np

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        prompt = rng.integers(0, vocab,
                              int(rng.integers(4, 25))).astype(np.int32)
        max_new = int(rng.choice([2, 4, 8, 32], p=[.3, .2, .2, .3]))
        reqs.append((prompt, max_new))
    return reqs


def make_shared_prefix_workload(n_requests, vocab, seed, prefix_len=24):
    """System-prompt traffic: one long shared prefix, short unique
    tails.  Today every request re-prefills the prefix; the reported
    duplicated-prefill tokens are the prefix cache's target."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, prefix_len).astype(np.int32)
    reqs = []
    for i in range(n_requests):
        tail = rng.integers(0, vocab,
                            int(rng.integers(4, 9))).astype(np.int32)
        reqs.append((np.concatenate([prefix, tail]),
                     int(rng.choice([4, 8]))))
    return reqs


def _arrival_schedule(n, *, every=1, burst=1, gap=0):
    """Arrival step for request i: steady (``every``) or bursty
    (``burst`` requests land together every ``gap`` steps)."""
    if burst <= 1:
        return [i * every for i in range(n)]
    return [(i // burst) * gap for i in range(n)]


def run_mode(model, params, workload, *, policy, slots, chunk,
             arrivals, reliability=None, clock=None, step_clock=False,
             deadline=None, block=16, prefix_cache=False,
             speculative=None, sparse_context=None, prefill_fairness=0,
             max_blocks=8, count_compiles=False):
    import jax

    from deepspeed_tpu.serving.engine import InferenceEngine
    from deepspeed_tpu.serving.metrics import CompilationCounter

    kw = {}
    if reliability is not None:
        kw["reliability"] = reliability
    if clock is not None:
        kw["clock"] = clock
    eng = InferenceEngine(model, params, max_slots=slots,
                          kv_block_size=block, prefill_chunk=chunk,
                          max_blocks_per_seq=max_blocks, policy=policy,
                          prefix_cache=prefix_cache,
                          speculative=speculative,
                          sparse_context=sparse_context,
                          prefill_fairness=prefill_fairness, **kw)
    eng.warmup()                       # compiles outside the timed region
    cc = CompilationCounter() if count_compiles else None
    if cc is not None:
        cc.__enter__()
    t0 = time.perf_counter()
    pending = [(arrivals[i], w) for i, w in enumerate(workload)]
    submitted = 0
    steps = 0
    while pending or eng.scheduler.has_work():
        while pending and pending[0][0] <= steps:
            _, (prompt, max_new) = pending.pop(0)
            eng.submit(prompt, max_new_tokens=max_new,
                       deadline_s=deadline)
            submitted += 1
        eng.step()
        if step_clock:
            clock.t += 1.0
        steps += 1
    # one drain point for the whole run, NOT per step
    jax.block_until_ready(eng.pool.tensors.k)
    wall = time.perf_counter() - t0
    if cc is not None:
        cc.__exit__(None, None, None)
    rep = eng.serving_report()
    rel = rep["reliability"]
    sp = rep["sparse_context"]
    return {
        "policy": policy,
        "submitted": submitted,
        "completed": rep["requests"]["completed"],
        "aborted": rep["requests"]["aborted"],
        "shed": rel["aborts"]["shed"],
        "expired": rel["aborts"]["expired"],
        "poisoned": rel["aborts"]["poisoned"],
        "journal_depth": rel["journal_depth"],
        "wall_s": _r(wall),
        "decode_steps": rep["steps"]["decode"],
        "tokens": rep["tokens"]["generated"],
        "tokens_useful": rep["tokens"]["useful"],
        "tokens_wasted": rep["tokens"]["wasted"],
        "tokens_per_s": _r(rep["tokens"]["generated"] / wall, 2),
        "tokens_per_slot_step":
            _r(rep["throughput"]["tokens_per_slot_step"]),
        "goodput_tokens_per_slot_step":
            _r(rep["throughput"]["goodput_tokens_per_slot_step"]),
        "useful_fraction": _r(rep["throughput"]["useful_fraction"]),
        "slot_utilization": _r(rep["throughput"]["slot_utilization"]),
        "ttft_mean": _r(rep["ttft_s"]["mean"]),
        "ttft_p95": _r(rep["ttft_s"]["p95"]),
        "tpot_mean": _r(rep["tpot_s"], 5),
        "predicted_ttft_mean":
            _r(rel["admission"]["predicted_ttft_s"]["mean"]),
        "kv_occupancy_mean": _r(rep["kv_pool"]["occupancy_mean"]),
        # ISSUE 17 cost-per-token accounting: what prefill actually ran
        # (vs what the cache served) and what each verify delivered
        "prefill_tokens_computed":
            rep["prefix_cache"]["prefill_tokens_computed"],
        "prefix_hit_rate": _r(rep["prefix_cache"]["hit_rate"]),
        "prefix_avoided_tokens":
            rep["prefix_cache"]["avoided_prefill_tokens"],
        "tokens_per_verify":
            _r(rep["speculative"]["tokens_per_verify"]),
        "spec_accept_hist": rep["speculative"]["accept_len_hist"],
        # ISSUE 20 long-context accounting: pages the decode/prefill
        # jits actually gathered vs the dense-equivalent full table,
        # what the window reclaimed, and the per-class TTFT split the
        # fairness guard reads
        "active_page_fraction": _r(sp["active_page_fraction"]),
        "gathered_pages_per_lane_step":
            _r(sp["gathered_pages_per_lane_step"], 2),
        "window_expired_frees": sp["window_expired_frees"],
        "short_ttft_p95": _r((sp["ttft_by_class"].get("short") or
                              {}).get("p95")),
        "long_ttft_p95": _r((sp["ttft_by_class"].get("long") or
                             {}).get("p95")),
        "compilations_in_flight": None if cc is None else cc.count,
    }


def _print_row(name, r):
    print(f"{name:>18}: {r['tokens']} tok ({r['tokens_useful']} useful) "
          f"in {r['wall_s']}s | {r['tokens_per_slot_step']} tok/slot-step "
          f"(goodput {r['goodput_tokens_per_slot_step']}) | "
          f"TTFT mean {r['ttft_mean']} p95 {r['ttft_p95']} | "
          f"shed {r['shed']} expired {r['expired']}")


def run_steady(model, params, args, out):
    """PR 5's continuous-vs-static policy A/B (>= 1.3x gate)."""
    workload = make_workload(args.requests, args.vocab, args.seed)
    arrivals = _arrival_schedule(len(workload), every=args.arrival_every)
    out["workload"] = {
        "requests": args.requests, "slots": args.slots,
        "prompt_lens": [len(pr) for pr, _ in workload],
        "max_new": [m for _, m in workload]}
    for policy in ("static", "continuous"):
        out[policy] = run_mode(model, params, workload, policy=policy,
                               slots=args.slots, chunk=args.chunk,
                               arrivals=arrivals)
        _print_row(policy, out[policy])
        assert out[policy]["completed"] == out[policy]["submitted"]
    ratio = out["continuous"]["tokens_per_slot_step"] \
        / out["static"]["tokens_per_slot_step"]
    wall_ratio = out["continuous"]["tokens_per_s"] \
        / out["static"]["tokens_per_s"]
    out["speedup_tokens_per_slot_step"] = round(ratio, 3)
    out["speedup_tokens_per_s_wall"] = round(wall_ratio, 3)
    print(f"continuous / static: {ratio:.2f}x tokens per slot-step "
          f"({wall_ratio:.2f}x wall tokens/s)")
    return 0 if ratio >= 1.3 else 1


def run_bursty(model, params, args, out):
    """Thundering-herd arrivals vs the same workload served steadily."""
    workload = make_workload(args.requests, args.vocab, args.seed)
    steady = run_mode(model, params, workload, policy="continuous",
                      slots=args.slots, chunk=args.chunk,
                      arrivals=_arrival_schedule(len(workload), every=2))
    bursty = run_mode(
        model, params, workload, policy="continuous", slots=args.slots,
        chunk=args.chunk,
        arrivals=_arrival_schedule(len(workload), burst=args.burst,
                                   gap=args.burst_gap))
    out["steady"], out["bursty"] = steady, bursty
    _print_row("steady", steady)
    _print_row("bursty", bursty)
    out["burst_ttft_p95_ratio"] = _r(
        bursty["ttft_p95"] / steady["ttft_p95"], 3) \
        if steady["ttft_p95"] else None
    print(f"bursty / steady p95 TTFT: {out['burst_ttft_p95_ratio']}x "
          f"(bursts of {args.burst} every {args.burst_gap} steps)")
    return 0


def run_overload(model, params, args, out):
    """2x-capacity traffic, shedding ARMED vs DISARMED (+ steady
    baseline) on a step clock — the reliability layer's A/B."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    n = args.requests
    workload = [(rng.integers(0, args.vocab, 6).astype(np.int32), 8)
                for _ in range(n)]
    slo, deadline = args.slo_steps, args.deadline_steps
    # capacity of this shape is admission-bound at ~1 request/step (ONE
    # chunked prefill in flight); 2x = two arrivals per step
    overload_arrivals = [i // args.overload_rate for i in range(n)]

    def drive(tag, slo_ttft, arrivals, deadline_s):
        clock = StepClock()
        rel = {"slo_ttft_s": slo_ttft} if slo_ttft else None
        return run_mode(model, params, workload, policy="continuous",
                        slots=args.slots, chunk=args.chunk,
                        arrivals=arrivals, reliability=rel, clock=clock,
                        step_clock=True, deadline=deadline_s)

    steady = drive("steady", None,
                   _arrival_schedule(n, every=3), None)
    armed = drive("armed", slo, overload_arrivals, deadline)
    disarmed = drive("disarmed", None, overload_arrivals, deadline)
    out.update({"steady": steady, "armed": armed, "disarmed": disarmed,
                "slo_steps": slo, "deadline_steps": deadline,
                "latency_unit": "serving steps (step clock)"})
    _print_row("steady (1x)", steady)
    _print_row("armed (2x)", armed)
    _print_row("DISARMED (2x)", disarmed)

    ok = True
    if not (armed["shed"] > 0):
        print("GUARD FAIL: overload never tripped the admission gate")
        ok = False
    if not (armed["ttft_p95"] <= 2 * slo):
        print(f"GUARD FAIL: armed p95 TTFT {armed['ttft_p95']} "
              f"> 2x SLO {2 * slo}")
        ok = False
    floor = 0.75 * steady["goodput_tokens_per_slot_step"]
    if not (armed["goodput_tokens_per_slot_step"] >= floor):
        print(f"GUARD FAIL: armed goodput "
              f"{armed['goodput_tokens_per_slot_step']} < floor {floor}")
        ok = False
    collapse = (disarmed["ttft_p95"] >= 1.5 * armed["ttft_p95"]
                and disarmed["expired"] > 0
                and disarmed["tokens_wasted"] > 0)
    if not collapse:
        print("GUARD FAIL: DISARMED baseline did not degrade — the "
              "armed win is not demonstrated")
        ok = False
    out["guard_ok"] = ok
    print(f"overload guard: {'OK' if ok else 'FAIL'} — armed p95 "
          f"{armed['ttft_p95']} steps vs DISARMED {disarmed['ttft_p95']}; "
          f"goodput {armed['goodput_tokens_per_slot_step']} vs "
          f"{disarmed['goodput_tokens_per_slot_step']} "
          f"(steady {steady['goodput_tokens_per_slot_step']})")
    return 0 if ok else 1


def run_shared_prefix(model, params, args, out):
    """Prefix-cache A/B on the exact r02 traffic shape: the SAME
    system-prompt workload with the radix cache DISARMED vs ARMED.
    Block size 8 so the 24-token prefix tiles 3 full shareable blocks;
    the gate (>= 2x fewer prefill tokens computed) mirrors tier-1
    ``test_prefix_cache_prefill_ratio_guard``."""
    workload = make_shared_prefix_workload(args.requests, args.vocab,
                                           args.seed)
    common = dict(policy="continuous", slots=args.slots,
                  chunk=args.chunk, block=8,
                  arrivals=_arrival_schedule(len(workload), every=1))
    nocache = run_mode(model, params, workload, **common)
    cached = run_mode(model, params, workload, prefix_cache=True,
                      **common)
    out["no_cache"], out["prefix_cache"] = nocache, cached
    prefix_tokens = 24 * (args.requests - 1)
    out["duplicated_prefill_tokens"] = prefix_tokens
    _print_row("no-cache", nocache)
    _print_row("prefix-cache", cached)
    ratio = (nocache["prefill_tokens_computed"]
             / cached["prefill_tokens_computed"]) \
        if cached["prefill_tokens_computed"] else None
    out["prefill_computed_ratio"] = _r(ratio, 3)
    ok = (ratio is not None and ratio >= 2.0
          and cached["completed"] == cached["submitted"]
          and cached["tokens"] == nocache["tokens"])
    out["guard_ok"] = ok
    print(f"shared-prefix guard: {'OK' if ok else 'FAIL'} — prefill "
          f"tokens computed {nocache['prefill_tokens_computed']} -> "
          f"{cached['prefill_tokens_computed']} ({ratio:.2f}x fewer); "
          f"hit rate {cached['prefix_hit_rate']}, "
          f"{cached['prefix_avoided_tokens']} tokens served from cache "
          f"(vs {prefix_tokens} duplicated prefix tokens priced by r02; "
          f"COW partial-tail sharing can exceed it)")
    return 0 if ok else 1


def run_spec_decode(model, params, args, out):
    """Speculative-decode A/B on the steady mixed workload: the SAME
    continuous-batching engine with plain one-token decode vs the
    draft-``k``/verify-once jit.  Greedy acceptance is bit-honest, so
    generated-token totals must MATCH; the win is fewer decode
    dispatches (each verify step can deliver up to k+1 tokens)."""
    workload = make_workload(args.requests, args.vocab, args.seed)
    common = dict(policy="continuous", slots=args.slots,
                  chunk=args.chunk,
                  arrivals=_arrival_schedule(len(workload),
                                             every=args.arrival_every))
    base = run_mode(model, params, workload, **common)
    spec = run_mode(model, params, workload,
                    speculative=args.draft_len, **common)
    out["baseline"], out["speculative"] = base, spec
    out["draft_len"] = args.draft_len
    _print_row("plain decode", base)
    _print_row(f"spec k={args.draft_len}", spec)
    step_ratio = (base["decode_steps"] / spec["decode_steps"]) \
        if spec["decode_steps"] else None
    out["decode_step_ratio"] = _r(step_ratio, 3)
    ok = (spec["completed"] == spec["submitted"]
          and spec["tokens"] == base["tokens"]
          and spec["tokens_per_verify"] is not None
          and spec["tokens_per_verify"] >= 1.0
          and spec["decode_steps"] <= base["decode_steps"])
    out["guard_ok"] = ok
    print(f"spec-decode guard: {'OK' if ok else 'FAIL'} — "
          f"{base['decode_steps']} -> {spec['decode_steps']} decode "
          f"dispatches ({_fmt_ratio(step_ratio)} fewer) at "
          f"{spec['tokens_per_verify']} tokens/verify, accept-length "
          f"hist {spec['spec_accept_hist']}, token totals "
          f"{'MATCH' if spec['tokens'] == base['tokens'] else 'DIFFER'}")
    return 0 if ok else 1


def _fmt_ratio(x):
    return "-" if x is None else f"{x:.2f}x"


def run_replica_failure(model, params, args, out):
    """Fleet resilience A/B: K replicas, same traffic, with and without
    a mid-run hard kill of replica 1.  Latencies on the step clock."""
    import tempfile
    import time as time_mod

    from deepspeed_tpu.runtime.resilience import chaos
    from deepspeed_tpu.serving.fleet import FleetRouter

    workload = make_workload(args.requests, args.vocab, args.seed)
    # 2 arrivals/step: a K=3 fleet is admission-bound at ~3/step, so
    # the whole fleet carries live work when the kill lands — the
    # failure leg actually exercises migration, not an idle corpse
    arrivals = [i // 2 for i in range(len(workload))]

    def drive(kill_step):
        clock = StepClock()
        jd = tempfile.mkdtemp(prefix="serve_bench_fleet_")
        router = FleetRouter(
            model, params, replicas=args.fleet, clock=clock,
            journal_dir=jd,
            config={"max_consecutive_failures": 2,
                    "retry_backoff_steps": 1},
            engine_kwargs=dict(max_slots=args.slots, kv_block_size=16,
                               prefill_chunk=args.chunk,
                               max_blocks_per_seq=8))
        router.warmup()
        if kill_step:
            chaos.arm(kill_replica_after_steps=kill_step,
                      kill_replica=1)
        t0 = time_mod.perf_counter()
        rids = []
        try:
            pending = [(arrivals[i], w) for i, w in enumerate(workload)]
            steps = 0
            while pending or router.has_work():
                while pending and pending[0][0] <= steps:
                    _, (prompt, max_new) = pending.pop(0)
                    rids.append(router.submit(prompt,
                                              max_new_tokens=max_new))
                router.step()
                clock.t += 1.0
                steps += 1
                assert steps < 5000, "fleet bench did not converge"
        finally:
            chaos.disarm()
        wall = time_mod.perf_counter() - t0
        rep = router.fleet_report()
        res = router.results
        finished = sum(1 for rid in rids
                       if res.get(rid, {}).get("status") == "finished")
        return {
            "submitted": len(rids), "completed": finished,
            "steps": steps, "wall_s": _r(wall),
            "replica_states": {k: v["state"]
                               for k, v in rep["replicas"].items()},
            "placements": rep["router"]["placements"],
            "migrations": rep["router"]["migrations"],
            "lost": rep["router"]["lost"],
            "ttft_mean": _r(rep["router"]["ttft_s"]["mean"]),
            "ttft_p95": _r(rep["router"]["ttft_s"]["p95"]),
            "goodput_tokens_per_slot_step":
                _r(rep["router"]["goodput_tokens_per_slot_step"]),
            "dispatch_armed": rep["config"]["dispatch_armed"],
        }

    baseline = drive(0)
    failure = drive(args.kill_step)
    out.update({
        "baseline": baseline, "failure": failure,
        "kill": {"replica": 1, "of": args.fleet,
                 "after_steps": args.kill_step},
        "latency_unit": "serving steps (step clock)",
    })
    out["ttft_p95_ratio"] = _r(
        failure["ttft_p95"] / baseline["ttft_p95"], 3) \
        if baseline["ttft_p95"] else None
    out["goodput_ratio"] = _r(
        failure["goodput_tokens_per_slot_step"]
        / baseline["goodput_tokens_per_slot_step"], 3) \
        if baseline["goodput_tokens_per_slot_step"] else None
    for tag, row in (("baseline", baseline), ("failure", failure)):
        print(f"{tag:>18}: {row['completed']}/{row['submitted']} done "
              f"in {row['steps']} steps | TTFT mean {row['ttft_mean']} "
              f"p95 {row['ttft_p95']} | goodput "
              f"{row['goodput_tokens_per_slot_step']} | migrations "
              f"{row['migrations']} lost {len(row['lost'])}")
    ok = (failure["completed"] == failure["submitted"]
          and not failure["lost"] and failure["migrations"] > 0
          and failure["replica_states"]["replica1"] == "dead")
    out["guard_ok"] = ok
    print(f"replica-failure guard: {'OK' if ok else 'FAIL'} — killing "
          f"1 of {args.fleet} mid-run lost ZERO requests "
          f"({failure['migrations']} migrated); p95 TTFT "
          f"{out['ttft_p95_ratio']}x, goodput {out['goodput_ratio']}x "
          f"vs the no-failure baseline")
    return 0 if ok else 1


def _diurnal_arrivals(n, *, quiet_every=4, peak_per_step=3,
                      quiet_frac=0.15):
    """Arrival steps for one quiet -> peak -> quiet day: ``quiet_frac``
    of the requests trickle in at 1 every ``quiet_every`` steps on each
    shoulder, the rest burst at ``peak_per_step`` per step in between.
    The long sparse shoulders are the point of the A/B: a fleet
    provisioned for the peak idles through them (and pays replica-steps
    for it), an autoscaled one does not."""
    n_quiet = max(1, int(n * quiet_frac))
    n_peak = n - 2 * n_quiet
    arrivals, step = [], 0
    for _ in range(n_quiet):                    # morning trough
        arrivals.append(step)
        step += quiet_every
    for i in range(n_peak):                     # midday burst
        arrivals.append(step + i // peak_per_step)
    step = arrivals[-1] + 1
    for _ in range(n_quiet):                    # evening trough
        arrivals.append(step)
        step += quiet_every
    return arrivals


def run_diurnal(model, params, args, out):
    """Autoscaling A/B (ISSUE 16): static peak-provisioned fleet vs an
    autoscaled fleet over the same diurnal arrival profile, compared on
    goodput per replica-step."""
    import tempfile
    import time as time_mod

    from deepspeed_tpu.serving.fleet import AutoscaleConfig, FleetRouter

    workload = make_workload(args.requests, args.vocab, args.seed)
    arrivals = _diurnal_arrivals(len(workload))

    def drive(autoscaled):
        clock = StepClock()
        jd = tempfile.mkdtemp(prefix="serve_bench_diurnal_")
        kw = dict(clock=clock, journal_dir=jd,
                  engine_kwargs=dict(max_slots=args.slots,
                                     kv_block_size=16,
                                     prefill_chunk=args.chunk,
                                     max_blocks_per_seq=8))
        if autoscaled:
            router = FleetRouter(
                model, params, replicas=1,
                autoscale=AutoscaleConfig(
                    min_replicas=1, max_replicas=args.fleet,
                    scale_up_queue_depth=2.0 * args.slots,
                    scale_down_queue_depth=0.5 * args.slots,
                    cooldown_steps=4), **kw)
        else:
            router = FleetRouter(model, params, replicas=args.fleet,
                                 **kw)
        router.warmup()
        t0 = time_mod.perf_counter()
        pending = [(arrivals[i], w) for i, w in enumerate(workload)]
        rids, steps = [], 0
        while pending or router.has_work():
            while pending and pending[0][0] <= steps:
                _, (prompt, max_new) = pending.pop(0)
                rids.append(router.submit(prompt,
                                          max_new_tokens=max_new))
            router.step()
            clock.t += 1.0
            steps += 1
            assert steps < 10000, "diurnal bench did not converge"
        wall = time_mod.perf_counter() - t0
        rep = router.fleet_report()
        res = router.results
        finished = sum(1 for rid in rids
                       if res.get(rid, {}).get("status") == "finished")
        return {
            "autoscaled": autoscaled,
            "submitted": len(rids), "completed": finished,
            "steps": steps, "wall_s": _r(wall),
            "replicas_end": rep["config"]["replicas"],
            "replica_steps": rep["router"]["replica_steps"],
            "scale_events": rep["router"]["scale_events"],
            "lost": rep["router"]["lost"],
            "ttft_mean": _r(rep["router"]["ttft_s"]["mean"]),
            "ttft_p95": _r(rep["router"]["ttft_s"]["p95"]),
            "goodput_tokens_per_slot_step":
                _r(rep["router"]["goodput_tokens_per_slot_step"]),
            "goodput_tokens_per_replica_step":
                _r(rep["router"]["goodput_tokens_per_replica_step"]),
        }

    static = drive(False)
    auto = drive(True)
    out.update({"static": static, "autoscaled": auto,
                "fleet_max": args.fleet,
                "latency_unit": "serving steps (step clock)"})
    out["goodput_per_replica_step_ratio"] = _r(
        auto["goodput_tokens_per_replica_step"]
        / static["goodput_tokens_per_replica_step"], 3) \
        if static["goodput_tokens_per_replica_step"] else None
    for tag, row in (("static (peak-K)", static), ("autoscaled", auto)):
        ups = sum(1 for e in row["scale_events"] if e["dir"] == "up")
        downs = sum(1 for e in row["scale_events"] if e["dir"] == "down")
        print(f"{tag:>18}: {row['completed']}/{row['submitted']} done "
              f"in {row['steps']} steps | {row['replica_steps']} "
              f"replica-steps | goodput/replica-step "
              f"{row['goodput_tokens_per_replica_step']} | TTFT p95 "
              f"{row['ttft_p95']} | scale up {ups} / down {downs}")
    ups = sum(1 for e in auto["scale_events"] if e["dir"] == "up")
    downs = sum(1 for e in auto["scale_events"] if e["dir"] == "down")
    ok = (auto["completed"] == auto["submitted"] and not auto["lost"]
          and ups >= 1 and downs >= 1
          and auto["goodput_tokens_per_replica_step"]
          >= static["goodput_tokens_per_replica_step"])
    out["guard_ok"] = ok
    print(f"diurnal autoscale guard: {'OK' if ok else 'FAIL'} — "
          f"{ups} scale-up / {downs} scale-down, "
          f"{out['goodput_per_replica_step_ratio']}x goodput per "
          f"replica-step vs the static {args.fleet}-replica fleet, "
          f"zero lost")
    return 0 if ok else 1


def build_long_context_toy(vocab, *, n_positions, n_embd=16, n_layer=1):
    """A deliberately thin model with a LONG position range: the
    long-context bench is a KV-gather benchmark, not a FLOPs one — the
    cost under test is pages touched per dispatched lane."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    cfg = GPT2Config(vocab_size=vocab, n_positions=n_positions,
                     n_embd=n_embd, n_layer=n_layer, n_head=2,
                     dtype=jnp.float32, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, vocab, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    return model, params


def make_long_context_workload(vocab, seed, *, n_long, long_len,
                               long_new, n_short):
    """The adversarial long-context mix: a few book-length prompts that
    monopolize prefill + chatty short requests arriving underneath
    them.  Shorts land while the longs are mid-prefill — the shape that
    exposes both the O(total pages) decode gather and head-of-line
    blocking in the prefill lane."""
    import numpy as np

    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, vocab, long_len).astype(np.int32), long_new)
            for _ in range(n_long)]
    for _ in range(n_short):
        reqs.append((rng.integers(0, vocab,
                                  int(rng.integers(8, 25)))
                     .astype(np.int32),
                     int(rng.choice([4, 8]))))
    return reqs


def run_long_context(model, params, args, out):
    """Sparse page attention A/B (ISSUE 20): the SAME 32k-token traffic
    served dense (every page of every lane gathered each dispatch) vs
    under a sliding-window + global-anchor SparseContext with window-
    expired page reclamation and chunked-prefill fairness.  Latencies
    on the step clock.  Guards: >= 4x fewer gathered pages per lane-
    step, ZERO XLA compilations in flight on the sparse leg, short-
    request p95 TTFT no worse than the dense baseline, and identical
    completion counts."""
    bs, win, g = args.lc_block, args.lc_window_blocks, args.lc_globals
    W = args.lc_len // bs + 1                    # headroom for max_new
    workload = make_long_context_workload(
        args.vocab, args.seed, n_long=args.lc_long, long_len=args.lc_len,
        long_new=8, n_short=args.lc_short)
    # longs first (steps 0, 1), shorts trickling in underneath while
    # the longs are still chunking through prefill
    arrivals = list(range(args.lc_long)) + \
        [2 + 2 * i for i in range(args.lc_short)]
    out["workload"] = {
        "long": {"n": args.lc_long, "prompt_tokens": args.lc_len},
        "short": {"n": args.lc_short},
        "block_size": bs, "table_width": W,
        "sparse": {"num_sliding_window_blocks": win,
                   "num_global_blocks": g},
        "prefill_fairness": args.lc_fairness,
    }

    def drive(sparse):
        clock = StepClock()
        return run_mode(
            model, params, workload, policy="continuous",
            slots=args.lc_slots, chunk=args.lc_chunk, arrivals=arrivals,
            clock=clock, step_clock=True, block=bs, max_blocks=W,
            sparse_context=({"num_sliding_window_blocks": win,
                             "num_global_blocks": g} if sparse else None),
            prefill_fairness=args.lc_fairness if sparse else 0,
            count_compiles=sparse)

    dense = drive(False)
    sparse = drive(True)
    out.update({"dense": dense, "sparse": sparse,
                "latency_unit": "serving steps (step clock)"})
    for tag, row in (("dense", dense), ("sparse", sparse)):
        print(f"{tag:>18}: {row['tokens']} tok in {row['wall_s']}s | "
              f"{row['gathered_pages_per_lane_step']} pages/lane-step "
              f"(fraction {row['active_page_fraction']}) | short p95 "
              f"TTFT {row['short_ttft_p95']} long {row['long_ttft_p95']}"
              f" | window frees {row['window_expired_frees']}")
    ratio = (dense["gathered_pages_per_lane_step"]
             / sparse["gathered_pages_per_lane_step"]) \
        if sparse["gathered_pages_per_lane_step"] else None
    out["gathered_pages_ratio"] = _r(ratio, 3)
    out["short_ttft_p95_ratio"] = _r(
        sparse["short_ttft_p95"] / dense["short_ttft_p95"], 3) \
        if dense["short_ttft_p95"] else None

    ok = True
    if not (ratio is not None and ratio >= 4.0):
        print(f"GUARD FAIL: gathered-pages reduction {ratio} < 4x")
        ok = False
    if sparse["compilations_in_flight"] != 0:
        print(f"GUARD FAIL: {sparse['compilations_in_flight']} XLA "
              f"compilations during the sparse timed region")
        ok = False
    if not (sparse["completed"] == sparse["submitted"]
            == dense["completed"]):
        print("GUARD FAIL: completion counts diverge")
        ok = False
    if dense["short_ttft_p95"] and \
            sparse["short_ttft_p95"] > dense["short_ttft_p95"]:
        print(f"GUARD FAIL: sparse short p95 TTFT "
              f"{sparse['short_ttft_p95']} worse than dense "
              f"{dense['short_ttft_p95']}")
        ok = False
    if not (sparse["window_expired_frees"] > 0):
        print("GUARD FAIL: the window never reclaimed a page")
        ok = False
    out["guard_ok"] = ok
    print(f"long-context guard: {'OK' if ok else 'FAIL'} — "
          f"{_fmt_ratio(ratio)} fewer pages gathered per lane-step at "
          f"{args.lc_len}-token prompts (win={win} g={g} blocks of "
          f"{bs}), {sparse['window_expired_frees']} window-expired page "
          f"frees, short p95 TTFT {out['short_ttft_p95_ratio']}x dense, "
          f"{sparse['compilations_in_flight']} compiles in flight")
    return 0 if ok else 1


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--traffic", default="steady",
                   choices=["steady", "bursty", "overload",
                            "shared-prefix", "spec-decode",
                            "replica-failure", "diurnal",
                            "long-context"])
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--chunk", type=int, default=16)
    p.add_argument("--n-embd", type=int, default=64)
    p.add_argument("--n-layer", type=int, default=2)
    p.add_argument("--vocab", type=int, default=128)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--arrival-every", type=int, default=1,
                   help="steps between request arrivals (steady)")
    p.add_argument("--burst", type=int, default=8,
                   help="requests per burst (bursty)")
    p.add_argument("--burst-gap", type=int, default=24,
                   help="steps between bursts (bursty)")
    p.add_argument("--overload-rate", type=int, default=2,
                   help="arrivals per step at overload (2 = 2x the "
                        "admission-bound capacity)")
    p.add_argument("--slo-steps", type=float, default=8.0,
                   help="TTFT SLO in steps (overload)")
    p.add_argument("--deadline-steps", type=float, default=24.0,
                   help="per-request deadline in steps (overload)")
    p.add_argument("--fleet", type=int, default=3,
                   help="replicas behind the router (replica-failure); "
                        "peak/max replicas (diurnal)")
    p.add_argument("--kill-step", type=int, default=12,
                   help="engine step at which chaos hard-kills replica "
                        "1 (replica-failure)")
    p.add_argument("--draft-len", type=int, default=3,
                   help="speculative draft length k (spec-decode)")
    p.add_argument("--lc-len", type=int, default=32768,
                   help="long-prompt tokens (long-context)")
    p.add_argument("--lc-block", type=int, default=512,
                   help="KV block size (long-context)")
    p.add_argument("--lc-chunk", type=int, default=512,
                   help="prefill chunk (long-context)")
    p.add_argument("--lc-window-blocks", type=int, default=8,
                   help="sliding window in blocks (long-context)")
    p.add_argument("--lc-globals", type=int, default=2,
                   help="global anchor blocks (long-context)")
    p.add_argument("--lc-slots", type=int, default=4)
    p.add_argument("--lc-long", type=int, default=2,
                   help="book-length prompts (long-context)")
    p.add_argument("--lc-short", type=int, default=12,
                   help="chatty short requests (long-context)")
    p.add_argument("--lc-fairness", type=int, default=4,
                   help="prefill pause quantum in chunks on the sparse "
                        "leg (long-context)")
    p.add_argument("--json", default=None)
    args = p.parse_args(argv)

    if args.traffic == "long-context":
        model, params = build_long_context_toy(
            args.vocab,
            n_positions=(args.lc_len // args.lc_block + 1)
            * args.lc_block)
    else:
        model, params = build_toy(args.n_embd, args.n_layer, args.vocab)
    out = {"traffic": args.traffic,
           "config": {"slots": args.slots, "requests": args.requests,
                      "chunk": args.chunk, "seed": args.seed}}
    rc = {"steady": run_steady, "bursty": run_bursty,
          "overload": run_overload,
          "shared-prefix": run_shared_prefix,
          "spec-decode": run_spec_decode,
          "replica-failure": run_replica_failure,
          "diurnal": run_diurnal,
          "long-context": run_long_context}[args.traffic](
        model, params, args, out)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
    return rc


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
