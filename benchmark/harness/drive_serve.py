"""Drives a serving mix (``"driver": "serve"`` in its traffic file) through
``InferenceEngine.submit`` / ``step``.  One process, one thread: the load
generator and the engine's host loop share it, as they would share a core,
and every latency is taken from the time a request was DUE, so a generator
that runs late shows as latency and is reported as lateness too."""
import time

import numpy as np

from deepspeed_tpu.serving import CompilationCounter, InferenceEngine

from harness import device as device_lib
from harness import traffic as traffic_lib
from harness.profiler import TracedStretch, span
from harness.stats import median, percentile

# Served tokens against the plain reference, after chip_smoke.py's rule.
# The served path computes in bf16 (8 significand bits), the reference in
# f32, and with random weights the two best logits of a row are often one
# bf16 spacing apart, so tokens cannot be compared for equality.  Instead:
# under the reference's teacher-forced forward of the served sequence, every
# served token's logit lies within NEAR_BEST_SPACINGS spacings of bf16 (at
# the magnitude of the row's best logit: 2^-6 near 2.0) of the best logit of
# its row.  chip_smoke.py allows 2 against the program's own bf16 forward;
# against f32 the served logit and its rival each carry the error of 24
# layers of bf16 activations as well, about one spacing each: the worst of
# some 5,600 served tokens over seven runs on the chip lay 2.12 under
# (PERF.md, section 6).  A wrong cache row, mask or position moves a logit
# by tenths, tens of spacings; a token picked blindly lies ~170 under.
NEAR_BEST_SPACINGS = 4.0


def _bf16_spacing(x):
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 2.0 ** -100))) - 7)


def check_served(arch, config, params, served, prompts, asked_new):
    """``served``: token arrays (prompt + generated) of finished requests.
    Returns (ok, what was seen)."""
    weights = arch.reference_weights(params, config)
    worst, failures = 0.0, []
    width = config["n_positions"]
    for i, (tokens, prompt, new) in enumerate(zip(served, prompts,
                                                  asked_new)):
        if len(tokens) != len(prompt) + new \
                or not (tokens[:len(prompt)] == prompt).all():
            failures.append(f"request {i}: prompt not echoed or "
                            f"{len(tokens) - len(prompt)} tokens for {new}")
            continue
        # one padded shape for every request: causal attention keeps the
        # padding out of the rows that count
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(tokens)] = tokens
        rows = np.arange(len(prompt) - 1, len(tokens) - 1)
        # only the rows that score a served token come to the host
        logits = np.asarray(
            arch.reference_logits(weights, config, ids)[0, rows])
        best = logits.max(axis=-1)
        of_served = logits[np.arange(len(rows)), tokens[rows + 1]]
        below = (best - of_served) / _bf16_spacing(best)
        worst = max(worst, float(below.max()))
        if below.max() > NEAR_BEST_SPACINGS:
            failures.append(
                f"request {i}: served token {int(rows[below.argmax()]) + 1} "
                f"lies {below.max():.2f} bf16 spacings under the reference's "
                f"best logit (allowed {NEAR_BEST_SPACINGS})")
    return not failures, {"requests_checked": len(served),
                          "worst_spacings_below_best": worst,
                          "allowed": NEAR_BEST_SPACINGS, "failures": failures}


def latencies(*, counted, due, finished, first_token, last_token,
              n_tokens, load_end):
    """Per counted request, from the time it was DUE: time to the first
    token, and (last - first) / (tokens - 1).  A request that failed, was
    refused or did not finish before ``load_end`` counts as the largest
    value: it waited from its due time to the end, and its gap between
    tokens is the largest seen."""
    ttft, tpot = [], []
    for i in counted:
        if i in finished:
            ttft.append(first_token[i] - due[i])
            if n_tokens[i] > 1:
                tpot.append((last_token[i] - first_token[i])
                            / (n_tokens[i] - 1))
        else:
            ttft.append(load_end - due[i])
    missing = len(counted) - len(tpot)
    tpot += [max(tpot, default=load_end)] * missing
    return {"ttft": ttft, "tpot": tpot}


def _inside(start, end, window):
    """Share of [start, end] that lies inside the window (an instant
    counts whole where it falls)."""
    if end <= start:
        return float(window[0] <= start < window[1])
    return max(0.0, min(end, window[1]) - max(start, window[0])) \
        / (end - start)


def completed_tokens_per_s(requests, window):
    """Tokens per second of the window, over requests that FINISHED (then
    or in the drain): each ``(admitted, first_token, last_token, prompt
    tokens, generated tokens)``.  A token counts when it was processed: the
    prompt and the first generated token evenly between admission and the
    first token (one prefill chunk a step), the other generated tokens
    evenly between the first and the last.  Counting a whole request at the
    instant it finishes measures the same rate, but moves ~750 tokens at a
    time across the window's edges: 2-3 % of spread at ~75 requests a
    window on the chip, where this keeps well under 1 %."""
    tokens = 0.0
    for admitted, first, last, prompt, generated in requests:
        tokens += (prompt + 1) * _inside(admitted, first, window) \
            + (generated - 1) * _inside(first, last, window)
    return tokens / (window[1] - window[0])


def lateness(submitted_at, due):
    """How late the generator ran: submit time minus due time."""
    late = [s - d for s, d in zip(submitted_at, due)]
    return {"median": median(late), "max": max(late, default=None)}


def run(cell, devices, *, seed, seconds, trace, process_start, log):
    arch, config, mix = cell.architecture(), cell.config, cell.traffic
    clock = time.perf_counter
    stages = {"imports": clock() - process_start}
    model = arch.build_model(config, mix["model_overrides"])
    params = arch.init_params(model, seed)
    telemetry = {"trace": True, "mfu": False} if trace else None
    engine = InferenceEngine(model, params, clock=clock,
                             telemetry=telemetry, **mix["engine"])
    ramp_s, drain_s = float(mix["ramp_s"]), float(mix["drain_s"])
    backlog = mix["arrivals"]["process"] == "backlog"
    load = traffic_lib.requests(mix, config["vocab_size"], seed,
                                ramp_s + seconds)
    due, prompts, new_tokens = load["due"], load["prompts"], \
        load["new_tokens"]
    stages["weights_engine_and_load"] = clock() - process_start
    engine.warmup()
    setup_s = stages["warmup"] = clock() - process_start

    # ---- the load: ramp, window, drain ---------------------------------
    n = len(due)
    rids, submitted_at, admitted_at = [None] * n, [None] * n, {}
    window = (ramp_s, ramp_s + seconds)
    # the trace covers the last seconds of the window, so that writing it
    # out falls into the drain and not among the requests that count
    trace_at = window[1] - float(mix["trace_s"])
    stretch = TracedStretch(cell.name)      # started only when traced
    m = engine.metrics
    steps_in_window, occupancy_sum, slots_before = 0, 0.0, None
    nxt, stopping = 0, False
    t0 = clock()
    with CompilationCounter() as compiles:
        while True:
            now = clock() - t0
            if trace and not stretch.running and not stopping \
                    and now >= trace_at:
                stretch.start()
            if now >= window[0] and slots_before is None:
                slots_before = (m.slot_steps, m.active_slot_steps)
            if now >= window[1] and not stopping:
                stopping = True
                slots_after = (m.slot_steps, m.active_slot_steps)
                queue_at_end = engine.scheduler.queue_depth()
                if stretch.running:
                    stretch.stop()
                if backlog:     # what is admitted is finished, no more
                    engine.request_drain()
            if now >= window[1] + drain_s:
                break
            tracing = stretch.running
            with span("bench:submit", tracing and nxt < n
                      and due[nxt] <= now):
                while nxt < n and due[nxt] <= now:
                    rids[nxt] = engine.submit(
                        prompts[nxt], max_new_tokens=int(new_tokens[nxt]))
                    submitted_at[nxt] = clock() - t0
                    nxt += 1
            busy = engine.scheduler.in_flight() if (stopping and backlog) \
                else engine.scheduler.has_work()
            if busy:
                t_step = clock() - t0
                with span("bench:engine_step", tracing):
                    events = engine.step()
                for rid in events["admitted"]:
                    admitted_at.setdefault(rid, t_step)
                if window[0] <= t_step < window[1]:
                    steps_in_window += 1
                    occupancy_sum += engine.pool.occupancy()
            elif nxt < n:
                with span("bench:wait_arrival", tracing):
                    time.sleep(max(0.0, min(due[nxt] - (clock() - t0),
                                            0.005)))
            else:
                break
        if stretch.running:
            stretch.stop()
    if not stopping:        # everything was served before the window ended
        slots_after = (m.slot_steps, m.active_slot_steps)
        queue_at_end = 0
    slots_before = slots_before or slots_after
    loop_end = clock() - t0

    # ---- per request ----------------------------------------------------
    if backlog:     # taken up: admitted before the window ended
        counted = [i for i in range(nxt) if rids[i] in admitted_at
                   and admitted_at[rids[i]] < window[1]]
    else:           # due inside the window
        counted = [i for i in range(nxt) if window[0] <= due[i] < window[1]]
    finished = [i for i in counted
                if engine.results.get(rids[i], {}).get("status") == "finished"]
    failed = len(counted) - len(finished)
    first = {i: m._first_token[rids[i]] - t0 for i in finished}
    last = {i: m._last_token[rids[i]] - t0 for i in finished}
    timing = latencies(counted=counted, due=due, finished=set(finished),
                       first_token=first, last_token=last,
                       n_tokens={i: m._tokens[rids[i]] for i in finished},
                       load_end=loop_end)
    ttft, tpot = timing["ttft"], timing["tpot"]
    tokens_per_s = completed_tokens_per_s(
        [(admitted_at[rids[i]], first[i], last[i], len(prompts[i]),
          int(new_tokens[i])) for i in finished], window)
    queue_wait = [admitted_at[rids[i]] - due[i] for i in counted
                  if rids[i] in admitted_at]

    # ---- correctness, outside the window ---------------------------------
    rng = np.random.default_rng(seed)
    sample = [finished[j] for j in sorted(rng.choice(
        len(finished), size=min(int(mix["check_requests"]), len(finished)),
        replace=False))] if finished else []
    tokens_ok, seen = check_served(
        arch, config, params,
        [np.asarray(engine.result(rids[i])) for i in sample],
        [prompts[i] for i in sample], [int(new_tokens[i]) for i in sample])
    checks = {
        "served_tokens_hold_to_reference": tokens_ok and bool(sample),
        "no_compile_in_window": compiles.count == 0,
        "no_request_failed": failed == 0,
    }
    log({"requests": {"generated": n, "submitted": nxt,
                      "counted": len(counted), "finished": len(finished)},
         "setup_reached_at_s": stages,
         "queue_depth_at_window_end": queue_at_end,
         "generator_lateness_s": lateness(submitted_at[:nxt], due[:nxt]),
         "reference": seen, "checks": checks,
         "ttft_s": {"p50": median(ttft), "p95": percentile(ttft, .95)},
         "tpot_s": {"p50": median(tpot), "p95": percentile(tpot, .95)},
         "loop_s": loop_end})

    reduction = stretch.reduce() if trace else None
    spans = {}
    if engine.telemetry is not None:
        for e in engine.telemetry.tracer.events():
            if e["ph"] == "X" and window[0] <= e["ts"] - t0 < window[1]:
                spans.setdefault(e["name"], []).append(
                    {"ms": 1e3 * e["dur"], "a0": e["a0"]})
    return {
        "correct": all(checks.values()),
        "attempted": len(counted),
        "failed": failed,
        "end_to_end": {
            "tpot_p95_s": percentile(tpot, .95),
            "serve_tokens_per_s": tokens_per_s,
            "setup_s": setup_s,
        },
        "observed": {
            "compiles_in_window": compiles.count,
            "memory_peak_bytes": device_lib.memory_peak_bytes(devices),
            "trace": reduction,
            "spans": spans,
            "queue_wait_s": queue_wait,
            "ttft_s": ttft,
            "counters": {
                "slot_steps": slots_after[0] - slots_before[0],
                "active_slot_steps": slots_after[1] - slots_before[1],
                "kv_occupancy_mean": occupancy_sum / max(1, steps_in_window),
                "steps": steps_in_window,
                "prefill_tokens_computed": m.prefill_computed_tokens,
                "evictions": m.evictions,
            },
        },
    }
