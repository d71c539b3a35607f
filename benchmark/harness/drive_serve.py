"""Drives a serving mix (``"driver": "serve"`` in its traffic file) through
``InferenceEngine.submit`` / ``step``.  One process, one thread: the load
generator and the engine's host loop share it, as they would share a core,
and every latency is taken from the time a request was DUE, so a generator
that runs late shows as latency and is reported as lateness too."""
import gc
import time

import numpy as np

from deepspeed_tpu.serving import CompilationCounter, InferenceEngine

from harness import device as device_lib
from harness import traffic as traffic_lib
from harness.profiler import TracedStretch, span
from harness.stats import judged_percentile, median, percentile


def _bf16_spacing(x):
    """The spacing of bfloat16 (8 significand bits) at the magnitude of x."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 2.0 ** -100))) - 7)


def row_gaps(logits, served_tokens):
    """Per row of (N, vocabulary) reference logits: how far the served
    token's logit lies under the row's best, in spacings of bf16 at the
    best's magnitude, and in the row's own logit sigma."""
    logits = np.asarray(logits, np.float32)
    best = logits.max(axis=-1)
    gap = best - logits[np.arange(len(logits)), served_tokens]
    return gap / _bf16_spacing(best), gap / logits.std(axis=-1)


def judge_gaps(spacings, sigmas, rule):
    """The one rule served tokens are held by, its numbers from the
    architecture's ``served_check``: of all rows at least ``share`` lie
    within ``near_best_spacings`` bf16 spacings of the reference's best
    logit, and EVERY row lies within ``every_row_sigma`` of its own row's
    logit sigma (None: no second bound, which ``share`` 1.0 makes idle).
    Returns (held, what was seen)."""
    spacings, sigmas = np.asarray(spacings), np.asarray(sigmas)
    near, share, far = rule["near_best_spacings"], rule["share"], \
        rule["every_row_sigma"]
    n = len(spacings)
    seen = {"rows_judged": n,
            "worst_spacings_below_best": float(spacings.max(initial=0.0)),
            "share_within": float(np.mean(spacings <= near)) if n else 0.0,
            "worst_sigma_below_best": float(sigmas.max(initial=0.0))}
    held = n > 0 and seen["share_within"] >= share \
        and (far is None or seen["worst_sigma_below_best"] <= far)
    return bool(held), seen


def judge_rows(logits, served_tokens, rule):
    """``judge_gaps`` of (N, vocabulary) reference logits and the N served
    tokens: arrays in, a verdict out, no device in it."""
    return judge_gaps(*row_gaps(logits, served_tokens), rule)


def check_served(arch, config, params, served, prompts, asked_new):
    """``served``: token arrays (prompt + generated) of finished requests.
    Returns (ok, what was seen)."""
    check = arch.served_check(config)
    rule = check["rule"]
    weights = arch.reference_weights(params, config)
    width = check["width"](max((len(t) for t in served), default=0))
    spacings, sigmas, by_request, failures, over = [], [], [], [], []
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
        # only the rows that score a served token pass the head and come
        # to the host
        rows = np.arange(len(prompt) - 1, len(tokens) - 1)
        below, in_sigma = row_gaps(
            arch.reference_logits(weights, config, ids, rows)[0],
            tokens[rows + 1])
        spacings.append(below)
        sigmas.append(in_sigma)
        by_request.append(float(below.max()))
        if below.max() > rule["near_best_spacings"]:
            over.append(
                f"request {i}: served token {int(rows[below.argmax()]) + 1} "
                f"lies {below.max():.2f} bf16 spacings "
                f"({in_sigma[below.argmax()]:.3f} sigma) under the "
                f"reference's best logit")
    held, seen = judge_gaps(np.concatenate(spacings or [[]]),
                            np.concatenate(sigmas or [[]]), rule)
    return held and not failures, dict(
        seen, requests_checked=len(served), rule=rule,
        worst_spacings_by_request=by_request, failures=failures[:20],
        over_near_best=over[:20])


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
    # A mix that states ``"freeze_setup_objects": true`` has what set-up
    # built (the runtime's and the program's modules, the compiled
    # programs, the load: some 10^5 objects, all of which stay to the end)
    # taken out of the collector's sight when the warm-up ends, as a
    # server's main may after its own: a full collection in the window
    # then walks what the window made, and not all of that for 0.13 s,
    # which every request in flight feels in its token gap and which
    # falls into one window twice and into the next not at all (PERF.md
    # section 6, PR 45).  The collector itself stays on; counted in
    # ``setup_s``.  A mix that does not state it runs as it always did.
    freeze = bool(mix.get("freeze_setup_objects", False))
    if freeze:
        gc.collect()
        gc.freeze()
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
    step_s = []     # every engine step begun inside the window, call to return
    nxt, stopping, trace_stop_s = 0, False, 0.0
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
                    # stop_trace blocks this thread for seconds (the more
                    # device events, the longer), and no request moves
                    # meanwhile: the drain clock does not run either
                    t_stop = clock()
                    stretch.stop()
                    trace_stop_s = clock() - t_stop
                if backlog:     # what is admitted is finished, no more
                    engine.request_drain()
            if now >= window[1] + trace_stop_s + drain_s:
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
                    step_s.append(clock() - t0 - t_step)
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
    if freeze:
        gc.unfreeze()

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

    spans = {}
    if engine.telemetry is not None:
        for e in engine.telemetry.tracer.events():
            if e["ph"] == "X" and window[0] <= e["ts"] - t0 < window[1]:
                spans.setdefault(e["name"], []).append(
                    {"ms": 1e3 * e["dur"], "a0": e["a0"]})

    # ---- correctness, outside the window ---------------------------------
    # a seeded sample of the finished requests, and the longest of them
    rng = np.random.default_rng(seed)
    sample = [finished[j] for j in sorted(rng.choice(
        len(finished), size=min(int(mix["check_requests"]), len(finished)),
        replace=False))] if finished else []
    longest = max(finished, key=lambda i: len(prompts[i]) + new_tokens[i],
                  default=None)
    if longest is not None and longest not in sample:
        sample.append(longest)
    served = [np.asarray(engine.result(rids[i])) for i in sample]
    # The program's peak is read and its state released before the
    # reference runs, so that a model whose weights fill the chip can be
    # checked beside them (``params`` stays: the reference reads them).
    memory_peak_bytes = device_lib.memory_peak_bytes(devices)
    in_use = {"with_engine": device_lib.bytes_in_use(devices)}
    del engine, model
    gc.collect()
    in_use["engine_released"] = device_lib.bytes_in_use(devices)
    t_reference = clock()
    tokens_ok, seen = check_served(
        arch, config, params, served,
        [prompts[i] for i in sample], [int(new_tokens[i]) for i in sample])
    reference_s = clock() - t_reference
    in_use["after_reference"] = device_lib.bytes_in_use(devices)
    checks = {
        "served_tokens_hold_to_reference": tokens_ok and bool(sample),
        "no_compile_in_window": compiles.count == 0,
        "no_request_failed": failed == 0,
    }
    rule = seen["rule"]
    compared = {     # each number compared, beside its limit
        "share_within_near_best": [seen["share_within"], rule["share"]],
        "worst_spacings_below_best": [
            seen["worst_spacings_below_best"],
            rule["near_best_spacings"] if rule["share"] >= 1.0 else None],
        "worst_sigma_below_best": [seen["worst_sigma_below_best"],
                                   rule["every_row_sigma"]],
        "requests_not_echoed": [len(seen["failures"]), 0],
        "compiles_in_window": [compiles.count, 0],
        "requests_failed": [failed, 0],
    }
    log({"requests": {"generated": n, "submitted": nxt,
                      "counted": len(counted), "finished": len(finished)},
         "setup_reached_at_s": stages,
         "queue_depth_at_window_end": queue_at_end,
         "generator_lateness_s": lateness(submitted_at[:nxt], due[:nxt]),
         "reference": seen, "reference_s": reference_s, "checks": checks,
         "device_bytes_in_use": in_use,
         "ttft_s": {"p50": median(ttft), "p95": percentile(ttft, .95)},
         "tpot_s": {"p50": median(tpot), "p95": percentile(tpot, .95)},
         "trace_stop_s": trace_stop_s, "loop_s": loop_end})

    reduction = stretch.reduce() if trace else None
    return {
        "correct": all(checks.values()),
        "attempted": len(counted),
        "failed": failed,
        "compared": compared,
        "requests_counted": len(counted),
        "end_to_end": {
            # None, and so no result line where the cell judges it, over
            # fewer requests than leave ten beyond the rank
            "tpot_p95_s": judged_percentile(tpot, .95),
            "serve_tokens_per_s": tokens_per_s,
            "setup_s": setup_s,
        },
        "observed": {
            "compiles_in_window": compiles.count,
            "memory_peak_bytes": memory_peak_bytes,
            "trace": reduction,
            "spans": spans,
            "queue_wait_s": queue_wait,
            "ttft_s": ttft,
            "tpot_s": tpot,
            "due_s": [float(due[i]) for i in counted],
            "step_s": step_s,
            "generator_lateness_s": [submitted_at[i] - due[i]
                                     for i in counted],
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
