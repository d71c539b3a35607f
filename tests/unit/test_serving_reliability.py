"""Serving reliability layer (deepspeed_tpu/serving/reliability.py).

The load-bearing acceptance properties of ISSUE 9:

- **Overload guard** (tier-1 graceful degradation): at 2x-capacity
  traffic with SLO shedding ARMED, the p95 TTFT of *admitted* requests
  stays bounded and goodput holds the steady-state ratio floor; the
  SAME traffic with shedding DISARMED demonstrably degrades (TTFT
  blow-up + wasted work) — congestion collapse pinned as the baseline.
- **Crash recovery**: chaos kill-mid-decode, then ``recover()`` on a
  fresh engine replays the journal through the eviction re-prefill
  path — greedy continuations BIT-IDENTICAL to the uninterrupted run,
  with ZERO recompiles (CompilationCounter pin).
- **Drain**: SIGTERM (``install_preemption_handler``) stops admission,
  finishes in-flight requests, leaves queued work journaled.
- **Isolation**: deadline expiry frees every block (allocator occupancy
  returns to zero) and a poisoned lane (non-finite logits) is
  quarantined without perturbing its batch peers bit-wise.

All latency/deadline tests run on a STEP-COUNT clock (1.0 per serving
step) so TTFT, deadlines and the predicted-TTFT admission model are
deterministic on any host.
"""
import os
import signal

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.resilience import chaos
from deepspeed_tpu.runtime.resilience.chaos import ChaosInterrupt
from deepspeed_tpu.runtime.resilience.watchdog import (ACTION_CONTINUE,
                                                       EVENT_STALL,
                                                       TrainingWatchdog)
from deepspeed_tpu.serving.engine import InferenceEngine
from deepspeed_tpu.serving.metrics import (CompilationCounter,
                                           ServingMetrics, _pct)
from deepspeed_tpu.serving.reliability import RequestJournal


@pytest.fixture(scope="module")
def toy():
    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                     n_head=4, dtype=jnp.float32, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    refs = {}

    def ref(prompt, max_new):
        key = (tuple(int(t) for t in prompt), max_new)
        if key not in refs:
            refs[key] = generate(model, params,
                                 np.asarray(prompt, np.int32)[None],
                                 max_new_tokens=max_new)[0]
        return refs[key]

    return model, params, ref


class StepClock:
    """Deterministic clock: the test advances it 1.0 per serving step,
    so every latency metric is measured in STEPS."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("kv_block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_blocks_per_seq", 8)
    return InferenceEngine(model, params, **kw)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# deadlines & work budgets
# ---------------------------------------------------------------------------

def test_deadline_expiry_frees_blocks_and_never_wedges(toy):
    """Two requests with a deadline too short to finish expire with
    reason 'expired', their KV blocks ALL return to the allocator, and
    a bystander without a deadline still finishes bit-identically."""
    model, params, ref = toy
    clock = StepClock()
    eng = _engine(model, params, clock=clock)
    prompts = _prompts(1, (5, 7, 4))
    bystander = eng.submit(prompts[2], max_new_tokens=6)
    doomed = [eng.submit(p, max_new_tokens=24, deadline_s=4.0)
              for p in prompts[:2]]
    expired = []
    for _ in range(60):
        if not eng.scheduler.has_work():
            break
        ev = eng.step()
        expired += ev["expired"]
        clock.t += 1.0
    res = eng.results
    assert sorted(expired) == sorted(doomed)
    for rid, p in zip(doomed, prompts[:2]):
        assert res[rid]["status"] == "expired"
        # partial output is a prefix of the reference continuation
        np.testing.assert_array_equal(
            res[rid]["tokens"], ref(p, 24)[:len(res[rid]["tokens"])])
    np.testing.assert_array_equal(res[bystander]["tokens"],
                                  ref(prompts[2], 6))
    assert eng.pool.blocks_in_use == 0
    assert eng.pool.occupancy() == 0.0
    rep = eng.serving_report()
    assert rep["requests"]["aborted"]["expired"] == 2
    assert rep["reliability"]["aborts"]["expired"] == 2
    assert rep["tokens"]["wasted"] > 0


def test_work_budget_bounds_scheduled_tokens(toy):
    """A request whose work budget cannot even cover its prompt aborts
    with reason 'budget' at the next step boundary — eviction
    re-prefill loops are bounded the same way."""
    model, params, _ = toy
    eng = _engine(model, params)
    prompt = _prompts(2, (6,))[0]
    rid = eng.submit(prompt, max_new_tokens=8, work_budget=4)
    eng.serve(max_steps=50)
    assert eng.results[rid]["status"] == "budget"
    assert eng.pool.blocks_in_use == 0
    assert eng.serving_report()["requests"]["aborted"]["budget"] == 1


def test_default_deadline_from_reliability_config(toy):
    model, params, _ = toy
    clock = StepClock()
    eng = _engine(model, params, clock=clock,
                  reliability={"default_deadline_s": 3.0})
    rid = eng.submit(_prompts(3, (5,))[0], max_new_tokens=25)
    for _ in range(40):
        if not eng.scheduler.has_work():
            break
        eng.step()
        clock.t += 1.0
    assert eng.results[rid]["status"] == "expired"
    assert eng.serving_report()["reliability"]["armed"]["deadlines"]


# ---------------------------------------------------------------------------
# SLO admission / load shedding (the tier-1 overload guard)
# ---------------------------------------------------------------------------

def _drive_overload(model, params, *, slo, arrival_every, n_requests,
                    deadline, max_steps=500):
    """Fixed traffic shape on a step clock: one request every
    ``arrival_every`` steps, each wanting 8 new tokens, every request
    carrying ``deadline`` steps of patience.  Returns the engine."""
    clock = StepClock()
    rel = {"slo_ttft_s": slo} if slo is not None else None
    eng = _engine(model, params, max_slots=3, clock=clock,
                  reliability=rel)
    prompts = _prompts(11, [6] * n_requests)
    pending = list(enumerate(prompts))
    steps = 0
    while pending or eng.scheduler.has_work():
        while pending and pending[0][0] * arrival_every <= steps:
            _, p = pending.pop(0)
            eng.submit(p, max_new_tokens=8, deadline_s=deadline)
        eng.step()
        clock.t += 1.0
        steps += 1
        assert steps < max_steps, "overload run did not converge"
    return eng


def test_overload_shedding_guard(toy):
    """THE graceful-degradation guard: 2x-capacity traffic.

    Measured capacity of this engine shape (3 lanes, 6-token prompts,
    8 new tokens, one chunked prefill in flight) is ~0.45 req/step;
    arrivals every step offer ~2.2x that — sustained overload.  Every
    request carries 24 steps of deadline patience.

    ARMED (slo_ttft_s=8 steps): the gate sheds at the door, admitted
    requests keep p95 TTFT within 2x the SLO, NOTHING expires, and
    goodput (useful tokens per slot-step) holds >= 75% of the
    steady-state baseline's.  DISARMED: the same traffic queues
    unboundedly — TTFT blow-up, deadline expiry, and already-decoded
    tokens thrown away.  Both halves are pinned, all on the step clock
    (fully deterministic)."""
    model, params, _ = toy
    steady = _drive_overload(model, params, slo=None, arrival_every=3,
                             n_requests=12, deadline=None)
    armed = _drive_overload(model, params, slo=8.0, arrival_every=1,
                            n_requests=32, deadline=24.0)
    disarmed = _drive_overload(model, params, slo=None, arrival_every=1,
                               n_requests=32, deadline=24.0)

    r_steady = steady.serving_report()
    r_armed = armed.serving_report()
    r_dis = disarmed.serving_report()
    assert r_steady["requests"]["completed"] == 12

    # the armed gate actually engaged...
    shed = r_armed["reliability"]["aborts"]["shed"]
    assert shed > 0, "overload never tripped the admission gate"
    assert r_armed["reliability"]["armed"]["shedding"]
    # ...admitted requests kept a bounded p95 TTFT (steps): within 2x
    # of the SLO target (prediction error is bounded by one queue
    # refill, not unbounded like the disarmed queue)...
    assert r_armed["ttft_s"]["p95"] <= 2 * 8.0, r_armed["ttft_s"]
    # ...every admitted request also met its DEADLINE...
    assert r_armed["requests"]["aborted"].get("expired", 0) == 0
    assert r_armed["tokens"]["wasted"] == 0
    # ...and goodput held the floor vs steady state (same denominator)
    g_steady = r_steady["throughput"]["goodput_tokens_per_slot_step"]
    g_armed = r_armed["throughput"]["goodput_tokens_per_slot_step"]
    assert g_armed >= 0.75 * g_steady, (g_armed, g_steady)

    # DISARMED baseline: same traffic, demonstrable congestion
    # collapse — TTFT blows past the armed band, deadlines expire, and
    # tokens already decoded for expiring requests are pure waste
    assert r_dis["reliability"]["aborts"]["shed"] == 0
    assert r_dis["ttft_s"]["p95"] >= 1.5 * r_armed["ttft_s"]["p95"], \
        (r_dis["ttft_s"], r_armed["ttft_s"])
    assert r_dis["requests"]["aborted"].get("expired", 0) > 0
    assert r_dis["tokens"]["wasted"] > 0
    assert r_dis["throughput"]["useful_fraction"] \
        < r_armed["throughput"]["useful_fraction"]
    assert r_dis["throughput"]["goodput_tokens_per_slot_step"] < g_armed
    # backpressure is visible where clients look for it
    adm = r_armed["reliability"]["admission"]
    assert adm["rejected"] + shed >= shed > 0
    assert adm["predicted_ttft_s"]["mean"] is not None


def test_shedding_prefers_lowest_priority_victims(toy):
    """Under overload a HIGH-importance newcomer sheds queued
    low-importance work instead of being turned away."""
    model, params, ref = toy
    clock = StepClock()
    eng = _engine(model, params, max_slots=2, clock=clock,
                  reliability={"slo_ttft_s": 6.0})
    # establish a measured step time + busy lanes
    warm = [eng.submit(p, max_new_tokens=10)
            for p in _prompts(5, (5, 6))]
    for _ in range(4):
        eng.step()
        clock.t += 1.0
    # overload the queue with low-importance (priority=2) work
    low = [eng.submit(p, max_new_tokens=8, priority=2)
           for p in _prompts(6, (6, 6, 6, 6, 6, 6))]
    vip_prompt = _prompts(7, (5,))[0]
    vip = eng.submit(vip_prompt, max_new_tokens=6, priority=0)
    shed_rids = [r for r in low if eng.results.get(r, {}).get("status")
                 == "shed"]
    assert shed_rids, "no low-priority work was shed for the VIP"
    assert vip not in eng.results, "the VIP itself must be admitted"
    while eng.scheduler.has_work():
        eng.step()
        clock.t += 1.0
    np.testing.assert_array_equal(eng.results[vip]["tokens"],
                                  ref(vip_prompt, 6))
    for rid in warm:
        assert eng.results[rid]["status"] == "finished"


def test_arm_shedding_disarms_loudly_on_nonpositive_slo(toy, caplog):
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    model, params, _ = toy
    ds_logger.propagate = True
    try:
        with caplog.at_level(logging.WARNING):
            eng = _engine(model, params,
                          reliability={"slo_ttft_s": 0.0})
    finally:
        ds_logger.propagate = False
    assert not eng.reliability.shedding_armed
    assert any("DISARMED" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# graceful drain (engine.drain / SIGTERM)
# ---------------------------------------------------------------------------

def test_drain_finishes_in_flight_and_journals_waiting(toy, tmp_path):
    model, params, ref = toy
    jpath = str(tmp_path / "journal.jsonl")
    eng = _engine(model, params, max_slots=2,
                  reliability={"journal_path": jpath})
    prompts = _prompts(8, (5, 7, 6, 4))
    rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    for _ in range(4):           # two requests admitted, two waiting
        eng.step()
    in_flight = {r.rid for r in eng.scheduler.running.values()}
    if eng.scheduler.prefilling is not None:
        in_flight.add(eng.scheduler.prefilling.rid)
    assert in_flight and len(in_flight) < len(rids)
    res = eng.drain()
    # every in-flight request FINISHED, bit-identically
    for rid, p in zip(rids, prompts):
        if rid in in_flight:
            assert res[rid]["status"] == "finished"
            np.testing.assert_array_equal(res[rid]["tokens"], ref(p, 8))
        else:
            assert rid not in res          # still queued, not lost...
    waiting = [rid for rid in rids if rid not in in_flight]
    assert eng.scheduler.queue_depth() == len(waiting)
    assert eng.reliability.journal_depth() == len(waiting)
    assert eng.serving_report()["reliability"]["draining"]
    # ...and a successor picks them up via the journal
    eng2 = _engine(model, params, max_slots=2)
    recovered = eng2.recover(jpath)
    assert sorted(recovered) == sorted(waiting)
    res2 = eng2.serve(max_steps=300)
    for rid, p in zip(rids, prompts):
        if rid in waiting:
            np.testing.assert_array_equal(res2[rid]["tokens"], ref(p, 8))


def test_sigterm_drains_gracefully(toy):
    """install_preemption_handler routes SIGTERM into request_drain:
    serve() finishes in-flight work and returns instead of dying."""
    model, params, ref = toy
    eng = _engine(model, params, max_slots=2)
    prev = signal.getsignal(signal.SIGTERM)
    try:
        eng.install_preemption_handler()
        prompts = _prompts(9, (5, 6, 7, 4))
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        for _ in range(3):
            eng.step()
        os.kill(os.getpid(), signal.SIGTERM)   # the preemption notice
        res = eng.serve(max_steps=300)
    finally:
        signal.signal(signal.SIGTERM, prev)
    assert eng.scheduler.draining
    finished = [rid for rid in rids if rid in res
                and res[rid]["status"] == "finished"]
    assert finished, "drain finished nothing"
    for rid, p in zip(rids, prompts):
        if rid in res and res[rid]["status"] == "finished":
            np.testing.assert_array_equal(res[rid]["tokens"], ref(p, 6))
    # admission is stopped: queued requests survive, unserved
    assert eng.scheduler.queue_depth() == len(rids) - len(finished)


# ---------------------------------------------------------------------------
# crash recovery (kill-mid-decode + journal replay)
# ---------------------------------------------------------------------------

def test_kill_mid_decode_recover_bit_identical(toy, tmp_path):
    """THE recovery acceptance: chaos kills the host mid-decode (after
    dispatch, before bookkeeping).  A fresh engine replays the journal
    and every journaled request's greedy continuation is BIT-IDENTICAL
    to the uninterrupted run — with ZERO recompiles after warmup."""
    model, params, ref = toy
    jpath = str(tmp_path / "crash.jsonl")
    prompts = _prompts(10, (5, 11, 3, 9, 6))
    maxnew = [6, 9, 12, 5, 8]

    eng = _engine(model, params, reliability={"journal_path": jpath})
    chaos.arm(kill_serving_after_steps=9)
    try:
        with pytest.raises(ChaosInterrupt):
            for p, m in zip(prompts, maxnew):
                eng.submit(p, max_new_tokens=m)
                eng.step()
                eng.step()
            eng.serve(max_steps=300)
        plan = chaos.active()
        assert any(k == "kill_serving" for k, _ in plan.fired)
    finally:
        chaos.disarm()
    survivors = {r.rid for r in eng.scheduler.requests.values()}
    assert survivors, "crash happened after all requests finished"

    eng2 = _engine(model, params,
                   reliability={"journal_path": str(tmp_path / "r2.jsonl")})
    eng2.warmup()
    with CompilationCounter() as cc:
        recovered = eng2.recover(jpath)
        res = eng2.serve(max_steps=400)
    assert cc.count == 0, \
        f"{cc.count} XLA compilations during recovery"
    assert sorted(recovered) == sorted(survivors)
    by_rid = {rid: (p, m) for rid, (p, m)
              in enumerate(zip(prompts, maxnew))}
    for rid in recovered:
        p, m = by_rid[rid]
        np.testing.assert_array_equal(res[rid]["tokens"], ref(p, m))
    # the recovered engine keeps journaling: everything ended cleanly
    assert eng2.reliability.journal_depth() == 0


def test_recover_preserves_rids_and_fcfs_order(toy, tmp_path):
    model, params, _ = toy
    jpath = str(tmp_path / "j.jsonl")
    eng = _engine(model, params, reliability={"journal_path": jpath})
    prompts = _prompts(12, (5, 6, 7))
    rids = [eng.submit(p, max_new_tokens=6, priority=i % 2)
            for i, p in enumerate(prompts)]
    eng.reliability.on_step_end()          # commit without serving
    eng2 = _engine(model, params)
    recovered = eng2.recover(jpath)
    assert recovered == rids               # original ids, original order
    # fresh submissions never collide with recovered rids
    nxt = eng2.submit(prompts[0], max_new_tokens=2)
    assert nxt == max(rids) + 1
    # priorities survived the journal round-trip
    for rid, i in zip(rids, range(len(rids))):
        assert eng2.scheduler.requests[rid].priority == i % 2


def test_journal_replay_units(tmp_path):
    class R:
        def __init__(self, rid, generated=()):
            self.rid = rid
            self.prompt = np.array([1, 2, 3], np.int32)
            self.max_new_tokens = 5
            self.priority = 1
            self.eos_token_id = None
            self.seed = 7
            self.deadline_s = 2.5
            self.work_budget = 99
            self.generated = list(generated)
            self.work_done = 0

    path = str(tmp_path / "j.jsonl")
    j = RequestJournal(path)
    j.record_submit(R(0))
    j.record_submit(R(1, generated=[4]))
    j.record_token(0, 11)
    j.record_token(0, 12)
    j.record_token(1, 13)
    j.commit()
    assert j.depth == 2
    j.record_end(1, "finished")
    j.commit()
    assert j.depth == 1
    j.close()
    with open(path, "a") as f:
        f.write('{"op": "tok", "rid": 0, "t": [9')   # torn final record
    live = RequestJournal.replay(path)
    assert len(live) == 1 and live[0]["rid"] == 0
    assert live[0]["generated"] == [11, 12]
    assert live[0]["deadline_s"] == 2.5
    assert live[0]["work_budget"] == 99
    assert live[0]["seed"] == 7


# ---------------------------------------------------------------------------
# poison quarantine (per-request fault isolation)
# ---------------------------------------------------------------------------

def test_poison_quarantines_one_lane_not_the_batch(toy):
    """NaN injected into one lane's embedding: THAT request aborts with
    reason 'poisoned'; its batch peers finish bit-identically; its
    freed (NaN-contaminated) blocks are safely reused by a later
    request — the value mask keeps stale NaN out of every einsum."""
    model, params, ref = toy
    eng = _engine(model, params)
    prompts = _prompts(13, (5, 7, 6))
    chaos.arm(poison_logits_at_step=7)
    try:
        rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
        res = eng.serve(max_steps=300)
        plan = chaos.active()
        poisoned_fired = [rid for k, rid in plan.fired
                          if k == "poison_logits"]
    finally:
        chaos.disarm()
    assert len(poisoned_fired) == 1
    bad = poisoned_fired[0]
    assert res[bad]["status"] == "poisoned"
    for rid, p in zip(rids, prompts):
        if rid != bad:
            assert res[rid]["status"] == "finished"
            np.testing.assert_array_equal(res[rid]["tokens"], ref(p, 10))
    assert eng.pool.blocks_in_use == 0
    # block reuse after quarantine: a new request over the freed pool
    # still matches generate() exactly (no NaN leakage)
    p2 = _prompts(14, (8,))[0]
    r2 = eng.submit(p2, max_new_tokens=8)
    res = eng.serve(max_steps=200)
    np.testing.assert_array_equal(res[r2]["tokens"], ref(p2, 8))
    rep = eng.serving_report()
    assert rep["reliability"]["aborts"]["poisoned"] == 1
    assert rep["requests"]["aborted"]["poisoned"] == 1


# ---------------------------------------------------------------------------
# chaos: slow steps (watchdog stall) + burst arrivals
# ---------------------------------------------------------------------------

def test_slow_step_chaos_trips_serving_stall_detector(toy):
    model, params, _ = toy
    events = []
    wd = TrainingWatchdog(stall_timeout=0.02)
    wd.add_callback(lambda e: events.append(e) or ACTION_CONTINUE)
    eng = _engine(model, params, watchdog=wd)
    chaos.arm(slow_serving_step_every=2, slow_serving_step_s=0.06)
    try:
        eng.submit(_prompts(15, (5,))[0], max_new_tokens=6)
        eng.serve(max_steps=100)
        plan = chaos.active()
        assert any(k == "slow_serving_step" for k, _ in plan.fired)
    finally:
        chaos.disarm()
    assert any(e.kind == EVENT_STALL for e in events), \
        "slowed serving steps never tripped the stall detector"


def test_burst_arrival_chaos_is_absorbed(toy):
    """Thundering-herd chaos: the armed plan releases extra arrivals in
    bursts; the engine absorbs them (evicting / queueing as needed) and
    every request stays bit-identical."""
    model, params, ref = toy
    eng = _engine(model, params, max_slots=2)
    base = _prompts(16, (5,))[0]
    burst_prompts = _prompts(17, (4, 6, 7, 5, 6, 4))
    chaos.arm(burst_arrival_every=3, burst_arrival_count=2)
    rids = {}
    try:
        rids[eng.submit(base, max_new_tokens=6)] = (base, 6)
        step = 0
        pending = list(burst_prompts)
        while eng.scheduler.has_work() or pending:
            step += 1
            for _ in range(chaos.serving_burst(step)):
                if pending:
                    p = pending.pop(0)
                    rids[eng.submit(p, max_new_tokens=5)] = (p, 5)
            eng.step()
            assert step < 400
        plan = chaos.active()
        assert any(k == "burst_arrival" for k, _ in plan.fired)
    finally:
        chaos.disarm()
    res = eng.results
    for rid, (p, m) in rids.items():
        np.testing.assert_array_equal(res[rid]["tokens"], ref(p, m))


# ---------------------------------------------------------------------------
# metrics edge cases + goodput accounting (satellite)
# ---------------------------------------------------------------------------

def test_percentiles_total_over_edge_cases():
    assert _pct([], .5) is None and _pct([], .95) is None
    assert _pct([3.0], .5) == 3.0 and _pct([3.0], .95) == 3.0
    assert _pct([1.0, 2.0], 0.0) == 1.0
    assert _pct([1.0, 2.0], 1.0) == 2.0
    assert _pct([1.0, 2.0], 7.5) == 2.0      # clamped, not an IndexError
    m = ServingMetrics(clock=lambda: 0.0)
    rep = m.report()                          # nothing recorded: no raise
    assert rep["ttft_s"]["p95"] is None
    assert rep["throughput"]["tokens_per_slot_step"] is None
    assert rep["throughput"]["goodput_tokens_per_slot_step"] is None
    m.record_submit(0)
    m.record_token(0)
    rep = m.report()                          # single sample: no raise
    assert rep["ttft_s"]["p50"] == rep["ttft_s"]["p95"]


def test_goodput_distinguishes_finished_from_aborted_tokens():
    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0])
    for rid in (1, 2):
        m.record_submit(rid)
        for _ in range(4):
            t[0] += 1.0
            m.record_token(rid)
    m.record_step(queue_depth=0, running=2, slots=4, occupancy=.5,
                  fragmentation=0., decoded=True)
    m.record_finish(1, "finished")
    m.record_finish(2, "shed")
    rep = m.report()
    assert rep["tokens"]["generated"] == 8
    assert rep["tokens"]["useful"] == 4
    assert rep["tokens"]["wasted"] == 4
    assert rep["throughput"]["useful_fraction"] == pytest.approx(0.5)
    assert rep["throughput"]["goodput_tokens_per_slot_step"] \
        == pytest.approx(rep["throughput"]["tokens_per_slot_step"] / 2)
    assert rep["requests"]["aborted"] == {"shed": 1}
    # step-time EMA armed after two steps
    t[0] += 1.0
    m.record_step(queue_depth=0, running=0, slots=4, occupancy=.0,
                  fragmentation=0., decoded=False)
    assert m.step_time() == pytest.approx(1.0)


def test_reliability_report_and_last_metrics_idiom(toy):
    model, params, _ = toy
    eng = _engine(model, params)
    eng.submit(_prompts(18, (5,))[0], max_new_tokens=4)
    eng.serve(max_steps=100)
    rel = eng.serving_report()["reliability"]
    assert set(rel) >= {"armed", "aborts", "admission", "journal_depth",
                        "draining"}
    assert rel["aborts"] == {"expired": 0, "budget": 0, "shed": 0,
                             "poisoned": 0}
    assert not rel["armed"]["shedding"] and not rel["armed"]["journal"]
    lm = eng._last_metrics
    for key in ("shed", "expired", "poisoned", "journal_depth",
                "draining"):
        assert key in lm, key
    assert set(lm["events"]) >= {"expired", "budget", "poisoned"}
