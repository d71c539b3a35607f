"""Fleet-scale serving: a host-level router over K engine replicas.

Millions of users means more than one engine — and at fleet scale a
replica draining or dying is a ROUTINE event, not an outage.  This
module composes the PR-9 reliability primitives into a fault-tolerant
fleet layer:

- **SLO-aware dispatch** — every arrival is placed on the replica with
  the lowest predicted TTFT, computed per replica by the SAME
  queue-depth x measured-TPOT estimator the admission gate uses
  (``reliability.Reliability.predicted_ttft_s``).  An idle or
  not-yet-measured replica predicts 0 and soaks up traffic first.
  ``dispatch="round-robin"`` is the baseline placement.
- **Replica health / circuit breaker** — each replica carries a
  watchdog heartbeat (the engine's per-step ``observe_serving_step``);
  stall events, poison quarantines and step crashes are health STRIKES.
  A strike puts the replica in bounded retry/backoff
  (``retry_backoff_steps`` x streak); ``max_consecutive_failures``
  consecutive strikes trip the breaker and the replica is marked DEAD.
  A clean step resets the streak.
- **Journal-backed migration** — a dead (or drained) replica's
  journal-live requests are re-placed onto survivors through the
  existing ``recover()``/eviction-re-prefill path: rids and FCFS order
  preserved, work budgets carried over, greedy continuations
  BIT-IDENTICAL, zero recompiles (same-config replicas share the
  lru-cached compiled programs, so the fleet-wide CompilationCounter
  pin holds).  The router assigns globally-unique rids in arrival
  order, which is what makes multi-journal merges
  (``RequestJournal.replay_many``) FCFS-correct by construction.
- **Role-tagged replicas** — ``roles=("prefill", "decode", ...)``
  splits prefill (compute-bound, bursty) from decode (memory-bound,
  steady) per the placement semantics of PAPERS.md 2601.02311.  A
  request prefills on a prefill replica; the moment its first token
  exists, its KV moves to a decode replica as a PAGED-BLOCK transfer
  (``engine.export_request``/``import_request`` — the same block-pool
  layout checkpoints round-trip), priced per handoff by
  ``runtime.comm_accounting.serving_kv_handoff_collectives``.

The router's step loop is pure host work (graftlint holds
``serving/fleet.py`` to the hot-path bar): the only device traffic is
the KV handoff itself — one batched fetch on export, one fixed-shape
scatter on import, at most one handoff per prefill replica per step.

Chaos: ``kill_replica_after_steps`` / ``slow_replica_step_every``
(runtime/resilience/chaos.py) target ONE replica so the whole failure
matrix — kill mid-decode, kill mid-drain, kill during migration
replay — is tier-1-testable on a deterministic StepClock, the same way
the PR-9 overload guard is.  The router observes chaos firings through
a weakref trampoline (the PR-10 idiom), so abandoned fleets never pin
K engines in the process-global observer list.
"""
import itertools
import time
import weakref
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from deepspeed_tpu.runtime.comm_accounting import (
    serving_kv_handoff_bytes)
from deepspeed_tpu.runtime.resilience import chaos
from deepspeed_tpu.runtime.resilience.watchdog import (ACTION_CONTINUE,
                                                       EVENT_STALL,
                                                       TrainingWatchdog)
from deepspeed_tpu.serving.decoder import decoder_for
from deepspeed_tpu.serving.engine import InferenceEngine
from deepspeed_tpu.serving.reliability import (ABORT_POISONED,
                                               RequestJournal)
from deepspeed_tpu.telemetry.metrics import nearest_rank
from deepspeed_tpu.utils.logging import logger

REPLICA_HEALTHY = "healthy"
REPLICA_BACKOFF = "backoff"    # struck out, waiting out a bounded retry
REPLICA_DEAD = "dead"          # breaker tripped: migrated, never stepped
REPLICA_DRAINED = "drained"    # graceful retirement: migrated, done

ROLE_BOTH = "both"
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"
_ROLES = (ROLE_BOTH, ROLE_PREFILL, ROLE_DECODE)


@dataclass(frozen=True)
class FleetConfig:
    """Router knobs.  ``dispatch="slo"`` is the armed default;
    ``"round-robin"`` is the explicit baseline (no DISARM warning — the
    caller asked for it).  The breaker fields bound how long a sick
    replica is retried before it is declared dead: strike k backs off
    ``retry_backoff_steps * k`` router steps, and
    ``max_consecutive_failures`` strikes with no clean step between
    them trip the breaker.  ``transport_timeout_steps`` is the
    step-clock heartbeat window for a transport-backed fleet (ISSUE
    16): a peer silent past it is voted on and — agreed — marked dead
    through the same breaker/migration path a crash takes."""
    dispatch: str = "slo"                 # "slo" | "round-robin"
    max_consecutive_failures: int = 3
    retry_backoff_steps: int = 2
    stall_timeout_s: float = 0.0          # per-replica stall detector
    transport_timeout_steps: int = 3


@dataclass(frozen=True)
class AutoscaleConfig:
    """Telemetry-driven replica-set sizing (ISSUE 16).  The signals are
    the unified metrics the router already computes every step: queue
    depth per active replica (waiting + running, the load the fleet is
    actually carrying) and — optionally — the worst predicted TTFT
    across replicas (the same estimator SLO dispatch uses;
    ``scale_up_ttft_s=0`` disables that trigger).  ``cooldown_steps``
    ticks must pass after any scale event before the next one, so a
    burst cannot thrash the set; scale-down is a graceful
    ``drain_replica`` (a death you scheduled — journal-backed, zero
    lost requests), never a kill."""
    min_replicas: int = 1
    max_replicas: int = 4
    scale_up_queue_depth: float = 4.0     # waiting+running per replica
    scale_down_queue_depth: float = 1.0
    scale_up_ttft_s: float = 0.0          # 0 = queue-depth trigger only
    cooldown_steps: int = 8
    evaluate_every: int = 1


class ReplicaHandle:
    """One replica's router-side state: the engine, its role, its
    health, and its journal path (the migration source of truth)."""

    def __init__(self, index, engine, role, journal_path):
        self.index = index
        self.engine = engine
        self.role = role
        self.journal_path = journal_path
        self.state = REPLICA_HEALTHY
        self.draining = False
        self.consecutive_failures = 0
        self.backoff_until = 0
        self.failures: Dict[str, int] = {}    # kind -> total strikes
        self.stall_flag = False
        self.placed = 0                       # requests routed here

    @property
    def alive(self) -> bool:
        return self.state in (REPLICA_HEALTHY, REPLICA_BACKOFF)


class FleetRouter:
    """Host-level router over K in-process :class:`InferenceEngine`
    replicas sharing one clock (a StepClock in tests/benches, so every
    latency and deadline is deterministic).

    The router owns the global rid space: every ``submit`` assigns the
    next rid and passes it down with ``_rid=``, so rids are unique and
    monotone in arrival order ACROSS replicas — journals from different
    replicas merge FCFS-correctly by rid alone.
    """

    def __init__(self, model, params, *, replicas=2, roles=None,
                 clock=time.monotonic, config=None, reliability=None,
                 journal_dir=None, engine_kwargs=None, telemetry=None,
                 autoscale=None, transport=None):
        assert replicas >= 1
        cfg = config if isinstance(config, FleetConfig) \
            else FleetConfig(**(config or {}))
        assert cfg.dispatch in ("slo", "round-robin"), cfg.dispatch
        self.config = cfg
        self.clock = clock
        roles = tuple(roles) if roles else (ROLE_BOTH,) * replicas
        assert len(roles) == replicas, (roles, replicas)
        assert all(r in _ROLES for r in roles), roles
        assert any(r in (ROLE_BOTH, ROLE_PREFILL) for r in roles), \
            "fleet needs at least one prefill-capable replica"
        if any(r != ROLE_BOTH for r in roles):
            assert any(r in (ROLE_BOTH, ROLE_DECODE) for r in roles), \
                "role-split fleet needs a decode-capable replica"
        self._role_split = any(r == ROLE_PREFILL for r in roles)
        # retained for autoscale scale-up: a grown replica is built from
        # the SAME spec as the founding set (and shares the lru-cached
        # compiled programs, so growing costs no recompile)
        self._model = model
        # held ONCE, as the model states (decoder.py, ``hold``): every
        # replica's engine finds the tree held and shares it
        self._params = decoder_for(model.config).hold(params)
        self._engine_kwargs = dict(engine_kwargs or {})
        self._reliability_spec = dict(reliability or {})
        self._journal_dir = journal_dir
        self.replicas: List[ReplicaHandle] = []
        for i in range(replicas):
            self.replicas.append(self._new_replica(i, roles[i]))
        self._rids = itertools.count()
        self._owner: Dict[int, int] = {}      # rid -> replica index
        self._router_results: Dict[int, dict] = {}   # lost requests
        self._rr = itertools.count()
        self._step_idx = 0
        self.migrations = 0
        self.handoffs: List[dict] = []
        self.handoff_bytes = 0
        self.lost: List[int] = []
        self.replica_steps = 0      # sum of alive replicas over steps:
        #                             the honest autoscale denominator
        self._arm_telemetry(telemetry)
        self._arm_autoscale(autoscale)
        self._arm_transport(transport)

    def _new_replica(self, i, role):
        """Build one replica handle from the retained fleet spec — the
        shared constructor of the founding set and every autoscale
        grow."""
        rel = dict(self._reliability_spec)
        jpath = None
        if self._journal_dir is not None:
            import os

            os.makedirs(str(self._journal_dir), exist_ok=True)
            jpath = os.path.join(str(self._journal_dir),
                                 f"replica{i}.jsonl")
            rel["journal_path"] = jpath
        wd = None
        if self.config.stall_timeout_s > 0:
            wd = TrainingWatchdog(
                stall_timeout=self.config.stall_timeout_s)
        eng = InferenceEngine(self._model, self._params, clock=self.clock,
                              reliability=rel or None, watchdog=wd,
                              **self._engine_kwargs)
        eng._replica_index = i
        rep = ReplicaHandle(i, eng, role, jpath)
        if wd is not None:
            wd.add_callback(self._stall_cb(rep))
        return rep

    @staticmethod
    def _stall_cb(rep):
        # plain function over the handle (no engine/router capture): the
        # watchdog lives on the handle, so no process-global pinning
        def _cb(event):
            if event.kind == EVENT_STALL:
                rep.stall_flag = True
            return ACTION_CONTINUE
        return _cb

    @property
    def dispatch_armed(self) -> bool:
        """SLO-aware placement unless round-robin was asked for."""
        return self.config.dispatch != "round-robin"

    # -- arming (DISARMED discipline) -----------------------------------
    def _arm_telemetry(self, spec):
        """Arm the router telemetry session (``router`` tracer lane +
        chaos instants via a weakref observer).  Disarmed fleets hold
        ``self._tracer = None`` — one attribute check per step.  A spec
        with ``enabled=false`` warns DISARMED instead of silently
        observing nothing."""
        self.telemetry = None
        self._tracer = None
        self._owns_telemetry = False
        self._lane_router = 0
        self._chaos_observer = None
        if spec is None:
            return
        from deepspeed_tpu.telemetry import Telemetry

        if isinstance(spec, Telemetry):
            tel = spec
        else:
            self._owns_telemetry = True
            tcfg = dict(spec)
            if not tcfg.pop("enabled", True):
                logger.warning(
                    "fleet telemetry: DISARMED — a telemetry config was "
                    "passed with enabled=false; no router lane or "
                    "per-replica metric stream will be produced")
                return
            tel = Telemetry(**tcfg)
        self.telemetry = tel
        self._tracer = tel.tracer
        if self._tracer is None:
            return
        self._lane_router = self._tracer.lane("router")
        self._tracer.intern("router_step", args=("step",))
        # weakref trampoline (PR-10 idiom): the process-global chaos
        # observer list must never pin the router (and through it K
        # engines and their pools) after the caller drops it
        ref = weakref.ref(self)

        def _chaos_obs(kind, detail=None):
            rt = ref()
            if rt is not None:
                rt._telemetry_chaos_cb(kind, detail)

        self._chaos_observer = chaos.add_observer(_chaos_obs)

    def _arm_autoscale(self, spec):
        """Arm telemetry-driven autoscaling (ISSUE 16), or warn loudly
        (DISARMED) naming every blocker and keep the replica set fixed.
        Blockers: a role-split fleet (growing a replica means choosing
        its prefill/decode role — a placement policy this autoscaler
        does not make) and invalid bounds."""
        self.autoscale_armed = False
        self._autoscale = None
        self.scale_events: List[dict] = []
        self._scale_cooldown_until = 0
        if spec is None:
            return
        cfg = spec if isinstance(spec, AutoscaleConfig) \
            else AutoscaleConfig(**spec)
        blockers = []
        if self._role_split:
            blockers.append(
                "the fleet is role-split (a grown replica needs a "
                "prefill/decode placement decision this autoscaler "
                "does not make)")
        if cfg.min_replicas < 1 or cfg.max_replicas < cfg.min_replicas:
            blockers.append(
                f"invalid replica bounds "
                f"[{cfg.min_replicas}, {cfg.max_replicas}]")
        if blockers:
            logger.warning(
                "fleet autoscaler: DISARMED — %s; the replica set stays "
                "fixed at %d.", "; ".join(blockers), len(self.replicas))
            return
        self._autoscale = cfg
        self.autoscale_armed = True

    def _arm_transport(self, transport):
        """Arm the cross-process peer bus (ISSUE 16 transport seam):
        replica ``i``'s host liveness rides transport peer ``i+1``
        (rank 0 is the router).  Armed, a peer silent past
        ``transport_timeout_steps`` router ticks is voted on and —
        agreed — its replica takes the breaker/migration path a crash
        takes.  Blockers warn DISARMED and leave replica liveness
        in-process (engine watchdog + chaos only): a world that does
        not map onto the replica set, or an armed autoscaler (a grown
        replica would have no transport peer)."""
        self._transport = None
        self.transport_armed = False
        if transport is None:
            return
        blockers = []
        if transport.world != len(self.replicas) + 1:
            blockers.append(
                f"transport world {transport.world} does not map onto "
                f"{len(self.replicas)} replicas + 1 router (peer rank "
                f"i+1 <-> replica i)")
        if self.autoscale_armed:
            blockers.append(
                "autoscaling is armed (a grown replica would have no "
                "transport peer; grow the transport world first)")
        if blockers:
            logger.warning(
                "fleet transport: DISARMED — %s; replica liveness stays "
                "in-process (watchdog/chaos only).", "; ".join(blockers))
            return
        self._transport = transport.start()
        self.transport_armed = True

    def _telemetry_chaos_cb(self, kind, detail=None):
        tr = self._tracer
        if tr is not None and kind in ("kill_replica", "slow_replica"):
            tr.instant(f"chaos_{kind}", self._lane_router,
                       a0=int(detail) if detail is not None else 0)

    def close(self):
        """Release process-global hooks (chaos observer) and close a
        telemetry session this router created from a dict spec.
        Idempotent; also runs at GC."""
        obs = getattr(self, "_chaos_observer", None)
        if obs is not None:
            self._chaos_observer = None
            chaos.remove_observer(obs)
        if getattr(self, "_owns_telemetry", False) \
                and self.telemetry is not None:
            self.telemetry.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # lint: allow-broad-except — interpreter
            # teardown can fail imports mid-GC; never raise from __del__
            pass

    # -- placement ------------------------------------------------------
    def _eligible(self, *, decode_target=False, exclude=None):
        """Replicas a new request (or a KV handoff when
        ``decode_target``) may land on: alive, not draining, role
        matches.  Healthy replicas are preferred over ones sitting out
        a backoff; a backoff replica is still a legal last resort (it
        is suspected, not dead)."""
        want = (ROLE_BOTH, ROLE_DECODE) if decode_target \
            else (ROLE_BOTH, ROLE_PREFILL)
        cands = [r for r in self.replicas
                 if r is not exclude and r.alive and not r.draining
                 and r.role in want]
        healthy = [r for r in cands if r.state == REPLICA_HEALTHY]
        return healthy or cands

    def _place(self, extra_tokens, *, decode_target=False, exclude=None):
        """Pick the target replica: lowest predicted TTFT when armed
        (an unmeasured/idle replica predicts 0 — it admits freely, so
        it fills first), round-robin otherwise.  None = no eligible
        replica (total outage)."""
        cands = self._eligible(decode_target=decode_target,
                               exclude=exclude)
        if not cands:
            return None
        if not self.dispatch_armed:
            return cands[next(self._rr) % len(cands)]
        scored = [(r.engine.reliability.predicted_ttft_s(
            extra_tokens=extra_tokens) or 0.0, r.index, r)
            for r in cands]
        return min(scored)[2]

    # -- public API -----------------------------------------------------
    def submit(self, prompt, max_new_tokens, *, priority=0,
               eos_token_id=None, seed=0, deadline_s=None,
               work_budget=None, replica=None) -> int:
        """Submit one request to the fleet: the router assigns the
        globally-unique rid and places the request (``replica=`` pins
        it — tests and sticky-routing callers).  The chosen replica's
        own admission gate still applies: under predicted overload it
        may shed it (``results[rid]["status"] == "shed"``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if replica is not None:
            rep = self.replicas[replica]
            if not rep.alive or rep.draining:
                raise RuntimeError(
                    f"fleet router: replica {replica} is "
                    f"{'draining' if rep.draining else rep.state} — a "
                    f"pinned submission there would queue forever "
                    f"(dead/drained replicas are never stepped); pin a "
                    f"live replica or let the router place it")
        else:
            rep = self._place(len(prompt))
        if rep is None:
            raise RuntimeError(
                "fleet router: no eligible replica (all dead, drained "
                "or draining) — total outage, submission refused")
        rid = next(self._rids)
        rep.engine.submit(prompt, max_new_tokens, priority=priority,
                          eos_token_id=eos_token_id, seed=seed,
                          deadline_s=deadline_s, work_budget=work_budget,
                          _rid=rid)
        self._owner[rid] = rep.index
        rep.placed += 1
        return rid

    def step(self) -> dict:
        """One router tick: step every live replica (health-checked,
        breaker-guarded), retire drained ones, run at most one KV
        handoff per prefill replica.  Pure host work apart from the
        handoff transfer itself."""
        self._step_idx += 1
        tr = self._tracer
        _t0 = tr.begin() if tr is not None else 0.0
        events = {"failures": [], "dead": [], "drained": [],
                  "migrated": [], "handoffs": [], "scaled": []}
        if self._transport is not None:
            self._transport_tick(events)
        for rep in self.replicas:
            self._step_replica(rep, events)
        if self.autoscale_armed:
            self._autoscale_tick(events)
        self.replica_steps += sum(1 for r in self.replicas if r.alive)
        self._last_metrics = {
            "step": self._step_idx,
            "alive": sum(1 for r in self.replicas if r.alive),
            "dead": sum(1 for r in self.replicas
                        if r.state == REPLICA_DEAD),
            "migrations": self.migrations,
            "handoffs": len(self.handoffs),
            "handoff_bytes": self.handoff_bytes,
            "lost": len(self.lost),
            "replica_steps": self.replica_steps,
            "scale_events": len(self.scale_events),
            **self._cache_spec_aggregates(),
        }
        if tr is not None:
            tr.complete("router_step", self._lane_router, _t0,
                        a0=self._step_idx)
        if self.telemetry is not None:
            self.telemetry.on_step(self._step_idx, self._last_metrics)
        return events

    def _step_replica(self, rep, events):
        if not rep.alive:
            return
        if rep.state == REPLICA_BACKOFF \
                and self._step_idx < rep.backoff_until:
            return
        eng = rep.engine
        if rep.state == REPLICA_BACKOFF and not eng.scheduler.has_work():
            # the backoff window elapsed and the replica has nothing to
            # retry against: close the probation instead of leaving it
            # deprioritized forever with a stale streak (a genuinely
            # hard-down replica re-strikes on its next real step)
            rep.state = REPLICA_HEALTHY
            rep.consecutive_failures = 0
        if eng.scheduler.has_work():
            poisoned0 = eng.reliability.aborts[ABORT_POISONED]
            try:
                eng.step()
            except Exception as e:  # lint: allow-broad-except — replica
                # fault ISOLATION is the router's job: any exception out
                # of one replica's step (chaos ChaosInterrupt, a real
                # crash) must strike that replica, never the fleet
                self._on_failure(rep, "crash", repr(e), events)
                return
            if rep.stall_flag:
                rep.stall_flag = False
                self._on_failure(rep, "stall",
                                 "stall detector fired", events)
                return
            if eng.reliability.aborts[ABORT_POISONED] > poisoned0:
                # the engine already quarantined the lane; the replica
                # made progress, but repeated poison is a sick host —
                # strike it (no early return: it can still drain/serve)
                self._on_failure(rep, "poison",
                                 "poisoned lane quarantined", events)
                if not rep.alive:
                    return
            else:
                rep.consecutive_failures = 0
                if rep.state == REPLICA_BACKOFF:
                    rep.state = REPLICA_HEALTHY
        if rep.draining and not eng.scheduler.in_flight():
            self._retire_drained(rep, events)
            return
        if self._role_split and rep.role == ROLE_PREFILL:
            self._handoff_tick(rep, events)

    def serve(self, *, max_steps=100000) -> dict:
        steps = 0
        while self.has_work():
            if steps >= max_steps:
                raise RuntimeError(
                    f"fleet serve() exceeded max_steps={max_steps}")
            self.step()
            steps += 1
        return self.results

    def has_work(self) -> bool:
        return any(r.alive and r.engine.scheduler.has_work()
                   for r in self.replicas)

    @property
    def results(self) -> dict:
        """Merged result view across the fleet (rids are globally
        unique, so the union is well-defined); router-level ``lost``
        entries cover requests no survivor could take."""
        out = dict(self._router_results)
        for rep in self.replicas:
            out.update(rep.engine.results)
        return out

    def warmup(self):
        """Compile everything steady state needs on every replica (the
        same-config replicas share the lru-cached programs, so the
        fleet pays for ONE compile set), plus — in a role-split fleet —
        one synthetic handoff to warm the paged-block gather/scatter
        shapes.  Resets every counter afterwards."""
        for rep in self.replicas:
            rep.engine.warmup()
        if self._role_split:
            for rep in self.replicas:
                rep.engine._warming = True
            try:
                # max_new must outlive the admission step: the engine
                # prefills AND decodes in one tick, so a 2-token request
                # finishes before the router's handoff tick can see it
                self.submit(np.zeros(2, np.int32), max_new_tokens=6)
                self.serve(max_steps=200)
                assert self.handoffs, \
                    "role-split warmup ran no KV handoff"
            finally:
                for rep in self.replicas:
                    rep.engine._warming = False
                    rep.engine.results.clear()
                    rep.engine.metrics.reset()
                    rep.engine._last_metrics = {}
                    rep.engine._step_idx = 0
        self._rids = itertools.count()
        self._rr = itertools.count()
        self._owner.clear()
        self._router_results.clear()
        self._step_idx = 0
        self.migrations = 0
        self.handoffs = []
        self.handoff_bytes = 0
        self.lost = []
        self.replica_steps = 0
        self.scale_events = []
        self._scale_cooldown_until = 0
        for rep in self.replicas:
            rep.placed = 0

    # -- drain / failure / migration ------------------------------------
    def drain_replica(self, index) -> None:
        """Gracefully retire one replica: admission stops at its next
        step boundary, in-flight requests finish there, queued ones
        migrate to survivors once it empties (journal-backed, same path
        as death — a drain is just a death you scheduled)."""
        rep = self.replicas[index]
        rep.draining = True
        rep.engine.request_drain()
        if self._tracer is not None:
            self._tracer.instant("drain_replica", self._lane_router,
                                 a0=index)
        logger.info("fleet: draining replica %d", index)

    # -- transport peer liveness (ISSUE 16) -----------------------------
    def _transport_tick(self, events):
        """One beat of the cross-process peer bus: broadcast the router
        step, classify each peer's step-clock lag, and turn an AGREED
        dead peer into the replica breaker/migration path.  Suspicion
        without agreement (the ack vote timed out on a wedged survivor)
        is a strike, never a one-sided verdict — the breaker's bounded
        streak still converges if the peer stays silent."""
        w = self._step_idx
        beats = self._transport.heartbeat_tick(w)
        timeout = self.config.transport_timeout_steps
        for rep in self.replicas:
            peer = rep.index + 1
            if not rep.alive:
                continue
            lag = w - beats.get(peer, 0)
            if lag <= timeout:
                continue
            if self._transport.vote_dead([peer], w):
                logger.warning(
                    "fleet: transport peer %d (replica %d) silent %d "
                    "steps — coordinated dead verdict at router step "
                    "%d; breaker tripped, migrating its journal",
                    peer, rep.index, lag, w)
                rep.failures["peer_dead"] = \
                    rep.failures.get("peer_dead", 0) + 1
                events["failures"].append(
                    {"replica": rep.index, "kind": "peer_dead"})
                if self._tracer is not None:
                    self._tracer.instant("replica_peer_dead",
                                         self._lane_router, a0=rep.index)
                self._transport.mark_dead(peer)
                self._mark_dead(rep, events)
            else:
                self._on_failure(
                    rep, "peer_stale",
                    f"transport peer {peer} silent {lag} steps, no "
                    f"verdict agreement yet", events)

    # -- telemetry-driven autoscaling (ISSUE 16) ------------------------
    def _autoscale_tick(self, events):
        """Resize the replica set from the unified metrics stream:
        queue depth per active replica (waiting + running) and — when
        the trigger is configured — the worst predicted TTFT across
        replicas.  Pure host bookkeeping; the only expensive act is the
        grow itself (one engine build sharing the lru-cached compiled
        programs) or a graceful drain."""
        cfg = self._autoscale
        w = self._step_idx
        if w < self._scale_cooldown_until \
                or (cfg.evaluate_every > 1 and w % cfg.evaluate_every):
            return
        active = [r for r in self.replicas
                  if r.alive and not r.draining]
        if not active:
            return
        depth = sum(r.engine.scheduler.queue_depth()
                    + len(r.engine.scheduler.running) for r in active)
        per_replica = depth / len(active)
        ttft = 0.0
        if cfg.scale_up_ttft_s > 0:
            ttft = max(r.engine.reliability.predicted_ttft_s(
                extra_tokens=0) or 0.0 for r in active)
        if len(active) < cfg.max_replicas \
                and (per_replica >= cfg.scale_up_queue_depth
                     or (cfg.scale_up_ttft_s > 0
                         and ttft >= cfg.scale_up_ttft_s)):
            self._scale_up(events, per_replica, ttft)
        elif len(active) > cfg.min_replicas \
                and per_replica <= cfg.scale_down_queue_depth \
                and not any(r.draining for r in self.replicas):
            self._scale_down(active, events, per_replica)

    def _record_scale(self, direction, replica, events, per_replica,
                      ttft):
        ev = {"step": self._step_idx, "dir": direction,
              "replica": replica,
              "active": sum(1 for r in self.replicas
                            if r.alive and not r.draining),
              "queue_depth_per_replica": round(per_replica, 4),
              "predicted_ttft_s": round(ttft, 4)}
        self.scale_events.append(ev)
        events["scaled"].append(dict(ev))
        self._scale_cooldown_until = self._step_idx \
            + self._autoscale.cooldown_steps
        if self._tracer is not None:
            self._tracer.instant(f"scale_{direction}", self._lane_router,
                                 a0=replica)
        logger.info(
            "fleet autoscaler: scale-%s replica %d at router step %d "
            "(queue depth/replica %.2f, predicted TTFT %.3fs) — %d "
            "active", direction.upper(), replica, self._step_idx,
            per_replica, ttft, ev["active"])

    def _scale_up(self, events, per_replica, ttft):
        idx = len(self.replicas)
        rep = self._new_replica(idx, ROLE_BOTH)
        self.replicas.append(rep)
        # same-config engines share the lru-cached compiled programs:
        # the grow pays host setup, never a recompile (the fleet-wide
        # CompilationCounter pin holds through scale events)
        rep.engine.warmup()
        self._record_scale("up", idx, events, per_replica, ttft)

    def _scale_down(self, active, events, per_replica):
        # retire the least-loaded active replica — but never the last
        # prefill-capable one (autoscale only arms on non-role-split
        # fleets, so any ROLE_BOTH survivor keeps the fleet whole)
        victim = min(active, key=lambda r: (
            r.engine.scheduler.queue_depth()
            + len(r.engine.scheduler.running), r.index))
        if sum(1 for r in active if r is not victim) < 1:
            return
        self.drain_replica(victim.index)
        self._record_scale("down", victim.index, events, per_replica,
                           0.0)

    def _on_failure(self, rep, kind, detail, events):
        rep.failures[kind] = rep.failures.get(kind, 0) + 1
        rep.consecutive_failures += 1
        events["failures"].append({"replica": rep.index, "kind": kind})
        if self._tracer is not None:
            self._tracer.instant(f"replica_{kind}", self._lane_router,
                                 a0=rep.index)
        if rep.consecutive_failures \
                >= self.config.max_consecutive_failures:
            logger.warning(
                "fleet: replica %d %s (%s) — strike %d/%d, breaker "
                "TRIPPED: marking dead and migrating its journal",
                rep.index, kind, detail, rep.consecutive_failures,
                self.config.max_consecutive_failures)
            self._mark_dead(rep, events)
        else:
            rep.state = REPLICA_BACKOFF
            rep.backoff_until = self._step_idx \
                + self.config.retry_backoff_steps \
                * rep.consecutive_failures
            logger.warning(
                "fleet: replica %d %s (%s) — strike %d/%d, backing off "
                "until router step %d",
                rep.index, kind, detail, rep.consecutive_failures,
                self.config.max_consecutive_failures, rep.backoff_until)

    def _mark_dead(self, rep, events):
        rep.state = REPLICA_DEAD
        events["dead"].append(rep.index)
        if self._tracer is not None:
            self._tracer.instant("replica_dead", self._lane_router,
                                 a0=rep.index)
        self._migrate(rep, events)

    def _retire_drained(self, rep, events):
        """The drain finished its in-flight work; move the queued
        remainder to survivors and retire the replica."""
        self._migrate(rep, events)
        rep.state = REPLICA_DRAINED
        events["drained"].append(rep.index)
        logger.info("fleet: replica %d drained and retired", rep.index)

    def _migrate(self, rep, events):
        """Re-place a dead/drained replica's journal-live requests onto
        survivors through the recover()/re-prefill path — FCFS order
        (the journal's submit order), rids, priorities and work budgets
        all preserved; greedy continuations bit-identical.  The JOURNAL
        is the source of truth (a crashed host's memory is not
        trustworthy); without one, the replica's requests are recorded
        as lost — loudly."""
        if rep.journal_path is None:
            lost = [r for r in rep.engine.scheduler.requests.values()]
            if lost:
                logger.warning(
                    "fleet: replica %d has NO journal armed "
                    "(journal_dir unset) — %d live requests are LOST, "
                    "not migrated", rep.index, len(lost))
            for req in lost:
                self._record_lost(req.rid, req.prompt, req.generated)
            return
        entries = RequestJournal.replay(rep.journal_path)
        # ownership filter: a rid this replica handed off (or that was
        # otherwise re-placed) can still read as live in ITS journal —
        # the "migrated" end record may be torn by the crash — but the
        # router's owner map is authoritative in-process; migrating it
        # again would put one rid live on two engines
        entries = [e for e in entries
                   if self._owner.get(e["rid"], rep.index) == rep.index]
        for e in entries:
            self._migrate_entry(rep, e, events)
        if entries:
            logger.warning(
                "fleet: migrated %d journal-live requests off replica "
                "%d onto survivors", len(entries), rep.index)

    def _migrate_entry(self, rep, e, events, *, timing_from=None):
        extra = len(e["prompt"]) + len(e["generated"])
        target = self._place(extra, exclude=rep)
        if target is None:
            self._record_lost(e["rid"], e["prompt"], e["generated"])
            return
        target.engine.submit(
            np.asarray(e["prompt"], np.int32), e["max_new"],
            priority=e["priority"], eos_token_id=e["eos"],
            seed=e["seed"], deadline_s=e["deadline_s"],
            work_budget=e["work_budget"], _generated=e["generated"],
            _rid=e["rid"], _work_done=e.get("work_done", 0),
            _readmit=True)
        src = rep if rep is not None else timing_from
        if src is not None:
            # in-process, the dead replica's metrics outlive it and the
            # clock is shared: carry the original arrival (the sample
            # must include time waited on the corpse) and, when a first
            # token already landed there, its stamp (so the fleet never
            # counts two TTFT samples for one rid)
            target.engine.metrics.adopt_timing(
                e["rid"], *src.engine.metrics.export_timing(e["rid"]))
        self._owner[e["rid"]] = target.index
        self.migrations += 1
        events["migrated"].append(e["rid"])
        if self._tracer is not None:
            self._tracer.instant("migrate", self._lane_router,
                                 a0=e["rid"], a1=target.index)

    def _record_lost(self, rid, prompt, generated):
        self.lost.append(rid)
        self._router_results[rid] = {
            "tokens": np.concatenate(
                [np.asarray(prompt, np.int32),
                 np.asarray(list(generated), np.int32)]),
            "status": "lost", "evictions": 0,
        }
        logger.warning(
            "fleet: request %d LOST — no surviving replica could take "
            "it", rid)

    def recover(self, journal_paths) -> list:
        """Whole-fleet cold recovery: merge SEVERAL dead predecessors'
        journals (``RequestJournal.replay_many`` — global FCFS by rid,
        per-journal torn-tail tolerance) and re-place every live
        request across this fleet.  Returns the recovered rids in
        FCFS order."""
        entries = RequestJournal.replay_many(journal_paths)
        rids = []
        events = {"failures": [], "dead": [], "drained": [],
                  "migrated": [], "handoffs": []}
        for e in entries:
            self._migrate_entry(None, e, events)
            rids.append(e["rid"])
        if rids:
            # never REWIND the global rid space: a warm fleet may have
            # issued rids above the recovered journals' range, and a
            # rewound counter would hand a live rid to a new request
            nxt = next(self._rids)
            self._rids = itertools.count(max(nxt, max(rids) + 1))
        logger.info("fleet recover: re-placed %d journaled requests "
                    "from %d journals", len(rids), len(journal_paths))
        return rids

    # -- KV handoff (role-split fleets) ---------------------------------
    def _handoff_tick(self, rep, events):
        """Move at most ONE just-prefilled request (oldest first) from
        this prefill replica to a decode replica: a paged-block KV
        transfer — one batched fetch, one fixed-shape scatter — instead
        of a re-prefill.  Bounded to one per replica per step so the
        router's step stays O(1) device transfers."""
        running = rep.engine.scheduler.running
        if not running:
            return
        req = min(running.values(), key=lambda r: r.submit_seq)
        target = self._place(0, decode_target=True, exclude=rep)
        if target is None:
            return        # no decode replica up: keep decoding here
        if not target.engine.can_adopt(
                rep.engine.pool.blocks_of(req.rid)):
            return        # decode tier full: exporting would discard
                          # the computed KV into a re-prefill — the
                          # request is better off decoding here
        try:
            entry = rep.engine.export_request(req.rid)
        except Exception as e:  # lint: allow-broad-except — fault
            # isolation: the export's device fetch runs first, so a
            # faulting SOURCE leaves the request untouched (still
            # RUNNING there); strike the source and move on
            self._on_failure(rep, "crash", repr(e), events)
            return
        try:
            outcome = target.engine.import_request(entry)
        except Exception as e:  # lint: allow-broad-except — fault
            # isolation: the source already detached the request, so
            # after a faulting import it exists ONLY in `entry` —
            # strike the target and re-place it through the journal
            # re-prefill path on whichever replica remains
            self._on_failure(target, "crash", repr(e), events)
            # exclude nobody: the SOURCE is prefill-capable and may
            # take its own request back through a re-prefill — but the
            # timing stamps still come from it (the rid's real arrival
            # and first token live there; a fresh arrival would fake a
            # second, re-prefill-sized TTFT sample)
            self._migrate_entry(None, {
                "rid": entry["rid"], "prompt": entry["prompt"],
                "generated": entry["generated"],
                "max_new": entry["max_new_tokens"],
                "priority": entry["priority"], "eos": entry["eos"],
                "seed": entry["seed"],
                "deadline_s": entry["deadline_s"],
                "work_budget": entry["work_budget"],
                "work_done": entry["work_done"]}, events,
                timing_from=rep)
            return
        eng = rep.engine
        nbytes = serving_kv_handoff_bytes(
            eng.cfg.n_layer, eng.cfg.n_head, eng.cfg.head_dim,
            blocks=entry["n_blocks"], block_size=eng.bs,
            kv_dtype=np.dtype(eng.pool.dtype).name,
            quantized=eng.pool.quantized)
        self.handoff_bytes += nbytes
        self.handoffs.append({
            "rid": entry["rid"], "src": rep.index, "dst": target.index,
            "blocks": entry["n_blocks"], "bytes": nbytes,
            "outcome": outcome})
        self._owner[entry["rid"]] = target.index
        events["handoffs"].append(entry["rid"])
        if self._tracer is not None:
            self._tracer.instant("kv_handoff", self._lane_router,
                                 a0=entry["rid"], a1=target.index)

    # -- reporting ------------------------------------------------------
    def request_ttft(self, rid):
        """Fleet-wide TTFT of one request (recorded at the replica that
        admitted it; migrated requests keep their original arrival)."""
        for rep in self.replicas:
            t = rep.engine.metrics.ttft_of(rid)
            if t is not None:
                return t
        return None

    def _cache_spec_aggregates(self) -> dict:
        """Fleet-wide prefix-cache and speculative-decode accounting:
        sums of every replica's counters, with the ratios recomputed
        from the sums (a mean of per-replica rates would weight an
        idle replica the same as a saturated one).  Migrated and
        journal-recovered requests re-enter through the normal
        admission probe, so the tokens their re-prefill did NOT pay
        for show up here as ``migration_avoided_prefill_tokens``."""
        reps = [r.engine.metrics for r in self.replicas]
        lookups = sum(m.prefix_lookups for m in reps)
        hits = sum(m.prefix_hits for m in reps)
        avoided = sum(m.prefix_avoided_tokens for m in reps)
        readmit = sum(m.readmit_avoided_tokens for m in reps)
        verify = sum(m.spec_verify_steps for m in reps)
        accepted = sum(m.spec_accepted_tokens for m in reps)
        hist: dict = {}
        for m in reps:
            for k, v in m.spec_accept_hist.items():
                hist[k] = hist.get(k, 0) + v
        return {
            "prefix_lookups": lookups,
            "prefix_hits": hits,
            "prefix_hit_rate": (hits / lookups) if lookups else None,
            "prefix_avoided_prefill_tokens": avoided,
            "migration_avoided_prefill_tokens": readmit,
            "spec_verify_steps": verify,
            "spec_accepted_tokens": accepted,
            "tokens_per_verify":
                (accepted / verify) if verify else None,
            "spec_accept_hist": dict(sorted(hist.items())),
        }

    def fleet_ttft(self) -> dict:
        """Fleet-wide TTFT distribution: the union of every replica's
        per-request TTFT samples."""
        ttfts = [t for rep in self.replicas
                 for t in rep.engine.metrics.ttft]
        return {"n": len(ttfts),
                "mean": (sum(ttfts) / len(ttfts)) if ttfts else None,
                "p50": nearest_rank(ttfts, .5),
                "p95": nearest_rank(ttfts, .95)}

    def fleet_report(self) -> dict:
        """Router + per-replica summary (the fleet face of
        ``serving_report()``): placement/dispatch state, the failure
        ledger, migration/handoff accounting, and each replica's full
        serving report under its ``replica<i>`` key."""
        agg_useful = sum(r.engine.metrics.useful_tokens
                         for r in self.replicas)
        agg_slot_steps = sum(r.engine.metrics.slot_steps
                             for r in self.replicas)
        return {
            "config": {
                "replicas": len(self.replicas),
                "roles": [r.role for r in self.replicas],
                "dispatch": self.config.dispatch,
                "dispatch_armed": self.dispatch_armed,
                "max_consecutive_failures":
                    self.config.max_consecutive_failures,
                "retry_backoff_steps": self.config.retry_backoff_steps,
                "autoscale_armed": self.autoscale_armed,
                "transport_armed": self.transport_armed,
            },
            "router": {
                "steps": self._step_idx,
                "placements": {f"replica{r.index}": r.placed
                               for r in self.replicas},
                "migrations": self.migrations,
                "handoffs": len(self.handoffs),
                "handoff_bytes": self.handoff_bytes,
                "lost": list(self.lost),
                "ttft_s": self.fleet_ttft(),
                "goodput_tokens_per_slot_step":
                    (agg_useful / agg_slot_steps) if agg_slot_steps
                    else None,
                "replica_steps": self.replica_steps,
                "goodput_tokens_per_replica_step":
                    (agg_useful / self.replica_steps)
                    if self.replica_steps else None,
                "scale_events": [dict(e) for e in self.scale_events],
                "cache_and_spec": self._cache_spec_aggregates(),
            },
            "replicas": {
                f"replica{r.index}": {
                    "state": r.state, "role": r.role,
                    "draining": r.draining,
                    "consecutive_failures": r.consecutive_failures,
                    "failures": dict(r.failures),
                    "journal_path": r.journal_path,
                    "report": r.engine.serving_report(),
                } for r in self.replicas
            },
        }

    def telemetry_report(self) -> dict:
        """Unified fleet observability: the full :meth:`fleet_report`
        plus the router telemetry sections and every replica's
        step-level metrics flattened under ``replica<i>/`` prefixes —
        one stream, one namespace, no per-engine consumers."""
        rep = self.fleet_report()
        tel = self.telemetry
        rep["telemetry_armed"] = tel is not None
        flat = {}
        for r in self.replicas:
            for k, v in (r.engine._last_metrics or {}).items():
                if isinstance(v, (bool, int, float)):
                    flat[f"replica{r.index}/{k}"] = v
        for k, v in (getattr(self, "_last_metrics", None) or {}).items():
            flat[f"router/{k}"] = v
        rep["replica_metrics"] = flat
        if tel is None:
            return rep
        rep["metrics"] = tel.registry.snapshot()
        if tel.tracer is not None:
            rep["trace"] = tel.tracer.summary()
        return rep

    def export_trace(self, path):
        tr = self._tracer
        if tr is None:
            return None
        return tr.export_chrome_trace(path)
