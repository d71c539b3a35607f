"""graftlint framework + rule-catalog tests.

Three layers:
1. framework — registry, suppression comments, baseline add/expire
   semantics, fingerprint stability, reporters, CLI exit codes;
2. rules — every AST rule class has known-bad fixture snippets it fires
   on and known-good (fixed) twins it stays quiet on (the acceptance
   criterion for each rule class);
3. repo — the full rule set over the real tree is exercised by
   tests/unit/test_lint_guards.py (tier-1), not here, so this file stays
   jax-free and fast.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from tools.graftlint import core  # noqa: E402
from tools.graftlint.core import (REGISTRY, load_baseline, run_paths,  # noqa: E402
                                  run_source, save_baseline)

EXPECTED_RULES = {"bare-except", "donated-state", "host-sync",
                  "rank-branch-collective", "disarmed-discipline",
                  "raw-ckpt-write"}


def lint(src, path="deepspeed_tpu/x.py", rules=None):
    picked = None if rules is None else [REGISTRY[r] for r in rules]
    return run_source(src, path, rules=picked)


def rule_names(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# framework
# ---------------------------------------------------------------------------

def test_registry_catalog():
    assert EXPECTED_RULES <= set(REGISTRY)
    for name, rule in REGISTRY.items():
        assert rule.name == name and rule.description


def test_syntax_error_surfaces_as_finding():
    got = lint("def f(:\n")
    assert len(got) == 1 and got[0].rule == "syntax"


def test_findings_sorted_and_formatted():
    src = ("try:\n    x()\nexcept:\n    raise ValueError()\n"
           "try:\n    y()\nexcept Exception:\n    pass\n")
    got = lint(src)
    assert [f.line for f in got] == sorted(f.line for f in got)
    assert got[0].format().startswith("deepspeed_tpu/x.py:3: [bare-except]")


def test_suppression_same_line_prev_line_and_wrong_rule():
    base = "try:\n    x()\nexcept:{}\n    raise ValueError()\n"
    assert rule_names(lint(base.format(""))) == ["bare-except"]
    assert lint(base.format("  # graftlint: disable=bare-except")) == []
    # suppression on the PRECEDING line (wrapped statements)
    src = ("try:\n    x()\n# graftlint: disable=bare-except\nexcept:\n"
           "    raise ValueError()\n")
    assert lint(src) == []
    # a different rule's token does not suppress
    assert rule_names(lint(base.format(
        "  # graftlint: disable=host-sync"))) == ["bare-except"]
    # disable=all suppresses any rule
    assert lint(base.format("  # graftlint: disable=all")) == []


def test_rule_scoping_by_path():
    src = ("class E:\n"
           "    def _arm_x(self):\n"
           "        self._x_armed = True\n")
    assert rule_names(lint(src, "deepspeed_tpu/runtime/foo.py")) \
        == ["disarmed-discipline"]
    # the discipline is an engine-source contract, not a test-file one
    assert lint(src, "tests/unit/test_foo.py") == []


def _write(tmp, rel, text):
    p = os.path.join(tmp, rel)
    os.makedirs(os.path.dirname(p), exist_ok=True)
    with open(p, "w", encoding="utf-8") as f:
        f.write(text)
    return p


BAD_FILE = "def f():\n    try:\n        g()\n    except:\n        raise V()\n"
GOOD_FILE = "def f():\n    g()\n"


def test_baseline_add_then_expire(tmp_path):
    tmp = str(tmp_path)
    baseline = os.path.join(tmp, "baseline.json")
    _write(tmp, "pkg/mod.py", BAD_FILE)

    r1 = run_paths(roots=("pkg",), baseline_path=baseline, repo_root=tmp)
    assert len(r1.new) == 1 and not r1.baselined and not r1.stale
    assert r1.exit_code == 1

    save_baseline(r1, path=baseline, notes={
        fp: "intentional fixture" for fp in r1.fingerprints})
    r2 = run_paths(roots=("pkg",), baseline_path=baseline, repo_root=tmp)
    assert not r2.new and len(r2.baselined) == 1 and not r2.stale
    assert r2.exit_code == 0
    entry = load_baseline(baseline)["entries"][0]
    assert entry["note"] == "intentional fixture"
    assert entry["rule"] == "bare-except"

    # fix the violation: the entry goes stale, lint still passes, and a
    # baseline update prunes it
    _write(tmp, "pkg/mod.py", GOOD_FILE)
    r3 = run_paths(roots=("pkg",), baseline_path=baseline, repo_root=tmp)
    assert not r3.new and not r3.baselined and len(r3.stale) == 1
    assert r3.exit_code == 0
    save_baseline(r3, path=baseline)
    assert load_baseline(baseline)["entries"] == []


def test_baseline_fingerprint_survives_line_moves(tmp_path):
    tmp = str(tmp_path)
    baseline = os.path.join(tmp, "baseline.json")
    _write(tmp, "pkg/mod.py", BAD_FILE)
    r1 = run_paths(roots=("pkg",), baseline_path=baseline, repo_root=tmp)
    save_baseline(r1, path=baseline)
    # shift the violation down two lines: same text -> same fingerprint
    _write(tmp, "pkg/mod.py", "\n\n" + BAD_FILE)
    r2 = run_paths(roots=("pkg",), baseline_path=baseline, repo_root=tmp)
    assert not r2.new and len(r2.baselined) == 1 and not r2.stale


def test_scoped_baseline_update_preserves_out_of_scope(tmp_path):
    """A scoped run (subset of roots or rules) must neither report
    out-of-coverage baseline entries as stale nor delete them on a
    baseline update — the baseline is a whole-repo artifact."""
    tmp = str(tmp_path)
    baseline = os.path.join(tmp, "b.json")
    _write(tmp, "a/f.py", BAD_FILE)
    _write(tmp, "b/g.py", BAD_FILE)
    r_full = run_paths(roots=("a", "b"), baseline_path=baseline,
                       repo_root=tmp)
    save_baseline(r_full, path=baseline,
                  notes={fp: "keep" for fp in r_full.fingerprints})
    assert len(load_baseline(baseline)["entries"]) == 2

    # root-scoped: b/ is out of coverage — not stale, survives the update
    r_a = run_paths(roots=("a",), baseline_path=baseline, repo_root=tmp)
    assert not r_a.new and not r_a.stale
    save_baseline(r_a, path=baseline)
    entries = load_baseline(baseline)["entries"]
    assert {e["path"] for e in entries} == {"a/f.py", "b/g.py"}
    assert all(e["note"] == "keep" for e in entries)

    # rule-scoped: bare-except entries are out of coverage for host-sync
    r_rule = run_paths(roots=("a", "b"), rules=[REGISTRY["host-sync"]],
                       baseline_path=baseline, repo_root=tmp)
    assert not r_rule.stale
    save_baseline(r_rule, path=baseline)
    assert len(load_baseline(baseline)["entries"]) == 2


def test_run_paths_skips_pycache(tmp_path):
    tmp = str(tmp_path)
    _write(tmp, "pkg/__pycache__/junk.py", BAD_FILE)
    _write(tmp, "pkg/ok.py", GOOD_FILE)
    r = run_paths(roots=("pkg",), repo_root=tmp, use_baseline=False)
    assert not r.new


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.graftlint", *args],
                          cwd=cwd, capture_output=True, text=True,
                          timeout=120)


def test_cli_clean_dir_exits_zero():
    proc = _cli("tools/graftlint", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["summary"]["new"] == 0
    assert set(EXPECTED_RULES) <= set(payload["rules"])


def test_cli_new_finding_exits_nonzero(tmp_path):
    bad = _write(str(tmp_path), "bad.py", BAD_FILE)
    proc = _cli(bad, "--no-baseline")
    assert proc.returncode == 1
    assert "[bare-except]" in proc.stdout


def test_cli_json_shape_on_findings(tmp_path):
    bad = _write(str(tmp_path), "bad.py", BAD_FILE)
    proc = _cli(bad, "--no-baseline", "--json")
    payload = json.loads(proc.stdout)
    assert payload["summary"]["new"] == 1
    f = payload["new"][0]
    assert f["rule"] == "bare-except" and f["line"] == 4 and f["message"]


def test_cli_baseline_update_roundtrip(tmp_path):
    tmp = str(tmp_path)
    bad = _write(tmp, "bad.py", BAD_FILE)
    baseline = os.path.join(tmp, "b.json")
    assert _cli(bad, "--baseline", baseline).returncode == 1
    assert _cli(bad, "--baseline", baseline,
                "--baseline-update").returncode == 0
    assert _cli(bad, "--baseline", baseline).returncode == 0
    assert _cli(bad, "--baseline", baseline,
                "--strict-stale").returncode == 0
    _write(tmp, "bad.py", GOOD_FILE)
    assert _cli(bad, "--baseline", baseline).returncode == 0
    assert _cli(bad, "--baseline", baseline,
                "--strict-stale").returncode == 1


def test_cli_strict_stale_composes_with_baseline_update(tmp_path):
    """ISSUE 19 satellite bugfix: --strict-stale --baseline-update must
    BOTH prune the stale entries AND exit 1 in the same run — before,
    --baseline-update returned 0 unconditionally, so a CI job asking to
    prune-and-flag saw the prune but never the flag (exit code and
    prune disagreed)."""
    tmp = str(tmp_path)
    bad = _write(tmp, "bad.py", BAD_FILE)
    baseline = os.path.join(tmp, "b.json")
    assert _cli(bad, "--baseline", baseline,
                "--baseline-update").returncode == 0
    assert len(load_baseline(baseline)["entries"]) == 1

    _write(tmp, "bad.py", GOOD_FILE)   # the finding is fixed -> stale
    # plain --strict-stale: flags the drift, does NOT prune
    assert _cli(bad, "--baseline", baseline,
                "--strict-stale").returncode == 1
    assert len(load_baseline(baseline)["entries"]) == 1
    # composed: prunes AND still exits 1 — one CI invocation sees both
    proc = _cli(bad, "--baseline", baseline,
                "--strict-stale", "--baseline-update")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "1 stale pruned" in proc.stdout
    assert load_baseline(baseline)["entries"] == []
    # pruned baseline, nothing stale left: the same invocation is clean
    assert _cli(bad, "--baseline", baseline,
                "--strict-stale", "--baseline-update").returncode == 0


def test_nonexistent_root_raises_not_empty_scan(tmp_path):
    """A missing root must error, not silently scan nothing — an empty
    scan feeding --baseline-update would wipe the baseline."""
    with pytest.raises(FileNotFoundError, match="no_such_dir"):
        run_paths(roots=("no_such_dir",), repo_root=str(tmp_path),
                  use_baseline=False)
    proc = _cli("no_such_dir_anywhere")
    assert proc.returncode == 2 and "not found" in proc.stderr


@pytest.mark.parametrize("name", ["HOT_FILES", "BENCH_FILES",
                                  "TELEMETRY_FILES", "DEFAULT_ROOTS"])
def test_every_path_a_rule_is_scoped_to_exists(name):
    """A rule scoped to a file that is gone lints nothing and says
    nothing: every path in host-sync's file sets, and every default
    root, names something in the tree."""
    from tools.graftlint.rules import host_sync

    paths = core.DEFAULT_ROOTS if name == "DEFAULT_ROOTS" \
        else getattr(host_sync, name)
    assert paths
    gone = sorted(p for p in paths
                  if not os.path.exists(os.path.join(REPO, p)))
    assert not gone, f"{name} names paths that do not exist: {gone}"


def test_cli_relative_roots_resolve_from_user_cwd(tmp_path):
    """`python -m tools.graftlint mydir` from any cwd lints that dir."""
    tmp = str(tmp_path)
    _write(tmp, "mydir/f.py", BAD_FILE)
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "mydir", "--no-baseline"],
        cwd=tmp, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "[bare-except]" in proc.stdout


def test_cli_rule_subset_and_unknown():
    assert _cli("--list-rules").returncode == 0
    proc = _cli("tools/graftlint", "--rules", "bare-except")
    assert proc.returncode == 0
    assert _cli("--rules", "no-such-rule").returncode == 2


def test_legacy_shim_still_works():
    """Satellite: tools/check_no_bare_except.py survives as a shim — same
    CLI, same check_source API (exercised by test_lint_guards.py)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_no_bare_except.py"),
         "tools/graftlint"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# rule: donated-state
# ---------------------------------------------------------------------------

DONATION_BAD = """
def t(engine, np, b):
    p0 = engine.state.params["w1"]
    engine.train_batch(batch=b)
    return np.sum(p0)
"""

DONATION_GOOD_MATERIALIZED = """
def t(engine, np, b, jax):
    p0 = jax.device_get(engine.state.params["w1"])
    engine.train_batch(batch=b)
    return np.sum(p0)
"""

DONATION_GOOD_REREAD = """
def t(engine, np, b):
    engine.train_batch(batch=b)
    return np.sum(engine.state.params["w1"])
"""

DONATION_GOOD_REBOUND = """
def t(engine, np, b):
    p0 = engine.state.params["w1"]
    engine.train_batch(batch=b)
    p0 = engine.state.params["w1"]
    return np.sum(p0)
"""

DONATION_BAD_STAGE = """
def t(engine, b):
    acc = engine.stage_states[0].accum
    engine.train_batch(batch=b)
    return acc
"""


def test_donated_state_fires_on_held_leaf():
    got = lint(DONATION_BAD, "tests/unit/t.py", rules=["donated-state"])
    assert rule_names(got) == ["donated-state"] and got[0].line == 5
    assert "donated" in got[0].message


def test_donated_state_quiet_on_fixes():
    for src in (DONATION_GOOD_MATERIALIZED, DONATION_GOOD_REREAD,
                DONATION_GOOD_REBOUND):
        assert lint(src, "tests/unit/t.py", rules=["donated-state"]) == [], src


def test_donated_state_tracks_stage_states():
    got = lint(DONATION_BAD_STAGE, "deepspeed_tpu/runtime/x.py",
               rules=["donated-state"])
    assert rule_names(got) == ["donated-state"]


def test_donated_state_use_before_step_is_fine():
    src = ("def t(engine, np, b):\n"
           "    p0 = engine.state.params\n"
           "    s = np.sum(p0)\n"
           "    engine.step()\n"
           "    return s\n")
    assert lint(src, "tests/unit/t.py", rules=["donated-state"]) == []


# ---------------------------------------------------------------------------
# rule: host-sync
# ---------------------------------------------------------------------------

HS_TRACED_BAD = """
import jax
import numpy as np
def micro(state, batch):
    return float(np.asarray(state.accum))
fn = jax.jit(micro)
"""

HS_TRACED_GOOD = """
import jax
import jax.numpy as jnp
def micro(state, batch):
    return jnp.asarray(state.accum)
fn = jax.jit(micro)
"""

HS_FACTORY_BAD = """
def _make_micro_fn(self):
    def micro(state, batch):
        return jax.device_get(state.accum)
    return micro
"""

HS_HOT_LOOP_BAD = """
class E:
    def train_batch(self, micros):
        for m in micros:
            loss = self._jit(m)
            total += float(jax.device_get(loss))
        return total
"""

HS_HOT_LOOP_GOOD = """
class E:
    def train_batch(self, micros):
        losses = []
        for m in micros:
            losses.append(self._jit(m))
        return float(np.sum(jax.device_get(losses)))
"""


def test_host_sync_fires_in_traced_fn():
    got = lint(HS_TRACED_BAD, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]
    assert "traced" in got[0].message


def test_host_sync_quiet_on_jnp_in_traced_fn():
    assert lint(HS_TRACED_GOOD, rules=["host-sync"]) == []


def test_host_sync_fires_in_make_factory_defs():
    got = lint(HS_FACTORY_BAD, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]


HS_PLAN_BUILDER_BAD = """
class E:
    def train_batch(self, batch):
        plan = build_gather_plan(self._names, self._shapes, self._dims, 8)
        return self._jit(batch, plan)
"""

HS_PLAN_BUILDER_GOOD = """
class E:
    def _arm_stage3(self, stage, dp):
        self._s3_plan = build_gather_plan(self._names, self._shapes,
                                          self._dims, dp)
        if not self._s3_plan.blocks:
            log_dist("stage-3 DISARMED - nothing partitionable")

    def train_batch(self, batch):
        return self._jit(batch, self._s3_plan)
"""


def test_host_sync_flags_plan_builder_in_hot_fn():
    """ISSUE 8 satellite: the stage-3 gather-plan builder (O(param-leaves)
    host work) is flagged ANYWHERE inside a hot step-driving function —
    not just in loops — and quiet when built once at arming time."""
    path = "deepspeed_tpu/runtime/engine.py"
    got = lint(HS_PLAN_BUILDER_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]
    assert "arming time" in got[0].message
    assert lint(HS_PLAN_BUILDER_GOOD, path, rules=["host-sync"]) == []
    # the bar applies to the engine files' hot fns only: a cold caller
    # (or a non-engine file) builds plans freely
    assert lint(HS_PLAN_BUILDER_BAD, "tools/somefile.py",
                rules=["host-sync"]) == []


@pytest.mark.parametrize("path", ["deepspeed_tpu/runtime/engine.py",
                                  "deepspeed_tpu/runtime/pipe/engine.py",
                                  "benchmark/harness/drive_train.py",
                                  "benchmark/harness/drive_serve.py",
                                  "benchmark/tools/knee_sweep.py"])
def test_host_sync_fires_in_hot_loop(path):
    got = lint(HS_HOT_LOOP_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"], path
    assert "per-iteration loop" in got[0].message


HS_SERVING_BAD = """
class InferenceEngine:
    def step(self):
        for slot, req in self.scheduler.running.items():
            tok = int(jax.device_get(self._nxt[slot]))
            req.generated.append(tok)
"""

HS_SERVING_GOOD = """
class InferenceEngine:
    def step(self):
        out = self._decode(self.params, self._tables)
        toks = np.asarray(jax.device_get(out))
        for slot, req in self.scheduler.running.items():
            req.generated.append(int(toks[slot]))
"""


@pytest.mark.parametrize("path", ["deepspeed_tpu/serving/engine.py",
                                  "deepspeed_tpu/serving/scheduler.py"])
def test_host_sync_serving_per_token_fetch_is_an_error(path):
    """PR-5 satellite: the serving hot paths are held to the training
    engines' bar — a per-slot/per-token device_get in the step loop
    fires; ONE batched fetch after dispatch is the blessed idiom."""
    got = lint(HS_SERVING_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"], path
    assert lint(HS_SERVING_GOOD, path, rules=["host-sync"]) == []


HS_RELIABILITY_BAD = """
class InferenceEngine:
    def _enforce_deadlines(self, events):
        now = self.clock()
        for req in list(self.scheduler.requests.values()):
            if float(jax.device_get(req.deadline_arr)) < now:
                self._abort(req, "expired", events)
"""

HS_RELIABILITY_GOOD = """
class InferenceEngine:
    def _enforce_deadlines(self, events):
        now = self.clock()
        for req in list(self.scheduler.requests.values()):
            if req.deadline is not None and now > req.deadline:
                self._abort(req, "expired", events)

    def recover(self, journal_path):
        entries = RequestJournal.replay(journal_path)
        return [self.submit(e["prompt"], e["max_new"]) for e in entries]

    def drain(self):
        while self.scheduler.in_flight():
            self.step()
        return self.results
"""

HS_RECOVER_BAD = """
class InferenceEngine:
    def recover(self, journal_path):
        rids = []
        for e in RequestJournal.replay(journal_path):
            rids.append(self.submit(e["prompt"], e["max_new"]))
            jax.device_get(self.pool.tensors.k)
        return rids
"""

HS_DRAIN_BAD = """
class InferenceEngine:
    def drain(self):
        while self.scheduler.in_flight():
            self.step()
            self.pool.tensors.k.block_until_ready()
        return self.results
"""


@pytest.mark.parametrize("src,label", [
    (HS_RELIABILITY_BAD, "_enforce_deadlines"),
    (HS_RECOVER_BAD, "recover"),
    (HS_DRAIN_BAD, "drain"),
])
@pytest.mark.parametrize("path", ["deepspeed_tpu/serving/engine.py",
                                  "deepspeed_tpu/serving/reliability.py"])
def test_host_sync_covers_serving_reliability_hot_fns(src, label, path):
    """ISSUE 9 satellite: the reliability layer's step-boundary fns
    (deadline sweep, journal replay/recovery, drain loop) are held to
    the hot-path bar — a per-request/per-step device sync fires."""
    got = lint(src, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"], (label, path)


HS_FLEET_BAD = """
class FleetRouter:
    def step(self):
        for rep in self.replicas:
            rep.engine.step()
            jax.device_get(rep.engine.pool.tensors.k)
"""

HS_FLEET_MIGRATE_BAD = """
class FleetRouter:
    def _migrate(self, rep, events):
        for e in RequestJournal.replay(rep.journal_path):
            target = self._place(len(e["prompt"]), exclude=rep)
            target.engine.submit(e["prompt"], e["max_new"])
            target.engine.pool.tensors.k.block_until_ready()
"""

HS_FLEET_GOOD = """
class FleetRouter:
    def step(self):
        events = {"failures": []}
        for rep in self.replicas:
            self._step_replica(rep, events)
        return events

    def _handoff_tick(self, rep, events):
        req = min(rep.engine.scheduler.running.values(),
                  key=lambda r: r.submit_seq)
        entry = rep.engine.export_request(req.rid)
        target = self._place(0, decode_target=True, exclude=rep)
        target.engine.import_request(entry)

    def _migrate(self, rep, events):
        for e in RequestJournal.replay(rep.journal_path):
            target = self._place(len(e["prompt"]), exclude=rep)
            target.engine.submit(e["prompt"], e["max_new"])
"""


@pytest.mark.parametrize("src,label", [
    (HS_FLEET_BAD, "step"),
    (HS_FLEET_MIGRATE_BAD, "_migrate"),
])
def test_host_sync_covers_fleet_router_hot_fns(src, label):
    """ISSUE 11 satellite: the fleet router's step loop and migration
    path are hot — a device sync per replica/request there serializes
    the whole fleet against the host."""
    got = lint(src, "deepspeed_tpu/serving/fleet.py", rules=["host-sync"])
    assert rule_names(got) == ["host-sync"], label


def test_host_sync_quiet_on_fleet_straight_line_handoff():
    # per-replica stepping through a helper, a straight-line handoff
    # (the ONE blessed device touch) and a sync-free migration loop:
    # no findings
    assert lint(HS_FLEET_GOOD, "deepspeed_tpu/serving/fleet.py",
                rules=["host-sync"]) == []


HS_SUPERVISOR_TICK_BAD = """
class TrainingSupervisor:
    def _heartbeat_tick(self, w):
        stale, dead = [], []
        for h in self.hosts:
            h.tick(w)
            lag = float(jax.device_get(self.engine.state.step)) - h.last_beat
            if lag > self.config.heartbeat_timeout_steps:
                dead.append(h.rank)
        return stale, dead
"""

HS_SUPERVISOR_ROLLBACK_BAD = """
class TrainingSupervisor:
    def _rollback(self, reason):
        for _attempt in range(self.config.max_recovery_attempts):
            _path, client = self.engine.load_checkpoint(
                self.save_dir, tag=self.last_committed_tag, elastic=True)
            for leaf in jax.tree_util.tree_leaves(self.engine.state.params):
                leaf.block_until_ready()
"""

HS_SUPERVISOR_GOOD = """
class TrainingSupervisor:
    def tick(self):
        self.wall_step += 1
        stale, dead = self._heartbeat_tick(self.wall_step)
        if dead and self._verdict(dead, self.wall_step):
            self._elastic_restart(dead)
            return
        self.supervised_step()

    def _heartbeat_tick(self, w):
        stale, dead = [], []
        for h in self.hosts:
            h.tick(w)
            lag = w - h.last_beat
            if lag > self.config.heartbeat_timeout_steps:
                dead.append(h.rank)
            elif lag > 0:
                stale.append(h.rank)
        return stale, dead

    def _rollback(self, reason):
        for _attempt in range(self.config.max_recovery_attempts):
            _path, client = self.engine.load_checkpoint(
                self.save_dir, tag=self.last_committed_tag, elastic=True)
            self._reseat_data(client)
"""


@pytest.mark.parametrize("src,label", [
    (HS_SUPERVISOR_TICK_BAD, "_heartbeat_tick"),
    (HS_SUPERVISOR_ROLLBACK_BAD, "_rollback"),
])
def test_host_sync_covers_supervisor_hot_fns(src, label):
    """ISSUE 12 satellite: the training supervisor's detection tick and
    recovery paths are hot — a device sync per simulated host (or per
    state leaf mid-rollback) would serialize every wall step, failure
    or not, against the host."""
    got = lint(src, "deepspeed_tpu/runtime/resilience/supervisor.py",
               rules=["host-sync"])
    assert rule_names(got) == ["host-sync"], label


def test_host_sync_quiet_on_supervisor_host_only_loop():
    # the real shape: pure host heartbeat bookkeeping and recovery
    # retries that touch the device only through the engine's own
    # load/init entry points — no findings
    assert lint(HS_SUPERVISOR_GOOD,
                "deepspeed_tpu/runtime/resilience/supervisor.py",
                rules=["host-sync"]) == []


HS_INTEGRITY_VOTE_BAD = """
class IntegrityMonitor:
    def state_vote(self, engine):
        digests = []
        for leaf in jax.tree_util.tree_leaves(engine.state.params):
            digests.append(int(jax.device_get(fold(leaf))))
        return digests
"""

HS_INTEGRITY_OBSERVE_BAD = """
class IntegrityMonitor:
    def observe_step(self, step, metrics):
        zs = {}
        for name, value in metrics.items():
            zs[name] = self.stats[name].z(float(jax.device_get(value)))
        return zs
"""

HS_INTEGRITY_GOOD = """
def state_vote(engine):
    with jax.set_mesh(engine.mesh):
        table = engine._integrity._vote_jit(tuple(leaves))
    rows = np.asarray(jax.device_get(table), dtype=np.int64)
    return classify_digests(rows)


class IntegrityMonitor:
    def observe_step(self, step, loss=None, grad_norm=None,
                     update_ratio=None, overflow=False):
        samples = {"loss": loss, "grad_norm": grad_norm,
                   "update_ratio": update_ratio}
        zs = {}
        for n, v in samples.items():
            if v is not None:
                zs[n] = self.stats[n].z(v)
        return any(z > self.config.z_threshold for z in zs.values())
"""


@pytest.mark.parametrize("src,label", [
    (HS_INTEGRITY_VOTE_BAD, "per-leaf digest fetch"),
    (HS_INTEGRITY_OBSERVE_BAD, "per-sentinel device fetch"),
])
def test_host_sync_covers_integrity_hot_fns(src, label):
    """ISSUE 13 satellite: the integrity monitor's per-step observe and
    the vote entry points are hot — the sentinel values must RIDE the
    engine's one batched fetch, and a vote may fetch its digest table
    exactly once (straight-line); a per-leaf/per-sentinel device_get
    loop serializes the state against the host every step."""
    got = lint(src, "deepspeed_tpu/runtime/resilience/integrity.py",
               rules=["host-sync"])
    assert rule_names(got) == ["host-sync"], label


def test_host_sync_quiet_on_integrity_batched_fetch():
    # the real shape: ONE straight-line device_get of the gathered
    # digest table per vote, pure host float math in observe_step
    assert lint(HS_INTEGRITY_GOOD,
                "deepspeed_tpu/runtime/resilience/integrity.py",
                rules=["host-sync"]) == []


def test_host_sync_quiet_on_host_only_reliability_fns():
    # the real implementations are pure host accounting: clock reads,
    # dict walks, journal appends — no findings
    assert lint(HS_RELIABILITY_GOOD, "deepspeed_tpu/serving/engine.py",
                rules=["host-sync"]) == []
    assert lint(HS_RELIABILITY_GOOD,
                "deepspeed_tpu/serving/reliability.py",
                rules=["host-sync"]) == []


def test_host_sync_quiet_on_batched_fetch_after_loop():
    assert lint(HS_HOT_LOOP_GOOD, "deepspeed_tpu/runtime/engine.py",
                rules=["host-sync"]) == []


def test_host_sync_hot_loop_scoped_to_hot_files():
    # the same loop in an arbitrary module is host-side code, not a
    # schedule hot path — only the traced-fn context applies there
    assert lint(HS_HOT_LOOP_BAD, "deepspeed_tpu/utils/foo.py",
                rules=["host-sync"]) == []


def test_host_sync_comprehension_counts_as_loop():
    src = ("class E:\n"
           "    def eval_batch(self, losses, np, jax):\n"
           "        return float(np.mean([float(jax.device_get(l)) "
           "for l in losses]))\n")
    got = lint(src, "deepspeed_tpu/runtime/pipe/engine.py",
               rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]


# ---------------------------------------------------------------------------
# rule: rank-branch-collective
# ---------------------------------------------------------------------------

SPMD_BAD = """
def body(x, jax):
    if jax.lax.axis_index("data") == 0:
        x = jax.lax.psum(x, "data")
    return x
"""

SPMD_BAD_ELSE = """
def body(x, jax):
    if jax.lax.axis_index("data") == 0:
        pass
    else:
        x = jax.lax.all_gather(x, "data")
    return x
"""

SPMD_BAD_HOST = """
def save(jax, mu, payload):
    if jax.process_index() == 0:
        return mu.process_allgather(payload)
"""

SPMD_GOOD = """
def body(x, jax, jnp):
    y = jax.lax.psum(x, "data")
    return jnp.where(jax.lax.axis_index("data") == 0, y, x)
"""

SPMD_GOOD_UNIFORM_GUARD = """
def save(jax, mu, payload):
    if jax.process_count() > 1:
        return mu.process_allgather(payload)
    return payload
"""


def test_rank_branch_collective_fires():
    got = lint(SPMD_BAD, rules=["rank-branch-collective"])
    assert rule_names(got) == ["rank-branch-collective"]
    assert "psum" in got[0].message and "deadlock" in got[0].message


def test_rank_branch_collective_fires_in_else_arm():
    got = lint(SPMD_BAD_ELSE, rules=["rank-branch-collective"])
    assert rule_names(got) == ["rank-branch-collective"]


def test_rank_branch_host_barrier_fires():
    got = lint(SPMD_BAD_HOST, rules=["rank-branch-collective"])
    assert rule_names(got) == ["rank-branch-collective"]


def test_rank_branch_collective_quiet_on_fixes():
    assert lint(SPMD_GOOD, rules=["rank-branch-collective"]) == []
    # process_count is uniform across ranks: not a divergence hazard
    assert lint(SPMD_GOOD_UNIFORM_GUARD,
                rules=["rank-branch-collective"]) == []


SPMD_BAD_QUANT_WIRE = """
def exchange(grads, jax, cc, mesh):
    if jax.lax.axis_index("data") == 0:
        grads = cc.quantized_all_reduce(grads, "data", bits=1)
    g = cc.quantized_all_gather(grads, mesh)
    if jax.process_index() == 0:
        g = cc.quantized_reduce_scatter(g, "data")
    return g
"""

SPMD_GOOD_QUANT_WIRE = """
def exchange(grads, jax, cc, mesh):
    grads = cc.quantized_all_reduce(grads, "data", bits=1)
    return cc.quantized_all_gather(grads, mesh)
"""

SPMD_BAD_TRANSPORT_BARRIER = """
def monitor(self, jax, wall_step):
    if jax.process_index() == 0:
        self.transport.heartbeat_tick(wall_step)
        return self.transport.vote_dead((), wall_step)
    return ()
"""

SPMD_GOOD_TRANSPORT_BARRIER = """
def monitor(self, jax, wall_step):
    self.transport.heartbeat_tick(wall_step)
    dead = self.transport.vote_dead((), wall_step)
    if jax.process_index() == 0:
        log_dead(dead)
    return dead
"""

SPMD_GOOD_SUBMIT_NOT_A_BARRIER = """
def admit(self, jax, prompt):
    if jax.process_index() == 0:
        return self.engine.submit(prompt, max_new_tokens=8)
"""


def test_rank_branch_quantized_collectives_fire():
    """ISSUE 19 satellite: the PR-18 quantized wire collectives are
    rank-gated deadlocks like their dense counterparts — all three
    custom ops under a rank branch fire; unconditional use is quiet."""
    got = lint(SPMD_BAD_QUANT_WIRE, rules=["rank-branch-collective"])
    assert rule_names(got) == ["rank-branch-collective"] * 2
    assert "quantized_all_reduce" in got[0].message
    assert "quantized_reduce_scatter" in got[1].message
    assert lint(SPMD_GOOD_QUANT_WIRE,
                rules=["rank-branch-collective"]) == []


def test_rank_branch_transport_barriers_fire():
    """ISSUE 19 satellite: transport-level quorum barriers
    (heartbeat_tick / vote_dead) wedge exactly like device collectives
    when only rank 0 posts them; running the round on every peer and
    rank-gating the LOGGING is the quiet twin.  serving's submit() is
    an unrelated name and must never fire."""
    got = lint(SPMD_BAD_TRANSPORT_BARRIER,
               rules=["rank-branch-collective"])
    assert rule_names(got) == ["rank-branch-collective"] * 2
    assert "heartbeat_tick" in got[0].message
    assert "vote_dead" in got[1].message
    assert lint(SPMD_GOOD_TRANSPORT_BARRIER,
                rules=["rank-branch-collective"]) == []
    assert lint(SPMD_GOOD_SUBMIT_NOT_A_BARRIER,
                rules=["rank-branch-collective"]) == []


# ---------------------------------------------------------------------------
# rule: disarmed-discipline
# ---------------------------------------------------------------------------

DISARM_BAD = """
class E:
    def _arm_thing(self):
        self._thing_armed = False
        if self.config.thing and self.dp > 1:
            self._thing_armed = True
"""

DISARM_GOOD = DISARM_BAD + """
        elif self.config.thing:
            log_dist("thing DISARMED — requires dp > 1",
                     ranks=[0], level=logging.WARNING)
"""

DISARM_BAD_ATTR_ONLY = """
class E:
    def configure(self):
        self._wire_armed = self.dp > 1
"""


def test_disarmed_discipline_fires_without_warning_path():
    got = lint(DISARM_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"] and got[0].line == 3
    assert "DISARMED" in got[0].message


def test_disarmed_discipline_quiet_with_warning():
    assert lint(DISARM_GOOD, rules=["disarmed-discipline"]) == []


def test_disarmed_discipline_catches_armed_attr_outside_arm_fns():
    got = lint(DISARM_BAD_ATTR_ONLY, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]


DISARM_S3_BAD = """
class E:
    def _arm_stage3(self, stage, dp, params_template):
        self._s3_sched_armed = stage == 3 and dp > 1
"""

DISARM_S3_GOOD = DISARM_S3_BAD + """
        if stage == 3 and not self._s3_sched_armed:
            log_dist("ZeRO stage-3: scheduled gathers DISARMED - dp is 1",
                     ranks=[0], level=logging.WARNING)
"""


def test_disarmed_discipline_covers_arm_stage3_path():
    """ISSUE 8 satellite: the new _arm_stage3_* arming path is held to
    the same discipline — fire without a DISARMED branch, quiet with."""
    got = lint(DISARM_S3_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_stage3" in got[0].message
    assert lint(DISARM_S3_GOOD, rules=["disarmed-discipline"]) == []


DISARM_SHED_BAD = """
class Reliability:
    def _arm_shedding(self):
        self.shedding_armed = self.config.slo_ttft_s is not None \\
            and self.engine.scheduler.policy == "continuous"
"""

DISARM_SHED_GOOD = DISARM_SHED_BAD + """
        if self.config.slo_ttft_s is not None and not self.shedding_armed:
            logger.warning("SLO shedding DISARMED - policy '%s' gates "
                           "admission on batch membership",
                           self.engine.scheduler.policy)
"""


def test_disarmed_discipline_covers_arm_shedding_path():
    """ISSUE 9 satellite: the serving overload guard's arming fn is
    held to the armed-or-warns discipline — an _arm_shedding that can
    silently leave the gate off fires; warning DISARMED quiets it."""
    got = lint(DISARM_SHED_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_shedding" in got[0].message
    assert lint(DISARM_SHED_GOOD, rules=["disarmed-discipline"]) == []


DISARM_DISPATCH_BAD = """
class FleetRouter:
    def _arm_dispatch(self):
        self.dispatch_armed = self.config.dispatch == "slo" and all(
            r.engine.scheduler.policy == "continuous"
            for r in self.replicas)
"""

DISARM_DISPATCH_GOOD = DISARM_DISPATCH_BAD + """
        if self.config.dispatch == "slo" and not self.dispatch_armed:
            logger.warning("SLO-aware dispatch DISARMED - a replica "
                           "policy the TTFT model cannot describe; "
                           "falling back to round-robin")
"""


def test_disarmed_discipline_covers_arm_dispatch_path():
    """ISSUE 11 satellite: the fleet router's placement arming fn is
    held to the armed-or-warns discipline — a silent round-robin
    fallback fires; warning DISARMED quiets it."""
    got = lint(DISARM_DISPATCH_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_dispatch" in got[0].message
    assert lint(DISARM_DISPATCH_GOOD, rules=["disarmed-discipline"]) == []


DISARM_SUPERVISOR_BAD = """
class DeepSpeedEngine:
    def _arm_supervisor(self, supervisor):
        if not supervisor.save_dir or not self._resilience.atomic_checkpoints:
            self._supervisor = None
            return False
        self._supervisor = supervisor
        return True
"""

DISARM_SUPERVISOR_GOOD = """
class DeepSpeedEngine:
    def _arm_supervisor(self, supervisor):
        if not supervisor.save_dir or not self._resilience.atomic_checkpoints:
            self._supervisor = None
            log_dist("self-healing supervision DISARMED - no committed-"
                     "tag directory / atomic commits off; steps run "
                     "unsupervised", ranks=[0], level=logging.WARNING)
            return False
        self._supervisor = supervisor
        return True
"""


def test_disarmed_discipline_covers_arm_supervisor_path():
    """ISSUE 12 satellite: the engine's supervision arming fn is held to
    the armed-or-warns discipline — silently refusing to supervise (no
    retry/rollback/elastic restart, run dies on the first fault) fires;
    warning DISARMED naming the blockers quiets it."""
    got = lint(DISARM_SUPERVISOR_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_supervisor" in got[0].message
    assert lint(DISARM_SUPERVISOR_GOOD, rules=["disarmed-discipline"]) == []


DISARM_INTEGRITY_BAD = """
class DeepSpeedEngine:
    def _arm_integrity(self):
        self._integrity = None
        if not self._resilience.integrity_enabled:
            return
        if self._offload or self._onebit_wire():
            return
        self._integrity = IntegrityMonitor(cfg, self.dp_world_size)
"""

DISARM_INTEGRITY_GOOD = """
class DeepSpeedEngine:
    def _arm_integrity(self):
        self._integrity = None
        if not self._resilience.integrity_enabled:
            return
        if self._offload or self._onebit_wire():
            log_dist("numerical-integrity defense DISARMED - "
                     "cpu_offload / 1-bit wire leave no device-resident "
                     "replicated state to vote over", ranks=[0],
                     level=logging.WARNING)
            return
        self._integrity = IntegrityMonitor(cfg, self.dp_world_size)
"""


def test_disarmed_discipline_covers_arm_integrity_path():
    """ISSUE 13 satellite: the integrity arming fn is held to the
    armed-or-warns discipline — silently skipping the defense (silent
    corruption then sails past every detector) fires; warning DISARMED
    naming the blockers quiets it."""
    got = lint(DISARM_INTEGRITY_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_integrity" in got[0].message
    assert lint(DISARM_INTEGRITY_GOOD, rules=["disarmed-discipline"]) == []


DISARM_AUTOSCALE_BAD = """
class FleetRouter:
    def _arm_autoscale(self, spec):
        self.autoscale_armed = False
        self._autoscale = None
        if spec is None:
            return
        if self._role_split or spec.min_replicas < 1:
            return
        self._autoscale = spec
        self.autoscale_armed = True
"""

DISARM_AUTOSCALE_GOOD = DISARM_AUTOSCALE_BAD.replace(
    "            return\n        self._autoscale = spec",
    '            logger.warning(\n'
    '                "fleet autoscaler: DISARMED - role-split fleet / "\n'
    '                "invalid replica bounds; the replica set stays "\n'
    '                "fixed")\n'
    "            return\n        self._autoscale = spec")


def test_disarmed_discipline_covers_arm_autoscale_path():
    """ISSUE 16 satellite: the router's autoscale arming fn is held to
    the armed-or-warns discipline — a fleet that silently never scales
    (the user asked for elasticity, provisioning stays frozen) fires;
    warning DISARMED naming the blockers quiets it."""
    got = lint(DISARM_AUTOSCALE_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_autoscale" in got[0].message
    assert lint(DISARM_AUTOSCALE_GOOD, rules=["disarmed-discipline"]) == []


DISARM_TRANSPORT_BAD = """
class FleetRouter:
    def _arm_transport(self, transport):
        self._transport = None
        self.transport_armed = False
        if transport is None:
            return
        if transport.world != len(self.replicas) + 1:
            return
        self._transport = transport.start()
        self.transport_armed = True
"""

DISARM_TRANSPORT_GOOD = DISARM_TRANSPORT_BAD.replace(
    "            return\n        self._transport = transport.start()",
    '            logger.warning(\n'
    '                "fleet transport: DISARMED - world does not map "\n'
    '                "onto the replica set; replica liveness stays "\n'
    '                "in-process")\n'
    "            return\n        self._transport = transport.start()")


def test_disarmed_discipline_covers_arm_transport_path():
    """ISSUE 16 satellite: the transport-seam arming fn is held to the
    armed-or-warns discipline — silently falling back to in-process
    liveness (peer death then goes undetected at the process level)
    fires; warning DISARMED naming the blockers quiets it."""
    got = lint(DISARM_TRANSPORT_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_transport" in got[0].message
    assert lint(DISARM_TRANSPORT_GOOD, rules=["disarmed-discipline"]) == []


# ---------------------------------------------------------------------------
# rule: raw-ckpt-write
# ---------------------------------------------------------------------------

RUNTIME_PATH = "deepspeed_tpu/runtime/somefile.py"

CKPT_BAD_OPEN = """
def write_side_metadata(path, meta):
    with open(path, "w") as f:
        json.dump(meta, f)
"""

CKPT_BAD_SAVEZ = """
def stash_state(path, arrays):
    np.savez(path, **arrays)
"""

CKPT_BAD_HASHED_OUTSIDE_COMMIT = """
def sneaky(path, arrays):
    savez_hashed(path, **arrays)
"""

CKPT_BAD_RENAME = """
def my_own_atomic_commit(tmp, final):
    os.replace(tmp, final)
"""

CKPT_GOOD_COMMIT_WRITER = """
def _write_snapshot_files(path, snap):
    fname = os.path.join(path, "model_states.npz")
    np.savez(fname, **snap["arrays"])
    chaos.file_written(fname)
    mpath = os.path.join(path, "metadata.pkl")
    with open(mpath, "wb") as f:
        pickle.dump(snap["meta"], f)
    chaos.file_written(mpath)
"""

CKPT_GOOD_READS_AND_LOOKALIKES = """
def harmless(path, d, s):
    with open(path) as f:
        data = f.read()
    with open(path, "rb") as f:
        more = f.read()
    d2 = d.copy()            # dict.copy, not shutil.copy
    s2 = s.replace("a", "b")  # str.replace, not os.replace
    arr = np.load(path)
    return data, more, d2, s2, arr
"""


def test_raw_ckpt_write_fires_on_each_writer_kind():
    for src, kind in ((CKPT_BAD_OPEN, "open"),
                      (CKPT_BAD_SAVEZ, "np.savez"),
                      (CKPT_BAD_HASHED_OUTSIDE_COMMIT, "savez_hashed"),
                      (CKPT_BAD_RENAME, "os.replace")):
        got = lint(src, path=RUNTIME_PATH, rules=["raw-ckpt-write"])
        assert got and got[0].rule == "raw-ckpt-write", kind
        assert "atomic commit path" in got[0].message
    # the bad open fixture flags both the open and the json.dump
    got = lint(CKPT_BAD_OPEN, path=RUNTIME_PATH, rules=["raw-ckpt-write"])
    assert len(got) == 2


def test_raw_ckpt_write_quiet_in_chaos_hooked_commit_writer():
    """The payload-writer discipline: writes that feed chaos.file_written
    are commit-path writes (kill-mid-write tests cover them)."""
    assert lint(CKPT_GOOD_COMMIT_WRITER, path=RUNTIME_PATH,
                rules=["raw-ckpt-write"]) == []


def test_raw_ckpt_write_quiet_on_reads_and_lookalikes():
    assert lint(CKPT_GOOD_READS_AND_LOOKALIKES, path=RUNTIME_PATH,
                rules=["raw-ckpt-write"]) == []


def test_raw_ckpt_write_scoped_to_runtime_and_exempts_atomic():
    # same bad source outside deepspeed_tpu/runtime/: out of scope
    assert lint(CKPT_BAD_OPEN, path="deepspeed_tpu/serving/x.py",
                rules=["raw-ckpt-write"]) == []
    # and atomic.py IS the commit path
    assert lint(CKPT_BAD_RENAME,
                path="deepspeed_tpu/runtime/resilience/atomic.py",
                rules=["raw-ckpt-write"]) == []


def test_raw_ckpt_write_suppressible_inline():
    src = ('def legacy(path, arrays):\n'
           '    np.savez(path, **arrays)'
           '  # graftlint: disable=raw-ckpt-write\n')
    assert lint(src, path=RUNTIME_PATH, rules=["raw-ckpt-write"]) == []


def test_raw_ckpt_write_repo_runtime_is_clean():
    """The acceptance bar: the rule runs over the real runtime tree with
    an EMPTY baseline — nothing writes around the atomic discipline."""
    from tools.graftlint.core import run_paths

    result = run_paths(["deepspeed_tpu/runtime"],
                       rules=[REGISTRY["raw-ckpt-write"]],
                       use_baseline=False)
    assert result.new == [], [f.format() for f in result.new]


# ---------------------------------------------------------------------------
# rule: bare-except (folded from check_no_bare_except)
# ---------------------------------------------------------------------------

def test_bare_except_rule_matches_legacy_checker():
    src = "try:\n    x()\nexcept Exception:\n    pass\n"
    got = lint(src, rules=["bare-except"])
    assert rule_names(got) == ["bare-except"]
    # the legacy opt-out marker keeps working through the rule
    src_ok = ("try:\n    x()\n"
              "except Exception:  # lint: allow-broad-except\n    pass\n")
    assert lint(src_ok, rules=["bare-except"]) == []


# ---------------------------------------------------------------------------
# telemetry coverage (ISSUE 10): hot-file host-sync + _arm_telemetry
# discipline
# ---------------------------------------------------------------------------

# span emit that pays a device round-trip per recorded event — the
# exact failure mode the telemetry host-sync bar exists to catch
TELEMETRY_HS_BAD = """
def record_spans(tracer, lane, arrays, jax):
    for a in arrays:
        tracer.complete("fetch", lane, float(jax.device_get(a)))
"""

# fixed twin: one batched fetch after the loop, spans from host floats
TELEMETRY_HS_GOOD = """
def record_spans(tracer, lane, arrays, jax):
    ts = jax.device_get(arrays)
    for t in ts:
        tracer.complete("fetch", lane, float(t))
"""


@pytest.mark.parametrize("path", ["deepspeed_tpu/telemetry/trace.py",
                                  "deepspeed_tpu/telemetry/metrics.py",
                                  "deepspeed_tpu/telemetry/mfu.py"])
def test_host_sync_fires_in_telemetry_loop(path):
    got = lint(TELEMETRY_HS_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]
    assert "per-iteration loop" in got[0].message


def test_host_sync_quiet_on_batched_telemetry_emit():
    assert lint(TELEMETRY_HS_GOOD, "deepspeed_tpu/telemetry/trace.py",
                rules=["host-sync"]) == []


def test_host_sync_telemetry_scope_is_telemetry_files_only():
    # the same loop in a non-hot module is plain host code
    assert lint(TELEMETRY_HS_BAD, "deepspeed_tpu/utils/foo.py",
                rules=["host-sync"]) == []


ARM_TELEMETRY_BAD = """
class E:
    def _arm_telemetry(self):
        self._telemetry = None
        if self.config.telemetry_enabled:
            self._telemetry = build_session(self.config)
"""

ARM_TELEMETRY_GOOD = ARM_TELEMETRY_BAD + """
        elif self.config.metrics_jsonl:
            log_dist("telemetry: DISARMED — metrics_jsonl set but "
                     "telemetry.enabled=false", ranks=[0],
                     level=logging.WARNING)
"""


def test_disarmed_discipline_covers_arm_telemetry():
    got = lint(ARM_TELEMETRY_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert lint(ARM_TELEMETRY_GOOD, rules=["disarmed-discipline"]) == []


# ---------------------------------------------------------------------------
# memory accounting (ISSUE 15): cold report builders + arming discipline
# ---------------------------------------------------------------------------

HS_MEMORY_READ_BAD = """
class E:
    def train_batch(self, batch):
        loss = self._jit(batch)
        watermark = self.memory_report()
        return loss, watermark
"""

HS_MEASURED_READ_BAD = """
class E:
    def step(self):
        self._take_step()
        self._last_mem = self._memacct.measured_memory()
"""

HS_MEMORY_READ_GOOD = """
class E:
    def memory_report(self):
        return build_report(self._analytic_memory_components(),
                            self._memacct.measured_memory(),
                            device_memory_report())

    def train_batch(self, batch):
        return self._jit(batch)
"""


def test_host_sync_flags_measured_memory_read_in_hot_fn():
    """ISSUE 15 satellite: a measured-memory read (memory_report /
    measured_memory — lazy compiles + whole-tree walks) inside a hot
    step fn is a finding; the same builders called from a cold report
    fn are quiet."""
    path = "deepspeed_tpu/runtime/engine.py"
    got = lint(HS_MEMORY_READ_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]
    assert "arming time" in got[0].message
    got = lint(HS_MEASURED_READ_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]
    assert lint(HS_MEMORY_READ_GOOD, path, rules=["host-sync"]) == []
    # the bar applies to engine/bench hot fns only
    assert lint(HS_MEMORY_READ_BAD, "tools/somefile.py",
                rules=["host-sync"]) == []


def test_host_sync_flags_memory_read_in_bench_timed_region():
    # the timed drivers hold EVERY fn to the bar
    got = lint(HS_MEMORY_READ_BAD, "benchmark/harness/drive_train.py",
               rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]


ARM_MEMORY_BAD = """
class E:
    def _arm_memory_accounting(self):
        self._memacct = None
        if self.config.telemetry_enabled and self.config.memory:
            self._memacct = MemoryAccounting(shared=self._telemetry.mfu)
"""

ARM_MEMORY_GOOD = ARM_MEMORY_BAD + """
        elif self.config.telemetry_enabled:
            log_dist("memory accounting: DISARMED — telemetry.memory="
                     "false; memory_report() stays analytic-only",
                     ranks=[0], level=logging.WARNING)
"""


def test_disarmed_discipline_covers_arm_memory_accounting():
    """ISSUE 15 satellite: the memory-accounting arming fn is held to
    the armed-or-warns discipline — a silent analytic-only fallback
    fires; warning DISARMED quiets it."""
    got = lint(ARM_MEMORY_BAD, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_memory_accounting" in got[0].message
    assert lint(ARM_MEMORY_GOOD, rules=["disarmed-discipline"]) == []


# ---------------------------------------------------------------------------
# rule: host-sync — prefix cache + speculative decode (ISSUE 17)
# ---------------------------------------------------------------------------

HS_RADIX_WALK_BAD = """
class PagedKVPool:
    def prefix_attach(self, rid, shard, tokens):
        blocks = []
        for node in self.prefix_lookup(shard, tokens)[0]:
            node.refs += 1
            jax.device_get(self.tensors.k[:, node.block])
            blocks.append(node.block)
        return blocks
"""

HS_COW_SPLIT_BAD = """
class PagedKVPool:
    def _cow_copy(self, shard, src, dst):
        arrs = _cow_copy_rows(self.tensors.arrays, src, dst)
        for a in arrs:
            a.block_until_ready()
        self.tensors = PoolTensors(*arrs)
"""

HS_RECLAIM_BAD = """
class PagedKVPool:
    def _reclaim_block(self, shard):
        while self._lru:
            node = self._lru.pop()
            if float(jax.device_get(node.score)) > 0:
                continue
            return node.block
"""

HS_DRAFT_BAD = """
class InferenceEngine:
    def _spec_decode_tick(self, events):
        for slot, req in self.scheduler.running.items():
            drafts = self._draft_tokens(req, self.spec_k)
            tok = int(jax.device_get(self._nxt[slot]))
            req.generated.append(tok)
"""

HS_PREFIX_SPEC_GOOD = """
class PagedKVPool:
    def prefix_attach(self, rid, shard, tokens):
        full, cow, cow_len = self.prefix_lookup(shard, tokens)
        blocks = []
        for node in full:
            node.refs += 1
            blocks.append(node.block)
        if cow is not None and cow_len > 0:
            self._cow_copy(shard, cow.block, blocks[-1])
        return blocks

    def _cow_copy(self, shard, src, dst):
        self.tensors = PoolTensors(
            *_cow_copy_rows(self.tensors.arrays, src, dst))


class InferenceEngine:
    def _spec_decode_tick(self, events):
        out = self._spec(self.params, self._tables)
        outs, fins = jax.device_get((out[-2], out[-1]))
        for slot, req in self.scheduler.running.items():
            req.generated.append(int(outs[slot, 0]))
"""


@pytest.mark.parametrize("src,label", [
    (HS_RADIX_WALK_BAD, "prefix_attach"),
    (HS_COW_SPLIT_BAD, "_cow_copy"),
    (HS_RECLAIM_BAD, "_reclaim_block"),
])
def test_host_sync_covers_radix_cow_refcount_fns(src, label):
    """ISSUE 17 satellite: the radix walk, COW split and LRU reclaim run
    at admission over every request — a device sync per tree node (or a
    block on the COW copy) fires; the single jitted copy dispatch and
    host-only refcount bookkeeping stay quiet."""
    path = "deepspeed_tpu/serving/kv_cache.py"
    got = lint(src, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"], (label, path)
    # scoped: the same walk in a test file is not a hot path
    assert lint(src, "tests/unit/t.py", rules=["host-sync"]) == []


def test_host_sync_covers_draft_verify_tick():
    """The draft-verify tick is held to the decode bar: a per-lane fetch
    fires; drafting + ONE batched fetch after the dispatch is quiet."""
    path = "deepspeed_tpu/serving/engine.py"
    got = lint(HS_DRAFT_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]
    assert lint(HS_PREFIX_SPEC_GOOD, path, rules=["host-sync"]) == []
    assert lint(HS_PREFIX_SPEC_GOOD,
                "deepspeed_tpu/serving/kv_cache.py",
                rules=["host-sync"]) == []


# ---------------------------------------------------------------------------
# rule: disarmed-discipline — cache/spec arming pairs (ISSUE 17)
# ---------------------------------------------------------------------------

DISARM_PREFIX_CACHE_BAD = """
class InferenceEngine:
    def _arm_prefix_cache(self, requested, quantize_kv):
        if not requested:
            return False
        if quantize_kv and not self.pool.quantized:
            return False
        return True
"""

DISARM_PREFIX_CACHE_GOOD = """
class InferenceEngine:
    def _arm_prefix_cache(self, requested, quantize_kv):
        if not requested:
            return False
        if quantize_kv and not self.pool.quantized:
            logger.warning("prefix cache: DISARMED - int8 KV was "
                           "requested but the pool disarmed it "
                           "(off-profitability)")
            return False
        if self.scheduler.draining:
            logger.warning("prefix cache: DISARMED - draining engine "
                           "admits nothing, the tree would pin blocks")
            return False
        return True
"""

DISARM_SPEC_BAD = """
class InferenceEngine:
    def _arm_speculative(self, spec):
        if not spec or self.temperature != 0.0:
            return 0
        return int(spec)
"""

DISARM_SPEC_GOOD = """
class InferenceEngine:
    def _arm_speculative(self, spec):
        if not spec:
            return 0
        if self.temperature != 0.0:
            logger.warning("speculative decoding: DISARMED - sampling "
                           "!= greedy: the acceptance rule is only "
                           "defined at temperature=0")
            return 0
        return int(spec)
"""


@pytest.mark.parametrize("bad,good", [
    (DISARM_PREFIX_CACHE_BAD, DISARM_PREFIX_CACHE_GOOD),
    (DISARM_SPEC_BAD, DISARM_SPEC_GOOD),
])
def test_disarmed_discipline_cache_and_spec_arming(bad, good):
    """ISSUE 17 satellite: the cache/spec arming decisions follow the
    armed-or-warns discipline — silently refusing a requested feature
    fires; a DISARMED warn naming the blocker (sampling != greedy,
    int8-off-profitability, draining) is quiet."""
    path = "deepspeed_tpu/serving/engine.py"
    assert rule_names(lint(bad, path,
                           rules=["disarmed-discipline"])) \
        == ["disarmed-discipline"]
    assert lint(good, path, rules=["disarmed-discipline"]) == []


DISARM_QUANT_KV_BAD = """
class PagedKVPool:
    def _arm_quantized_kv(self, requested):
        if not requested:
            return False
        elem = np.dtype(self.dtype).itemsize
        if self.cfg.head_dim * (elem - 1) <= 4:
            return False
        return True
"""

DISARM_QUANT_KV_GOOD = """
class PagedKVPool:
    def _arm_quantized_kv(self, requested):
        if not requested:
            return False
        elem = np.dtype(self.dtype).itemsize
        if self.cfg.head_dim * (elem - 1) <= 4:
            logger.warning("PagedKVPool: int8 KV quantization DISARMED "
                           "- the per-row f32 scale outweighs the "
                           "element savings; int8 would GROW the pool")
            return False
        return True
"""


def test_disarmed_discipline_covers_arm_quantized_kv():
    """ISSUE 19 satellite: the KV pool's int8 arming decision follows
    the armed-or-warns discipline — silently serving full-precision KV
    after int8 was REQUESTED (off-profitability head_dim) fires; a
    DISARMED warn naming the blocker is quiet."""
    path = "deepspeed_tpu/serving/kv_cache.py"
    got = lint(DISARM_QUANT_KV_BAD, path, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_quantized_kv" in got[0].message
    assert lint(DISARM_QUANT_KV_GOOD, path,
                rules=["disarmed-discipline"]) == []


# ---------------------------------------------------------------------------
# 0/1 Adam wire (PR 18): arming discipline + hot step/pack fn coverage
# ---------------------------------------------------------------------------

DISARM_ZEROONE_BAD = """
class E:
    def _arm_zeroone(self, params):
        self._zeroone_armed = False
        if self.dp_world_size <= 1 or self.zero_optimization_stage() != 0:
            return False
        self._zeroone_armed = True
        return True
"""

DISARM_ZEROONE_GOOD = """
class E:
    def _arm_zeroone(self, params):
        self._zeroone_armed = False
        blockers = []
        if self.dp_world_size <= 1:
            blockers.append("data-parallel degree is 1")
        if self.zero_optimization_stage() != 0:
            blockers.append("zero_optimization.stage shards the "
                            "accumulator")
        if blockers:
            log_dist("ZeroOneAdam: wire compression DISARMED - "
                     f"({', '.join(blockers)})", ranks=[0],
                     level=logging.WARNING)
            return False
        self._zeroone_armed = True
        return True
"""

DISARM_QAR_BAD = """
class E:
    def _arm_quantized_allreduce(self, dp, params=None):
        self._qar_armed = False
        if dp <= 1:
            return 0
        self._qar_armed = True
        return self._resolve_intra(dp, params)
"""

DISARM_QAR_GOOD = """
class E:
    def _arm_quantized_allreduce(self, dp, params=None):
        self._qar_armed = False
        if dp <= 1:
            log_dist("quantized_all_reduce: DISARMED - data-parallel "
                     "degree is 1, no wire to shrink", ranks=[0],
                     level=logging.WARNING)
            return 0
        self._qar_armed = True
        return self._resolve_intra(dp, params)
"""


@pytest.mark.parametrize("bad,good,name", [
    (DISARM_ZEROONE_BAD, DISARM_ZEROONE_GOOD, "_arm_zeroone"),
    (DISARM_QAR_BAD, DISARM_QAR_GOOD, "_arm_quantized_allreduce"),
])
def test_disarmed_discipline_covers_zeroone_arming(bad, good, name):
    """PR 18 satellite: the 0/1 Adam wire arming decisions follow the
    armed-or-warns discipline — silently falling back to the dense
    optimizer path (or the flat wire) fires; a DISARMED warn naming the
    blockers (dp=1, zero stage, offload, sparse grads) is quiet."""
    path = "deepspeed_tpu/runtime/engine.py"
    got = lint(bad, path, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert name in got[0].message
    assert lint(good, path, rules=["disarmed-discipline"]) == []


# phase selection that re-reads a device counter per step — the exact
# serialization the _zeroone_frozen_latch exists to avoid
HS_ZEROONE_STEP_BAD = """
class E:
    def _zeroone_phase(self):
        while self._pending:
            s = int(self._step_counter.item())
            self._pending.pop()
        return self.optimizer.cadence(s)
"""

HS_ZEROONE_STEP_GOOD = """
class E:
    def _zeroone_phase(self):
        return self.optimizer.cadence(self.global_steps -
                                      self.skipped_steps)
"""

# a sign-pack kernel that syncs per block — inside every sync round's
# program this would stall the wire once per 128 floats
HS_PACK_BAD = """
def quantize_signs_rows(x, block_size=128):
    scales = []
    for blk in split_blocks(x, block_size):
        scales.append(float(jax.device_get(abs_mean(blk))))
    return pack_bits(x), scales
"""

HS_PACK_GOOD = """
def quantize_signs_rows(x, block_size=128):
    blocks = reshape_blocks(x, block_size)
    scales = abs_mean(blocks)
    return pack_bits(x), scales
"""


def test_host_sync_covers_zeroone_step_and_pack_fns():
    """PR 18 satellite: the per-step phase selector (engine.py) and the
    sign pack/quantize kernels (quantization.py / custom_collectives.py)
    are hot — a device sync in any of their loops fires; pure host
    bookkeeping / straight-line array math is quiet."""
    epath = "deepspeed_tpu/runtime/engine.py"
    got = lint(HS_ZEROONE_STEP_BAD, epath, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"]
    assert "per-iteration loop" in got[0].message
    assert lint(HS_ZEROONE_STEP_GOOD, epath, rules=["host-sync"]) == []
    for qpath in ("deepspeed_tpu/runtime/quantization.py",
                  "deepspeed_tpu/runtime/custom_collectives.py"):
        got = lint(HS_PACK_BAD, qpath, rules=["host-sync"])
        assert rule_names(got) == ["host-sync"], qpath
        assert lint(HS_PACK_GOOD, qpath, rules=["host-sync"]) == []
    # scope: the same pack loop outside the wire files is plain host code
    assert lint(HS_PACK_BAD, "tools/somefile.py", rules=["host-sync"]) == []


HS_REARM_BAD = """
class E:
    def train_batch(self, batch):
        self._arm_zeroone(self._opt_params)
        self._compile_zeroone()
        return self._jit_micro(batch)
"""

HS_REARM_GOOD = """
class E:
    def _configure_optimizer(self):
        if self._arm_zeroone(self._opt_params):
            self._intra = self._arm_quantized_allreduce(self.dp)

    def train_batch(self, batch):
        return self._jit_micro(batch)
"""


def test_host_sync_flags_zeroone_rearm_in_hot_fn():
    """PR 18 satellite: re-arming the wire (blocker scan + program-cache
    rebuild) from a hot step fn is flagged as cold-builder work — arm
    once at configure time, reuse the decision."""
    path = "deepspeed_tpu/runtime/engine.py"
    got = lint(HS_REARM_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync", "host-sync"]
    assert "arming time" in got[0].message
    assert lint(HS_REARM_GOOD, path, rules=["host-sync"]) == []


# ---------------------------------------------------------------------------
# sparse page attention (ISSUE 20): LUT walk hot, arming cold + disarmed
# ---------------------------------------------------------------------------

HS_ACTIVE_ROW_BAD = """
class SparseContext:
    def active_row(self, table_row, pos):
        qb = min(int(pos) // self.bs, self.W - 1)
        phys = [int(jax.device_get(table_row[max(b, 0)]))
                for b in self.lut[qb]]
        return phys, self.lut[qb] * self.bs
"""

HS_WINDOW_FREE_BAD = """
class PagedKVPool:
    def window_expired_free(self, rid, first_active_block, keep_blocks=0):
        for i in range(keep_blocks, first_active_block):
            b = self._blocks[rid][i]
            if float(jax.device_get(self.tensors.k[0, b]).sum()) == 0:
                continue
            self._blocks[rid][i] = None
"""

HS_SPARSE_GOOD = """
class SparseContext:
    def active_row(self, table_row, pos):
        qb = min(int(pos) // self.bs, self.W - 1)
        row = self.lut[qb]
        phys = table_row[np.maximum(row, 0)].astype(np.int32)
        live = (row >= 0) & (phys != TRASH_BLOCK)
        return (np.where(live, phys, 0),
                np.where(live, row * self.bs, self.sentinel))

    def prefill_active_row(self, table_row, start, n, bucket):
        row = self.lut[min(int(start) // self.bs, self.W - 1)]
        return table_row[np.maximum(row, 0)], row * self.bs
"""


@pytest.mark.parametrize("src,path,label", [
    (HS_ACTIVE_ROW_BAD, "deepspeed_tpu/serving/sparse_context.py",
     "active_row"),
    (HS_WINDOW_FREE_BAD, "deepspeed_tpu/serving/kv_cache.py",
     "window_expired_free"),
])
def test_host_sync_covers_sparse_lut_walk(src, path, label):
    """ISSUE 20 satellite: the per-lane LUT walk and the window-expired
    sweep run once per decode dispatch over every running lane — a
    device fetch per lane (or per candidate block) serializes decode
    against the host and fires; the pure-numpy row refresh is quiet."""
    got = lint(src, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync"], (label, path)
    # scoped to the hot files: the same walk elsewhere is free
    assert lint(src, "tests/unit/t.py", rules=["host-sync"]) == []


def test_host_sync_sparse_row_refresh_quiet():
    assert lint(HS_SPARSE_GOOD, "deepspeed_tpu/serving/sparse_context.py",
                rules=["host-sync"]) == []


HS_SPARSE_REARM_BAD = """
class InferenceEngine:
    def _decode_tick(self, events):
        sparse = self._arm_sparse_context(self._sparse_spec)
        sparse._compile_luts()
        return self._decode(*self._decode_args())
"""

HS_SPARSE_REARM_GOOD = """
class InferenceEngine:
    def __init__(self, spec):
        self.sparse = self._arm_sparse_context(spec)

    def _decode_tick(self, events):
        return self._decode(*self._decode_args())
"""


def test_host_sync_flags_sparse_rearm_in_hot_fn():
    """Arming the policy (blocker scan + (W, K) LUT compile) is cold
    -builder work: re-arming per decode tick rebuilds the LUTs and the
    DISARMED decision every step and fires; arm-once at engine build is
    quiet."""
    path = "deepspeed_tpu/serving/engine.py"
    got = lint(HS_SPARSE_REARM_BAD, path, rules=["host-sync"])
    assert rule_names(got) == ["host-sync", "host-sync"]
    assert "arming time" in got[0].message
    assert lint(HS_SPARSE_REARM_GOOD, path, rules=["host-sync"]) == []


DISARM_SPARSE_BAD = """
class InferenceEngine:
    def _arm_sparse_context(self, spec):
        if not spec:
            return None
        if self.spec_k:
            return None
        if int(spec.get("window_tokens", 0)) % self.bs != 0:
            return None
        return SparseContext(block_size=self.bs, table_width=self.W)
"""

DISARM_SPARSE_GOOD = """
class InferenceEngine:
    def _arm_sparse_context(self, spec):
        if not spec:
            return None
        if self.spec_k:
            logger.warning("sparse context: DISARMED - draft-k "
                           "speculation gathers the full table; "
                           "composing the policies is unsupported")
            return None
        if int(spec.get("window_tokens", 0)) % self.bs != 0:
            logger.warning("sparse context: DISARMED - window_tokens "
                           "is not a multiple of the KV block size; "
                           "the window edge would land mid-page")
            return None
        return SparseContext(block_size=self.bs, table_width=self.W)
"""


def test_disarmed_discipline_covers_sparse_context_arming():
    """ISSUE 20 satellite: _arm_sparse_context follows the armed-or-
    warns discipline — silently serving dense when a sparse policy was
    requested fires; DISARMED warns naming the blocker (speculation,
    mid-page window edge) are quiet."""
    path = "deepspeed_tpu/serving/engine.py"
    got = lint(DISARM_SPARSE_BAD, path, rules=["disarmed-discipline"])
    assert rule_names(got) == ["disarmed-discipline"]
    assert "_arm_sparse_context" in got[0].message
    assert lint(DISARM_SPARSE_GOOD, path,
                rules=["disarmed-discipline"]) == []
