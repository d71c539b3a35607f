"""Whole-program HLO lint (tools/graftlint/program_lint.py) tests.

Two layers:

1. fixtures — each program-lint analysis has a known-bad registry it
   fires on and a known-good twin it stays quiet on (wire widening,
   collective order, donation translation, lower errors, baseline
   round-trip), built from tiny hand-registered jits;
2. autopilot (tier-1) — ONE subprocess run of
   ``python -m tools.graftlint --programs --json`` over the real
   tiny-engine corpus asserts the whole repo is contract-clean, the
   registries are complete (every program family the engines build is
   registered), and the hand-written HLO contract assertions this PR
   ported into registry declarations actually resolved.  Registering a
   new jit IS opting into coverage — this one test polices all of them.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from deepspeed_tpu.telemetry.programs import (CONTRACT_KEYS,  # noqa: E402
                                              ProgramRegistry,
                                              register_program)
from tools.graftlint.core import load_baseline, save_baseline  # noqa: E402
from tools.graftlint.program_lint import (CORPUS_BUILDERS,  # noqa: E402
                                          PROGRAM_RULES, build_corpus,
                                          collective_order, lint_programs,
                                          program_rules)

# every program each corpus engine must have registered — an engine that
# builds a jit without registering it (or renames one) fails HERE, not
# in some per-jit test that nobody wrote
EXPECTED_PROGRAMS = {
    "base-qgz": {"apply_step", "eval_loss", "micro_step"},
    "stage3": {"apply_step", "s3_bwd", "s3_fwd"},
    "zeroone": {"zeroone_fused:warmup_k1", "zeroone_fused:local_k2",
                "zeroone_fused:sync_k2"},
    "onebit": {"onebit_fused:warmup", "onebit_fused:frozen"},
    "pipe": {"chunk0:apply_step", "chunk0:bwd_dgrad_stash",
             "chunk0:bwd_wgrad_stash", "chunk0:fwd_stash", "chunk0:sqnorm",
             "chunk1:apply_step", "chunk1:bwd_dgrad_stash",
             "chunk1:bwd_wgrad_stash", "chunk1:fwd_stash",
             "chunk1:mean_scalar", "chunk1:sqnorm"},
    "pipe-bf16": {"chunk0:apply_step", "chunk0:bwd_mid", "chunk0:fwd",
                  "chunk0:sqnorm", "chunk1:apply_step", "chunk1:bwd_last",
                  "chunk1:mean_scalar", "chunk1:sqnorm"},
    "serving": {"decode_step", "prefill_chunk8_final"},
    "serving-spec": {"cow_copy", "prefill_chunk4_final", "prefill_chunk8",
                     "spec_verify"},
    "serving-sparse": {"sparse_decode_step", "sparse_prefill_chunk8",
                       "sparse_prefill_chunk4_final"},
}


def rule_names(result):
    return [f.rule for f in result.new]


# ---------------------------------------------------------------------------
# framework
# ---------------------------------------------------------------------------

def test_program_rule_catalog():
    assert {"program-lower-error", "program-host-transfer",
            "program-collective-free", "program-wire-widening",
            "program-forbidden-collective", "program-op-count",
            "program-collective-budget", "program-donation",
            "program-output-alias", "program-boundary-dtype",
            "program-collective-order"} == set(PROGRAM_RULES)
    for r in program_rules():
        assert r.name in PROGRAM_RULES and r.description


def test_contract_key_typo_fails_loudly():
    reg = ProgramRegistry(engine="t")
    with pytest.raises(ValueError, match="wire_dtpye"):
        reg.register("p", lambda: None, contract={"wire_dtpye": "s8"})
    reg.register("p", lambda: None, contract={"wire_dtype": "s8"})
    with pytest.raises(ValueError, match="donatez"):
        reg.declare("p", donatez=[0])
    assert "collective_free" in CONTRACT_KEYS


def test_lower_error_is_a_finding_not_a_crash():
    def boom():
        raise RuntimeError("registration drift")

    reg = ProgramRegistry(engine="t")
    reg.register("broken", boom, contract={"host_transfer_free": True})
    res = lint_programs([reg], use_baseline=False)
    assert rule_names(res) == ["program-lower-error"]
    assert "registration drift" in res.new[0].message
    assert res.new[0].path == "<t:broken>"


def test_build_corpus_rejects_unknown_engine():
    with pytest.raises(ValueError, match="no-such-engine"):
        build_corpus(only=["no-such-engine"])
    assert (set(EXPECTED_PROGRAMS) - {"serving-spec", "serving-sparse"}
            == set(CORPUS_BUILDERS))


# ---------------------------------------------------------------------------
# wire widening — the GSPMD re-widened-quantized-wire class
# ---------------------------------------------------------------------------

def _wire_registry(pin_before_dequant, eight):
    """An int8 'gather then dequantize' program pair (the qwZ wire trick,
    see test_quantization.py): constraining the s8 array replicated
    BEFORE the astype pins the all-gather to the 1-byte payload; the
    twin without the constraint lets GSPMD commute the convert across
    the collective and gather f32 — 4x the declared wire."""
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    n = 1024

    def quiet_fn(q, s):
        q = jax.lax.with_sharding_constraint(q, rep)
        return q.astype(jnp.float32).reshape(8, -1) * s[:, None]

    def fire_fn(q, s):
        return q.astype(jnp.float32).reshape(8, -1) * s[:, None]

    fn = quiet_fn if pin_before_dequant else fire_fn
    q = jax.device_put(np.ones(n, np.int8), sharded)
    s = jax.device_put(np.ones(8, np.float32), rep)
    reg = ProgramRegistry(engine="wire-fixture")
    register_program(reg, "gather_dequant", jax.jit(fn, out_shardings=rep),
                     (q, s), mesh=mesh,
                     contract={"wire_dtype": "s8", "wire_min_elements": 256})
    return reg


def test_wire_widening_fires_on_gspmd_rewiden(eight_devices):
    res = lint_programs([_wire_registry(False, eight_devices)],
                        use_baseline=False)
    assert rule_names(res) == ["program-wire-widening"]
    assert "all-gather[f32x1024]" in res.new[0].message


def test_wire_widening_quiet_when_wire_pinned_s8(eight_devices):
    res = lint_programs([_wire_registry(True, eight_devices)],
                        use_baseline=False)
    assert not res.new, [f.message for f in res.new]
    # the clean program still counts as covered (stale pruning works)
    assert "<wire-fixture:gather_dequant>" in res.scanned_paths


def test_program_baseline_roundtrip_and_stale(tmp_path):
    """Program findings ride the same baseline machinery as file
    findings: baselining silences, fixing the program makes the entry
    stale (pseudo-path coverage)."""
    baseline = str(tmp_path / "b.json")

    def boom():
        raise RuntimeError("drift")

    bad = ProgramRegistry(engine="bl")
    bad.register("prog", boom)
    r1 = lint_programs([bad], baseline_path=baseline)
    assert len(r1.new) == 1 and not r1.baselined
    fp = next(fp for fp, f in r1.fingerprints.items() if f is r1.new[0])
    save_baseline(r1, path=baseline,
                  notes={fp: "known-broken, tracked elsewhere"})

    bad2 = ProgramRegistry(engine="bl")
    bad2.register("prog", boom)
    r2 = lint_programs([bad2], baseline_path=baseline)
    assert not r2.new and len(r2.baselined) == 1 and not r2.stale

    # "fix" the program: same pseudo-path, now lowers to a contract-free
    # module -> no findings -> the baselined entry is stale
    class _FakeCompiled:
        def as_text(self):
            return "HloModule empty"

    class _FakeLowered:
        def compile(self):
            return _FakeCompiled()

    fixed = ProgramRegistry(engine="bl")
    fixed.register("prog", _FakeLowered)
    r3 = lint_programs([fixed], baseline_path=baseline)
    assert not r3.new and not r3.baselined and len(r3.stale) == 1
    save_baseline(r3, path=baseline)
    assert load_baseline(baseline)["entries"] == []


# ---------------------------------------------------------------------------
# collective order — static SPMD deadlock across programs
# ---------------------------------------------------------------------------

def _order_registry(divergent, eight):
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    def ar_only(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                             in_specs=P("data"), out_specs=P("data"))(x)

    def ag_then_ar(x):
        def body(v):
            g = jax.lax.all_gather(v, "data")
            return jax.lax.psum(v, "data") + g.sum(0)
        return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"))(x)

    x = jax.device_put(np.ones(1024, np.float32), sharded)
    reg = ProgramRegistry(engine="order-fixture")
    register_program(reg, "caller_a", jax.jit(ar_only), (x,), mesh=mesh,
                     contract={"uniform_group": "step-slot"})
    second = ag_then_ar if divergent else ar_only
    register_program(reg, "caller_b", jax.jit(second), (x,), mesh=mesh,
                     contract={"uniform_group": "step-slot"})
    return reg


def test_collective_order_divergence_fires(eight_devices):
    res = lint_programs([_order_registry(True, eight_devices)],
                        use_baseline=False)
    assert rule_names(res) == ["program-collective-order"]
    f = res.new[0]
    assert f.path == "<order-fixture:caller_b>"
    assert "uniform_group 'step-slot'" in f.message
    assert "deadlock" in f.message


def test_collective_order_identical_is_quiet(eight_devices):
    res = lint_programs([_order_registry(False, eight_devices)],
                        use_baseline=False)
    assert not res.new, [f.message for f in res.new]
    # and the signature extractor itself sees the one psum
    reg = _order_registry(False, eight_devices)
    order = collective_order(reg.get("caller_a").hlo())
    assert ("all-reduce", "f32") in order


def test_uniform_groups_scoped_per_engine(eight_devices):
    """The same group name on two DIFFERENT engines must not couple —
    programs from different engines never share an SPMD dispatch slot."""
    a = _order_registry(False, eight_devices)
    b = _order_registry(True, eight_devices)
    b.engine = "order-fixture-2"
    # within-engine divergence in b still fires; a+b cross-engine doesn't
    res = lint_programs([a, b], use_baseline=False)
    assert rule_names(res) == ["program-collective-order"]
    assert res.new[0].path.startswith("<order-fixture-2:")


# ---------------------------------------------------------------------------
# donation — kept_var_idx translation and the alias tables
# ---------------------------------------------------------------------------

def _donation_registry(donates, eight):
    """jit f(a, b, c) with b UNUSED (jit prunes it: entry params are
    a->0, c->1) and only a donated.  Declared flat ``donates`` indices
    must be translated through kept_var_idx before reading the HLO
    alias tables."""
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    rep = NamedSharding(mesh, P())

    def f(a, b, c):
        return a + c

    a = jax.device_put(np.ones(512, np.float32), rep)
    reg = ProgramRegistry(engine="don-fixture")
    register_program(reg, "prog", jax.jit(f, donate_argnums=(0,)),
                     (a, a, a), mesh=mesh, contract={"donates": donates})
    return reg


def test_donation_translates_flat_indices_through_pruning(eight_devices):
    # flat 0 (donated, kept at entry pos 0) -> clean
    res = lint_programs([_donation_registry([0], eight_devices)],
                        use_baseline=False)
    assert not res.new, [f.message for f in res.new]
    # flat 1 is PRUNED (never copied) -> trivially satisfied, clean
    res = lint_programs([_donation_registry([1], eight_devices)],
                        use_baseline=False)
    assert not res.new, [f.message for f in res.new]
    # flat 2 (kept at entry pos 1, NOT donated) -> dropped donation fires
    res = lint_programs([_donation_registry([2], eight_devices)],
                        use_baseline=False)
    assert rule_names(res) == ["program-donation"]
    assert "[2]" in res.new[0].message and "silent copy" in res.new[0].message


def test_donation_min_elements_exempts_tiny_leaves(eight_devices):
    """A sub-threshold undonated leaf (an rng key XLA declines to alias)
    is exempt under donation_min_elements; a full-size one is not."""
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    rep = NamedSharding(mesh, P())

    def f(big, tiny):
        return big * 2.0, tiny + 1

    big = jax.device_put(np.ones(512, np.float32), rep)
    tiny = jax.device_put(np.ones(2, np.uint32), rep)

    reg = ProgramRegistry(engine="don-min")
    register_program(reg, "prog", jax.jit(f), (big, tiny), mesh=mesh,
                     contract={"donates": [0, 1],
                               "donation_min_elements": 4})
    res = lint_programs([reg], use_baseline=False)
    # nothing is donated: the tiny leaf (2 elements < 4) is exempt, the
    # 512-element leaf still fires
    assert rule_names(res) == ["program-donation"]
    assert "[0]" in res.new[0].message


# ---------------------------------------------------------------------------
# autopilot (tier-1): the real corpus, contract-clean, registries complete
# ---------------------------------------------------------------------------

# generous CI budget; a clean run measures ~45s on the 8-device CPU mesh
AUTOPILOT_BUDGET_S = 420


def test_programs_autopilot_corpus_is_clean_and_complete():
    """THE contract autopilot: one subprocess run of the --programs lint
    over every engine family.  New findings, stale baseline entries, a
    missing registration, or a contract that stopped resolving all fail
    here — this replaces the per-jit HLO contract tests it ported."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--programs", "--json"],
        cwd=REPO, capture_output=True, text=True,
        timeout=AUTOPILOT_BUDGET_S + 60)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert elapsed < AUTOPILOT_BUDGET_S, \
        f"program lint took {elapsed:.0f}s (budget {AUTOPILOT_BUDGET_S}s)"

    # stdout is pure JSON (engine logs go to stderr)
    payload = json.loads(proc.stdout)
    assert payload["summary"]["new"] == 0, payload["new"]
    assert payload["summary"]["stale_baseline"] == 0, \
        payload["stale_baseline"]
    assert set(PROGRAM_RULES) <= set(payload["rules"])

    # registry completeness: every engine family, every program family
    progs = payload["programs"]
    assert set(progs) == set(EXPECTED_PROGRAMS)
    for eng, expected in EXPECTED_PROGRAMS.items():
        assert set(progs[eng]) == expected, \
            f"{eng}: {sorted(progs[eng])} != {sorted(expected)}"

    def contract(eng, name):
        return progs[eng][name]["contract"]

    # the ported hand-written HLO contract assertions, now declarations:
    # 1. qgZ micro step: host-transfer free, s8 wire, analytic budget
    c = contract("base-qgz", "micro_step")
    assert c["host_transfer_free"] and c["wire_dtype"] == "s8"
    assert c["comm_budget_key"] == "grad_exchange_bytes_per_step"
    assert isinstance(c["comm_budget_bytes"], (int, float)) \
        and c["comm_budget_bytes"] > 0
    # 2. ...and donates the full train-state arg (flat leaves 0..N)
    assert c["donates"] and c["donates"][0] == 0
    # 3. stage-3 forward: one s8 gather per scheduled leaf, exactly
    assert contract("stage3", "s3_fwd")["expect_op_counts"] == \
        [["all-gather", "s8", 3]]
    # 4. stage-3 backward: no remat-refetch gathers; stash donated in
    c = contract("stage3", "s3_bwd")
    assert "all-gather" in c["forbid_collectives"] and c["donates"]
    # 5. 0/1 Adam local round: ZERO collectives
    assert contract("zeroone", "zeroone_fused:local_k2")["collective_free"]
    # 6. 0/1 Adam sync round: packed u8/s8 wire within the analytic budget
    c = contract("zeroone", "zeroone_fused:sync_k2")
    assert sorted(c["wire_dtype"]) == ["s8", "u8"]
    assert c["comm_budget_key"] == "optimizer_wire.sync_round_bytes"
    # 7. 1-bit Adam frozen phase: sign-packed wire
    assert sorted(contract("onebit", "onebit_fused:frozen")["wire_dtype"]) \
        == ["s8", "u8"]
    # 8. bf16 pipeline boundary: stage output leaves in bf16
    assert contract("pipe-bf16", "chunk0:fwd")["boundary_dtypes"] == ["bf16"]
    # 9. zb-h1 wgrad: consumes the donated stash, writes grads in place
    c = contract("pipe", "chunk0:bwd_wgrad_stash")
    assert c["outputs_aliased"] >= 1 and c["donates"]
    # 10. serving decode: batch-sharded, collective-free, pool donated
    c = contract("serving", "decode_step")
    assert c["collective_free"] and c["donates"] == [28, 29]
    # 11. sparse page attention (ISSUE 20): same pool-donation contract
    # as dense decode (pools sit at the same flat arg slots — the extra
    # stables/sbase operands ride AFTER the tables), and the bucketed
    # sparse prefills stay shape-uniform within their group
    c = contract("serving-sparse", "sparse_decode_step")
    assert c["collective_free"] and c["host_transfer_free"] \
        and c["donates"] == [28, 29]
    assert contract("serving-sparse", "sparse_prefill_chunk8")[
        "uniform_group"] == "serving:sparse_prefill"
    assert contract("serving-sparse", "sparse_prefill_chunk4_final")[
        "uniform_group"] == "serving:sparse_prefill_final"
