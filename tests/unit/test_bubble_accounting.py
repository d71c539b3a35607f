"""Bubble-accounting simulator tests + the tier-1 schedule-quality guard.

The guard (test_schedule_quality_guard) is the analytic counterpart of
comm_budget: if a schedule change regresses the interleaved or zero-bubble
win at the canonical pipe=4/gas=8 point, the suite fails — the bubble
claim in the schedule's docstring is enforced, not aspirational."""
import pytest

from deepspeed_tpu.runtime.pipe import bubble_accounting as ba
from deepspeed_tpu.runtime.pipe import schedule as sched_lib


def test_1f1b_matches_closed_form():
    """Equal f/b costs: the simulation reproduces (S-1)/(M+S-1) exactly."""
    for stages, micros in [(2, 4), (4, 4), (4, 8), (2, 8), (3, 6)]:
        rep = ba.bubble_report("1f1b", micros, stages,
                               costs=ba.CostModel.equal_fwd_bwd())
        assert rep["bubble_fraction"] == pytest.approx(
            ba.ideal_1f1b_bubble(micros, stages), abs=1e-12)


def test_1f1b_bubble_fraction_at_gas4():
    """The closed form's values at gas=4 that the module's docstring
    quotes: 0.20 at pipe=2, 0.43 at pipe=4."""
    eq = ba.CostModel.equal_fwd_bwd()
    assert ba.bubble_report("1f1b", 4, 2, costs=eq)["bubble_fraction"] == \
        pytest.approx(0.20, abs=5e-3)
    assert ba.bubble_report("1f1b", 4, 4, costs=eq)["bubble_fraction"] == \
        pytest.approx(0.43, abs=5e-3)


def test_schedule_quality_guard():
    """Tier-1 guard (ISSUE 3 + ISSUE 6 acceptance): at pipe=4, gas=8 the
    analytic bubble fraction must order interleaved(v=2) < 1f1b and
    zb-h1 <= interleaved(v=2) — and with activation stashing (ISSUE 6),
    zb-h1 must be a genuine THROUGHPUT win: makespan 27 < 1f1b's 33
    under CostModel(dgrad=1, wgrad=1), replayed by the simulator, with
    the worst-stage activation peak still within 1F1B's bound."""
    base = ba.bubble_report("1f1b", 8, 4)["bubble_fraction"]
    inter = ba.bubble_report("interleaved", 8, 4,
                             virtual_stages=2)["bubble_fraction"]
    zb = ba.bubble_report("zb-h1", 8, 4)["bubble_fraction"]
    assert inter < base, f"interleaved v=2 {inter} !< 1f1b {base}"
    assert zb <= inter, f"zb-h1 {zb} !<= interleaved {inter}"
    # the margins the PR shipped with — allow improvement, not regression
    assert base == pytest.approx(0.2727, abs=2e-3)
    assert inter <= 0.16
    assert zb <= 0.13
    # --- the stashing flip: zb-h1 WINS makespan, not just bubble -------
    stash_costs = ba.CostModel(fwd=1, bwd=2, dgrad=1, wgrad=1)
    zb_stash = ba.bubble_report("zb-h1", 8, 4, stash=True,
                                costs=stash_costs)
    base_stash = ba.bubble_report("1f1b", 8, 4, costs=stash_costs)
    assert zb_stash["makespan"] < base_stash["makespan"], \
        (f"zb-h1+stash makespan {zb_stash['makespan']} !< 1f1b "
         f"{base_stash['makespan']}")
    assert zb_stash["makespan"] == pytest.approx(27.0)
    assert base_stash["makespan"] == pytest.approx(33.0)
    # memory bound: stashing must not grow the worst-stage peak beyond
    # 1F1B's (the documented min(S, M) in-flight cap), and the stash
    # lifetime (F -> W) peaks at the same count
    assert max(zb_stash["peak_live_buffers"]) <= \
        max(base_stash["peak_live_buffers"])
    assert max(zb_stash["peak_live_stash"]) <= 4  # min(S, M) at 4/8
    # stash=True is also the simulator default for stash-compiled streams
    assert ba.bubble_report("zb-h1", 8, 4, stash=True)["makespan"] == \
        pytest.approx(27.0)


@pytest.mark.parametrize("schedule,v", [("1f1b", 1), ("interleaved", 2),
                                        ("interleaved", 3), ("zb-h1", 1)])
@pytest.mark.parametrize("stages,micros", [(2, 4), (2, 8), (4, 4), (4, 8)])
def test_deadlock_freedom(schedule, v, stages, micros):
    """Every compiled schedule completes under queue semantics (a wedged
    stream raises DeadlockError instead of looping forever)."""
    if schedule == "interleaved" and micros % stages != 0:
        pytest.skip("interleaved needs micros % stages == 0")
    rep = ba.bubble_report(schedule, micros, stages, virtual_stages=v)
    assert rep["makespan"] > 0
    assert all(0.0 <= f < 1.0 for f in rep["idle_fraction"])


def test_interleaving_shrinks_bubble_about_v():
    """The Megatron claim: interleaving with v chunks cuts the bubble
    TIME to 1/v of 1f1b's — per stage, idle time (S-1)(f+b) becomes
    (S-1)(f+b)/v while busy time W stays fixed, so the fraction is
    (B/v) / (W + B/v)."""
    base = ba.bubble_report("1f1b", 8, 4)
    busy = base["busy"][0]
    bubble_time = base["makespan"] - busy
    for v in (2, 4):
        rep = ba.bubble_report("interleaved", 8, 4, virtual_stages=v)
        expected = (bubble_time / v) / (busy + bubble_time / v)
        assert rep["bubble_fraction"] == pytest.approx(expected, rel=1e-6)


def test_interleaved_p2p_cost_reported():
    """The bubble win is not free: (S*v - 1) boundaries vs (S - 1)."""
    base = ba.bubble_report("1f1b", 8, 4)
    rep = ba.bubble_report("interleaved", 8, 4, virtual_stages=2)
    assert base["p2p_transfers"] == 2 * 3 * 8        # 2 dirs x edges x gas
    assert rep["p2p_transfers"] == 2 * 7 * 8


def test_zb_peak_buffers_bounded():
    """ZB-H1's wgrad deferral must not grow the WORST-stage activation
    peak beyond 1F1B's (uniform provisioning is sized by stage 0)."""
    base = ba.bubble_report("1f1b", 8, 4)
    zb = ba.bubble_report("zb-h1", 8, 4)
    assert max(zb["peak_live_buffers"]) <= max(base["peak_live_buffers"])


def test_deadlock_detection_raises():
    """A stream whose Recv has no matching Send must raise, not hang."""
    compiled = sched_lib.compile_schedule("1f1b", 4, 2)
    # drop stage 0's first SendActivation: stage 1 can never start
    s0 = [c for c in compiled.streams[0]
          if not isinstance(c, sched_lib.SendActivation)]
    bad = sched_lib.CompiledSchedule(
        "broken", 4, 2, 1, [s0, compiled.streams[1]],
        compiled.num_buffers)
    with pytest.raises(ba.DeadlockError):
        ba.simulate(bad)


def test_cost_model_scales_with_virtual_stages():
    """Chunk compute is 1/v of a stage pass: interleaving moves the SAME
    total work as 1f1b. zb-h1 moves 4/3 of it under the default model —
    the split passes each pay their own forward recompute (d + w = b + f),
    which is exactly the remat tax the report must not hide."""
    base = ba.bubble_report("1f1b", 8, 4)
    rep = ba.bubble_report("interleaved", 8, 4, virtual_stages=2)
    assert sum(rep["busy"]) == pytest.approx(sum(base["busy"]))
    zb = ba.bubble_report("zb-h1", 8, 4)
    assert sum(zb["busy"]) == pytest.approx(sum(base["busy"]) * 4 / 3)


def test_zb_remat_tax_shows_in_makespan():
    """A zb-h1 stream compiled WITHOUT stash slots still pays the remat
    tax, and the report must not hide it: under the remat-honest default
    model its makespan exceeds 1f1b's at the guard point.  The same
    schedule compiled with stash slots defaults to CostModel.stash() and
    IS a genuine makespan win; both facts are the documented trade in
    docs/tutorials/pipeline_schedules.md."""
    base = ba.bubble_report("1f1b", 8, 4)
    zb = ba.bubble_report("zb-h1", 8, 4)
    assert zb["makespan"] > base["makespan"]
    assert zb["stash"] is False and zb["peak_live_stash"] == [0] * 4
    zb_stash = ba.bubble_report("zb-h1", 8, 4, stash=True)
    assert zb_stash["stash"] is True
    assert zb_stash["cost_model"]["dgrad"] == 1.0   # stash default model
    assert zb_stash["makespan"] < base["makespan"]


def test_stash_slots_only_on_stash_compile():
    """Stash slots are an explicit compile artifact: a remat stream
    declares none (executors/tools must refuse stash-mode accounting on
    it), a stash stream declares one per buffer slot."""
    import deepspeed_tpu.runtime.pipe.schedule as sched_lib

    remat = sched_lib.compile_schedule("zb-h1", 8, 4)
    stash = sched_lib.compile_schedule("zb-h1", 8, 4, stash=True)
    assert remat.num_stash_slots == [0] * 4
    assert stash.num_stash_slots == stash.num_buffers
    assert all(n > 0 for n in stash.num_stash_slots)
    with pytest.raises(AssertionError):
        sched_lib.compile_schedule("1f1b", 8, 4, stash=True)


@pytest.mark.parametrize("stages,micros", [(2, 4), (2, 8), (4, 4), (4, 8)])
def test_stash_peak_bounded_by_inflight_cap(stages, micros):
    """Peak live stash count never exceeds the planner's in-flight cap
    min(S, M) on any stage, for any pipe x gas — the analytic bound the
    engine's pipeline.stash_budget check multiplies by per-micro bytes."""
    rep = ba.bubble_report("zb-h1", micros, stages, stash=True)
    cap = max(2, min(stages, micros))
    assert all(p <= cap for p in rep["peak_live_stash"]), rep
    # deadlock-free and still the best makespan among the three schedules
    assert rep["makespan"] <= ba.bubble_report(
        "1f1b", micros, stages, costs=ba.CostModel.stash())["makespan"]
