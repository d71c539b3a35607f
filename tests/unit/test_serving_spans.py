"""The serving step from the inside: ``host_gap`` and the three ``run_*``
spans split the serve thread's time into "a program of ours is in flight"
and "the device has nothing of ours queued", in the engine's ring and in
the profiler's trace; one level down the phase spans of ``_mark`` split
the same time by what the thread is doing; a disarmed engine pays for
none of it."""
import glob

import numpy as np
import pytest

import jax

from deepspeed_tpu.serving.engine import InferenceEngine
from deepspeed_tpu.serving.metrics import CompilationCounter
from deepspeed_tpu.telemetry import Telemetry

RUNS = ("run_decode", "run_prefill", "run_prefill_decode")
PARTITION = RUNS + ("host_gap",)
PHASES = ("step_begin", "prefill_prep", "dispatch", "tables", "fetch",
          "tokens", "step_end", "caller")
# the spans that time a call from its own boundary, each with the phase
# that begins where it begins and the phase that begins where it ends
TICKS = {"serving_step": ("step_begin", "caller"),
         "prefill_tick": ("prefill_prep", "tables"),
         "decode_step": ("tables", "step_end")}
# the most events one step() of a model with one cache group and no
# counters of its own may record (docs/tutorials/observability.md)
EVENTS_A_STEP = 32
CHUNK = 8


@pytest.fixture(scope="module")
def toy():
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                     n_head=4, dtype=jnp.float32, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    return model, params


class TickClock:
    """Every read is one whole second later than the last: two spans
    share an instant only if they share a clock read."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1.0
        return self.now


def _engine(toy, *, armed=True, clock=None, **over):
    model, params = toy
    kwargs = dict(max_slots=3, kv_block_size=4, prefill_chunk=CHUNK,
                  max_blocks_per_seq=8)
    kwargs.update(over)
    if clock is not None:
        kwargs["clock"] = clock
    telemetry = None
    if armed:
        telemetry = Telemetry(mfu=False, **(
            {"clock": clock} if clock is not None else {}))
    return InferenceEngine(model, params, telemetry=telemetry, **kwargs)


def _prompt(n, seed=1):
    return np.random.default_rng(seed).integers(0, 97, n).astype(np.int32)


def _partition(eng):
    return [e for e in eng.telemetry.tracer.events()
            if e["name"] in PARTITION]


def _phases(eng):
    return [e for e in eng.telemetry.tracer.events()
            if e["name"] in PHASES and e["ph"] == "X"]


def _mixed_load(eng):
    """A final chunk alone, chunked steps under running lanes, and a
    request into an engine that stood empty."""
    eng.warmup()
    eng.submit(_prompt(5), 12)
    eng.step()
    eng.submit(_prompt(2 * CHUNK + 3, seed=2), 4)
    eng.serve()
    eng.submit(_prompt(6, seed=3), 3)          # after an empty engine
    eng.serve()


def _inside(events, step):
    """Names of the partition spans that END inside one serving_step."""
    lo, hi = step["ts"], step["ts"] + step["dur"]
    return [e["name"] for e in events
            if lo <= e["ts"] + e["dur"] <= hi]


@pytest.mark.parametrize("speculative", [None, 2])
def test_spans_partition_the_serve_thread_exactly(toy, speculative):
    clock = TickClock()
    eng = _engine(toy, clock=clock, speculative=speculative)
    _mixed_load(eng)
    spans = _partition(eng)
    assert {e["name"] for e in spans} == set(PARTITION)
    # run, gap, run, ..., run: each begins at the instant the last ended
    assert spans[0]["name"] in RUNS and spans[-1]["name"] in RUNS
    for before, after in zip(spans, spans[1:]):
        assert before["ts"] + before["dur"] == after["ts"]
        assert (before["name"] == "host_gap") != (after["name"] == "host_gap")
    first_dispatch = spans[0]["ts"]
    last_fetch = spans[-1]["ts"] + spans[-1]["dur"]
    assert sum(e["dur"] for e in spans) == last_fetch - first_dispatch
    assert all(e["dur"] > 0 for e in spans)
    # the open gap after the last fetch is not an event yet
    assert eng._run is None and eng._gap is not None


@pytest.mark.parametrize("speculative", [None, 2])
def test_phases_partition_the_serve_thread_exactly(toy, speculative):
    eng = _engine(toy, clock=TickClock(), speculative=speculative)
    _mixed_load(eng)
    phases, spans = _phases(eng), _partition(eng)
    assert {e["name"] for e in phases} == set(PHASES)
    assert all(e["dur"] > 0 for e in phases)
    # one cursor: each phase begins at the instant the one before ended
    for before, after in zip(phases, phases[1:]):
        assert before["ts"] + before["dur"] == after["ts"]
    # from the first dispatch to the last fetch they are the thread's time,
    # as host_gap and run_* are one level up
    first_dispatch, last_fetch = spans[0]["ts"], \
        spans[-1]["ts"] + spans[-1]["dur"]
    between = [e for e in phases if first_dispatch <= e["ts"] < last_fetch]
    assert (between[0]["name"], between[-1]["name"]) == ("dispatch", "fetch")
    assert between[0]["ts"] == first_dispatch
    assert between[-1]["ts"] + between[-1]["dur"] == last_fetch
    assert sum(e["dur"] for e in between) == sum(e["dur"] for e in spans) \
        == last_fetch - first_dispatch
    # the cursor stands in the caller's time after the last step
    assert eng._phase.name == "caller"
    # ``admit`` stays what it was: an instant a request, never a span
    admits = [e for e in eng.telemetry.tracer.events()
              if e["name"] == "admit"]
    assert len(admits) >= 3 and all(e["ph"] == "i" for e in admits)


@pytest.mark.parametrize("speculative", [None, 2])
def test_every_phase_lies_inside_one_partition_span(toy, speculative):
    eng = _engine(toy, clock=TickClock(), speculative=speculative)
    _mixed_load(eng)
    spans = _partition(eng)
    lo, hi = spans[0]["ts"], spans[-1]["ts"] + spans[-1]["dur"]
    allowed = {"dispatch": RUNS, "fetch": RUNS}
    host = ("host_gap", "run_prefill_decode")
    seen = set()
    for e in _phases(eng):
        if not lo <= e["ts"] < hi:
            continue                        # warm-up's caller, the last one
        around = [s["name"] for s in spans if s["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= s["ts"] + s["dur"]]
        assert len(around) == 1, e
        assert around[0] in allowed.get(e["name"], host), e
        seen.add((e["name"], around[0]))
    # a chunk into running lanes: the host's phases run under the stretch
    assert {("tables", "run_prefill_decode"), ("tables", "host_gap"),
            ("tokens", "host_gap"), ("step_end", "host_gap"),
            ("caller", "host_gap"), ("step_begin", "host_gap"),
            ("prefill_prep", "host_gap")} <= seen
    # a dispatch is named for what it sends: a chunk's bucket, else 0
    sent = [e["a0"] for e in _phases(eng) if e["name"] == "dispatch"]
    assert {0, CHUNK} <= set(sent) <= {0, 4, CHUNK, 2 * CHUNK}


@pytest.mark.parametrize("speculative", [None, 2])
def test_own_boundary_spans_share_their_instants_with_marks(toy,
                                                            speculative):
    """``serving_step``, ``prefill_tick`` and ``decode_step`` begin and end
    where a phase does, on the same clock read, so that a phase lies
    inside each of them it touches or outside, never across its edge."""
    eng = _engine(toy, clock=TickClock(), speculative=speculative)
    _mixed_load(eng)
    events = [e for e in eng.telemetry.tracer.events() if e["ph"] == "X"]
    begins = {name: {e["ts"] for e in events if e["name"] == name}
              for name in PHASES}
    begins["caller"].add(eng._phase.t0)     # open: not an event yet
    for tick, (first, after) in TICKS.items():
        spans = [e for e in events if e["name"] == tick]
        assert len(spans) >= 8
        assert {e["ts"] for e in spans} <= begins[first], tick
        assert {e["ts"] + e["dur"] for e in spans} <= begins[after], tick
        for e in _phases(eng):
            inside = e["ts"] >= spans[0]["ts"] and any(
                s["ts"] <= e["ts"] and e["ts"] + e["dur"] <= s["ts"]
                + s["dur"] for s in spans)
            outside = all(e["ts"] + e["dur"] <= s["ts"]
                          or s["ts"] + s["dur"] <= e["ts"] for s in spans)
            assert inside or outside, (tick, e)
    # one clock read a mark: a step of one decode program reads it eight
    # times (its eight phases), the two levels and the ticks among them
    eng.submit(_prompt(5), 6)
    eng.step()
    reads, tick = [], eng._tracer.clock
    eng._tracer.clock = lambda: reads.append(1) or tick()
    eng.step()
    assert len(reads) == len(PHASES)


def test_chunks_into_an_empty_engine_keep_the_host_inside_the_stretch(toy):
    eng = _engine(toy, clock=TickClock())
    eng.warmup()
    tr = eng.telemetry.tracer
    tr.reset()
    eng.submit(_prompt(2 * CHUNK + 3), 2)
    for _ in range(3):
        eng.step()
    stretch = next(e for e in tr.events()
                   if e["name"] == "run_prefill_decode")
    under = [e["name"] for e in _phases(eng) if stretch["ts"] <= e["ts"]
             and e["ts"] + e["dur"] <= stretch["ts"] + stretch["dur"]]
    # two non-final chunks and the final one, no lane to decode in between
    # (``dispatch`` runs on over the host's work while the chunk is in
    # flight; ``tables`` of no lanes is the empty decode tick)
    assert under == ["dispatch", "tables", "step_end", "caller",
                     "step_begin", "prefill_prep"] * 2 + ["dispatch", "fetch"]
    assert [e["a0"] for e in _phases(eng) if e["name"] == "tables"][:2] \
        == [0, 0]


def test_step_host_us_is_the_time_between_fetches_less_the_wait(toy):
    """On a clock that ticks a second a read, by hand: from one fetch's
    return to the next one's, less the ``fetch`` phase in between; none
    across a gap in which the engine stood empty."""
    eng = _engine(toy, clock=TickClock())
    eng.warmup()
    tr = eng.telemetry.tracer
    tr.reset()
    eng.submit(_prompt(5), 4)
    eng.serve()
    eng.submit(_prompt(2 * CHUNK + 3, seed=2), 3)   # after an empty engine
    eng.serve()
    events = tr.events()
    fetches = [e for e in events if e["name"] == "fetch"]
    gaps = [e for e in events if e["name"] == "host_gap"]
    counts = [e for e in events if e["name"] == "step_host_us"]
    assert all(e["dur"] == 0 and e["ph"] == "X" for e in counts)
    by_hand = {}
    for before, after in zip(fetches, fetches[1:]):
        end = after["ts"] + after["dur"]
        empty = any(g["a0"] == 0 and before["ts"] < g["ts"] < end
                    for g in gaps)
        if not empty:
            by_hand[end] = round(1e6 * (
                end - (before["ts"] + before["dur"]) - after["dur"]))
    assert {e["ts"]: e["a0"] for e in counts} == by_hand
    # the first fetch of each load follows an empty engine
    assert len(by_hand) == len(fetches) - 2 and counts[0]["ts"] > \
        fetches[1]["ts"]
    # the same sum, from the phases: everything but the waits
    phases = _phases(eng)
    for c in counts[:3]:
        last = max(f["ts"] + f["dur"] for f in fetches
                   if f["ts"] + f["dur"] < c["ts"])
        assert c["a0"] == round(1e6 * sum(
            e["dur"] for e in phases
            if last <= e["ts"] < c["ts"] and e["name"] != "fetch"))


@pytest.mark.parametrize("speculative", [None, 2])
def test_a_step_records_a_bounded_number_of_events(toy, speculative):
    """The ring (``trace.DEFAULT_CAPACITY``) is sized by this: a traced
    benchmark run is ~4,500 steps from ramp to drain."""
    from deepspeed_tpu.telemetry import trace

    eng = _engine(toy, speculative=speculative)
    eng.warmup()
    tr = eng.telemetry.tracer
    assert tr.capacity == trace.DEFAULT_CAPACITY == 1 << 18
    eng.submit(_prompt(5), 12)
    eng.submit(_prompt(2 * CHUNK + 3, seed=2), 4)
    eng.submit(_prompt(CHUNK + 1, seed=3), 6)
    worst = 0
    while eng.scheduler.has_work():
        before = tr.recorded
        eng.step()
        worst = max(worst, tr.recorded - before)
    assert 12 <= worst <= EVENTS_A_STEP
    assert 8192 * EVENTS_A_STEP <= trace.DEFAULT_CAPACITY
    assert tr.summary()["dropped"] == 0


def test_every_program_is_jitted_under_its_registry_name(toy):
    """One name a program, everywhere: the jit's module is named as the
    program registry names the program, and is otherwise the program it
    was as the ``run`` of its factory."""
    eng = _engine(toy, armed=False, speculative=2)
    eng.submit(_prompt(5), 3)
    eng.submit(_prompt(2 * CHUNK + 3, seed=2), 2)
    eng.serve()
    registry = eng.program_registry
    assert {"spec_verify", "prefill_chunk8", "prefill_chunk4_final",
            "prefill_chunk8_final"} <= set(registry.names())
    for entry in registry.entries():
        assert f"module @jit_{entry.name} " in \
            entry.make_lowered().as_text()[:200]
    eng = _engine(toy, armed=False)
    assert eng._decode_name == eng._decode.__name__ == "decode_step"
    args = eng._decode_args()
    named = eng._decode.lower(*args).as_text()
    assert "module @jit_decode_step " in named

    def run(*a):
        return eng._decode.__wrapped__(*a)

    n_pool = eng.n_pool_tensors()
    parent = jax.jit(run, donate_argnums=tuple(range(1, 1 + n_pool))) \
        .lower(*args).as_text()
    assert "module @jit_run " in parent
    assert named == parent.replace("@jit_run ", "@jit_decode_step ")


def test_what_each_stretch_is_named_for(toy):
    eng = _engine(toy, clock=TickClock())
    eng.warmup()
    tr = eng.telemetry.tracer
    eng.submit(_prompt(5), 12)
    eng.step()                      # final chunk alone, then one lane decodes
    tr.reset()
    eng.submit(_prompt(2 * CHUNK + 3, seed=2), 4)
    eng.step()                      # non-final chunk under the decode fetch
    eng.step()                      # the same again
    eng.step()                      # final chunk, fetched; then the decode
    eng.step()                      # nothing to prefill
    events = tr.events()
    steps = [e for e in events if e["name"] == "serving_step"]
    spans = [e for e in events if e["name"] in PARTITION]
    assert _inside(spans, steps[0]) == ["host_gap", "run_prefill_decode"]
    assert _inside(spans, steps[1]) == ["host_gap", "run_prefill_decode"]
    assert _inside(spans, steps[2]) == ["host_gap", "run_prefill",
                                        "host_gap", "run_decode"]
    assert _inside(spans, steps[3]) == ["host_gap", "run_decode"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e["a0"])
    assert by_name["run_prefill_decode"] == [1, 1]      # lanes decoded
    assert by_name["run_prefill"] == [4]                # 3 tokens: bucket 4
    assert by_name["run_decode"] == [2, 2]
    assert set(by_name["host_gap"]) == {1}              # never empty
    # the kept spans time their tick from the tick's own boundary: the
    # decode tick of a step whose chunk was not final also waits for it
    ticks = [e for e in events if e["name"] == "decode_step"]
    chunked = next(e for e in spans if e["name"] == "run_prefill_decode")
    assert ticks[0]["ts"] > chunked["ts"]
    assert ticks[0]["ts"] + ticks[0]["dur"] > chunked["ts"] + chunked["dur"]


def test_a_decode_program_records_its_keys_and_their_pages(toy):
    """``attn_keys_decode``: the positions the live lanes attend;
    ``attn_pages_decode`` beside it: the pages those lie in, which over
    ``max_slots x max_blocks_per_seq`` is the share of a fixed-shape view
    that attention over live pages still reads.  Host arithmetic, one of
    each a decode program."""
    eng = _engine(toy)
    eng.warmup()
    tr = eng.telemetry.tracer
    tr.reset()
    eng.submit(_prompt(5), 6)               # pages of 4 rows: 2, then 3
    eng.submit(_prompt(9, seed=2), 6)       # 3 pages
    eng.serve(max_steps=50)
    events = tr.events()
    keys, pages = ([e["a0"] for e in events if e["name"] == name]
                   for name in ("attn_keys_decode", "attn_pages_decode"))
    assert len(keys) == len(pages) == sum(
        e["name"] in ("run_decode", "run_prefill_decode") and e["a0"] > 0
        for e in events)
    # the first decode program: one lane, its 5 prompt rows and the token
    # just sampled
    assert (keys[0], pages[0]) == (6, 2)
    assert all(k / 4 <= p < k / 4 + 2 for k, p in zip(keys, pages))
    # the last one: the later lane alone, 9 + 5 rows
    assert (keys[-1], pages[-1]) == (14, 4)


def test_gap_across_an_empty_engine_and_chunks_into_one(toy):
    eng = _engine(toy, clock=TickClock())
    eng.warmup()
    tr = eng.telemetry.tracer
    tr.reset()
    # three chunks into an empty engine: no decode tick fetches, so the
    # stretch crosses two step() boundaries and no lane decoded in it
    eng.submit(_prompt(2 * CHUNK + 3), 2)
    for _ in range(3):
        eng.step()
    spans = [e for e in tr.events() if e["name"] in PARTITION]
    assert [(e["name"], e["a0"]) for e in spans[:2]] == [
        ("host_gap", 0),                    # warm-up left the engine empty
        ("run_prefill_decode", 0)]
    steps = [e for e in tr.events() if e["name"] == "serving_step"]
    assert spans[1]["ts"] < steps[0]["ts"] + steps[0]["dur"]
    assert spans[1]["ts"] + spans[1]["dur"] > steps[2]["ts"]
    eng.serve()
    gaps = [e["a0"] for e in tr.events() if e["name"] == "host_gap"]
    assert gaps[0] == 0 and set(gaps[1:]) == {1}


class _Refused:
    def __init__(self, *a, **k):
        raise AssertionError("a TraceAnnotation was made")

    @staticmethod
    def is_enabled():
        return True


def test_disarmed_engine_pays_nothing(toy, monkeypatch):
    counts = {}
    for armed in (True, False):
        eng = _engine(toy, armed=armed)
        eng.warmup()
        if not armed:
            monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
            # the phase cursor's sites are the ``is None`` test alone
            monkeypatch.setattr(InferenceEngine, "_end_phase", _Refused)
        with CompilationCounter() as cc:
            rids = [eng.submit(_prompt(n, seed=n), 3)
                    for n in (5, 2 * CHUNK + 3, 3)]
            eng.serve()
        counts[armed] = cc.count
        assert all(eng.results[r]["status"] == "finished" for r in rids)
        if not armed:
            # no state of the spans was written either
            assert eng._tracer is None
            assert (eng._run, eng._gap, eng._gap_idle) == (None, None, False)
            assert (eng._phase, eng._fetched_at) == (None, None)
    assert counts == {True: 0, False: 0}
    # and the patch bites where an annotation IS made: by an armed engine
    # inside a profiler session, and only there
    monkeypatch.undo()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
    with pytest.raises(AssertionError, match="TraceAnnotation"):
        _engine(toy, armed=True).step()
    monkeypatch.setattr(_Refused, "is_enabled", staticmethod(lambda: False))
    _engine(toy, armed=True).step()


def test_spans_are_annotations_in_the_profilers_trace(toy, tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(toy)
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(_prompt(5), 4)
        eng.submit(_prompt(CHUNK + 2, seed=2), 3)
        eng.serve()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    seen, at = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dstpu:"):
                    seen.setdefault(e.name, []).append(e.duration_ns)
                    at.setdefault(e.name[len("dstpu:serve/"):], []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    ring = {}
    for e in eng.telemetry.tracer.events():
        if e["ph"] == "X":
            ring[e["name"]] = ring.get(e["name"], 0) + 1
    for name in ("host_gap", "run_decode", "run_prefill", "serving_step",
                 "prefill_tick", "decode_step") + PHASES:
        assert all(d > 0 for d in seen[f"dstpu:serve/{name}"]), name
    # the annotations nest: a phase is left before the span that ends at
    # its instant and entered after the span that begins there, so on the
    # profiler's own clock no phase lies across the edge of a partition
    # span or of a tick
    for outer in PARTITION + tuple(TICKS):
        for lo, hi in at[outer]:
            for name in PHASES:
                for a, b in at[name]:
                    assert b <= lo or hi <= a or (lo <= a and b <= hi), \
                        (name, outer)
    for inner, outer in (("prefill_prep", "prefill_tick"),
                         ("tables", "decode_step"),
                         ("step_end", "serving_step")):
        assert all(any(lo <= a and b <= hi for lo, hi in at[outer])
                   for a, b in at[inner]), (inner, outer)
    # both levels in one trace, and a phase's annotation inside its span's
    assert len(seen["dstpu:serve/fetch"]) >= len(seen["dstpu:serve/run_decode"])
    # the trace began after warm-up: every span begun since is in both
    assert len(seen["dstpu:serve/run_decode"]) <= ring["run_decode"]
    assert len(seen["dstpu:serve/run_decode"]) >= 3
