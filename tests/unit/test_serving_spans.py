"""The serving step from the inside: ``host_gap`` and the three ``run_*``
spans split the serve thread's time into "a program of ours is in flight"
and "the device has nothing of ours queued", in the engine's ring and in
the profiler's trace; a disarmed engine pays for none of it."""
import glob

import numpy as np
import pytest

import jax

from deepspeed_tpu.serving.engine import InferenceEngine
from deepspeed_tpu.serving.metrics import CompilationCounter
from deepspeed_tpu.telemetry import Telemetry

RUNS = ("run_decode", "run_prefill", "run_prefill_decode")
PARTITION = RUNS + ("host_gap",)
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


def _inside(events, step):
    """Names of the partition spans that END inside one serving_step."""
    lo, hi = step["ts"], step["ts"] + step["dur"]
    return [e["name"] for e in events
            if lo <= e["ts"] + e["dur"] <= hi]


@pytest.mark.parametrize("speculative", [None, 2])
def test_spans_partition_the_serve_thread_exactly(toy, speculative):
    clock = TickClock()
    eng = _engine(toy, clock=clock, speculative=speculative)
    eng.warmup()
    eng.submit(_prompt(5), 12)
    eng.step()
    eng.submit(_prompt(2 * CHUNK + 3, seed=2), 4)
    eng.serve()
    eng.submit(_prompt(6, seed=3), 3)          # after an empty engine
    eng.serve()
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


def test_disarmed_engine_pays_nothing(toy, monkeypatch):
    counts = {}
    for armed in (True, False):
        eng = _engine(toy, armed=armed)
        eng.warmup()
        if not armed:
            monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
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
    assert counts == {True: 0, False: 0}
    # and the patch bites where an annotation IS made
    with pytest.raises(AssertionError, match="TraceAnnotation"):
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
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dstpu:"):
                    seen.setdefault(e.name, []).append(e.duration_ns)
    ring = {}
    for e in eng.telemetry.tracer.events():
        if e["ph"] == "X":
            ring[e["name"]] = ring.get(e["name"], 0) + 1
    for name in ("host_gap", "run_decode", "run_prefill", "serving_step",
                 "prefill_tick", "decode_step"):
        assert all(d > 0 for d in seen[f"dstpu:serve/{name}"]), name
    # the trace began after warm-up: every span begun since is in both
    assert len(seen["dstpu:serve/run_decode"]) <= ring["run_decode"]
    assert len(seen["dstpu:serve/run_decode"]) >= 3
