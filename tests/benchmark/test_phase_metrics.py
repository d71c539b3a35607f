"""The per-layer readers of the engine's phase spans (``dispatch``, ``fetch``,
``tables``, ``tokens``, ``step_end``) and of its ``step_host_us`` counter.

As in ``test_span_metrics.py``: the driver runs a PR's benchmark over the
PARENT's program too, whose traced run has spans, only not the new ones.  So
every reader is checked on what ``drive_serve.run`` really returns from the
rehearsal cell, on the same run with the new names taken out, on the empty
cases and on numbers worked by hand."""
import copy
import os
import re
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
REHEARSAL = os.path.join(HERE, "cells")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

# reader -> (the layer its entry names, the ring names it reads)
READERS = {
    "decode_dispatch_ms": ("model step", ("dispatch",)),
    "chunk_dispatch_ms": ("model step", ("dispatch",)),
    "fetch_wait_ms": ("model step", ("fetch",)),
    "host_tables_ms": ("scheduler", ("tables",)),
    "host_tokens_ms": ("scheduler", ("tokens",)),
    "host_step_end_ms": ("scheduler", ("step_end",)),
    "step_host_ms": ("scheduler", ("step_host_us",)),
}
NEW_METRICS = tuple(READERS) + tuple("chat_" + name for name in READERS)
NEW_NAMES = ("step_begin", "prefill_prep", "dispatch", "tables", "fetch",
             "tokens", "step_end", "caller", "step_host_us")
PARENT_SPANS = {"serving_step", "decode_step", "prefill_tick", "host_gap",
                "run_decode", "run_prefill", "run_prefill_decode"}
CHAT, OFFLINE = "gpt2-350m.serve-chat", "gpt2-350m.serve-offline"
KEPT_METRIC = {CHAT: "decode_program_ms",
               OFFLINE: "offline_decode_program_ms"}
# The seven entries name OFFLINE and their ``chat_`` twins CHAT; a later PR
# may append to those lists any cell that judges the metric they move (the
# other serving cells record the same spans), and nothing here holds the
# lists, or the benchmark's other entries, to what they are today.


def _base(name):
    return name[len("chat_"):] if name.startswith("chat_") else name


def _reader(name):
    return cells.load_module(
        os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
        f"bench_metric_t_{name}").read


@pytest.fixture(scope="module")
def observed(devices):
    """What the serving driver returns today from a traced run of the
    rehearsal cell (the CPU backend: no device metric is read here)."""
    benchmark = cells.load_benchmark(os.path.join(REHEARSAL,
                                                  "BENCHMARK.json"))
    cell = cells.Cell(benchmark, "gpt2-tiny.serve-tiny-backlog",
                      root=REHEARSAL)
    run = cell.driver().run(cell, devices[:1], seed=7, seconds=1.5,
                            trace=True, process_start=time.perf_counter(),
                            log=lambda record: None)
    assert run["correct"]
    return run["observed"]


def _parent_shaped(observed):
    """The same run as the parent's program would have reported it: every
    span it records today, none of the names this PR adds."""
    parent = copy.deepcopy(observed)
    for name in NEW_NAMES:
        del parent["spans"][name]
    return parent


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_without_its_span(observed, name):
    read = _reader(name)
    assert read({}) is None
    assert read({"spans": {}}) is None
    assert read({"spans": None}) is None
    parent = _parent_shaped(observed)
    assert PARENT_SPANS <= set(parent["spans"])
    assert read(parent) is None
    # its own names alone are enough, and an empty list of them is nothing
    _, reads = READERS[_base(name)]
    alone = {"spans": {n: observed["spans"][n] for n in reads}}
    assert read(alone) == read(observed)
    assert read({"spans": {n: [] for n in reads}}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_gives_a_number_on_the_run_itself(observed, name):
    assert set(NEW_NAMES) | PARENT_SPANS <= set(observed["spans"])
    value = _reader(name)(observed)
    assert 0 < value < 1e3
    assert value == _reader(_base(name))(observed)


def test_the_phases_account_for_the_window(observed):
    """What the readers are medians of: inside the window the phases sum to
    what ``host_gap`` and ``run_*`` sum to, but for the spans the window's
    two edges cut (a span counts where it ENDS: one of each level a side)."""
    spans = observed["spans"]
    phases = sum(s["ms"] for n in NEW_NAMES[:-1] for s in spans[n])
    partition = sum(s["ms"] for n in PARENT_SPANS - {
        "serving_step", "decode_step", "prefill_tick"} for s in spans[n])
    longest = max(s["ms"] for n in PARENT_SPANS for s in spans[n])
    assert abs(phases - partition) <= 2 * longest
    # a program's three phases are its stretch, fetch the large part
    per_program = _reader("decode_dispatch_ms")(observed) \
        + _reader("fetch_wait_ms")(observed)
    assert per_program < 2 * _reader("decode_program_ms")(observed)


def test_the_parents_result_line_leaves_the_new_metrics_out(observed):
    """``run.py`` over a parent-shaped run of each serving cell: the line
    holds the cell's other per-layer metrics and none of the new names;
    over the run itself it holds all seven under the cell's names."""
    runner = cells.load_module(os.path.join(BENCH_DIR, "run.py"),
                               "bench_run_phases")
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    traced = dict(observed, trace={"busy_s": 1.0, "window_s": 1.0,
                                   "device_ops": [], "idle_gaps": [],
                                   "idle_pct": 0.0})
    for cell_name, kept in KEPT_METRIC.items():
        cell = cells.Cell(cells.load_benchmark(withheld=True),
                          cell_name)
        new = {("chat_" if cell_name == CHAT else "") + name
               for name in READERS}
        lines = []
        for shaped, expected in ((_parent_shaped(traced), set()),
                                 (traced, new)):
            run = {"correct": True, "attempted": 1, "failed": 0,
                   "observed": shaped, "compared": {"x": [0, 0]}}
            line = runner.result_line(cell, run, tpu, 1)
            assert list(line)[-1] == "compared"
            assert set(line["metrics"]) & set(NEW_METRICS) == expected
            assert {"compiles_in_window", kept} <= set(line["metrics"])
            lines.append(line["metrics"])
        # and the metrics that were there read the same beside the new ones
        assert {k: v for k, v in lines[1].items() if k not in new} == lines[0]


def test_readers_by_hand():
    spans = {
        "dispatch": [{"ms": 0.9, "a0": 0}, {"ms": 1.4, "a0": 2048},
                     {"ms": 0.5, "a0": 0}, {"ms": 0.7, "a0": 0},
                     {"ms": 1.0, "a0": 512}, {"ms": 1.2, "a0": 2048}],
        "fetch": [{"ms": 2.0, "a0": 28}, {"ms": 40.0, "a0": 0},
                  {"ms": 1.0, "a0": 27}],
        "tables": [{"ms": 0.3, "a0": 28}, {"ms": 0.5, "a0": 27},
                   {"ms": 0.01, "a0": 0}, {"ms": 0.02, "a0": 0}],
        "tokens": [{"ms": 0.2, "a0": 0}, {"ms": 0.6, "a0": 28},
                   {"ms": 0.4, "a0": 27}, {"ms": 0.8, "a0": 28}],
        "step_end": [{"ms": 0.25, "a0": -1}],
        "step_host_us": [{"ms": 0.0, "a0": 1500}, {"ms": 0.0, "a0": 900},
                         {"ms": 0.0, "a0": 2100}],
    }
    observed = {"spans": spans}
    # nearest rank: of an even number the lower of the middle two
    assert _reader("decode_dispatch_ms")(observed) == 0.7
    assert _reader("chunk_dispatch_ms")(observed) == 1.2
    assert _reader("chat_fetch_wait_ms")(observed) == 2.0
    assert _reader("host_tables_ms")(observed) == 0.3
    assert _reader("chat_host_tokens_ms")(observed) == 0.4
    assert _reader("host_step_end_ms")(observed) == 0.25
    assert _reader("step_host_ms")(observed) == 1.5
    # (a decode tick with no lane to decode is not among the ``tables``)
    # a run of decode programs alone has no chunk to read, and the reverse
    decodes = {"spans": {"dispatch": [{"ms": 0.9, "a0": 0}]}}
    assert _reader("chunk_dispatch_ms")(decodes) is None
    assert _reader("decode_dispatch_ms")(decodes) == 0.9
    chunks = {"spans": {"dispatch": [{"ms": 1.4, "a0": 2048}]}}
    assert _reader("chat_decode_dispatch_ms")(chunks) is None


def test_new_readers_know_nothing_of_the_program():
    """Each of the fourteen is an entry with its file, layer, source and
    ``moves``; its cells judge that metric and are there, the cell it was
    entered for among them."""
    benchmark = cells.load_benchmark(withheld=True)
    entries = {m["name"]: m for m in benchmark["per_layer"]}
    judged = {m["name"]: m["workloads"] for m in benchmark["end_to_end"]
              if "workloads" in m}
    for name in NEW_METRICS:
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               name + ".py")) as f:
            source = f.read()
        assert "deepspeed_tpu" not in source, name
        imported = re.findall(r"^from\s+([\w.]+)\s+import\s|^import\s+(\S+)",
                              source, re.MULTILINE)
        assert {a or b for a, b in imported} <= {"harness.spans", "harness.cells"}, name
        entry = entries[name]
        assert (entry["source"], entry["better"], entry["unit"]) \
            == ("program_span", "lower", "ms")
        assert entry["layer"] == READERS[_base(name)][0]
        chat = name.startswith("chat_")
        assert entry["moves"] == ("tpot_p95_s" if chat
                                  else "serve_tokens_per_s")
        assert (CHAT if chat else OFFLINE) in entry["workloads"]
        assert set(entry["workloads"]) <= set(judged[entry["moves"]])
        for cell_name in entry["workloads"]:
            assert callable(cells.Cell(benchmark, cell_name).reader(name))
