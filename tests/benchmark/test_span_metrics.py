"""The per-layer readers of the engine's ``host_gap`` / ``run_*`` spans.

The driver runs a PR's benchmark over the PARENT's program too, and the
parent's traced run hands the readers an ``observed`` that has spans, only
not the new ones.  So the readers are checked on what ``drive_serve.run``
really returns from the rehearsal cell, with and without the new names,
besides the empty cases, and by hand."""
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

READERS = ("host_gap_pct", "decode_program_ms", "prefill_program_ms",
           "chunk_step_ms")
NEW_METRICS = READERS + tuple("offline_" + name for name in READERS)
NEW_SPANS = ("host_gap", "run_decode", "run_prefill", "run_prefill_decode")
PARENT_SPANS = {"serving_step", "decode_step", "prefill_tick"}
SERVING_CELLS = {"gpt2-350m.serve-chat": READERS,
                 "gpt2-350m.serve-offline": NEW_METRICS[len(READERS):]}
KEPT_METRIC = {"gpt2-350m.serve-chat": "decode_step_ms",
               "gpt2-350m.serve-offline": "offline_decode_step_ms"}


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
    run = cell.driver().run(cell, devices[:1], seed=5, seconds=1.5,
                            trace=True, process_start=time.perf_counter(),
                            log=lambda record: None)
    assert run["correct"]
    return run["observed"]


def _parent_shaped(observed):
    """The same run as the parent's program would have reported it: every
    span it records today, none of the four this PR adds."""
    parent = copy.deepcopy(observed)
    for name in NEW_SPANS:
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
    # a program that has the gap but none of the runs, and the reverse
    only_gap = _parent_shaped(observed)
    only_gap["spans"]["host_gap"] = observed["spans"]["host_gap"]
    only_runs = copy.deepcopy(observed)
    del only_runs["spans"]["host_gap"]
    if name.endswith("host_gap_pct"):
        assert read(only_gap) is None and read(only_runs) is None
    else:
        assert read(only_gap) is None and read(only_runs) > 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_gives_a_number_on_the_run_itself(observed, name):
    assert set(NEW_SPANS) | PARENT_SPANS <= set(observed["spans"])
    value = _reader(name)(observed)
    assert value > 0
    if name.endswith("_pct"):
        assert value < 100


def test_the_parents_result_line_leaves_the_new_metrics_out(observed):
    """``run.py`` over a parent-shaped run of each serving cell: the line
    holds the cell's other per-layer metrics and none of the new names;
    over the run itself it holds all four."""
    runner = cells.load_module(os.path.join(BENCH_DIR, "run.py"),
                               "bench_run_spans")
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    traced = dict(observed, trace={"busy_s": 1.0, "window_s": 1.0,
                                   "device_ops": [], "idle_gaps": [],
                                   "idle_pct": 0.0})
    for cell_name, new in SERVING_CELLS.items():
        cell = cells.Cell(cells.load_benchmark(withheld=True),
                          cell_name)
        for shaped, expected in ((_parent_shaped(traced), set()),
                                 (traced, set(new))):
            run = {"correct": True, "attempted": 1, "failed": 0,
                   "observed": shaped, "compared": {"x": [0, 0]}}
            line = runner.result_line(cell, run, tpu, 1)
            assert list(line)[-1] == "compared"
            assert set(line["metrics"]) & set(NEW_METRICS) == expected
            assert {"compiles_in_window", KEPT_METRIC[cell_name]} \
                <= set(line["metrics"])


def test_readers_by_hand():
    spans = {
        "host_gap": [{"ms": 4.0, "a0": 1}, {"ms": 50.0, "a0": 0},
                     {"ms": 2.0, "a0": 1}],
        "run_decode": [{"ms": 70.0, "a0": 3}, {"ms": 74.0, "a0": 28},
                       {"ms": 72.0, "a0": 5}],
        "run_prefill": [{"ms": 56.0, "a0": 256}, {"ms": 30.0, "a0": 64}],
        "run_prefill_decode": [{"ms": 500.0, "a0": 0},
                               {"ms": 128.0, "a0": 7}],
    }
    observed = {"spans": spans}
    # the empty engine's gap is want of demand and counts on neither side
    assert _reader("host_gap_pct")(observed) == pytest.approx(
        100 * 6.0 / (6.0 + 216.0 + 86.0 + 628.0))
    assert _reader("offline_decode_program_ms")(observed) == 72.0
    assert _reader("prefill_program_ms")(observed) == 30.0
    # a stretch under which no lane decoded is left out
    assert _reader("offline_chunk_step_ms")(observed) == 128.0
    assert _reader("chunk_step_ms")({"spans": {"run_prefill_decode": [
        {"ms": 500.0, "a0": 0}]}}) is None


def test_new_readers_know_nothing_of_the_program():
    entries = {m["name"]: m for m in cells.load_benchmark(
        withheld=True)["per_layer"]}
    for name in NEW_METRICS:
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               name + ".py")) as f:
            source = f.read()
        assert "deepspeed_tpu" not in source, name
        imported = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source,
                              re.MULTILINE)
        assert set(imported) <= {"harness.stats", "harness.cells"}, name
        entry = entries[name]
        assert entry["source"] == "program_span" and entry["better"] == "lower"
        assert len(entry["workloads"]) == 1
        assert name in SERVING_CELLS[entry["workloads"][0]]
