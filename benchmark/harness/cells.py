"""Reads ``BENCHMARK.json`` and the files it names.  Nothing here knows a
particular cell: a cell is its entry plus the files found by its names."""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """Import one file of the benchmark by path (readers, architectures,
    drivers): a later PR adds a file and nothing has to list it."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{name}: no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def sibling_reader(file, name):
    """The ``read`` of the reader ``<name>.py`` beside ``file``: for a
    metric that one file reads and ``BENCHMARK.json`` enters once per
    end-to-end metric it moves."""
    return load_module(os.path.join(os.path.dirname(file), name + ".py"),
                       f"bench_metric_{name}").read


class Cell:
    """One entry of ``workloads`` with everything it names resolved.

    ``root`` is the directory the names are resolved under (the
    benchmark's own; the tests' rehearsal cell brings its own)."""

    def __init__(self, benchmark, name, root=BENCH_DIR):
        entries = {w["name"]: w for w in benchmark["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has {sorted(entries)})")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.root = root
        configs = {c["name"]: c for c in benchmark["configs"]}
        self.config = _load_json(os.path.join(
            CHECKOUT, configs[self.entry["config"]]["file"]))
        self.traffic = _load_json(os.path.join(
            root, "traffic", self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in benchmark["end_to_end"]
                           if self._reports(m)]
        self.per_layer = [m for m in benchmark["per_layer"]
                          if self._reports(m)]

    def _reports(self, metric):
        return self.name in metric.get("workloads", [self.name])

    def _own_or_shared(self, folder, name, what):
        """``<folder>/<name>.py`` under the cell's own root, else under
        the benchmark's: a test cell brings a stand-in that way."""
        for root in (self.root, BENCH_DIR):
            path = os.path.join(root, folder, name + ".py")
            if os.path.isfile(path):
                return load_module(path, f"bench_{what}_{name}")
        raise FileNotFoundError(f"{what} {name!r} has no file "
                                f"{folder}/{name}.py")

    def architecture(self):
        return self._own_or_shared("architectures",
                                   self.config["architecture"], "arch")

    def driver(self):
        kind = self.traffic["driver"]
        return load_module(os.path.join(
            BENCH_DIR, "harness", f"drive_{kind}.py"), f"bench_drive_{kind}")

    def reader(self, metric_name):
        return self._own_or_shared("layer_metrics", metric_name,
                                   "metric").read


def load_benchmark(path=None, withheld=False):
    """``BENCHMARK.json`` (or the file at ``path``).  ``withheld=True``
    adds the cells of ``benchmark/withheld/*.json``: cells whose files are
    all here and tested, but which ``BENCHMARK.json`` does not enter,
    because no judged number of theirs can be told under a bound yet.
    Each file says why and holds, under the keys of ``BENCHMARK.json``, the
    entries a later benchmark PR puts back.  The tools and the tests read a
    withheld cell this way; ``run.py`` runs what ``BENCHMARK.json`` has."""
    benchmark = _load_json(path or os.path.join(CHECKOUT, "BENCHMARK.json"))
    held = os.path.join(BENCH_DIR, "withheld")
    if withheld and os.path.isdir(held):
        for name in sorted(os.listdir(held)):
            if name.endswith(".json"):
                entries = _load_json(os.path.join(held, name))
                for key in ("workloads", "end_to_end", "per_layer"):
                    benchmark[key] = benchmark[key] + entries.get(key, [])
    return benchmark
