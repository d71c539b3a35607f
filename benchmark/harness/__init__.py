"""The benchmark's own code: everything a cell is measured with.

Found by name from ``BENCHMARK.json``: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``layer_metrics/<metric>.py``,
``architectures/<architecture>.py``, ``harness/drive_<kind>.py``.
"""
