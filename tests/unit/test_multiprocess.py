"""Real 2-process jax.distributed tests (reference tests/unit/common.py
fork-N-processes harness analog).

Each test spawns 2 worker processes (tests/unit/multiproc_worker.py), each
with 2 local CPU devices, joined through a localhost coordinator — covering
the code paths a single-process virtual mesh cannot reach:
make_array_from_process_local_data feeding, cross-process checkpoint tag
validation, and the shard-local offload fetch/step/save."""
import os
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "multiproc_worker.py")
WORLD = 2


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_world(scenario, tmpdir, timeout=300):
    port = _free_port()
    procs = []
    for rank in range(WORLD):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        env.update(DSTPU_MP_SCENARIO=scenario, DSTPU_MP_RANK=str(rank),
                   DSTPU_MP_WORLD=str(WORLD), DSTPU_MP_PORT=str(port),
                   DSTPU_MP_TMPDIR=str(tmpdir))
        procs.append(subprocess.Popen(
            [sys.executable, WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, \
            f"rank {rank} rc={p.returncode}\n{out[-3000:]}"
        assert f"OK {scenario} rank={rank}" in out, out[-3000:]
    return outs


@pytest.mark.multiprocess
def test_two_process_engine_train(tmp_path):
    _run_world("engine_train", tmp_path)


@pytest.mark.multiprocess
def test_two_process_tag_validation(tmp_path):
    _run_world("tag_validation", tmp_path)


@pytest.mark.multiprocess
def test_two_process_offload_fetch(tmp_path):
    _run_world("offload_fetch", tmp_path)
