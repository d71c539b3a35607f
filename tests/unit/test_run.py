"""Launcher tests — reference tests/unit/test_run.py pattern: hostfile and
resource-filter parsing, world-info encoding, launch env setup."""
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.launcher import launch as launch_mod
from deepspeed_tpu.launcher import runner
from deepspeed_tpu.launcher.multinode_runner import (OpenMPIRunner,
                                                     PDSHRunner, SSHRunner)


def _hostfile(tmp_path, text):
    p = tmp_path / "hostfile"
    p.write_text(text)
    return str(p)


def test_fetch_hostfile(tmp_path):
    path = _hostfile(tmp_path, "worker-0 slots=4\nworker-1 slots=8\n")
    pool = runner.fetch_hostfile(path)
    assert list(pool.items()) == [("worker-0", 4), ("worker-1", 8)]


def test_fetch_hostfile_comments_and_blank(tmp_path):
    path = _hostfile(tmp_path,
                     "# cluster\n\nworker-0 slots=2\n# tail\nworker-1 slots=2\n")
    pool = runner.fetch_hostfile(path)
    assert len(pool) == 2


def test_fetch_hostfile_bad_format(tmp_path):
    path = _hostfile(tmp_path, "worker-0 gpus=4\n")
    with pytest.raises(ValueError):
        runner.fetch_hostfile(path)


def test_fetch_hostfile_duplicate(tmp_path):
    path = _hostfile(tmp_path, "w0 slots=2\nw0 slots=4\n")
    with pytest.raises(ValueError):
        runner.fetch_hostfile(path)


def test_fetch_hostfile_missing():
    assert runner.fetch_hostfile("/nonexistent/hostfile") is None


def _pool():
    from collections import OrderedDict

    return OrderedDict([("w0", 4), ("w1", 4), ("w2", 4)])


def test_include_whole_host():
    out = runner.parse_resource_filter(_pool(), include_str="w1")
    assert dict(out) == {"w1": 4}


def test_include_slots():
    out = runner.parse_resource_filter(_pool(), include_str="w0:0,1@w2")
    assert dict(out) == {"w0": 2, "w2": 4}


def test_exclude_whole_host():
    out = runner.parse_resource_filter(_pool(), exclude_str="w1")
    assert dict(out) == {"w0": 4, "w2": 4}


def test_exclude_slots():
    out = runner.parse_resource_filter(_pool(), exclude_str="w0:3")
    assert out["w0"] == 3 and out["w1"] == 4


def test_include_and_exclude_mutually_exclusive():
    with pytest.raises(ValueError):
        runner.parse_resource_filter(_pool(), include_str="w0",
                                     exclude_str="w1")


def test_include_unknown_host():
    with pytest.raises(ValueError):
        runner.parse_resource_filter(_pool(), include_str="nope")


def test_include_bad_slot():
    with pytest.raises(ValueError):
        runner.parse_resource_filter(_pool(), include_str="w0:9")


def test_world_info_roundtrip():
    encoded = runner.encode_world_info(_pool())
    decoded = launch_mod.decode_world_info(encoded)
    assert decoded == {"w0": [0, 1, 2, 3], "w1": [0, 1, 2, 3],
                       "w2": [0, 1, 2, 3]}


def test_launch_sets_env(tmp_path):
    """launch.py spawns the script with RANK/WORLD_SIZE/MASTER_* set."""
    script = tmp_path / "probe.py"
    script.write_text(
        "import os, json\n"
        "print(json.dumps({k: os.environ[k] for k in "
        "['RANK','WORLD_SIZE','MASTER_ADDR','MASTER_PORT','LOCAL_RANK']}))\n")
    encoded = runner.encode_world_info({"hostA": 4, "hostB": 4})
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={encoded}", "--node_rank=1",
         "--master_addr=10.0.0.1", "--master_port=29501", str(script)],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    import json

    env = json.loads(proc.stdout.strip().splitlines()[-1])
    assert env == {"RANK": "1", "WORLD_SIZE": "2",
                   "MASTER_ADDR": "10.0.0.1", "MASTER_PORT": "29501",
                   "LOCAL_RANK": "0"}


def test_runner_single_node_spawn(tmp_path):
    """End-to-end: runner main() on a single node runs the user script."""
    marker = tmp_path / "ran.txt"
    script = tmp_path / "train.py"
    script.write_text(f"open({str(marker)!r}, 'w').write('ok')\n")
    rc = runner.main(["--hostfile", "/nonexistent", str(script)])
    assert rc == 0
    assert marker.read_text() == "ok"


def _args(extra=None):
    return runner.parse_args(["--master_port", "29500",
                              "--master_addr", "10.0.0.1", "train.py",
                              "--lr", "0.1"] + (extra or []))


def test_pdsh_runner_cmd():
    args = _args()
    r = PDSHRunner(args, "WORLDINFO")
    cmd = r.get_cmd({"PYTHONPATH": "/x"}, _pool())
    assert cmd[0] == "pdsh"
    assert "w0,w1,w2" in cmd
    joined = " ".join(cmd)
    assert "--node_rank=%n" in joined
    assert "train.py" in joined


def test_openmpi_runner_cmd():
    args = _args()
    r = OpenMPIRunner(args, "WORLDINFO")
    cmd = r.get_cmd({"PYTHONPATH": "/x"}, _pool())
    assert cmd[0] == "mpirun"
    assert "-n" in cmd and "3" in cmd
    assert "train.py" in cmd


def test_ssh_runner_cmd():
    args = _args()
    r = SSHRunner(args, "WORLDINFO")
    cmd = r.get_cmd({}, _pool())
    assert cmd[0] == "bash"
    assert "--node_rank=0" in cmd[2] and "--node_rank=2" in cmd[2]
    assert "wait" in cmd[2]


def test_env_report_runs(capsys):
    from deepspeed_tpu.env_report import main

    main()
    out = capsys.readouterr().out
    assert "cpu_adam" in out
    assert "jax version" in out


def test_env_report_names_the_backend(capsys):
    """The Pallas line says which backend answered — interpret mode only
    because the backend IS the CPU, never as the word for 'no TPU seen'."""
    from deepspeed_tpu.env_report import main

    main()
    assert "interpret-mode (backend is cpu)" in capsys.readouterr().out


def test_parents_of_chip_processes_stay_off_jax():
    """A chip belongs to one process: the launchers start the child
    that needs it, so importing them must not touch JAX."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    code = ("import sys, deepspeed_tpu.launcher.launch, "
            "deepspeed_tpu.launcher.runner; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code], cwd=repo,
                          timeout=120).returncode == 0
