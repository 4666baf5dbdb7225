"""Launcher / ds_report tests (parity model: reference
``tests/unit/test_ds_arguments.py`` + runner hostfile unit coverage)."""

import os
import subprocess
import sys
import textwrap

import pytest

from deepspeed_tpu.launcher.runner import (fetch_hostfile,
                                           parse_resource_filter,
                                           encode_world_info, parse_args)


def _hostfile(tmp_path, text):
    p = tmp_path / "hostfile"
    p.write_text(textwrap.dedent(text))
    return str(p)


def test_fetch_hostfile(tmp_path):
    path = _hostfile(tmp_path, """\
        worker-0 slots=4
        worker-1 slots=8
    """)
    pool = fetch_hostfile(path)
    assert pool == {"worker-0": 4, "worker-1": 8}


def test_fetch_hostfile_missing(tmp_path):
    assert fetch_hostfile(str(tmp_path / "nope")) is None


def test_fetch_hostfile_duplicate(tmp_path):
    path = _hostfile(tmp_path, """\
        worker-0 slots=4
        worker-0 slots=4
    """)
    with pytest.raises(ValueError):
        fetch_hostfile(path)


def test_resource_filter_include():
    pool = {"worker-0": 4, "worker-1": 4}
    out = parse_resource_filter(pool, include_str="worker-1:0,2")
    assert out == {"worker-1": [0, 2]}
    out = parse_resource_filter(pool, include_str="worker-0@worker-1:1")
    assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [1]}


def test_resource_filter_exclude():
    pool = {"worker-0": 4, "worker-1": 4}
    out = parse_resource_filter(pool, exclude_str="worker-1")
    assert out == {"worker-0": [0, 1, 2, 3]}
    out = parse_resource_filter(pool, exclude_str="worker-0:1,3")
    assert out["worker-0"] == [0, 2]


def test_resource_filter_errors():
    pool = {"worker-0": 2}
    with pytest.raises(ValueError):
        parse_resource_filter(pool, include_str="a", exclude_str="b")
    with pytest.raises(ValueError):
        parse_resource_filter(pool, include_str="missing-host")
    with pytest.raises(ValueError):
        parse_resource_filter(pool, include_str="worker-0:7")


def test_encode_world_info_roundtrip():
    import base64
    import json
    enc = encode_world_info({"h0": [0, 1], "h1": 2})
    dec = json.loads(base64.urlsafe_b64decode(enc))
    assert dec == {"h0": [0, 1], "h1": [0, 1]}


def test_parse_args_remainder():
    args = parse_args(["--num_nodes", "2", "train.py", "--lr", "0.1"])
    assert args.user_script == "train.py"
    assert args.user_args == ["--lr", "0.1"]
    assert args.num_nodes == 2


def test_single_host_launch(tmp_path):
    """End-to-end: launcher runs a user script in a subprocess."""
    script = tmp_path / "user.py"
    script.write_text("import os, sys; print('RANK=' + os.environ['RANK']); "
                      "sys.exit(0)\n")
    from deepspeed_tpu.launcher.runner import main
    rc = main([str(script)])
    assert rc == 0


def test_ds_report_runs():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    code = "from deepspeed_tpu import env_report; env_report.main()"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=180,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr
    assert "op report" in out.stdout
    assert "general environment info" in out.stdout


def test_ds_elastic_runs(tmp_path):
    import json
    cfg = {"train_batch_size": 0,
           "elasticity": {"enabled": True, "max_train_batch_size": 2000,
                          "micro_batch_sizes": [2, 4], "min_gpus": 1,
                          "max_gpus": 64, "min_time": 20, "version": 0.1}}
    p = tmp_path / "ds.json"
    p.write_text(json.dumps(cfg))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "bin/ds_elastic", "-c", str(p),
                          "-w", "8"], env=env, capture_output=True, text=True,
                         timeout=120,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr
    assert "final_batch_size" in out.stdout


def test_multinode_runner_commands():
    """Transport parity (reference multinode_runner.py): each runner builds
    the expected fan-out command lines with the jax.distributed env."""
    import argparse
    from deepspeed_tpu.launcher.multinode_runner import (SSHRunner, PDSHRunner,
                                                         OpenMPIRunner,
                                                         MVAPICHRunner, RUNNERS)
    assert set(RUNNERS) == {"ssh", "pdsh", "openmpi", "mvapich"}
    args = argparse.Namespace(user_script="train.py", user_args=["--x", "1"],
                              ssh_port=None)
    env = {"coordinator": "worker-0:29500"}
    active = {"worker-0": 4, "worker-1": 4}

    ssh_cmds = SSHRunner(args, "w").get_cmd(env, active)
    assert len(ssh_cmds) == 2 and ssh_cmds[0][0] == "ssh"
    assert "JAX_PROCESS_ID=0" in ssh_cmds[0][-1]
    assert "JAX_PROCESS_ID=1" in ssh_cmds[1][-1]
    assert "JAX_COORDINATOR_ADDRESS=worker-0:29500" in ssh_cmds[0][-1]

    pdsh_cmds = PDSHRunner(args, "w").get_cmd(env, active)
    assert len(pdsh_cmds) == 1 and pdsh_cmds[0][0] == "pdsh"
    assert "worker-0,worker-1" in pdsh_cmds[0]
    shell = pdsh_cmds[0][-1]
    # the id must be EXPORTED after the cd (a VAR=... prefix before 'cd'
    # would never reach the user process), and a lookup miss must be fatal
    assert "export JAX_PROCESS_ID;" in shell
    assert shell.index("cd ") < shell.index("JAX_PROCESS_ID=$(")
    assert "exit 1" in shell
    # the shell actually resolves an id and exports it (run it with the
    # local hostname patched into the table)
    import socket, subprocess as sp
    host_shell = shell.replace("worker-0", socket.gethostname())
    host_shell = host_shell.split("exec ")[0] + "exec printenv JAX_PROCESS_ID"
    out = sp.run(["bash", "-c", host_shell], capture_output=True, text=True)
    assert out.stdout.strip() == "0", (out.stdout, out.stderr)

    mpi_cmds = OpenMPIRunner(args, "w").get_cmd(env, active)
    assert len(mpi_cmds) == 1 and mpi_cmds[0][0] == "mpirun"
    assert "--npernode" in mpi_cmds[0]
    assert any(x.startswith("JAX_COORDINATOR_ADDRESS=") for x in mpi_cmds[0])
    # the wrapped shell exports the OMPI rank explicitly (JAX's auto-detect
    # breaks on OpenMPI>=5) and execs the user script
    assert mpi_cmds[0][-2] == "-c"
    assert "JAX_PROCESS_ID=${OMPI_COMM_WORLD_RANK:?}" in mpi_cmds[0][-1]
    assert "train.py" in mpi_cmds[0][-1]

    mv = MVAPICHRunner(args, "w")
    mv_cmds = mv.get_cmd(env, active)
    assert len(mv_cmds) == 1 and mv_cmds[0][0] == "mpirun_rsh"
    assert "-hostfile" in mv_cmds[0]
    # env rides as KEY=VALUE args (mpirun_rsh forwards no environment)
    assert any(x.startswith("JAX_COORDINATOR_ADDRESS=") for x in mv_cmds[0])
    assert "JAX_PROCESS_ID=${MV2_COMM_WORLD_RANK:?}" in mv_cmds[0][-1]
    with open(mv.hostfile) as f:
        assert f.read().splitlines() == ["worker-0", "worker-1"]


def test_launcher_flag_selects_runner(monkeypatch, tmp_path):
    """--launcher pdsh errors cleanly when the backend binary is missing."""
    from deepspeed_tpu.launcher import runner as R
    hostfile = tmp_path / "hf"
    hostfile.write_text("worker-0 slots=4\nworker-1 slots=4\n")
    import shutil as _sh
    monkeypatch.setattr(_sh, "which",
                        lambda name: None if name == "pdsh" else "/usr/bin/x")
    rc = R.main(["-H", str(hostfile), "--launcher", "pdsh", "train.py"])
    assert rc == 1
