"""Launcher + multi-host data path tests (reference pattern:
tests/unit/launcher/test_ds_arguments.py + the DistributedTest multiproc
harness)."""

import os
import subprocess
import sys

import pytest

from deepspeed_tpu.launcher import parse_hostfile
from deepspeed_tpu.launcher.runner import ssh_commands


class TestHostfile:
    def test_parse(self):
        pool = parse_hostfile(
            "worker-0 slots=4\n# comment\n\nworker-1 slots=8\n")
        assert pool == {"worker-0": 4, "worker-1": 8}

    def test_default_slots_and_errors(self):
        assert parse_hostfile("h1\n") == {"h1": 1}
        with pytest.raises(ValueError, match="duplicate"):
            parse_hostfile("h1\nh1 slots=2\n")
        with pytest.raises(ValueError, match="empty"):
            parse_hostfile("# nothing\n")

    def test_ssh_commands_carry_rendezvous_env(self):
        pool = parse_hostfile("a slots=4\nb slots=4\n")
        cmds = ssh_commands(pool, "a:29500", "train.py", ["--x", "1"])
        assert len(cmds) == 2
        (h0, c0), (h1, c1) = cmds
        assert h0 == "a" and h1 == "b"
        assert "JAX_COORDINATOR_ADDRESS=a:29500" in c0
        assert "JAX_PROCESS_ID=0" in c0 and "JAX_PROCESS_ID=1" in c1
        assert "JAX_NUM_PROCESSES=2" in c0


class TestSimFleet:
    def test_two_process_train_and_checkpoint(self, tmp_path):
        """The VERDICT item-9 'done' bar: a 2-process CPU fleet launched via
        the CLI trains (process-local data assembled into global arrays) and
        checkpoints."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = os.path.join(repo, "tests", "launcher_train_script.py")
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)   # launcher sets cpu itself
        r = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.launcher",
             "--sim_hosts", "2", "--devices_per_host", "4",
             "--sim_port", "29741", script, str(tmp_path)],
            cwd=repo, env=env, capture_output=True, text=True, timeout=90)
        assert r.returncode == 0, r.stderr[-3000:]
        assert (tmp_path / "rank0.ok").exists()
        assert (tmp_path / "rank1.ok").exists()
        assert (tmp_path / "ckpt").exists()
