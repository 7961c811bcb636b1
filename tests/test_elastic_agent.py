"""Elastic agent test (reference analog: elasticity/elastic_agent.py
DSElasticAgent behavior under a worker death + tests/unit/elasticity).

A 3-host simulated fleet loses one host mid-train; the agent must detect it,
re-solve the batch geometry, relaunch at world size 2, and training must
resume from the universal checkpoint with a CONTINUOUS loss curve."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "tests", "elastic_train_script.py")


def _worker_env(run_dir, *, rank=0, world=1, batch=8, micro=4, restart=0,
                kill_at=0, total_steps=12, extra=None):
    env = dict(os.environ)
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DSTPU_SIM_FLEET": "1",
        "DSTPU_SIM_RANK": str(rank),
        "DSTPU_SIM_WORLD": str(world),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "DSTPU_ELASTIC_BATCH": str(batch),
        "DSTPU_ELASTIC_MICRO": str(micro),
        "DSTPU_RESTART_COUNT": str(restart),
        "DSTPU_RUN_DIR": run_dir,
        "DSTPU_KILL_AT": str(kill_at),
        "DSTPU_TOTAL_STEPS": str(total_steps),   # tier-1 stays CPU-fast
    })
    env.update(extra or {})
    return env


def _wait_for_losses(run_dir, n, timeout=60):
    path = os.path.join(run_dir, "losses.txt")
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            if len(open(path).read().splitlines()) >= n:
                return
        time.sleep(0.25)
    raise AssertionError(f"worker never reached {n} logged steps")


def test_agent_survives_host_loss(tmp_path):
    from deepspeed_tpu.elasticity import ElasticityConfig
    from deepspeed_tpu.launcher.elastic_agent import ElasticAgent

    run_dir = str(tmp_path)
    cfg = ElasticityConfig(micro_batch_sizes=[1, 2, 4],
                           max_train_batch_size=48,
                           min_chips=2, max_chips=6, chips_per_host=2)
    agent = ElasticAgent(SCRIPT, n_hosts=3, elastic_config=cfg,
                         run_dir=run_dir, devices_per_host=2,
                         min_hosts=1, max_restarts=3, base_port=29931,
                         extra_env={"DSTPU_TOTAL_STEPS": "16"})
    rc = agent.run()
    assert rc == 0

    with open(os.path.join(run_dir, "agent_status.json")) as f:
        status = json.load(f)
    assert status["phase"] == "done"
    # membership change happened: gen 0 world 3 → gen 1 world 2
    worlds = [g["world"] for g in status["history"]]
    assert worlds[0] == 3 and worlds[-1] == 2 and len(worlds) >= 2

    # loss continuity: steps keep counting (no restart from 1), and the
    # post-resume losses continue the pre-kill trajectory
    rows = [ln.split() for ln in
            open(os.path.join(run_dir, "losses.txt")).read().splitlines()]
    steps = [int(r[0]) for r in rows]
    worlds_seen = [int(r[1]) for r in rows]
    losses = [float(r[2]) for r in rows]
    assert steps[-1] == 16
    assert 3 in worlds_seen and 2 in worlds_seen
    i_resume = worlds_seen.index(2)       # first step at the new world size
    assert steps[i_resume] > 1            # resumed, not restarted
    # continuous: the first resumed loss is below the run's initial loss and
    # within a modest band of the last pre-kill loss
    assert losses[i_resume] < losses[0]
    assert abs(losses[i_resume] - losses[i_resume - 1]) < 0.5 * losses[0]
    # still training downward after the membership change
    assert losses[-1] < losses[i_resume]


def test_agent_cli_smoke(tmp_path):
    """The dstpu-elastic CLI wires the same agent (arg parsing only — the
    full run is covered above)."""
    from deepspeed_tpu.launcher import elastic_agent as ea
    assert callable(ea.main)


def test_worker_drains_on_sigterm_and_resumes(tmp_path):
    """Graceful preemption end to end: SIGTERM mid-train → the worker's
    PreemptionHandler drains (final universal export + fingerprints) and
    exits EXIT_DRAINED; a replacement incarnation resumes from the drained
    export with the step count intact."""
    from deepspeed_tpu.checkpoint import latest_universal
    from deepspeed_tpu.runtime.resilience import (EXIT_DRAINED,
                                                  FINGERPRINTS_FILE)
    run_dir = str(tmp_path)
    p = subprocess.Popen(
        [sys.executable, SCRIPT],
        env=_worker_env(run_dir,
                        extra={"DSTPU_STEP_DELAY": "0.3"}), cwd=REPO)
    try:
        _wait_for_losses(run_dir, 3)
        p.send_signal(signal.SIGTERM)       # the preemption notice
        rc = p.wait(timeout=60)     # a drain: one export, seconds
    finally:
        if p.poll() is None:
            p.kill()
    assert rc == EXIT_DRAINED
    src = latest_universal(run_dir)
    assert src is not None
    assert os.path.exists(os.path.join(run_dir, FINGERPRINTS_FILE))
    drained_step = json.load(open(os.path.join(src, "meta.json")))["step"]
    assert drained_step >= 3

    # replacement incarnation: resumes at the drained step and finishes
    r = subprocess.run([sys.executable, SCRIPT],
                       env=_worker_env(run_dir, restart=1), cwd=REPO,
                       timeout=75)     # a dozen steps after one compile
    assert r.returncode == 0
    rows = [ln.split() for ln in
            open(os.path.join(run_dir, "losses.txt")).read().splitlines()]
    steps = [int(r0[0]) for r0 in rows]
    assert steps[-1] == 12
    # the resumed incarnation continued from the drained export, it did
    # not restart from step 1
    resumed_first = steps[rows.index(
        [r0 for r0 in rows if int(r0[0]) > drained_step][0])]
    assert resumed_first == drained_step + 1


def test_worker_host_loss_mid_export_resumes_from_previous(tmp_path):
    """Chaos leg (runtime/faults.py via the DSTPU_FAULTS spawn env): the
    worker dies ABRUPTLY (os._exit) mid-write of its third export; the torn
    export refuses restore, the previous COMPLETE one resumes."""
    from deepspeed_tpu.checkpoint import latest_universal
    from deepspeed_tpu.runtime.faults import HOST_LOSS_EXIT_CODE
    run_dir = str(tmp_path)
    r = subprocess.run(
        [sys.executable, SCRIPT],
        env=_worker_env(run_dir, extra={
            "DSTPU_FAULTS": "host_loss@universal.mid_fragments+2"}),
        cwd=REPO, timeout=75)
    assert r.returncode == HOST_LOSS_EXIT_CODE
    src = latest_universal(run_dir)
    assert src is not None
    # newest COMPLETE export is the one BEFORE the torn third write
    assert json.load(open(os.path.join(src, "meta.json")))["step"] == 2

    r = subprocess.run([sys.executable, SCRIPT],
                       env=_worker_env(run_dir, restart=1), cwd=REPO,
                       timeout=75)     # a dozen steps after one compile
    assert r.returncode == 0
    rows = [ln.split() for ln in
            open(os.path.join(run_dir, "losses.txt")).read().splitlines()]
    assert int(rows[-1][0]) == 12


def test_agent_gives_up_below_min_hosts(tmp_path):
    from deepspeed_tpu.elasticity import ElasticityConfig
    from deepspeed_tpu.launcher.elastic_agent import ElasticAgent
    bad = os.path.join(str(tmp_path), "exit1.py")
    with open(bad, "w") as f:
        f.write("import sys; sys.exit(1)\n")
    cfg = ElasticityConfig(micro_batch_sizes=[1], max_train_batch_size=8,
                           min_chips=2, max_chips=4, chips_per_host=2)
    agent = ElasticAgent(bad, n_hosts=2, elastic_config=cfg,
                         run_dir=str(tmp_path / "run"), devices_per_host=2,
                         min_hosts=2, max_restarts=3, base_port=29961)
    assert agent.run() == 1
    with open(os.path.join(str(tmp_path / "run"), "agent_status.json")) as f:
        assert json.load(f)["phase"] == "failed"
