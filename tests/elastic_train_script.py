"""Elastic worker for the agent tests (the reference pattern: an
--elastic_training run whose worker group survives a membership change).

Contract with the agent (launcher/elastic_agent.py):
- batch geometry from DSTPU_ELASTIC_BATCH / DSTPU_ELASTIC_MICRO,
- on start, ``engine.resume_from_latest(DSTPU_RUN_DIR)`` (newest COMPLETE
  universal export via checkpoint.latest_universal — the library scan, not
  a hand-rolled pointer),
- host 0 exports a universal checkpoint every step (crash-safe commit +
  latest_universal pointer) and appends losses,
- a PreemptionHandler turns SIGTERM into a graceful drain: final export,
  fingerprints, exit resilience.EXIT_DRAINED,
- generation 0: the LAST host os._exit()s mid-train (the simulated ABRUPT
  host failure the survival test asserts recovery from; DSTPU_KILL_AT=0
  disables it for the drain tests).

Simulation note: each "host" is a single-process JAX runtime (the CPU
backend has no cross-process collectives).  Data selection is keyed on the
STEP ONLY, so every host computes the identical global batch and all hosts
hold bit-identical params — exactly what the dp all-reduce would produce on
a real mesh, minus the wire.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models import GPT, GPTConfig  # noqa: E402
from deepspeed_tpu.runtime.resilience import (EXIT_DRAINED,  # noqa: E402
                                              PreemptionHandler)

TOTAL_STEPS = int(os.environ.get("DSTPU_TOTAL_STEPS", "24"))


def _rows(path):
    try:
        with open(path) as f:
            return len(f.read().splitlines())
    except FileNotFoundError:
        return 0


def _wait(cond, timeout=120.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.05)


def main():
    run_dir = os.environ["DSTPU_RUN_DIR"]
    batch = int(os.environ["DSTPU_ELASTIC_BATCH"])
    micro = int(os.environ["DSTPU_ELASTIC_MICRO"])
    restart = int(os.environ["DSTPU_RESTART_COUNT"])
    kill_at = int(os.environ.get("DSTPU_KILL_AT", "8"))
    # tiny CPU steps finish in ~10 ms; the SIGTERM-drain test needs a
    # realistic step duration so a preemption notice can land MID-train
    step_delay = float(os.environ.get("DSTPU_STEP_DELAY", "0"))
    deepspeed_tpu.comm.init_distributed()
    rank = deepspeed_tpu.comm.host_rank()
    world = deepspeed_tpu.comm.host_world_size()
    handler = PreemptionHandler().install()

    cfg = GPTConfig.tiny(vocab_size=64, max_seq_len=16)
    config = {
        "train_batch_size": batch,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        "seed": 7,                      # same init on every incarnation
        # fast resume: replacement incarnations compile from the shared
        # persistent cache + the drained fingerprints instead of cold XLA
        "resilience": {"compilation_cache_dir":
                       os.path.join(run_dir, "xla_cache")},
    }
    rng = np.random.default_rng(0)
    pool = rng.integers(0, 64, size=(64, 16)).astype(np.int32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), config=config,
        example_batch={"input_ids": pool[:1]})

    # resume from the newest COMPLETE universal export — a death mid-export
    # can never corrupt the resume source (crash-safe commit protocol)
    engine.resume_from_latest(run_dir)

    loss_log = os.path.join(run_dir, "losses.txt")
    while engine.global_steps < TOTAL_STEPS:
        step = engine.global_steps
        rows = pool[(np.arange(batch) + step * batch) % 64]
        m = engine.train_batch({"input_ids": rows})
        if step_delay:
            time.sleep(step_delay)      # stand-in for a real step's compute
        if rank == 0:
            with open(loss_log, "a") as f:
                f.write(f"{engine.global_steps} {world} "
                        f"{float(m.loss):.6f}\n")
            engine.export_universal_checkpoint(
                os.path.join(run_dir, f"universal_{engine.global_steps}"),
                run_dir=run_dir)
        if handler.requested:
            # graceful drain: host 0 commits the final export (sim hosts
            # hold identical params, one writer is enough); everyone exits
            # the drained code so the agent books a membership change, not
            # a host loss
            if rank == 0:
                engine.drain(run_dir, reason=handler.reason or "preemption")
            sys.exit(EXIT_DRAINED)
        if (kill_at and restart == 0 and world > 1
                and engine.global_steps >= kill_at):
            # the simulated ABRUPT host failure, paced through the shared
            # loss log and not by each host's own clock: with the compile
            # cache live (one host compiles, the others load) the hosts no
            # longer step in lockstep.  The last host dies once host 0 has
            # logged kill_at steps; host 0 holds two steps later until the
            # agent tears this incarnation down.
            if rank == world - 1:
                _wait(lambda: _rows(loss_log) >= kill_at)
                os._exit(17)
            if rank == 0 and engine.global_steps >= kill_at + 2:
                _wait(lambda: False)
    return 0


if __name__ == "__main__":
    raise SystemExit(main() or 0)
