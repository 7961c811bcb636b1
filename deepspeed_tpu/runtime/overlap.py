"""Compute–collective overlap: the XLA scheduler-regime half.

The ``overlap`` config block (config.py OverlapConfig) has three levers; this
module owns the first — steering the TPU compiler's latency-hiding scheduler
and async-collective fusion.  The other two (chunked ZeRO-3 collectives, ring
collective-matmul fusions) live in runtime/zero.py and
ops/collective_matmul.py.

Reference parity: DeepSpeed hides ZeRO-3 gather latency with a Python-side
prefetch coordinator (runtime/zero/partitioned_param_coordinator.py) and
``overlap_comm`` bucketing (stage_1_and_2.py).  On TPU the machinery is the
COMPILER's: XLA splits collectives into ``-start``/``-done`` pairs and its
latency-hiding scheduler moves compute between them — but only under the
right flags, and those flags are parsed ONCE, when libtpu initializes.

Where the flags go: the TPU compiler lives in libtpu, which reads its
arguments from ``LIBTPU_INIT_ARGS``.  ``XLA_FLAGS`` is parsed by jaxlib for
every backend it brings up (the CPU backend comes up beside the TPU), and
jaxlib ABORTS the process on a name it does not know
(parse_flags_from_env.cc) — which includes every flag below.  libtpu in turn
exits on an unknown name in ``LIBTPU_INIT_ARGS``, so only flags the installed
libtpu accepts may be composed here; tests/test_chip_compile.py compiles
with the full set against a described v5e.  Hence the contract:

- ``apply_overlap_flags(cfg)`` must run BEFORE the first jax backend touch
  (the engine calls it first thing in ``__init__``, before
  ``comm.init_distributed``; ``deepspeed_tpu.initialize`` reaches it through
  engine construction).  If the backend is already up, the flags are still
  exported (child processes, launcher re-exec inherit them) but this
  process's compiles keep the old regime — a loud warning says so.
- user-set flags win: a flag already present in ``LIBTPU_INIT_ARGS`` is
  never overridden, only recorded.
- off a TPU target nothing is exported: there is no libtpu to read it.
- the *effective* regime is observable everywhere: ``effective_xla_flags``
  feeds env_report, the telemetry snapshot, and the postmortem bundle, so
  every trace records the scheduler regime it ran under.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List

from deepspeed_tpu.utils.logging import logger

LIBTPU_ENV = "LIBTPU_INIT_ARGS"

# flags composed when overlap.enabled — all accepted by the installed libtpu
_ASYNC_COLLECTIVE_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def compose_xla_flags(cfg) -> List[str]:
    """The flag list the ``overlap`` block resolves to (pure function — the
    validation/echo surface for tests, env_report and telemetry)."""
    if not cfg.enabled:
        return []
    flags: List[str] = []
    if cfg.async_collectives:
        flags.extend(_ASYNC_COLLECTIVE_FLAGS)
    if cfg.latency_hiding_scheduler:
        flags.append("--xla_latency_hiding_scheduler_rerun="
                     f"{int(cfg.scheduler_rerun)}")
        flags.append("--xla_tpu_scheduler_percent_shared_memory_limit="
                     f"{int(cfg.scheduler_memory_limit_pct)}")
    flags.extend(cfg.extra_xla_flags)
    return flags


def _flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


def _backend_initialized() -> bool:
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def tpu_target() -> bool:
    """Will this process run on a TPU backend?  Decided WITHOUT initializing
    jax (that would freeze the flags): explicit JAX_PLATFORMS wins, else the
    presence of a libtpu install."""
    plats = os.environ.get("JAX_PLATFORMS", "").lower()
    if plats:
        return "tpu" in plats.split(",")
    return importlib.util.find_spec("libtpu") is not None


def apply_overlap_flags(cfg) -> List[str]:
    """Export the block's flags into ``os.environ['LIBTPU_INIT_ARGS']``
    (skipping any flag the user already set — their value wins) and return
    the list actually added.

    Off-TPU the flags are composed and RECORDED but never exported (the CPU
    CI still validates composition, config plumbing and the echo surfaces).
    Warns when the jax backend is already initialized: libtpu reads its
    arguments once, so this process's compiles keep the regime they started
    with (spawned workers still inherit the updated env)."""
    flags = compose_xla_flags(cfg)
    if not flags:
        return []
    if not tpu_target():
        logger.info(
            "overlap: not a TPU target — composed flags recorded but not "
            "exported (no libtpu to read them): %s", " ".join(flags))
        return []
    current = os.environ.get(LIBTPU_ENV, "")
    present = {_flag_name(tok) for tok in current.split()}
    added = [f for f in flags if _flag_name(f) not in present]
    if added:
        os.environ[LIBTPU_ENV] = (current + " " + " ".join(added)).strip()
        if _backend_initialized():
            logger.warning(
                "overlap: %s updated AFTER jax backend init — the "
                "latency-hiding/async-collective flags (%s) will not affect "
                "this process's compiles; construct the engine before any "
                "other jax use (or export them in the launcher) for them to "
                "take effect", LIBTPU_ENV,
                " ".join(_flag_name(f) for f in added))
        else:
            logger.info("overlap: applied %s: %s", LIBTPU_ENV,
                        " ".join(added))
    return added


def effective_xla_flags() -> str:
    """The compiler flags this process sees right now — ``XLA_FLAGS`` (read
    by jaxlib) then ``LIBTPU_INIT_ARGS`` (read by the TPU compiler) — what
    env_report, the telemetry snapshot and the postmortem bundle record."""
    return " ".join(v for v in (os.environ.get("XLA_FLAGS", ""),
                                os.environ.get(LIBTPU_ENV, "")) if v)


def overlap_snapshot(cfg) -> Dict[str, object]:
    """JSON-stable record of the scheduler regime: the resolved ``overlap``
    block, the flags it composes, and the effective env — embedded in every
    telemetry snapshot and postmortem bundle so traces are attributable to
    the regime they ran under."""
    return {
        "config": cfg.model_dump(),
        "composed_flags": compose_xla_flags(cfg),
        "effective_xla_flags": effective_xla_flags(),
    }
