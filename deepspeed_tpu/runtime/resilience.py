"""Elastic, preemption-tolerant fleet operation: graceful drain + fast resume.

At preemptible-capacity scale, host loss and mesh-shape change are supported
events, not crashes.  This module owns the two host-side halves of that
contract (the elastic agent in launcher/elastic_agent.py owns the
fleet-supervision half, checkpoint/reshard.py the cross-topology restore):

**Graceful drain** — a preemption notice (SIGTERM on GCE/TPU preemptible
VMs, or a flag file the cluster manager touches) is caught by
:class:`PreemptionHandler`; the worker finishes its current step and calls
``engine.drain(run_dir)``, which fences the overlapped ZeRO-Offload host
step and any in-flight async checkpoint write, commits a final universal
export under the crash-safe protocol, and persists the recompile-watchdog
executable fingerprints — everything a replacement host needs to resume in
seconds.

**Fast resume** — ``engine.resume_from_latest(run_dir)`` restores the
newest COMPLETE universal export (``checkpoint.latest_universal``) and then
replays the drained host's executable fingerprints through an AOT warmup:
each recorded input signature is lowered and compiled BEFORE the first real
step, against the persistent XLA compilation cache
(``resilience.compilation_cache_dir``), so a replacement host rebuilds its
step programs from the cache instead of recompiling for minutes, and the
recompile watchdog observes ZERO new executables once real batches flow.

Lifecycle telemetry (docs/resilience.md "Gauge triage"): ``drain`` /
``resume`` spans, ``preemptions_total{reason}``, ``restarts_total``, and a
``time_to_resume_ms`` histogram.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
from typing import Any, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger

FINGERPRINTS_FILE = "fingerprints.json"
_FP_FORMAT = "deepspeed_tpu_fingerprints/1"

# exit code an elastically-managed worker uses after a successful drain —
# the agent counts it as a graceful departure (membership change), not a
# failure (launcher/elastic_agent.py)
EXIT_DRAINED = 83


class PreemptionHandler:
    """Latches a preemption notice: OS signal (SIGTERM by default — the
    GCE/TPU preemptible-VM notice) and/or a flag file the cluster manager
    touches.  The handler only SETS a flag; the training loop polls
    ``requested`` at step boundaries and drains at its own pace — a drain
    must never run inside a signal frame."""

    def __init__(self, signals=(signal.SIGTERM,),
                 flag_file: Optional[str] = None,
                 on_notice=None):
        self._signals = tuple(signals)
        self.flag_file = flag_file
        self._event = threading.Event()
        self.reason: Optional[str] = None
        self._prev: Dict[int, Any] = {}
        self._installed = False
        self._on_notice = on_notice

    def set_notice_callback(self, fn) -> None:
        """Register a callback fired ONCE when the notice first latches.
        It may run inside a signal frame, so it must only set flags / poke
        queues (the serving fleet uses it to wake a sleeping dispatcher
        tick) — never drain, join, or touch the device."""
        self._on_notice = fn

    def install(self) -> "PreemptionHandler":
        if self._installed:
            return self
        for sig in self._signals:
            self._prev[sig] = signal.signal(sig, self._on_signal)
        self._installed = True
        return self

    def _on_signal(self, signum, frame) -> None:
        self.request(reason=signal.Signals(signum).name.lower())
        prev = self._prev.get(signum)
        if callable(prev) and prev not in (signal.SIG_IGN, signal.SIG_DFL):
            prev(signum, frame)          # chain a wrapped foreign handler

    def request(self, reason: str = "manual") -> None:
        if self.reason is None:
            self.reason = reason
        first = not self._event.is_set()
        self._event.set()
        if first and self._on_notice is not None:
            try:
                self._on_notice(self.reason)
            except Exception:  # noqa: BLE001 — a notice callback must
                pass           # never turn a preemption into a crash

    @property
    def requested(self) -> bool:
        """True once a preemption notice arrived (signal, flag file, or an
        explicit ``request()``)."""
        if not self._event.is_set() and self.flag_file \
                and os.path.exists(self.flag_file):
            self.request(reason="flag_file")
        return self._event.is_set()


# ---------------------------------------------------------------------------
# persistent XLA compilation cache
# ---------------------------------------------------------------------------

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout: the path is part of jax's cache key, so a
# directory that moves (temp name, pid, timestamp) never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    Placement is decided from OUTSIDE first: when ``JAX_COMPILATION_CACHE_DIR``
    is set, jax has already read it and no path is set in code (a differing
    ``cache_dir`` is ignored with one log line).  Otherwise the cache goes to
    ``cache_dir`` (``resilience.compilation_cache_dir``) or, absent that, to
    the fixed in-checkout ``DEFAULT_CACHE_DIR``.  The size/compile-time
    floors are dropped so EVERY executable lands in it — a replacement
    host's step program is exactly the artifact the floors would otherwise
    skip.  Shared across processes/restarts: the cache key is the (devices,
    HLO, flags) fingerprint, so a replacement host with the same mesh shape
    gets byte-identical hits.  Call before the first compile: jax binds the
    cache directory once."""
    import jax
    env_dir = os.environ.get(CACHE_DIR_ENV)
    if env_dir:
        if cache_dir and os.path.abspath(cache_dir) != os.path.abspath(env_dir):
            logger.info(f"resilience: {CACHE_DIR_ENV}={env_dir} is set; "
                        f"ignoring compilation_cache_dir={cache_dir}")
        cache_dir = env_dir
    else:
        cache_dir = cache_dir or DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    logger.info(f"resilience: persistent XLA compilation cache at "
                f"{cache_dir}")
    return cache_dir


# ---------------------------------------------------------------------------
# executable fingerprints (recompile-watchdog signatures) → AOT warmup
# ---------------------------------------------------------------------------

def save_fingerprints(engine, path: str) -> str:
    """Persist the recompile watchdog's signature cache — the exact
    (function, input-signature) set this host compiled — so a replacement
    host can pre-build the same executables from the compilation cache."""
    wd = engine.telemetry.watchdog
    fns = {fn: [[list(leaf) for leaf in sig] for sig in sigs]
           for fn, sigs in wd._known.items()}
    payload = {"format": _FP_FORMAT, "fns": fns}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def load_fingerprints(path: str) -> Dict[str, List[tuple]]:
    with open(path) as f:
        payload = json.load(f)
    if payload.get("format") != _FP_FORMAT:
        raise ValueError(f"{path}: not a fingerprints manifest")
    return {fn: [tuple((p, tuple(shape), dtype) for p, shape, dtype in sig)
                 for sig in sigs]
            for fn, sigs in payload["fns"].items()}


def _batch_from_signature(sig) -> Optional[dict]:
    """Rebuild a zeros host batch from a ``train_batch`` signature — the
    leaves are the SHARDED global batch ([gas, micro_global, ...]) whose
    (path, shape, dtype) the watchdog recorded.  Supports the standard
    dict-of-arrays batch contract; anything else returns None (warmup
    skipped, first step compiles normally)."""
    import re as _re

    import numpy as np
    batch: dict = {}
    for path, shape, dtype in sig:
        keys = _re.findall(r"\['([^']+)'\]", path)
        if not keys or _re.sub(r"\['[^']+'\]", "", path):
            return None              # non-dict structure in the batch tree
        node = batch
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        try:
            node[keys[-1]] = np.zeros(tuple(shape), dtype)
        except TypeError:
            return None              # exotic dtype string
    return batch or None


def warm_resume(engine, manifest: Dict[str, List[tuple]]) -> int:
    """AOT warmup: for every recorded ``train_batch`` input signature,
    observe it into the watchdog and compile the step program ahead of the
    first real batch (a persistent-cache hit when the cache is warm).
    Returns the number of signatures warmed."""
    import jax

    jfn = (engine._jit_grads_batch if engine.offloading
           else engine._jit_train_batch)
    tel = engine.telemetry
    nproc = jax.process_count()
    warmed = 0
    for sig in manifest.get("train_batch", []):
        batch = _batch_from_signature(sig)
        if batch is None:
            logger.warning("resilience: unsupported batch structure in "
                           "fingerprint manifest; skipping one warmup")
            continue
        if nproc > 1:
            # the signature records the GLOBAL sharded shape
            # [gas, micro_global, ...]; _shard_batch on a real fleet takes
            # process-LOCAL rows and assembles the global array — feed it
            # this host's slice or the warmed program is N x too large
            import numpy as np
            batch = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:, :x.shape[1] // nproc], batch)
        dev = engine._shard_batch(batch, leading_gas=True)
        if tel.enabled:
            # observes the signature AND (hlo_stats) runs the
            # compiled-program analysis — the bookkeeping a cold first step
            # would have done, minus the surprise; count_execution=False:
            # the warmed program never dispatches, so the per-execution
            # HLO byte counters must not move
            tel.before_dispatch("train_batch", dev, step=0,
                                lower=lambda d=dev: jfn.lower(engine.state,
                                                              d),
                                count_execution=False)
            if not tel.hlo_stats:
                jfn.lower(engine.state, dev).compile()  # sync-ok: warmup IS
                #                                         the compile fence
        else:
            from deepspeed_tpu.telemetry.watchdog import signature_of
            tel.watchdog.observe_signature("train_batch", signature_of(dev),
                                           step=0)
            jfn.lower(engine.state, dev).compile()      # sync-ok: warmup
        warmed += 1
    return warmed


# ---------------------------------------------------------------------------
# drain / resume
# ---------------------------------------------------------------------------

def drain(engine, run_dir: str, *, reason: str = "preemption",
          out_dir: Optional[str] = None) -> Optional[str]:
    """Graceful shutdown on a preemption notice: fence every in-flight
    asynchronous subsystem, commit a final universal export + the
    executable fingerprints, and return the export path.  Called from the
    step loop (never a signal frame).  Every blocking fence below is the
    point of the drain — disclosed ``sync-ok`` for the no-sync lint."""
    from deepspeed_tpu.runtime import faults
    tel = engine.telemetry
    t0 = time.perf_counter()
    os.makedirs(run_dir, exist_ok=True)
    with tel.span("drain", step=engine.global_steps, reason=reason):
        faults.fire("drain.begin", step=engine.global_steps)
        # fence 1: the overlapped ZeRO-Offload host step — params must be
        # committed before they are exported
        engine._join_host_step()                     # sync-ok: drain fence
        faults.fire("drain.pre_checkpoint_fence", step=engine.global_steps)
        # fence 2: an in-flight async checkpoint write must commit (or
        # surface its failure) before the final export claims "newest"
        engine.wait_for_checkpoint()                 # sync-ok: drain fence
        faults.fire("drain.pre_export", step=engine.global_steps)
        if out_dir is None:
            out_dir = os.path.join(run_dir,
                                   f"universal_{engine.global_steps}")
        from deepspeed_tpu.checkpoint import (_universal_step,
                                              universal_complete)
        if (universal_complete(out_dir)
                and _universal_step(out_dir) == engine.global_steps):
            # the worker contract already committed this step's export —
            # re-exporting would put the in-progress marker BACK onto
            # durable data, and a hard kill mid-drain would then tear a
            # previously committed resume source
            path = out_dir
        else:
            path = engine.export_universal_checkpoint(out_dir,
                                                      run_dir=run_dir)
        faults.fire("drain.post_export", step=engine.global_steps)
        save_fingerprints(engine,
                          os.path.join(run_dir, FINGERPRINTS_FILE))
    tel.registry.counter(
        "preemptions_total",
        "graceful drains executed, by preemption reason "
        "(sigterm/flag_file/manual)").inc(1, reason=reason)
    if tel.enabled:
        tel.export(step=engine.global_steps)
    logger.info(f"drain ({reason}): committed {path} in "
                f"{(time.perf_counter() - t0) * 1e3:.0f} ms")
    return path


def resume(engine, run_dir: str, *, warmup: Optional[bool] = None
           ) -> Optional[str]:
    """Resume from the newest COMPLETE universal export under ``run_dir``
    (None when there is none — cold start).  ``warmup`` defaults to the
    ``resilience.aot_warmup`` config knob; when on and a fingerprints
    manifest exists, the step programs are AOT-compiled before the first
    real batch so the watchdog sees zero new executables afterwards."""
    tel = engine.telemetry
    if warmup is None:
        warmup = bool(engine.config.resilience.aot_warmup)
    t0 = time.perf_counter()
    with tel.span("resume", step=engine.global_steps):
        from deepspeed_tpu.checkpoint import (CheckpointCorrupt,
                                              universal_candidates)
        src = None
        for cand in universal_candidates(run_dir):
            try:
                engine.load_universal_checkpoint(cand)
                src = cand
                break
            except CheckpointCorrupt as e:
                # committed-looking but unreadable (e.g. power loss tore
                # fragment bytes the marker protocol couldn't see): degrade
                # to the previous complete export instead of crash-looping
                # every replacement incarnation on the same torn source
                logger.warning(f"resume: {cand} is unreadable ({e}); "
                               f"trying the previous complete export")
        if src is None:
            return None
        warmed = 0
        if warmup:
            man = os.path.join(run_dir, FINGERPRINTS_FILE)
            if os.path.exists(man):
                warmed = warm_resume(engine, load_fingerprints(man))
    dt_ms = (time.perf_counter() - t0) * 1e3
    reg = tel.registry
    reg.counter("restarts_total",
                "successful resumes from a persisted export after a "
                "restart/preemption").inc(1)
    reg.histogram("time_to_resume_ms",
                  "wall time from resume start to ready (restore + AOT "
                  "warmup)").observe(dt_ms)
    logger.info(f"resume: restored {src} (step {engine.global_steps}, "
                f"{warmed} executable(s) warmed) in {dt_ms:.0f} ms")
    return src
