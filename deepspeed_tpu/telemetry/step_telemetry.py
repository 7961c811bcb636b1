"""StepTelemetry — the per-step telemetry facade the engine drives.

One object owning the four telemetry pieces (span tracer, recompile
watchdog, metric registries, snapshot exporter) plus the per-executable
compiled-program analysis that connects them to XLA ground truth:

- ``span(name, step)``         — host-phase spans around engine step stages
- ``before_dispatch(...)``     — watchdog fingerprint + (on a signature
                                 miss) compiled-HLO collective bytes and
                                 ``cost_analysis``/``memory_analysis``
                                 figures + per-execution byte counters
- ``end_step(...)``            — cadence-gated memory sampling and snapshot
                                 export (JSON + Prometheus + monitor fan-out)

Everything but ``span`` is inert when ``telemetry.enabled`` is false: the
hooks return immediately, and ``span`` is the ``ds.<name>`` profiler
annotation alone (well under a microsecond with no profiler session open;
see telemetry/tracer.py), so a ``jax.profiler`` trace of any engine names
the host phases whether or not telemetry was configured.
"""

from __future__ import annotations

import math
import os
import time
from typing import Callable, Dict, Optional

from deepspeed_tpu.telemetry.exporter import SnapshotExporter
from deepspeed_tpu.telemetry.registry import MetricRegistry, default_registry
from deepspeed_tpu.telemetry.tracer import SpanTracer, TraceEmitter
from deepspeed_tpu.telemetry.watchdog import RecompileWatchdog
from deepspeed_tpu.utils.logging import logger

HLO_BYTES = "hlo_collective_bytes_total"
HLO_CALLS = "hlo_collective_calls_total"

# cost_analysis keys worth keeping (the full dict carries dozens of
# backend-specific entries)
_COST_KEYS = ("flops", "bytes accessed", "transcendentals")
_MEMORY_ATTRS = ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes")


class StepTelemetry:
    def __init__(self, config, monitor=None,
                 registry: Optional[MetricRegistry] = None):
        tcfg = config.telemetry
        self.enabled = bool(tcfg.enabled)
        self.monitor = monitor
        self.registry = registry if registry is not None else default_registry
        import jax
        pid = jax.process_index()
        self._rank0 = pid == 0
        self.tracer = SpanTracer(
            enabled=self.enabled and bool(tcfg.trace_enabled), pid=pid,
            max_events=int(tcfg.max_trace_events))
        self.emitter = TraceEmitter()
        self.watchdog = RecompileWatchdog(
            warmup_steps=int(tcfg.recompile_warmup_steps),
            registry=self.registry if self.enabled else None,
            emit_warnings=self._rank0)
        self.exporter = SnapshotExporter(self.registry, self.tracer)
        base = os.path.join(tcfg.output_path or "./telemetry", tcfg.job_name)
        self.trace_path = tcfg.trace_path or os.path.join(base, "trace.json")
        self.snapshot_path = (tcfg.snapshot_path
                              or os.path.join(base, "snapshot.json"))
        self.prometheus_path = (tcfg.prometheus_path
                                or os.path.join(base, "metrics.prom"))
        self.hlo_stats = bool(tcfg.hlo_stats)
        self.snapshot_interval = int(tcfg.snapshot_interval)
        self.monitor_fanout = bool(tcfg.monitor_fanout)
        # fn -> {signatures, executions, collectives, per-exec figures}
        # (collectives/cost/memory reflect the most recent signature; the
        # per-signature truth for counter attribution lives in _sig_stats)
        self._exec: Dict[str, dict] = {}
        self._sig_stats: Dict[tuple, dict] = {}
        self._trace_flush_mark = 0

        # ---- numerics health monitor + flight recorder (telemetry.health
        # block) — active INDEPENDENTLY of the parent enabled switch: a
        # postmortem is wanted exactly when nothing else is being watched
        hc = tcfg.health
        self.health_cfg = hc
        self.health_enabled = bool(hc.enabled)
        self.recorder = None
        self.anomaly = None
        self._config = config
        self._prev_skipped: Optional[int] = 0
        self._overflow_streak = 0
        # hook-out for the guardian control loop (runtime/guardian.py):
        # the anomaly rules that fired on the LAST health_step, and the
        # dump-trigger reason (None when nothing tripped)
        self.last_anomalies: list = []
        self.last_dump_reason: Optional[str] = None
        if self.health_enabled:
            from deepspeed_tpu.telemetry.flight_recorder import (
                FlightRecorder, install_crash_handler)
            from deepspeed_tpu.telemetry.health import AnomalyDetector
            self.recorder = FlightRecorder(
                capacity=int(hc.recorder_steps),
                dump_dir=hc.dump_path or os.path.join(base, "postmortem"),
                write_files=self._rank0, registry=self.registry)
            self.recorder.add_bundle_writer("config.json",
                                            self._write_bundle_config)
            self.recorder.add_bundle_writer("snapshot.prom",
                                            self._write_bundle_prometheus)
            self.recorder.add_bundle_writer("trace.json",
                                            self._write_bundle_trace)
            self.recorder.add_bundle_writer("env.txt", self._write_bundle_env)
            self.recorder.set_meta_fn(lambda: {
                "process_index": pid, "spans": self.tracer.summary()})
            self.anomaly = AnomalyDetector(
                window=int(hc.anomaly_window),
                loss_spike_zscore=float(hc.loss_spike_zscore),
                grad_norm_factor=float(hc.grad_norm_factor),
                scale_collapse_factor=float(hc.scale_collapse_factor),
                registry=self.registry, emit_warnings=self._rank0)
            if hc.crash_dump:
                install_crash_handler(self.recorder)

    # ------------------------------------------------------------- spans

    def span(self, name: str, step: Optional[int] = None, **args):
        return self.tracer.span(name, step=step, **args)

    # --------------------------------------------------------- dispatch

    def before_dispatch(self, fn_name: str, args_tree, step: int,
                        lower: Optional[Callable] = None,
                        count_execution: bool = True) -> bool:
        """Watchdog-observe one jitted dispatch.  Returns True on a
        signature miss (== an XLA compile).  On a miss, ``lower`` (a thunk
        returning ``jitted.lower(*args)``) is used — when hlo_stats is on —
        to pull collective bytes and cost/memory figures out of the compiled
        program; every call then bumps the per-execution HLO byte counters
        by the figures of THE SIGNATURE BEING DISPATCHED (shape buckets of
        one function keep distinct per-step byte costs).
        ``count_execution=False`` (the resume AOT warmup) registers the
        signature and runs the compile analysis WITHOUT booking an
        execution — the program never actually dispatched, so the
        per-execution byte counters must not move."""
        if not self.enabled:
            return False
        from deepspeed_tpu.telemetry.watchdog import signature_of
        sig = signature_of(args_tree)
        miss = self.watchdog.observe_signature(fn_name, sig, step)
        info = self._exec.setdefault(
            fn_name, {"signatures": 0, "executions": 0, "collectives": {},
                      "cost_analysis": {}, "memory_analysis": {}})
        if miss:
            info["signatures"] += 1
            collected = {}
            if self.hlo_stats and lower is not None:
                collected = self._analyze_executable(fn_name, lower, info)
            # per-signature figures: counters for this and every later
            # execution of this bucket use ITS compiled program — on an
            # analysis failure the bucket counts NOTHING rather than
            # inheriting another signature's bytes
            self._sig_stats[(fn_name, sig)] = dict(collected)
        if not count_execution:
            return miss
        info["executions"] += 1
        collectives = self._sig_stats.get((fn_name, sig), {})
        if collectives:
            bytes_c = self.registry.counter(
                HLO_BYTES, "collective payload bytes per execution of each "
                "compiled step program (from compiled HLO), per kind")
            calls_c = self.registry.counter(
                HLO_CALLS, "collective op executions per compiled step "
                "program run, per kind")
            for kind, rec in collectives.items():
                bytes_c.inc(rec["bytes"], kind=kind, fn=fn_name)
                calls_c.inc(rec["count"], kind=kind, fn=fn_name)
        return miss

    def invalidate(self, fn_name: Optional[str] = None) -> None:
        """Forget signature caches and per-executable figures — the engine
        calls this when it re-jits its step programs (configure_moq): the
        fresh jit caches are empty, so the next dispatch is a real compile
        and the old compiled figures no longer describe the program."""
        self.watchdog.invalidate(fn_name)
        if fn_name is None:
            self._exec.clear()
            self._sig_stats.clear()
        else:
            self._exec.pop(fn_name, None)
            for key in [k for k in self._sig_stats if k[0] == fn_name]:
                del self._sig_stats[key]

    def _analyze_executable(self, fn_name: str, lower: Callable,
                            info: dict) -> dict:
        """Compile the (freshly missed) signature AOT and harvest static
        figures; returns this signature's collective figures ({} on
        failure).  Gated behind ``telemetry.hlo_stats``.  Failures degrade
        to a warning: telemetry must never kill training.

        What it costs, as the set-up account (telemetry/startup.py) read it
        on ``train-gpt2m-1chip`` (PERF.md section 6, PR 40): on jax 0.9.0
        the step is NOT traced, lowered or compiled twice.  This AOT copy
        pays them (trace 6.2 s, lower 2.8 s, cache load 7.4 s warm or
        compile 72 s cold; booked as ``other``) and the real call then finds
        jax's in-memory caches (its ``train_batch`` record reads 0.0006 s);
        the price is the HLO text dump and its walk, 1.6 s on a first step
        of 17.4 s.  That holds only while ``lower`` is handed the very
        arguments of the call: an AOT copy from other avals, a second
        ``jax.jit`` of the function or its jaxpr made apart pays everything
        again, a program (PR 39 was refused for +16-22 s of serving
        set-up).  Do not copy the pattern to time or name a step program:
        the account hears jax's own durations during the one call."""
        from deepspeed_tpu.comm.comm import hlo_collective_bytes
        from deepspeed_tpu.telemetry.registry import \
            suppress_collective_recording
        info["collectives"] = {}
        try:
            # the AOT lower() traces the step — silence the wrapper-level
            # trace-time hooks so their byte counters don't double-count
            # where the real call traces it again (other avals)
            with suppress_collective_recording():
                compiled = lower().compile()
        except Exception as e:  # noqa: BLE001
            logger.warning(f"telemetry: compile analysis of '{fn_name}' "
                           f"failed: {e!r}")
            return {}
        try:
            hlo_text = compiled.as_text()
            info["collectives"] = hlo_collective_bytes(hlo_text)
        except Exception as e:  # noqa: BLE001
            logger.warning(f"telemetry: HLO collective walk of '{fn_name}' "
                           f"failed: {e!r}")
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            cost = {k: float(ca[k]) for k in _COST_KEYS if k in ca}
            info["cost_analysis"] = cost
            for k, v in cost.items():
                self.registry.gauge(
                    "xla_cost_" + k.replace(" ", "_"),
                    "compiled-program cost_analysis figure, per jitted "
                    "function").set(v, fn=fn_name)
        except Exception:  # noqa: BLE001 — not all backends implement it
            pass
        try:
            ma = compiled.memory_analysis()
            mem = {}
            for attr in _MEMORY_ATTRS:
                v = getattr(ma, attr, None)
                if v is not None:
                    mem[attr] = int(v)
            info["memory_analysis"] = mem
            g = self.registry.gauge(
                "xla_memory_bytes", "compiled-program memory_analysis "
                "figures, per jitted function")
            for attr, v in mem.items():
                g.set(v, fn=fn_name,
                      kind=attr.replace("_size_in_bytes", ""))
        except Exception:  # noqa: BLE001
            pass
        return info["collectives"]

    # ------------------------------------------------------------ MoE

    def moe_step(self, stats_host: dict) -> None:
        """Publish one step's HOST-side expert-load stats (engine
        ``_fetch_metrics`` already paid the device fetch; ``stats_host`` is
        plain python — moe/layer.py ``_sow_stats`` aggregated across layers
        and microbatches).  Gauges overwrite per step; the drop counter
        accumulates so rate() works over scrape intervals."""
        toks = stats_host.get("expert_tokens") or []
        g = self.registry.gauge(
            "moe_expert_tokens",
            "tokens assigned to each expert this step, summed over MoE "
            "layers and microbatches (expert label = global expert index)")
        for e, v in enumerate(toks):
            g.set(float(v), expert=str(e))
        self.registry.counter(
            "moe_dropped_tokens_total",
            "token->expert assignments dropped by the capacity limit "
            "(always 0 on the dropless route)").inc(
                float(stats_host.get("dropped_tokens", 0.0)))
        self.registry.gauge(
            "moe_aux_loss",
            "load-balancing auxiliary loss, averaged over MoE layers "
            "(1.0 = perfectly uniform routing under the GShard loss)"
        ).set(float(stats_host.get("aux_loss", 0.0)))
        self.registry.gauge(
            "moe_gate_entropy",
            "mean per-token entropy of the router softmax, averaged over "
            "MoE layers (nats; ln(num_experts) = uniform)"
        ).set(float(stats_host.get("gate_entropy", 0.0)))

    # ------------------------------------------------------------ health

    def health_step(self, step: int, metrics_host, health=None,
                    lr: Optional[float] = None,
                    samples: Optional[int] = None) -> Optional[str]:
        """Feed one step's HOST-side scalars into the numerics pipeline:
        anomaly rules, the flight-recorder ring buffer, cross-host
        aggregation, and the automatic dump triggers (non-finite loss,
        overflow streak).  ``metrics_host`` is the engine's cached host
        ``StepMetrics`` (plain floats — the caller already paid the single
        ``jax.device_get``); ``health`` is the plain per-group stats dict.
        Returns the bundle path when a trigger fired, else None."""
        if not self.health_enabled:
            return None
        loss = float(metrics_host.loss)
        grad_norm = float(metrics_host.grad_norm)
        scale = float(metrics_host.loss_scale)
        skipped = int(metrics_host.skipped_steps)
        # overflow streak: consecutive steps whose update was skipped.
        # _prev_skipped is None right after a checkpoint restore (the
        # cumulative counter may have jumped either way) — resync the
        # baseline without reading a phantom overflow into the streak.
        if self._prev_skipped is None:
            self._overflow_streak = 0
        elif skipped > self._prev_skipped:
            self._overflow_streak += 1
        else:
            self._overflow_streak = 0
        self._prev_skipped = skipped
        fired = self.anomaly.observe(step, loss, grad_norm, scale)
        reason = None
        if not math.isfinite(loss):
            reason = "nonfinite_loss"
        elif (int(self.health_cfg.overflow_streak) > 0
              and self._overflow_streak
              >= int(self.health_cfg.overflow_streak)):
            reason = "overflow_streak"
        self.last_anomalies = list(fired)
        self.last_dump_reason = reason
        rec = {
            "step": int(step),
            "unix_time": time.time(),
            "loss": loss,
            "grad_norm": grad_norm,
            "loss_scale": scale,
            "skipped_steps": skipped,
            "overflow_streak": self._overflow_streak,
            "anomalies": fired,
            "health": health or {},
        }
        if lr is not None:
            rec["lr"] = float(lr)
        if self.tracer.enabled and self.tracer.last_dur_ms:
            rec["spans_ms"] = dict(self.tracer.last_dur_ms)
        import jax
        # fleet view (min/max/mean per scalar + tripping-process index) at
        # the fleet_interval cadence, and always when a dump trigger or
        # anomaly fires — NOT every step: the gather is a blocking
        # cross-host collective.  Every input to this decision (loss,
        # grad_norm, scale, streak — all replicated values) is identical on
        # every process, so all processes reach the collective together.
        fi = int(self.health_cfg.fleet_interval)
        want_fleet = (reason is not None or bool(fired)
                      or (fi > 0 and step % fi == 0))
        if want_fleet and jax.process_count() > 1:
            from deepspeed_tpu.comm.aggregation import (
                aggregate_health_scalars)
            from deepspeed_tpu.telemetry.health import flatten_health
            try:
                flat = {"loss": loss, "grad_norm": grad_norm,
                        **flatten_health(health or {})}
                rec["fleet"] = aggregate_health_scalars(flat)
            except Exception as e:  # noqa: BLE001 — never kill training
                logger.warning(f"telemetry: fleet aggregation failed: {e!r}")
        self.recorder.record(rec)
        if fired and self.monitor is not None and getattr(
                self.monitor, "enabled", False):
            x = samples if samples is not None else step
            self.monitor.write_events(
                [(f"Train/Numerics/anomaly/{rule}", 1.0, int(x))
                 for rule in fired])
        if reason is not None:
            return self.recorder.dump(reason, note=f"step {step}")
        return None

    @property
    def overflow_streak(self) -> int:
        """Consecutive overflow-skipped steps so far — the guardian reads
        this alongside ``last_anomalies`` after each step."""
        return self._overflow_streak

    def reset_numerics_baseline(self) -> None:
        """Called after a checkpoint restore: the cumulative skipped_steps
        counter may have jumped in either direction, so the overflow-streak
        comparison must resync its baseline on the next observation instead
        of counting the jump as an overflow (or missing a real one)."""
        self._prev_skipped = None
        self._overflow_streak = 0
        self.last_anomalies = []
        self.last_dump_reason = None

    def dump_postmortem(self, reason: str = "manual",
                        note: Optional[str] = None) -> Optional[str]:
        """Explicitly write a postmortem bundle (engine.dump_postmortem).
        Requires ``telemetry.health.enabled``; returns the bundle dir."""
        if self.recorder is None:
            logger.warning("dump_postmortem: telemetry.health is disabled — "
                           "no flight recorder to dump")
            return None
        return self.recorder.dump(reason, note=note, force=True)

    # ---- bundle artifact writers (registered with the flight recorder;
    # each failure degrades to a warning inside the recorder) ----

    def _write_bundle_config(self, bundle_dir: str) -> None:
        with open(os.path.join(bundle_dir, "config.json"), "w") as f:
            f.write(self._config.model_dump_json(indent=2))

    def _write_bundle_prometheus(self, bundle_dir: str) -> None:
        self.exporter.write_prometheus(
            os.path.join(bundle_dir, "snapshot.prom"))

    def _write_bundle_trace(self, bundle_dir: str) -> None:
        if self.tracer.enabled and self.tracer.events:
            self.emitter.write(os.path.join(bundle_dir, "trace.json"),
                               self.tracer)

    def _write_bundle_env(self, bundle_dir: str) -> None:
        # a LIGHT env report: the full ``env_report()`` probes the op
        # registry (pallas kernel compiles, ~10s) — too slow for a dump
        # that may be racing a dying process
        import platform
        import sys as _sys

        import jax
        lines = ["deepspeed_tpu postmortem environment report"]
        from deepspeed_tpu.version import __version__
        lines.append(f"deepspeed_tpu ... {__version__}")
        for mod in ("jax", "jaxlib", "flax", "optax", "numpy"):
            try:
                import importlib
                v = getattr(importlib.import_module(mod), "__version__", "?")
            except Exception:  # noqa: BLE001
                v = "not importable"
            lines.append(f"{mod:<16}{v}")
        lines.append(f"python ......... {_sys.version.split()[0]} "
                     f"({platform.platform()})")
        try:
            devs = jax.devices()
            lines.append(f"backend ........ {jax.default_backend()} "
                         f"({len(devs)} device(s)); process "
                         f"{jax.process_index()}/{jax.process_count()}")
        except Exception as e:  # noqa: BLE001
            lines.append(f"backend ........ unavailable ({e})")
        env_keys = [k for k in sorted(os.environ)
                    if k.startswith(("JAX_", "XLA_", "LIBTPU", "TPU_"))]
        for k in env_keys:
            lines.append(f"env {k}={os.environ[k]}")
        # resolved overlap regime (config + composed flags): the postmortem
        # must say which scheduler regime the crashed run compiled under
        from deepspeed_tpu.runtime.overlap import compose_xla_flags
        ocfg = self._config.overlap
        for key, val in sorted(ocfg.model_dump().items()):
            lines.append(f"overlap.{key}={val}")
        composed = compose_xla_flags(ocfg)
        lines.append("overlap.composed_xla_flags="
                     + (" ".join(composed) if composed else "(none)"))
        with open(os.path.join(bundle_dir, "env.txt"), "w") as f:
            f.write("\n".join(lines) + "\n")

    # ------------------------------------------------------------ memory

    def sample_memory(self) -> None:
        """Live/peak/limit bytes per local device + host RSS, as gauges
        (reference see_memory_usage, now on a cadence instead of ad hoc)."""
        if not self.enabled:
            return
        from deepspeed_tpu.utils.memory import collect_memory_stats
        stats = collect_memory_stats()
        g = self.registry.gauge(
            "device_memory_bytes",
            "XLA allocator stats per local device (in_use/peak/limit)")
        for i, dev in enumerate(stats["devices"]):
            for key, label in (("bytes_in_use", "in_use"),
                               ("peak_bytes_in_use", "peak"),
                               ("bytes_limit", "limit")):
                if key in dev:
                    g.set(dev[key], device=str(i), kind=label)
        if stats.get("host_rss_bytes"):
            self.registry.gauge(
                "host_memory_rss_bytes",
                "process max RSS on this host").set(stats["host_rss_bytes"])

    def record_flops(self, metrics: Dict[str, float]) -> None:
        """Flops-profiler figures as gauges (profiling/flops_profiler.py
        ``as_metrics``) so the snapshot carries the model-cost numbers."""
        if not self.enabled:
            return
        for name, value in metrics.items():
            self.registry.gauge(
                "flops_profiler_" + name,
                "flops profiler figure for the profiled step").set(value)

    # ----------------------------------------------------------- export

    def end_step(self, step: int, samples: Optional[int] = None,
                 tokens: int = 0) -> None:
        if not self.enabled:
            return
        self.registry.counter("engine_steps_total",
                              "optimizer steps taken").inc(1)
        if tokens:
            self.registry.counter("train_tokens_total",
                                  "tokens consumed by train_batch").inc(
                                      tokens)
        if self.snapshot_interval and step % self.snapshot_interval == 0:
            self.export(step=step, samples=samples, throttle_trace=True)

    def export(self, step: Optional[int] = None,
               samples: Optional[int] = None, write: bool = True,
               throttle_trace: bool = False) -> dict:
        """Assemble a snapshot; write the JSON/Prometheus/trace files
        (rank 0) and fan the scalar subset through MonitorMaster.  Returns
        the snapshot dict either way.

        ``throttle_trace`` (the per-step cadence path) rewrites the trace
        file only after the buffer grew ~10% since the last flush: the
        trace dump is O(buffer), so unthrottled per-step rewrites of a
        long run's buffer would dominate step bookkeeping.  Small runs
        flush every export (the threshold rounds up to one event);
        explicit exports and checkpoint flushes always write."""
        if not self.enabled:
            return {}
        self.sample_memory()
        executables = {}
        for fn, info in self._exec.items():
            per_exec = sum(rec["bytes"]
                           for rec in info["collectives"].values())
            executables[fn] = {**info,
                               "per_execution_collective_bytes": per_exec}
        # every snapshot records the scheduler regime it ran under: the
        # resolved overlap block + the XLA_FLAGS this process actually saw
        # (runtime/overlap.py — satellite of the compute–collective
        # overlap work; a trace without its regime is unattributable)
        from deepspeed_tpu.runtime.overlap import overlap_snapshot
        snap = self.exporter.snapshot(
            step=step,
            extra={"executables": executables,
                   "env": overlap_snapshot(self._config.overlap)})
        if write and self._rank0:
            try:
                self.exporter.write_json(self.snapshot_path, snap)
                self.exporter.write_prometheus(self.prometheus_path, snap)
                if self.tracer.enabled:
                    new = self.tracer.total_recorded - self._trace_flush_mark
                    if (not throttle_trace
                            or new >= max(1, len(self.tracer.events) // 10)):
                        self.emitter.write(self.trace_path, self.tracer)
                        self._trace_flush_mark = self.tracer.total_recorded
            except Exception as e:  # noqa: BLE001 — never kill training
                logger.warning(f"telemetry: export failed: {e!r}")
        if (self.monitor_fanout and self.monitor is not None
                and getattr(self.monitor, "enabled", False)):
            x = samples if samples is not None else (step or 0)
            self.monitor.write_events(
                self.exporter.scalar_events(snap, x=x))
        return snap
