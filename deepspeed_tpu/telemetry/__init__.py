"""Unified step telemetry.

The reference ships monitoring as scattered pieces (MonitorMaster fan-out,
EngineTimers, flops profiler, see_memory_usage); this package correlates
them per step and adds the TPU-specific hazards nothing else watches:

- ``tracer``         — host-phase spans, each a ``ds.<name>`` annotation
                       inside any ``jax.profiler`` trace (one clock with the
                       device ops) and, when enabled, a Chrome-trace/Perfetto
                       JSON event (incl. cross-file flow events)
- ``tracecontext``   — per-request distributed trace/span ids threaded
                       through the serving fleet (router -> replicas)
- ``timeseries``     — bounded ring-buffer sampling of registry metrics
                       with rate()/window-delta reads (SLO burn input)
- ``critical_path``  — merged-trace e2e latency decomposition
                       (queue_wait / prefill / handoff / decode terms
                       that sum exactly; ``scripts/trace_report.py``)
- ``watchdog``       — jit recompile detection with leaf-level shape diffs
- ``registry``       — labeled counter/gauge registries (collective bytes,
                       memory gauges, cache misses)
- ``histogram``      — log-bucketed histograms with exact quantiles under
                       a cap (serving latency percentiles)
- ``serving``        — request-level serving telemetry facade (lifecycle
                       spans, scheduler-phase spans, TTFT/TPOT histograms,
                       KV-pool and speculative-decode counters)
- ``exporter``       — snapshot serialization: JSON, Prometheus text
                       exposition, MonitorMaster fan-out
- ``health``         — in-graph per-module-group numerics stats (grad/param
                       norms, NaN/Inf counts, update ratios) + anomaly rules
- ``flight_recorder``— host ring buffer of step records with postmortem
                       bundle dumps on NaN / overflow streak / crash
- ``postmortem``     — bundle summarizer CLI
                       (``python -m deepspeed_tpu.telemetry.postmortem``)
- ``step_telemetry`` — the engine-facing facade driving all of the above
- ``startup``        — where start-up went: jax's own trace / lower /
                       compile / cache-load durations booked to the step
                       program whose first call paid them
                       (``program_setup``), the engines' ``ds.engine_init``
                       spans and ``import_seconds``; read with
                       ``setup_account()``

See docs/observability.md for the config block and workflows.
"""

from deepspeed_tpu.telemetry.exporter import SnapshotExporter
from deepspeed_tpu.telemetry.flight_recorder import (FlightRecorder,
                                                     install_crash_handler)
from deepspeed_tpu.telemetry.health import (AnomalyDetector,
                                            compute_group_health,
                                            flatten_health, group_names)
from deepspeed_tpu.telemetry.histogram import (DEFAULT_BUCKETS, Histogram,
                                               log_buckets)
from deepspeed_tpu.telemetry.registry import (Counter, Gauge, MetricRegistry,
                                              default_registry,
                                              record_collective)
from deepspeed_tpu.telemetry.serving import (ServingTelemetry,
                                             ServingTelemetryConfig)
from deepspeed_tpu.telemetry.startup import setup_account
from deepspeed_tpu.telemetry.step_telemetry import StepTelemetry
from deepspeed_tpu.telemetry.timeseries import (TimeSeriesStore,
                                                histogram_attainment)
from deepspeed_tpu.telemetry.tracecontext import TraceContext, new_trace
from deepspeed_tpu.telemetry.tracer import SpanTracer, TraceEmitter
from deepspeed_tpu.telemetry.watchdog import RecompileWatchdog, signature_of

__all__ = [
    "AnomalyDetector",
    "Counter",
    "DEFAULT_BUCKETS",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "RecompileWatchdog",
    "ServingTelemetry",
    "ServingTelemetryConfig",
    "SnapshotExporter",
    "SpanTracer",
    "StepTelemetry",
    "TimeSeriesStore",
    "TraceContext",
    "TraceEmitter",
    "histogram_attainment",
    "new_trace",
    "log_buckets",
    "compute_group_health",
    "default_registry",
    "flatten_health",
    "group_names",
    "install_crash_handler",
    "record_collective",
    "setup_account",
    "signature_of",
]
