"""Unified step telemetry (deepspeed_tpu/telemetry/): registries, span
tracer, recompile watchdog, collective byte counters, and the engine-driven
trace/snapshot/Prometheus export loop.

The engine-level cases use the duck-typed ``(init_fn, apply_fn)`` model
contract with a sequence-length-agnostic loss so the recompile tests can
change the batch shape without changing the math.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

try:
    from jax import shard_map
except ImportError:  # older jax
    from jax.experimental.shard_map import shard_map

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh
from deepspeed_tpu.telemetry import (MetricRegistry, RecompileWatchdog,
                                     SnapshotExporter, SpanTracer,
                                     TraceEmitter, default_registry)
from deepspeed_tpu.telemetry.registry import (COLLECTIVE_BYTES,
                                              COLLECTIVE_CALLS)


# ------------------------------------------------------------------ helpers

def _init_fn(rng, batch):
    return {"scale": jnp.ones((8,)), "bias": jnp.zeros((8,))}


def _apply_fn(params, batch, rng):
    # any sequence length works: reduce over the trailing dim first
    feat = jnp.tanh(batch["x"]).mean(axis=-1, keepdims=True)        # [B, 1]
    pred = (feat * params["scale"] + params["bias"]).mean(axis=-1)  # [B]
    return jnp.mean((pred - batch["y"]) ** 2)


def _engine(tmp_path, extra_cfg=None):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2},
        "mesh": {"dp": -1},
        "steps_per_print": 1,
        "telemetry": {"enabled": True, "output_path": str(tmp_path),
                      "job_name": "job"},
        **(extra_cfg or {}),
    }
    example = {"x": np.zeros((1, 16), np.float32),
               "y": np.zeros((1,), np.float32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=(_init_fn, _apply_fn), config=cfg, example_batch=example)
    return engine


def _batch(rng, bs, seq=16):
    return {"x": rng.normal(size=(bs, seq)).astype(np.float32),
            "y": rng.normal(size=(bs,)).astype(np.float32)}


# ----------------------------------------------------------------- registry

class TestRegistry:
    def test_counter_labels_and_values(self):
        reg = MetricRegistry()
        c = reg.counter("bytes_total", "help text")
        c.inc(10, kind="all_reduce", axis="dp")
        c.inc(5, kind="all_reduce", axis="dp")
        c.inc(7, kind="all_gather", axis="dp")
        assert c.value(kind="all_reduce", axis="dp") == 15
        assert c.value(kind="all_gather", axis="dp") == 7
        assert c.value(kind="missing", axis="dp") == 0

    def test_counter_rejects_decrease(self):
        c = MetricRegistry().counter("c")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_overwrites(self):
        g = MetricRegistry().gauge("mem")
        g.set(100, device="0")
        g.set(50, device="0")
        assert g.value(device="0") == 50

    def test_kind_mismatch_raises(self):
        reg = MetricRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_snapshot_shape(self):
        reg = MetricRegistry()
        reg.counter("c", "ch").inc(3, a="1")
        reg.gauge("g", "gh").set(2.5)
        snap = reg.snapshot()
        assert snap["counters"]["c"]["samples"] == [
            {"labels": {"a": "1"}, "value": 3.0}]
        assert snap["gauges"]["g"]["samples"] == [
            {"labels": {}, "value": 2.5}]

    def test_prometheus_text_format(self):
        reg = MetricRegistry()
        reg.counter("bytes_total", "moved bytes").inc(
            1024, kind="all-reduce", axis="dp")
        reg.gauge("mem_bytes").set(7, device="0")
        text = SnapshotExporter(reg).prometheus_text()
        assert "# TYPE deepspeed_tpu_bytes_total counter" in text
        assert ('deepspeed_tpu_bytes_total{axis="dp",kind="all-reduce"} 1024'
                in text)
        assert "# TYPE deepspeed_tpu_mem_bytes gauge" in text
        assert 'deepspeed_tpu_mem_bytes{device="0"} 7' in text

    def test_snapshot_json_roundtrip(self, tmp_path):
        reg = MetricRegistry()
        reg.counter("c", "help").inc(42, k="v")
        reg.gauge("g").set(3.5, device="1")
        exp = SnapshotExporter(reg)
        path = str(tmp_path / "snap.json")
        exp.write_json(path, step=7)
        loaded = json.loads(open(path).read())
        assert loaded["step"] == 7
        assert loaded["counters"] == reg.snapshot()["counters"]
        assert loaded["gauges"] == reg.snapshot()["gauges"]

    def test_scalar_events_flatten_labels(self):
        reg = MetricRegistry()
        reg.counter("bytes_total").inc(9, axis="dp", kind="all_reduce")
        events = SnapshotExporter(reg).scalar_events(x=5)
        assert events == [
            ("Train/Telemetry/bytes_total/dp/all_reduce", 9.0, 5)]

    def test_prometheus_nonfinite_values_render(self):
        """NaN/Inf gauges must render as exposition-format tokens, not
        crash the export (telemetry must never kill training)."""
        reg = MetricRegistry()
        reg.gauge("g").set(float("nan"), k="a")
        reg.gauge("g").set(float("inf"), k="b")
        reg.gauge("g").set(float("-inf"), k="c")
        text = SnapshotExporter(reg).prometheus_text()
        assert 'deepspeed_tpu_g{k="a"} NaN' in text
        assert 'deepspeed_tpu_g{k="b"} +Inf' in text
        assert 'deepspeed_tpu_g{k="c"} -Inf' in text

    def test_prometheus_large_counter_full_precision(self):
        reg = MetricRegistry()
        reg.counter("bytes_total").inc(10 * 2 ** 30 + 1)
        text = SnapshotExporter(reg).prometheus_text()
        assert f"deepspeed_tpu_bytes_total {10 * 2 ** 30 + 1}" in text

    def test_suppression_context_silences_recording(self):
        from deepspeed_tpu.telemetry.registry import (
            record_collective, suppress_collective_recording)
        default_registry.reset()
        with suppress_collective_recording():
            record_collective("all_reduce", 64, "dp")
        assert default_registry.counter(COLLECTIVE_BYTES).value(
            kind="all_reduce", axis="dp") == 0
        record_collective("all_reduce", 64, "dp")
        assert default_registry.counter(COLLECTIVE_BYTES).value(
            kind="all_reduce", axis="dp") == 64
        default_registry.reset()


# ------------------------------------------------------------------- tracer

class TestTracer:
    def test_spans_export_chrome_trace(self, tmp_path):
        tracer = SpanTracer(pid=0)
        for step in (1, 2):
            for phase in ("batch_input", "dispatch", "device_complete"):
                with tracer.span(phase, step=step):
                    pass
        path = str(tmp_path / "trace.json")
        TraceEmitter().write(path, tracer)
        trace = json.loads(open(path).read())
        evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        assert len(evs) == 6
        assert all({"name", "ts", "dur", "pid", "tid"} <= set(e) for e in evs)
        assert {e["args"]["step"] for e in evs} == {1, 2}
        # monotone, relative-microsecond timestamps
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts) and ts[0] >= 0

    def test_disabled_tracer_records_nothing(self):
        tracer = SpanTracer(enabled=False)
        with tracer.span("x", step=1):
            pass
        assert not tracer.events

    def test_event_buffer_bounded(self):
        tracer = SpanTracer(max_events=10)
        for i in range(25):
            tracer.record("p", float(i), 1.0)
        assert len(tracer.events) == 10
        assert tracer.dropped_events == 15
        # oldest dropped, newest kept
        assert tracer.events[-1]["ts"] == 24

    def test_summary_aggregates_per_phase(self):
        tracer = SpanTracer()
        tracer.record("a", 0.0, 2000.0)   # 2 ms
        tracer.record("a", 0.0, 4000.0)
        tracer.record("b", 0.0, 1000.0)
        s = tracer.summary()
        assert s["a"]["count"] == 2
        assert s["a"]["total_ms"] == pytest.approx(6.0)
        assert s["a"]["max_ms"] == pytest.approx(4.0)
        assert s["b"]["count"] == 1


# ----------------------------------------------------------------- watchdog

class TestWatchdog:
    def test_repeat_signature_is_a_hit(self):
        reg = MetricRegistry()
        wd = RecompileWatchdog(warmup_steps=1, registry=reg,
                               emit_warnings=False)
        batch = {"x": np.zeros((2, 16), np.float32)}
        assert wd.observe("step", batch, 1) is True
        assert wd.observe("step", batch, 2) is False
        assert wd.observe("step", batch, 3) is False
        assert reg.counter("jit_cache_misses_total").value(fn="step") == 1
        assert wd.warnings_emitted == 0

    def test_changed_shape_after_warmup_warns_once_with_diff(self):
        reg = MetricRegistry()
        wd = RecompileWatchdog(warmup_steps=1, registry=reg,
                               emit_warnings=False)
        wd.observe("step", {"x": np.zeros((2, 16), np.float32)}, 1)
        wd.observe("step", {"x": np.zeros((2, 16), np.float32)}, 2)
        assert wd.observe("step", {"x": np.zeros((2, 24), np.float32)},
                          3) is True
        assert wd.warnings_emitted == 1
        assert "(2, 16)" in wd.last_warning and "(2, 24)" in wd.last_warning
        assert "'x'" in wd.last_warning
        # the changed shape is now cached: no further warning on reuse
        wd.observe("step", {"x": np.zeros((2, 24), np.float32)}, 4)
        assert wd.warnings_emitted == 1
        assert reg.counter("jit_cache_misses_total").value(fn="step") == 2
        assert reg.counter("jit_recompile_warnings_total").value(
            fn="step") == 1

    def test_first_compile_within_warmup_is_silent(self):
        wd = RecompileWatchdog(warmup_steps=2, emit_warnings=False)
        wd.observe("step", {"x": np.zeros((2, 16))}, 1)
        # second shape still inside warmup (known gas/curriculum buckets)
        wd.observe("step", {"x": np.zeros((2, 8))}, 2)
        assert wd.warnings_emitted == 0
        wd.observe("step", {"x": np.zeros((2, 4))}, 3)
        assert wd.warnings_emitted == 1

    def test_dtype_change_is_a_new_signature(self):
        wd = RecompileWatchdog(warmup_steps=0, emit_warnings=False)
        wd.observe("f", {"x": np.zeros((2,), np.float32)}, 1)
        assert wd.observe("f", {"x": np.zeros((2,), np.int32)}, 2) is True
        assert "float32" in wd.last_warning and "int32" in wd.last_warning

    def test_invalidate_forgets_signatures(self):
        """Re-jitting (configure_moq) empties jit's caches; after
        invalidate the same signature must count as a fresh compile."""
        wd = RecompileWatchdog(warmup_steps=10, emit_warnings=False)
        batch = {"x": np.zeros((2, 16), np.float32)}
        assert wd.observe("step", batch, 1) is True
        assert wd.observe("step", batch, 2) is False
        wd.invalidate("step")
        assert wd.observe("step", batch, 3) is True


# ----------------------------------------- collective wrapper byte counters

class TestCollectiveCounters:
    def test_shard_map_counters_match_analytic(self, devices):
        """A jitted (pjit) step over a 2-device mesh: the wrapper-level
        trace-time counters must carry exactly the analytic WIRE bytes for
        each collective kind (comm/collectives.py convention — per-
        participant ring bytes; at n=2 both formulas below reduce to the
        shard payload: all_reduce 2·B·(n−1)/n = B, all_gather
        B·(n−1) = B)."""
        default_registry.reset()
        mesh = build_mesh(MeshSpec(dp=2, fsdp=1))

        def body(x):
            r = comm.all_reduce(x, "dp")              # [2, 8] f32 per shard
            g = comm.all_gather(x, "dp")              # [2, 8] f32 per shard
            return r + g.sum()

        x = jnp.ones((4, 8), jnp.float32)
        with mesh:
            out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                                    out_specs=P("dp")))(x)
        jax.device_get(out)
        shard_bytes = 2 * 8 * 4                       # rows/2 per device
        bc = default_registry.counter(COLLECTIVE_BYTES)
        cc = default_registry.counter(COLLECTIVE_CALLS)
        assert bc.value(kind="all_reduce", axis="dp") == shard_bytes
        assert bc.value(kind="all_gather", axis="dp") == shard_bytes
        assert cc.value(kind="all_reduce", axis="dp") == 1
        assert cc.value(kind="all_gather", axis="dp") == 1
        default_registry.reset()


# ------------------------------------------------------- engine integration

class TestEngineTelemetry:
    def test_three_step_run_exports_trace_snapshot_prometheus(self,
                                                              tmp_path):
        """The tentpole acceptance loop: a 3-step run with telemetry
        enabled produces (a) a Perfetto-loadable trace with >= 5 distinct
        phase spans per step, (b) snapshot JSON + Prometheus text with
        nonzero collective byte counters and memory gauges, and (c) zero
        recompile warnings on steady-state steps."""
        default_registry.reset()
        engine = _engine(tmp_path)
        rng = np.random.default_rng(0)
        for _ in range(3):
            engine.train_batch(_batch(rng, engine.train_batch_size))

        # (a) Chrome-trace JSON, >= 5 distinct phases per step
        trace = json.loads(
            open(os.path.join(str(tmp_path), "job", "trace.json")).read())
        assert isinstance(trace["traceEvents"], list)
        by_step = {}
        for e in trace["traceEvents"]:
            # (the constructor's ds.engine_init spans and the set-up
            # account's program_setup records of `other` belong to no step)
            if e.get("ph") == "X" and "step" in e["args"]:
                by_step.setdefault(e["args"]["step"], set()).add(e["name"])
        assert set(by_step) == {1, 2, 3}
        for step, phases in by_step.items():
            assert len(phases) >= 5, (step, phases)
        assert {"train_step", "batch_input", "host_to_device", "dispatch",
                "step_bookkeeping"} <= by_step[1]
        # the tracer forces no sync: the wait is a span only where
        # wall_clock_breakdown or the flops profiler asks for it
        assert "device_complete" not in by_step[1]

        # (b) snapshot + prometheus with nonzero collective bytes + memory
        snap = json.loads(
            open(os.path.join(str(tmp_path), "job", "snapshot.json")).read())
        hlo = snap["counters"]["hlo_collective_bytes_total"]["samples"]
        assert hlo and all(s["value"] > 0 for s in hlo)
        assert snap["gauges"]["host_memory_rss_bytes"]["samples"][0][
            "value"] > 0
        exe = snap["executables"]["train_batch"]
        assert exe["executions"] == 3
        assert exe["per_execution_collective_bytes"] > 0
        assert snap["counters"]["engine_steps_total"]["samples"][0][
            "value"] == 3
        prom = open(
            os.path.join(str(tmp_path), "job", "metrics.prom")).read()
        assert "# TYPE deepspeed_tpu_hlo_collective_bytes_total counter" \
            in prom
        assert "deepspeed_tpu_engine_steps_total 3" in prom

        # (c) steady state: one compile, zero warnings
        assert engine.telemetry.watchdog.misses("train_batch") == 1
        assert engine.telemetry.watchdog.warnings_emitted == 0
        default_registry.reset()

    def test_shape_change_triggers_exactly_one_warning(self, tmp_path):
        default_registry.reset()
        engine = _engine(tmp_path)
        rng = np.random.default_rng(0)
        for _ in range(3):
            engine.train_batch(_batch(rng, engine.train_batch_size, seq=16))
        engine.train_batch(_batch(rng, engine.train_batch_size, seq=24))
        wd = engine.telemetry.watchdog
        assert wd.warnings_emitted == 1
        assert "(1, 16, 16)" in wd.last_warning      # [gas, micro, T]
        assert "(1, 16, 24)" in wd.last_warning
        assert "'x'" in wd.last_warning
        # re-feeding the same changed shape hits the new cache entry
        engine.train_batch(_batch(rng, engine.train_batch_size, seq=24))
        assert wd.warnings_emitted == 1
        assert default_registry.counter("jit_cache_misses_total").value(
            fn="train_batch") == 2
        default_registry.reset()

    def test_monitor_fanout_writes_telemetry_series(self, tmp_path):
        """Scalar subset rides the existing MonitorMaster: the CSV monitor
        must grow Train/Telemetry/* series alongside the classic ones."""
        default_registry.reset()
        out = str(tmp_path / "csv")
        engine = _engine(tmp_path, {"csv_monitor": {
            "enabled": True, "output_path": out, "job_name": "job"}})
        rng = np.random.default_rng(0)
        for _ in range(2):
            engine.train_batch(_batch(rng, engine.train_batch_size))
        names = os.listdir(os.path.join(out, "job"))
        assert any(n.startswith("Train_Telemetry_engine_steps_total")
                   for n in names)
        assert any(n.startswith(
            "Train_Telemetry_hlo_collective_bytes_total") for n in names)
        default_registry.reset()

    def test_disabled_telemetry_writes_nothing(self, tmp_path):
        default_registry.reset()
        engine = _engine(tmp_path, {"telemetry": {
            "enabled": False, "output_path": str(tmp_path),
            "job_name": "job"}})
        rng = np.random.default_rng(0)
        engine.train_batch(_batch(rng, engine.train_batch_size))
        assert not os.path.exists(os.path.join(str(tmp_path), "job"))
        assert not engine.telemetry.tracer.events

    def test_checkpoint_span_recorded(self, tmp_path):
        """The async-checkpoint split (PR 3) renamed the SAVE path's span to
        checkpoint_snapshot + checkpoint_write (recorded at commit); only
        the LOAD path still records checkpoint_io.  The old assertion
        checked checkpoint_io after a save, which failed standalone on a
        clean tree — assert what each path actually records, with no
        dependence on test order."""
        default_registry.reset()
        engine = _engine(tmp_path)
        rng = np.random.default_rng(0)
        engine.train_batch(_batch(rng, engine.train_batch_size))
        engine.save_checkpoint(str(tmp_path / "ckpt"))
        names = [e["name"] for e in engine.telemetry.tracer.events]
        assert "checkpoint_snapshot" in names
        assert "checkpoint_write" in names    # blocking save commits inline
        engine.load_checkpoint(str(tmp_path / "ckpt"))
        names = [e["name"] for e in engine.telemetry.tracer.events]
        assert "checkpoint_io" in names       # the load-path span
        default_registry.reset()
