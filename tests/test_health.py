"""Numerics health monitor + postmortem flight recorder (ISSUE 4).

Covers the tentpole acceptance bar — a NaN-loss run with
``telemetry.health`` enabled produces a postmortem bundle with >= the last
16 step records carrying per-group norms and NaN counts, and enabling
health stats does not change the number of jit compilations — plus the
satellites: the single-fetch host-metrics cache, the offload overflow
sentinel regression, the postmortem CLI, the no-sync lint, anomaly rules,
and cross-host aggregation (single-process degradation).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.engine import OVERFLOW_GNORM, StepMetrics
from deepspeed_tpu.telemetry import default_registry
from deepspeed_tpu.telemetry.health import (AnomalyDetector,
                                            compute_group_health,
                                            flatten_health, group_names,
                                            to_python)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ helpers

def _init_fn(rng, batch):
    return {"scale": jnp.ones((8,)), "bias": jnp.zeros((8,))}


def _apply_fn(params, batch, rng):
    feat = jnp.tanh(batch["x"]).mean(axis=-1, keepdims=True)      # [B, 1]
    pred = (feat * params["scale"] + params["bias"]).mean(axis=-1)
    return jnp.mean((pred - batch["y"]) ** 2)


def _engine(tmp_path, extra_cfg=None, health=True, telemetry=False):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2},
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        "telemetry": {"enabled": telemetry, "output_path": str(tmp_path),
                      "job_name": "job",
                      "health": {"enabled": health}},
        **(extra_cfg or {}),
    }
    example = {"x": np.zeros((1, 16), np.float32),
               "y": np.zeros((1,), np.float32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=(_init_fn, _apply_fn), config=cfg, example_batch=example)
    return engine


def _batch(rng, bs, nan=False):
    b = {"x": rng.normal(size=(bs, 16)).astype(np.float32),
         "y": rng.normal(size=(bs,)).astype(np.float32)}
    if nan:
        b["x"][0, 0] = np.nan
    return b


# --------------------------------------------------- in-graph health stats

class TestGroupHealth:
    def test_norms_and_counts_match_analytic(self):
        params = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.asarray([2.0])}
        grads = {"a": jnp.asarray([1.0, np.nan]), "b": jnp.asarray([6.0])}
        newp = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.asarray([2.2])}
        h = to_python(compute_group_health(params, grads, newp, depth=1))
        assert set(h) == {"a", "b"}
        assert h["a"]["param_norm"] == pytest.approx(5.0)
        assert np.isnan(h["a"]["grad_norm"])
        assert h["a"]["grad_nan"] == 1 and h["a"]["grad_inf"] == 0
        assert h["b"]["grad_norm"] == pytest.approx(6.0)
        assert h["b"]["update_ratio"] == pytest.approx(0.2 / 2.0, rel=1e-4)
        # a's params were untouched
        assert h["a"]["update_ratio"] == pytest.approx(0.0, abs=1e-6)

    def test_inf_counted_separately(self):
        params = {"w": jnp.ones((4,))}
        grads = {"w": jnp.asarray([1.0, np.inf, -np.inf, np.nan])}
        h = to_python(compute_group_health(params, grads))
        assert h["w"]["grad_inf"] == 2 and h["w"]["grad_nan"] == 1
        assert "update_ratio" not in h["w"]      # no new_params given

    def test_group_depth_skips_params_collection(self):
        tree = {"params": {"backbone": {"block_0": {"w": jnp.ones(2)},
                                        "wte": jnp.ones(2)},
                           "lm_head": jnp.ones(2)}}
        assert group_names(tree, depth=2) == [
            "backbone/block_0", "backbone/wte", "lm_head"]

    def test_flatten_health(self):
        flat = flatten_health({"g": {"grad_norm": 1.5, "grad_nan": 2}})
        assert flat == {"g/grad_norm": 1.5, "g/grad_nan": 2.0}


# --------------------------------------------------------- anomaly rules

class TestAnomalyDetector:
    def test_loss_spike_fires_and_warns_once(self):
        det = AnomalyDetector(window=16, loss_spike_zscore=6.0,
                              emit_warnings=False)
        for i in range(10):
            assert det.observe(i, 1.0 + 0.01 * (i % 3), 1.0, 1.0) == []
        fired = det.observe(10, 50.0, 1.0, 1.0)
        assert fired == ["loss_spike"]
        assert "loss_spike" in det.last_warning
        assert det.warned == {"loss_spike"}
        det.observe(11, 60.0, 1.0, 1.0)          # counted, not re-warned
        assert det.warned == {"loss_spike"}

    def test_grad_norm_explosion(self):
        det = AnomalyDetector(window=16, grad_norm_factor=10.0,
                              emit_warnings=False)
        for i in range(10):
            det.observe(i, 1.0, 0.5, 1.0)
        assert "grad_norm_explosion" in det.observe(10, 1.0, 50.0, 1.0)

    def test_loss_scale_collapse(self):
        det = AnomalyDetector(window=16, scale_collapse_factor=16.0,
                              emit_warnings=False)
        det.observe(0, 1.0, 1.0, 2 ** 16)
        assert det.observe(1, 1.0, 1.0, 2 ** 10) == ["loss_scale_collapse"]

    def test_nonfinite_inputs_never_crash(self):
        det = AnomalyDetector(emit_warnings=False)
        for i in range(12):
            det.observe(i, float("nan"), float("inf"), 0.0)

    def test_counter_increments(self):
        from deepspeed_tpu.telemetry import MetricRegistry
        reg = MetricRegistry()
        det = AnomalyDetector(window=16, emit_warnings=False, registry=reg)
        for i in range(10):
            det.observe(i, 1.0, 1.0, 1.0)
        det.observe(10, 99.0, 99.0, 1.0)
        c = reg.counter("numerics_anomalies_total")
        assert c.value(rule="loss_spike") == 1
        assert c.value(rule="grad_norm_explosion") == 1


# ------------------------------------------------- cross-host aggregation

class TestAggregation:
    def test_single_process_degrades_to_identity(self):
        from deepspeed_tpu.comm import aggregate_health_scalars
        agg = aggregate_health_scalars({"loss": 2.5, "g/grad_nan": 3.0})
        assert agg["loss"] == {"min": 2.5, "max": 2.5, "mean": 2.5,
                               "argmax_process": 0}
        assert agg["g/grad_nan"]["argmax_process"] == 0

    def test_nan_ranks_as_tripping_value(self):
        from deepspeed_tpu.comm import aggregate_health_scalars
        agg = aggregate_health_scalars({"x": float("nan")})
        assert agg["x"]["argmax_process"] == 0
        assert np.isnan(agg["x"]["mean"])

    def test_empty_dict(self):
        from deepspeed_tpu.comm import aggregate_health_scalars
        assert aggregate_health_scalars({}) == {}

    def test_nan_outranks_inf_for_tripping_process(self):
        from deepspeed_tpu.comm.aggregation import _tripping_process
        col = np.asarray([1.0, np.inf, 2.0, np.nan])
        assert _tripping_process(col) == 3
        assert _tripping_process(np.asarray([1.0, np.inf, 2.0])) == 1
        assert _tripping_process(np.asarray([1.0, -3.0, 2.0])) == 1
        # ties break to the lowest index
        assert _tripping_process(np.asarray([np.nan, np.nan])) == 0


# ----------------------------------------------------- flight recorder unit

class TestFlightRecorder:
    def test_ring_buffer_and_one_shot_dump(self, tmp_path):
        from deepspeed_tpu.telemetry import FlightRecorder
        rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
        for i in range(10):
            rec.record({"step": i, "loss": float(i)})
        assert len(rec.records) == 4
        d1 = rec.dump("nonfinite_loss")
        assert d1 and os.path.isdir(d1)
        lines = open(os.path.join(d1, "records.jsonl")).read().splitlines()
        assert [json.loads(ln)["step"] for ln in lines] == [6, 7, 8, 9]
        # same automatic reason: one-shot
        assert rec.dump("nonfinite_loss") is None
        # manual always writes
        assert rec.dump("manual") is not None

    def test_failed_write_does_not_consume_one_shot_reason(self, tmp_path):
        """A transient bundle-write failure (disk full, permissions) must
        not suppress every later dump for that reason, nor count a bundle
        that does not exist."""
        from deepspeed_tpu.telemetry import FlightRecorder, MetricRegistry
        reg = MetricRegistry()
        rec = FlightRecorder(capacity=2, dump_dir=str(tmp_path / "f" / "x"),
                             registry=reg)
        rec.record({"step": 1})
        blocker = tmp_path / "f"
        blocker.write_text("not a directory")     # makedirs will fail
        assert rec.dump("nonfinite_loss") is None
        assert reg.counter("postmortem_dumps_total").value(
            reason="nonfinite_loss") == 0
        blocker.unlink()                          # "disk recovered"
        assert rec.dump("nonfinite_loss") is not None
        assert reg.counter("postmortem_dumps_total").value(
            reason="nonfinite_loss") == 1
        # now handled: the reason is one-shot again
        assert rec.dump("nonfinite_loss") is None

    def test_reinstall_does_not_rewrap_excepthook(self, tmp_path):
        """A second install after another library wrapped sys.excepthook
        (chaining to ours) must not capture that wrapper as our previous
        hook — crash time would recurse wrapper -> us -> wrapper."""
        import sys as _sys

        from deepspeed_tpu.telemetry import (FlightRecorder,
                                             install_crash_handler)
        from deepspeed_tpu.telemetry import flight_recorder as fr
        old_hook, old_prev = _sys.excepthook, fr._prev_excepthook
        old_installed = fr._hook_installed
        try:
            fr._hook_installed = False
            r1 = FlightRecorder(capacity=1, dump_dir=str(tmp_path),
                                write_files=False)
            install_crash_handler(r1)
            assert _sys.excepthook is fr._crash_excepthook
            wrapper = lambda *a: fr._crash_excepthook(*a)  # noqa: E731
            _sys.excepthook = wrapper
            r2 = FlightRecorder(capacity=1, dump_dir=str(tmp_path),
                                write_files=False)
            install_crash_handler(r2)
            # no re-wrap: the foreign wrapper stays installed and our
            # chain target is NOT the wrapper (no cycle)
            assert _sys.excepthook is wrapper
            assert fr._prev_excepthook is not wrapper
            assert r2 in fr._crash_recorders
        finally:
            _sys.excepthook = old_hook
            fr._prev_excepthook = old_prev
            fr._hook_installed = old_installed
            fr._crash_recorders.discard(r1)
            fr._crash_recorders.discard(r2)

    def test_failing_bundle_writer_degrades(self, tmp_path):
        from deepspeed_tpu.telemetry import FlightRecorder
        rec = FlightRecorder(capacity=2, dump_dir=str(tmp_path))
        rec.add_bundle_writer("boom", lambda d: 1 / 0)
        rec.record({"step": 1})
        d = rec.dump("manual")
        assert d is not None and os.path.exists(
            os.path.join(d, "records.jsonl"))

    def test_crash_excepthook_dumps_live_recorders(self, tmp_path):
        from deepspeed_tpu.telemetry import FlightRecorder
        from deepspeed_tpu.telemetry import flight_recorder as fr
        rec = FlightRecorder(capacity=2, dump_dir=str(tmp_path))
        rec.record({"step": 3})
        fr._crash_recorders.add(rec)
        try:
            # chain target: swallow instead of printing a scary traceback
            called = []
            old = fr._prev_excepthook
            fr._prev_excepthook = lambda *a: called.append(a)
            fr._crash_excepthook(ValueError, ValueError("boom"), None)
            assert rec.dumps and "exception" in rec.dumps[0]
            meta = json.load(open(os.path.join(rec.dumps[0], "meta.json")))
            assert meta["reason"] == "exception"
            assert "boom" in (meta.get("note") or "")
            assert called                          # original hook still ran
        finally:
            fr._prev_excepthook = old
            fr._crash_recorders.discard(rec)


# --------------------------------------------------- engine device path

class TestEngineHealth:
    def test_records_carry_per_group_stats(self, tmp_path):
        engine = _engine(tmp_path)
        rng = np.random.default_rng(0)
        for _ in range(3):
            engine.train_batch(_batch(rng, engine.train_batch_size))
        recs = list(engine.telemetry.recorder.records)
        assert len(recs) == 3
        for rec in recs:
            assert set(rec["health"]) == {"scale", "bias"}
            for stats in rec["health"].values():
                assert np.isfinite(stats["grad_norm"])
                assert stats["grad_nan"] == 0 and stats["grad_inf"] == 0
                assert "update_ratio" in stats
            assert np.isfinite(rec["loss"])
        assert recs[-1]["step"] == 3

    def test_health_does_not_add_compiles(self, tmp_path):
        """Acceptance: enabling health stats must not change the number of
        jit compilations in the steady state."""
        rng = np.random.default_rng(0)
        sizes = {}
        for name, health in (("off", False), ("on", True)):
            engine = _engine(tmp_path / name, health=health, telemetry=True)
            for _ in range(3):
                engine.train_batch(_batch(rng, engine.train_batch_size))
            assert engine.telemetry.watchdog.misses("train_batch") == 1
            cache_size = getattr(engine._jit_train_batch, "_cache_size",
                                 None)
            sizes[name] = cache_size() if cache_size is not None else 1
        assert sizes["on"] == sizes["off"] == 1

    def test_nan_loss_dumps_bundle_with_16_records(self, tmp_path):
        """Acceptance + satellite: a NaN loss produces a bundle holding >=
        the last 16 step records with per-group norms and NaN counts, plus
        config + Prometheus snapshot, and the postmortem CLI summarizes it
        without error."""
        engine = _engine(tmp_path)
        rng = np.random.default_rng(0)
        for _ in range(17):
            engine.train_batch(_batch(rng, engine.train_batch_size))
        m = engine.train_batch(_batch(rng, engine.train_batch_size,
                                      nan=True))
        assert not np.isfinite(float(m.loss))
        dumps = engine.telemetry.recorder.dumps
        assert len(dumps) == 1, "nonfinite loss must dump exactly once"
        bundle = dumps[0]
        assert "nonfinite_loss" in os.path.basename(bundle)
        recs = [json.loads(ln) for ln in
                open(os.path.join(bundle, "records.jsonl"))]
        assert len(recs) >= 16
        assert np.isnan(recs[-1]["loss"])
        nan_counts = sum(s["grad_nan"] for s in recs[-1]["health"].values())
        assert nan_counts > 0, "the NaN step must attribute non-finite grads"
        for rec in recs[:-1]:
            assert all(np.isfinite(s["grad_norm"])
                       for s in rec["health"].values())
        # bundle artifacts
        cfg = json.load(open(os.path.join(bundle, "config.json")))
        assert cfg["telemetry"]["health"]["enabled"] is True
        prom = open(os.path.join(bundle, "snapshot.prom")).read()
        assert "deepspeed_tpu_postmortem_dumps_total" in prom
        meta = json.load(open(os.path.join(bundle, "meta.json")))
        assert meta["reason"] == "nonfinite_loss"
        assert os.path.exists(os.path.join(bundle, "env.txt"))
        # a second NaN step must NOT dump again (one-shot)
        engine.train_batch(_batch(rng, engine.train_batch_size, nan=True))
        assert len(engine.telemetry.recorder.dumps) == 1
        # the CLI summarizes without error
        from deepspeed_tpu.telemetry.postmortem import main as pm_main
        assert pm_main([bundle]) == 0

    def test_overflow_streak_triggers_dump(self, tmp_path):
        """Unit-level trigger check: k consecutive overflow-skipped steps
        (finite loss) dump with reason=overflow_streak."""
        from deepspeed_tpu.config import parse_config
        from deepspeed_tpu.telemetry import StepTelemetry
        cfg = parse_config({"telemetry": {
            "output_path": str(tmp_path), "job_name": "job",
            "health": {"enabled": True, "overflow_streak": 3}}})
        tel = StepTelemetry(cfg)
        skipped = 0
        for step in range(1, 3):
            tel.health_step(step, StepMetrics(1.0, 0.5, 2.0 ** 16, skipped))
        for step in range(3, 6):
            skipped += 1
            path = tel.health_step(
                step, StepMetrics(1.0, OVERFLOW_GNORM, 2.0 ** 15, skipped))
        assert path and "overflow_streak" in os.path.basename(path)
        recs = [json.loads(ln) for ln in
                open(os.path.join(path, "records.jsonl"))]
        assert recs[-1]["overflow_streak"] == 3

    def test_streak_baseline_resyncs_after_restore(self, tmp_path):
        """A checkpoint restore can jump the cumulative skipped_steps
        counter in either direction — the first post-restore step must
        resync the baseline, not read the jump as an overflow."""
        from deepspeed_tpu.config import parse_config
        from deepspeed_tpu.telemetry import StepTelemetry
        cfg = parse_config({"telemetry": {
            "output_path": str(tmp_path), "job_name": "job",
            "health": {"enabled": True, "overflow_streak": 2}}})
        tel = StepTelemetry(cfg)
        tel.health_step(1, StepMetrics(1.0, 0.5, 2.0 ** 16, 0))
        # "restore" a checkpoint whose counter reads 20
        tel.reset_numerics_baseline()
        tel.health_step(2, StepMetrics(1.0, 0.5, 2.0 ** 16, 20))
        assert tel._overflow_streak == 0       # clean step, no phantom
        tel.health_step(3, StepMetrics(1.0, OVERFLOW_GNORM, 2.0 ** 15, 21))
        assert tel._overflow_streak == 1       # real overflow still counted

    def test_explicit_dump_postmortem(self, tmp_path):
        engine = _engine(tmp_path)
        rng = np.random.default_rng(0)
        engine.train_batch(_batch(rng, engine.train_batch_size))
        bundle = engine.dump_postmortem(note="user requested")
        assert bundle and os.path.exists(
            os.path.join(bundle, "records.jsonl"))
        meta = json.load(open(os.path.join(bundle, "meta.json")))
        assert meta["reason"] == "manual"

    def test_health_disabled_is_inert(self, tmp_path):
        engine = _engine(tmp_path, health=False)
        rng = np.random.default_rng(0)
        engine.train_batch(_batch(rng, engine.train_batch_size))
        assert engine.telemetry.recorder is None
        assert engine._last_health == {}
        assert engine.dump_postmortem() is None
        assert not os.path.exists(
            os.path.join(str(tmp_path), "job", "postmortem"))

    def test_anomaly_counter_reaches_snapshot(self, tmp_path):
        """Anomaly detections must ride the registry into the Prometheus
        snapshot (MonitorMaster fan-out shares the same samples)."""
        default_registry.reset()
        engine = _engine(tmp_path, telemetry=True)
        rng = np.random.default_rng(0)
        for _ in range(10):
            engine.train_batch(_batch(rng, engine.train_batch_size))
        # 100x the targets => loss spike without NaN
        bad = _batch(rng, engine.train_batch_size)
        bad["y"] += 100.0
        engine.train_batch(bad)
        snap = engine.telemetry.export(write=False)
        samples = snap["counters"]["numerics_anomalies_total"]["samples"]
        assert any(s["labels"]["rule"] == "loss_spike" and s["value"] >= 1
                   for s in samples)
        default_registry.reset()


# ----------------------------------------- single-fetch host metrics cache

class TestSingleFetchCache:
    def test_getters_share_one_fetch(self, tmp_path):
        engine = _engine(tmp_path, health=False)
        rng = np.random.default_rng(0)
        fetches = []
        orig = engine._fetch_metrics

        def counting_fetch(metrics, health=None):
            fetches.append(1)
            return orig(metrics, health)

        engine._fetch_metrics = counting_fetch
        engine.train_batch(_batch(rng, engine.train_batch_size))
        # steps_per_print=0, no monitors, health off: the step itself must
        # not have fetched
        assert fetches == []
        gn = engine.get_global_grad_norm()
        sk = engine.skipped_steps
        lr = engine.get_lr()[0]
        assert len(fetches) == 1, "getters must share ONE device fetch"
        assert isinstance(gn, float) and np.isfinite(gn)
        assert sk == 0 and lr > 0

    def test_cache_refreshes_per_step(self, tmp_path):
        engine = _engine(tmp_path, health=False)
        rng = np.random.default_rng(0)
        engine.train_batch(_batch(rng, engine.train_batch_size))
        g1 = engine.get_global_grad_norm()
        engine.train_batch(_batch(rng, engine.train_batch_size))
        g2 = engine.get_global_grad_norm()
        assert engine._host_metrics_step == engine.global_steps == 2
        assert g1 != g2 or True                  # values refreshed, no stale step

    def test_print_path_uses_host_copy(self, tmp_path, caplog):
        engine = _engine(tmp_path, health=False,
                         extra_cfg={"steps_per_print": 1})
        rng = np.random.default_rng(0)
        engine.train_batch(_batch(rng, engine.train_batch_size))
        assert engine._last_metrics_host is not None
        assert isinstance(engine._last_metrics_host.loss, float)


# ------------------------------------------- offload sentinel regression

class TestOffloadOverflowSentinel:
    def _offload_engine(self, tmp_path):
        return _engine(tmp_path, extra_cfg={
            "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
            "fp16": {"enabled": True, "initial_scale_power": 4},
            "zero_optimization": {"stage": 2,
                                  "offload_optimizer": {"device": "cpu"}},
        })

    def test_host_step_reports_finite_sentinel(self, tmp_path):
        """Regression (ISSUE 4 satellite): the offload path used to leak
        grad_norm=NaN on overflow steps; it must now record the overflow in
        skipped_steps and surface the same finite sentinel as the device
        path."""
        engine = self._offload_engine(tmp_path)
        rng = np.random.default_rng(0)
        m = engine.train_batch(_batch(rng, engine.train_batch_size))
        assert np.isfinite(float(m.grad_norm))
        m = engine.train_batch(_batch(rng, engine.train_batch_size,
                                      nan=True))
        assert float(m.grad_norm) == OVERFLOW_GNORM
        assert int(m.skipped_steps) == 1
        assert engine.get_global_grad_norm() == OVERFLOW_GNORM
        assert engine.skipped_steps == 1
        # health recorded the offload step too (both paths feed the recorder)
        recs = list(engine.telemetry.recorder.records)
        assert len(recs) == 2
        assert recs[-1]["skipped_steps"] == 1
        assert sum(s["grad_nan"] + s["grad_inf"]
                   for s in recs[-1]["health"].values()) > 0

    def test_trio_offload_path_records_health(self, tmp_path):
        """forward()/backward()/step() on the offload path must feed the
        recorder with per-group stats too (the accumulated grads never pass
        through _jit_grads_batch, so this exercises the dedicated jitted
        health program)."""
        engine = self._offload_engine(tmp_path)
        rng = np.random.default_rng(0)
        micro = (engine.train_micro_batch_size_per_gpu
                 * engine.dp_world_size)
        for _ in range(engine.gas):
            loss = engine.forward(_batch(rng, micro))
            engine.backward(loss)
        m = engine.step()
        assert m is not None
        recs = list(engine.telemetry.recorder.records)
        assert len(recs) == 1
        assert set(recs[-1]["health"]) == {"scale", "bias"}
        for stats in recs[-1]["health"].values():
            assert np.isfinite(stats["grad_norm"])

    def test_device_path_sentinel_matches(self, tmp_path):
        engine = _engine(tmp_path, extra_cfg={
            "fp16": {"enabled": True, "initial_scale_power": 4}})
        rng = np.random.default_rng(0)
        m = engine.train_batch(_batch(rng, engine.train_batch_size,
                                      nan=True))
        assert float(m.grad_norm) == OVERFLOW_GNORM
        assert int(m.skipped_steps) == 1


# ------------------------------------------------------- CI tooling smoke

class TestTooling:
    # the whole-repo green run of check_no_sync moved into the unified
    # lint driver (scripts/lint_all.py, shelled once by
    # tests/test_lint_all.py); the violation/behavior tests stay here

    def test_check_no_sync_lint_catches_violation(self, tmp_path):
        bad = tmp_path / "engine.py"
        bad.write_text(
            "class E:\n"
            "    def train_batch(self, metrics):\n"
            "        return float(metrics.loss)\n")
        p = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_no_sync.py"), str(bad)],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 1
        assert "train_batch" in p.stderr

    def test_check_no_sync_ignores_traced_inner_closures(self, tmp_path):
        """float(...) inside a jit-traced inner closure runs once at trace
        time, not per step — the lint must only scan top-level functions
        and class methods, not nested defs that happen to share a step-path
        name."""
        src = tmp_path / "engine.py"
        src.write_text(
            "class E:\n"
            "    def _make_train_batch(self):\n"
            "        def train_batch(state, batch):\n"
            "            scale = float(self.gas)\n"
            "            return state, scale\n"
            "        return train_batch\n")
        p = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_no_sync.py"), str(src)],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stderr

    def test_postmortem_cli_module_smoke(self, tmp_path):
        """``python -m deepspeed_tpu.telemetry.postmortem`` runs end to end
        on a synthetic bundle (and resolves a parent dir to its newest
        bundle)."""
        bundle = tmp_path / "postmortem" / "20260101-000000-step5-manual"
        bundle.mkdir(parents=True)
        with open(bundle / "records.jsonl", "w") as f:
            f.write(json.dumps({"step": 5, "loss": 1.0, "grad_norm": 0.5,
                                "loss_scale": 1.0, "skipped_steps": 0,
                                "health": {"g": {"grad_norm": 0.5,
                                                 "grad_nan": 0,
                                                 "grad_inf": 0}}}) + "\n")
        with open(bundle / "meta.json", "w") as f:
            json.dump({"reason": "manual", "last_step": 5,
                       "num_records": 1}, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.telemetry.postmortem",
             str(tmp_path / "postmortem")],
            capture_output=True, text=True, cwd=REPO, env=env, timeout=60)
        assert p.returncode == 0, p.stderr
        assert "manual" in p.stdout and "step" in p.stdout

    def test_postmortem_cli_missing_dir(self):
        from deepspeed_tpu.telemetry.postmortem import main as pm_main
        assert pm_main(["/nonexistent/bundle/dir"]) == 2
