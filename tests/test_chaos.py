"""Chaos suite — deterministic fault injection across the resilience
lifecycle (runtime/faults.py driving the drain → export → restore path).

Reference analog: the reference's elasticity/checkpoint tests kill
torch.multiprocessing workers and truncate files by hand; here the
injection sites are part of the library surface, so these tests drive the
SAME durability-ordering code the fleet runs.  Everything here is
CPU-fast and in-process where the on-disk outcome is identical (an ``exc``
fault leaves exactly the bytes a SIGKILL at that site would); the one true
process-death leg rides the elastic-agent suite (test_elastic_agent.py,
DSTPU_FAULTS host_loss)."""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.checkpoint import (CheckpointCorrupt, CheckpointNotFound,
                                      latest_universal, universal_complete)
from deepspeed_tpu.checkpoint.universal import load_universal
from deepspeed_tpu.models import GPT, GPTConfig
from deepspeed_tpu.runtime import faults
from deepspeed_tpu.runtime.resilience import \
    EXIT_DRAINED as resilience_EXIT_DRAINED

VOCAB, SEQ = 64, 16


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _build(telemetry=False, stage=2, mesh_kw=None):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage},
        "bf16": {"enabled": True},
        "mesh": mesh_kw or {"dp": -1},
        "steps_per_print": 0,
    }
    if telemetry:
        cfg["telemetry"] = {"enabled": True, "snapshot_interval": 0}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)),
        config=cfg,
        example_batch={"input_ids": np.zeros((2, SEQ), np.int32)})
    return engine


def _batch(engine, seed=0):
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, VOCAB, size=(8, SEQ)).astype(np.int32)
    return {"input_ids": pool[rng.integers(
        0, 8, size=(engine.train_batch_size,))]}


@pytest.fixture(scope="module")
def engine(devices):
    return _build(telemetry=True)


class TestFaultInjector:
    def test_spec_parsing_and_determinism(self):
        inj = faults.FaultInjector()
        inj.configure("exc@a.b, sleep@c:0.02, exc@d*2, exc@e+2")
        assert inj.armed("a.b") == 1
        assert inj.armed("d") == 2
        with pytest.raises(faults.InjectedFault):
            inj.fire("a.b")
        inj.fire("a.b")                  # one-shot: disarmed after tripping
        assert inj.fired("a.b") == 1
        t0 = time.perf_counter()
        inj.fire("c")
        assert time.perf_counter() - t0 >= 0.02
        for _ in range(2):
            with pytest.raises(faults.InjectedFault):
                inj.fire("d")
        inj.fire("d")
        # +after: the first two firings pass, the third trips
        inj.fire("e")
        inj.fire("e")
        with pytest.raises(faults.InjectedFault):
            inj.fire("e")

    def test_bad_specs_raise(self):
        inj = faults.FaultInjector()
        with pytest.raises(ValueError, match="kind@site"):
            inj.configure("no-site-separator")
        with pytest.raises(ValueError, match="unknown fault kind"):
            inj.inject("x", "explode")

    def test_unarmed_site_is_noop(self):
        faults.fire("never.armed")       # must not raise


class TestTornUniversalExport:
    """Satellite: torn-universal refusal + the newest-COMPLETE scan."""

    def test_torn_write_refused_and_skipped(self, engine, tmp_path):
        run_dir = str(tmp_path)
        engine.train_batch(_batch(engine))
        step = engine.global_steps
        good = engine.export_universal_checkpoint(
            os.path.join(run_dir, f"universal_{step}"), run_dir=run_dir)
        assert universal_complete(good)
        assert latest_universal(run_dir) == good

        engine.train_batch(_batch(engine, seed=1))
        torn = os.path.join(run_dir, f"universal_{engine.global_steps}")
        faults.inject("universal.mid_fragments", "exc")
        with pytest.raises(faults.InjectedFault):
            engine.export_universal_checkpoint(torn, run_dir=run_dir)
        # the torn export refuses restore with the TYPED error...
        with pytest.raises(CheckpointCorrupt, match="never\\s+committed"):
            load_universal(torn)
        # ...and the newest-COMPLETE scan never selects it
        assert latest_universal(run_dir) == good

    @pytest.mark.parametrize("site", ["universal.pre_fragments",
                                      "universal.pre_meta",
                                      "universal.pre_commit"])
    def test_fault_before_commit_leaves_previous_export(self, engine,
                                                        tmp_path, site):
        run_dir = str(tmp_path)
        good = engine.export_universal_checkpoint(
            os.path.join(run_dir, "universal_a"), run_dir=run_dir)
        faults.inject(site, "exc")
        with pytest.raises(faults.InjectedFault):
            engine.export_universal_checkpoint(
                os.path.join(run_dir, "universal_b"), run_dir=run_dir)
        assert latest_universal(run_dir) == good
        frags, meta = load_universal(latest_universal(run_dir))
        assert frags                     # previous export fully loadable

    def test_fault_after_commit_is_still_newest(self, engine, tmp_path):
        """A death BETWEEN the commit (marker off) and the pointer move
        loses only the pointer: the scan fallback still finds the new
        export."""
        run_dir = str(tmp_path)
        engine.export_universal_checkpoint(
            os.path.join(run_dir, "universal_a"), run_dir=run_dir)
        engine.train_batch(_batch(engine, seed=9))   # newer step to commit
        faults.inject("universal.pre_pointer", "exc")
        new = os.path.join(run_dir, f"universal_{engine.global_steps}")
        with pytest.raises(faults.InjectedFault):
            engine.export_universal_checkpoint(new, run_dir=run_dir)
        assert universal_complete(new)   # data committed before the fault
        # pointer is stale (still the old export) — the scan wins
        assert latest_universal(run_dir) == new

    def test_truncated_fragment_is_corrupt(self, engine, tmp_path):
        run_dir = str(tmp_path)
        d = engine.export_universal_checkpoint(
            os.path.join(run_dir, "universal_t"), run_dir=run_dir)
        frag = None
        for root, _, files in os.walk(os.path.join(d, "zero")):
            for f in files:
                if f == "fp32.npy":
                    frag = os.path.join(root, f)
                    break
            if frag:
                break
        with open(frag, "r+b") as f:
            f.truncate(8)                # tear the payload, keep the file
        with pytest.raises(CheckpointCorrupt, match="unreadable|torn"):
            load_universal(d)

    def test_slow_commit_race_reads_previous(self, engine, tmp_path):
        """A reader scanning while a commit is stretched out must see the
        PREVIOUS complete export, never the half-committed one."""
        run_dir = str(tmp_path)
        good = engine.export_universal_checkpoint(
            os.path.join(run_dir, "universal_a"), run_dir=run_dir)
        faults.inject("universal.pre_commit", "sleep", arg=0.5)
        seen = {}

        def exporter():
            engine.export_universal_checkpoint(
                os.path.join(run_dir, "universal_b"), run_dir=run_dir)
        t = threading.Thread(target=exporter)
        t.start()
        time.sleep(0.15)                 # mid-commit window
        seen["during"] = latest_universal(run_dir)
        t.join()
        seen["after"] = latest_universal(run_dir)
        assert seen["during"] == good
        assert seen["after"] == os.path.join(run_dir, "universal_b")


class TestTypedErrors:
    """Satellite: missing/torn checkpoints raise CheckpointNotFound /
    CheckpointCorrupt instead of backend-dependent exceptions."""

    def test_universal_not_found(self, tmp_path):
        with pytest.raises(CheckpointNotFound):
            load_universal(str(tmp_path / "nope"))
        (tmp_path / "not_universal").mkdir()
        with pytest.raises(CheckpointNotFound, match="zero/"):
            load_universal(str(tmp_path / "not_universal"))

    def test_orbax_missing_tag(self, engine, tmp_path):
        engine.save_checkpoint(str(tmp_path), tag="exists")
        with pytest.raises(CheckpointNotFound):
            engine.load_checkpoint(str(tmp_path), "missing_tag")

    def test_orbax_torn_tag_refused(self, engine, tmp_path):
        tag = engine.save_checkpoint(str(tmp_path))
        # a crash mid-async-write leaves the in-progress marker behind
        from deepspeed_tpu.checkpoint import IN_PROGRESS_FILE
        with open(os.path.join(str(tmp_path), tag, IN_PROGRESS_FILE),
                  "w") as f:
            f.write("torn")
        with pytest.raises(CheckpointCorrupt, match="never committed"):
            engine.load_checkpoint(str(tmp_path), tag)

    def test_latest_universal_empty_dir(self, tmp_path):
        assert latest_universal(str(tmp_path)) is None
        assert latest_universal(str(tmp_path / "missing")) is None


class TestDrainLifecycle:
    """Tentpole: a fault at EVERY drain phase still leaves a loadable
    newest export (the resume source can regress to the previous step but
    can never be torn)."""

    DRAIN_SITES = ["drain.begin", "drain.pre_checkpoint_fence",
                   "drain.pre_export", "universal.mid_fragments",
                   "universal.pre_meta", "universal.pre_commit",
                   "universal.pre_pointer", "drain.post_export"]

    @pytest.mark.parametrize("site", DRAIN_SITES)
    def test_fault_at_drain_phase_preserves_resume_source(self, engine,
                                                          tmp_path, site):
        run_dir = str(tmp_path)
        engine.train_batch(_batch(engine, seed=2))
        baseline = engine.export_universal_checkpoint(
            os.path.join(run_dir, f"universal_{engine.global_steps}"),
            run_dir=run_dir)
        baseline_step = engine.global_steps
        engine.train_batch(_batch(engine, seed=3))
        faults.inject(site, "exc")
        with pytest.raises(faults.InjectedFault):
            engine.drain(run_dir, reason="chaos")
        src = latest_universal(run_dir)
        assert src is not None, f"{site}: no loadable export left"
        frags, meta = load_universal(src)   # loadable, not torn
        # a fault before the drain-export commit leaves the baseline; one
        # after the commit leaves the (newer) drain export — both are
        # legitimate resume sources, torn is the only illegal outcome
        assert meta["step"] in (baseline_step, engine.global_steps)
        if site in ("universal.pre_pointer", "drain.post_export"):
            assert meta["step"] == engine.global_steps
        else:
            assert src == baseline

    def test_clean_drain_commits_fingerprints_and_counters(self, engine,
                                                           tmp_path):
        from deepspeed_tpu.runtime.resilience import FINGERPRINTS_FILE
        run_dir = str(tmp_path)
        e = engine
        path = e.drain(run_dir, reason="manual")
        assert universal_complete(path)
        assert latest_universal(run_dir) == path
        assert os.path.exists(os.path.join(run_dir, FINGERPRINTS_FILE))
        snap = e.telemetry.export(write=False)
        blob = json.dumps(snap)
        assert "preemptions_total" in blob and '"manual"' in blob


class TestFastResume:
    """Tentpole: warm resume compiles ZERO new executables (recompile
    watchdog) and emits time_to_resume_ms."""

    def test_warm_resume_zero_new_executables(self, engine, tmp_path):
        run_dir = str(tmp_path)
        e1 = engine                      # same config as a fresh _build
        e1.train_batch(_batch(e1, seed=41))
        e1.drain(run_dir, reason="sigterm")

        e2 = _build(telemetry=True)
        src = e2.resume_from_latest(run_dir)
        assert src is not None and e2.global_steps == e1.global_steps
        wd = e2.telemetry.watchdog
        misses_before = wd.misses("train_batch")
        assert misses_before >= 1        # the AOT warmup registered it
        e2.train_batch(_batch(e2, seed=7))
        assert wd.misses("train_batch") == misses_before, \
            "warm resume must compile 0 new executables"
        assert wd.warnings_emitted == 0
        snap = e2.telemetry.export(write=False)
        blob = json.dumps(snap)
        assert "time_to_resume_ms" in blob and "restarts_total" in blob

    def test_resume_cold_start_returns_none(self, engine, tmp_path):
        before = engine.global_steps
        assert engine.resume_from_latest(str(tmp_path)) is None
        assert engine.global_steps == before

    def test_cache_dir_placed_from_outside(self, tmp_path, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR wins: with it set the helper sets no
        path in code (jax already read it) and ignores the config value;
        unset, the config value is used and created."""
        from deepspeed_tpu.runtime import resilience
        before = jax.config.jax_compilation_cache_dir
        floors = (jax.config.jax_persistent_cache_min_entry_size_bytes,
                  jax.config.jax_persistent_cache_min_compile_time_secs)
        asked = str(tmp_path / "cache")
        try:
            monkeypatch.setenv(resilience.CACHE_DIR_ENV,
                               str(tmp_path / "outside"))
            got = resilience.enable_compilation_cache(asked)
            assert got == str(tmp_path / "outside")
            assert jax.config.jax_compilation_cache_dir == before
            assert not os.path.exists(asked)
            monkeypatch.delenv(resilience.CACHE_DIR_ENV)
            assert resilience.enable_compilation_cache(asked) == asked
            assert jax.config.jax_compilation_cache_dir == asked
            assert os.path.isdir(asked)
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              floors[0])
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              floors[1])

    def test_preemption_handler_flag_file_and_manual(self, tmp_path):
        from deepspeed_tpu.runtime.resilience import PreemptionHandler
        flag = str(tmp_path / "preempt.flag")
        h = PreemptionHandler(signals=(), flag_file=flag)
        assert not h.requested
        with open(flag, "w") as f:
            f.write("now")
        assert h.requested and h.reason == "flag_file"
        h2 = PreemptionHandler(signals=())
        h2.request("manual")
        assert h2.requested and h2.reason == "manual"

    def test_resume_falls_back_past_corrupt_export(self, engine, tmp_path):
        """A committed-LOOKING export with torn fragment bytes (power loss
        the marker protocol couldn't see) must not crash-loop resume: the
        previous complete export wins."""
        run_dir = str(tmp_path)
        good = engine.export_universal_checkpoint(
            os.path.join(run_dir, f"universal_{engine.global_steps}"),
            run_dir=run_dir)
        good_step = engine.global_steps
        engine.train_batch(_batch(engine, seed=51))
        newer = engine.export_universal_checkpoint(
            os.path.join(run_dir, f"universal_{engine.global_steps}"),
            run_dir=run_dir)
        frag = next(os.path.join(r, f) for r, _, fs in
                    os.walk(os.path.join(newer, "zero"))
                    for f in fs if f == "fp32.npy")
        with open(frag, "r+b") as f:
            f.truncate(8)                # torn bytes, marker already off
        src = engine.resume_from_latest(run_dir, warmup=False)
        assert src == good
        assert engine.global_steps == good_step

    def test_drain_reuses_committed_same_step_export(self, engine,
                                                     tmp_path):
        """Drain right after the worker contract's per-step export must NOT
        re-open the committed dir (re-marking durable data in-progress): it
        reuses it — asserted by arming a fault that would trip any fresh
        export."""
        run_dir = str(tmp_path)
        engine.train_batch(_batch(engine, seed=52))
        committed = engine.export_universal_checkpoint(
            os.path.join(run_dir, f"universal_{engine.global_steps}"),
            run_dir=run_dir)
        faults.inject("universal.pre_fragments", "exc")
        path = engine.drain(run_dir, reason="manual")
        assert path == committed         # no fresh export ran
        assert universal_complete(path)
        assert faults.injector.fired("universal.pre_fragments") == 0

    def test_fingerprints_roundtrip(self, engine, tmp_path):
        from deepspeed_tpu.runtime.resilience import (load_fingerprints,
                                                      save_fingerprints)
        p = save_fingerprints(engine, str(tmp_path / "fp.json"))
        manifest = load_fingerprints(p)
        assert "train_batch" in manifest
        sigs = manifest["train_batch"]
        assert sigs and all(len(leaf) == 3 for sig in sigs for leaf in sig)
        with pytest.raises(ValueError, match="fingerprints"):
            bad = str(tmp_path / "bad.json")
            with open(bad, "w") as f:
                json.dump({"format": "other"}, f)
            load_fingerprints(bad)


# ---------------------------------------------------------------------------
# nan@ fault kind + guardian self-healing (runtime/guardian.py)
# ---------------------------------------------------------------------------

def _guardian_build(tmp, **guardian_over):
    """fp32 engine (exact universal roundtrip — the bitwise legs compare
    restored fp32 params, no low-precision cast in the way) with health
    monitoring on and a fast guardian ring cadence."""
    g = {"enabled": True, "checkpoint_interval": 2, "ring_keep": 4,
         "clean_window": 1, "max_rollbacks": 2,
         # watchdog stays armed but far out of the way (no false trips on
         # a loaded CI box); the hang legs configure it tight explicitly
         "watchdog": {"warmup_deadline_s": 600.0, "min_deadline_s": 120.0,
                      "deadline_factor": 100.0}}
    g.update(guardian_over)
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        "data_pipeline": {"prefetch_depth": 2},
        "telemetry": {"enabled": False,
                      "health": {"enabled": True, "dump_path": str(tmp),
                                 "overflow_streak": 3}},
        "guardian": g,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)),
        config=cfg,
        example_batch={"input_ids": np.zeros((2, SEQ), np.int32)})
    return engine


def _guardian_batch_fn(i):
    rng = np.random.default_rng(1000 + i)
    return {"input_ids": rng.integers(0, VOCAB,
                                      size=(16, SEQ)).astype(np.int32)}


class TestNanFaultKind:
    """Satellite: the ``nan`` fault kind — spec parsing, fired/armed
    accounting, and the engine-site injection at ``step.grads``."""

    def test_spec_parsing_and_signal_return(self):
        inj = faults.FaultInjector()
        inj.configure("nan@step.grads*2+1")
        assert inj.armed("step.grads") == 2
        assert inj.fire("step.grads") is None        # +1: first call passes
        assert inj.fire("step.grads") == "nan"
        assert inj.fire("step.grads") == "nan"
        assert inj.fire("step.grads") is None        # disarmed
        assert inj.fired("step.grads") == 2

    def test_fire_return_values_by_kind(self):
        inj = faults.FaultInjector()
        assert inj.fire("unarmed") is None
        inj.inject("s", "sleep", arg=0.0)
        assert inj.fire("s") == "sleep"

    def test_engine_site_injection(self, devices, tmp_path):
        """nan@step.grads drives the step's loss and grads non-finite and
        the corruption persists — only a rollback heals it."""
        e = _guardian_build(tmp_path)
        e.train_batch(_guardian_batch_fn(0))
        assert np.isfinite(float(e._host_metrics().loss))
        faults.inject("step.grads", "nan")
        e.train_batch(_guardian_batch_fn(1))
        assert faults.fired("step.grads") == 1
        host = e._host_metrics()
        assert not np.isfinite(host.loss)
        health = e._last_health_host
        assert any(rec.get("grad_nan", 0) + rec.get("grad_inf", 0) > 0
                   for rec in health.values())
        # fault disarmed, but the poison persists in the live state: the
        # NEXT (fault-free) step is still non-finite
        e.train_batch(_guardian_batch_fn(2))
        assert not np.isfinite(float(e._host_metrics().loss))


class TestGuardianSelfHealing:
    """Tentpole e2e: poisoned step → rollback to the health-verified ring
    entry → seed-stable skip → trajectory BITWISE equal to a run that
    never saw the fault (same effective batch sequence)."""

    def test_rollback_skip_bitwise_trajectory(self, devices, tmp_path):
        run_dir = str(tmp_path / "run")
        e = _guardian_build(tmp_path / "pm")
        reg = e.telemetry.registry

        def _val(name, **labels):
            # the default registry is process-shared: assert DELTAS
            m = reg._metrics.get(name)
            return m.value(**labels) if m is not None else 0.0

        rb0 = _val("rollbacks_total", reason="nonfinite_loss")
        pm0 = _val("postmortem_dumps_total", reason="nonfinite_loss")
        faults.inject("step.grads", "nan", after=5)   # poisons step 6
        g = e.guardian(run_dir, batch_fn=_guardian_batch_fn)
        report = g.run(10)
        assert report.status == "completed"
        assert report.steps == 10
        assert report.rollbacks == 1
        # ring exports at 0,2,4 were stamped (clean_window=1); the anomaly
        # at step 6 rolled back to step 4 and skipped sources 4,5
        assert report.skipped_sources == [4, 5]
        assert g.cursor.history[:10] == [0, 1, 2, 3, 6, 7, 8, 9, 10, 11]
        assert report.rollback_recovery_ms and \
            report.rollback_recovery_ms[0] > 0
        assert _val("rollbacks_total", reason="nonfinite_loss") == rb0 + 1
        # the nonfinite step also dumped a postmortem (flight recorder)
        assert _val("postmortem_dumps_total",
                    reason="nonfinite_loss") == pm0 + 1

        # clean reference: a fresh engine trained on the guardian run's
        # EFFECTIVE source sequence, never seeing the fault
        faults.reset()
        e2 = _guardian_build(tmp_path / "pm2")
        for i in g.cursor.history[:10]:
            m = e2.train_batch(_guardian_batch_fn(i))
        assert float(m.loss) == report.final_loss      # bitwise
        p1 = jax.device_get(e.state.params)
        p2 = jax.device_get(e2.state.params)
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_double_fault_rolls_back_to_fresh_reexport(self, devices,
                                                       tmp_path):
        """Second incident after a heal: the rollback target is a ring
        entry RE-exported at a step number the abandoned timeline had
        also exported.  The stale (pre-skip) entry was discarded at the
        first rollback, so the second restore is the fresh-timeline state
        — pinned, as always, bitwise against a clean run on the effective
        sequence."""
        run_dir = str(tmp_path / "run")
        e = _guardian_build(tmp_path / "pm", max_rollbacks=2,
                            clamp_after_rollbacks=10)
        # fire-call schedule (one call per train_batch, incl. replays; a
        # call that fires one fault does NOT decrement a co-armed fault's
        # +after): call 5 = timeline-1 step 5; call 10 = timeline-2 step 7
        faults.inject("step.grads", "nan", after=4)
        faults.inject("step.grads", "nan", after=8)
        g = e.guardian(run_dir, batch_fn=_guardian_batch_fn)
        report = g.run(10)
        assert report.status == "completed"
        assert report.rollbacks == 2
        # incident 1: step 5 → rollback to 2 (ring_4's window was
        # tainted), skip sources 2,3,4; incident 2: step 7 → rollback to
        # the RE-exported, re-stamped step-4 entry, skip the replayed
        # sources 7,8,9
        assert report.skipped_sources == [2, 3, 4, 7, 8, 9]
        assert g.cursor.history[:10] == [0, 1, 5, 6, 10, 11, 12, 13, 14, 15]

        faults.reset()
        e2 = _guardian_build(tmp_path / "pm2")
        for i in g.cursor.history[:10]:
            m = e2.train_batch(_guardian_batch_fn(i))
        assert float(m.loss) == report.final_loss      # bitwise
        p1 = jax.device_get(e.state.params)
        p2 = jax.device_get(e2.state.params)
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_repeated_poison_escalates_to_drain(self, devices, tmp_path):
        """When rollbacks stop helping (every replay re-poisons), the
        bounded budget escalates: postmortem bundle + graceful drain."""
        run_dir = str(tmp_path / "run")
        pm = tmp_path / "pm"
        e = _guardian_build(pm, max_rollbacks=2,
                            clamp_after_rollbacks=10)   # keep re-jits out
        reg = e.telemetry.registry
        m = reg._metrics.get("guardian_escalations_total")
        esc0 = m.value(reason="nonfinite_loss") if m is not None else 0.0
        faults.inject("step.grads", "nan", count=10, after=4)
        g = e.guardian(run_dir, batch_fn=_guardian_batch_fn)
        report = g.run(12)
        assert report.status == "escalated"
        assert report.exit_code == resilience_EXIT_DRAINED
        assert report.rollbacks == 2                    # budget honored
        assert reg._metrics["guardian_escalations_total"].value(
            reason="nonfinite_loss") == esc0 + 1
        # the escalation bundle landed, with all-thread stacks riding along
        bundles = [d for d in os.listdir(str(pm))
                   if "guardian_escalation" in d]
        assert bundles
        assert os.path.exists(os.path.join(str(pm), bundles[0],
                                           "stacks.txt"))
        # ...and the drain committed a final export for the postmortem loop
        assert latest_universal(run_dir) is not None

    def test_clamp_down_on_second_rollback(self, devices, tmp_path):
        """From the (clamp_after_rollbacks+1)-th retry of one incident the
        guardian clamps LR and loss scale down."""
        run_dir = str(tmp_path / "run")
        e = _guardian_build(tmp_path / "pm", max_rollbacks=3,
                            clamp_after_rollbacks=1)
        lr0 = e.get_lr()[0]
        faults.inject("step.grads", "nan", count=2, after=4)
        g = e.guardian(run_dir, batch_fn=_guardian_batch_fn)
        report = g.run(10)
        assert report.status == "completed"
        assert report.rollbacks == 2
        # first rollback: no clamp; second: LR halved (default factor)
        assert e.get_lr()[0] == pytest.approx(lr0 * 0.5)

    def test_no_eligible_checkpoint_escalates(self, devices, tmp_path):
        """An anomaly before any ring entry earned its stamp has no
        rollback source: immediate escalation, never a crash loop."""
        run_dir = str(tmp_path / "run")
        e = _guardian_build(tmp_path / "pm")
        reg = e.telemetry.registry
        m = reg._metrics.get("guardian_escalations_total")
        esc0 = (m.value(reason="no_eligible_checkpoint")
                if m is not None else 0.0)
        faults.inject("step.grads", "nan")              # poison step 1
        g = e.guardian(run_dir, batch_fn=_guardian_batch_fn)
        report = g.run(6)
        assert report.status == "escalated"
        assert report.rollbacks == 0
        assert reg._metrics["guardian_escalations_total"].value(
            reason="no_eligible_checkpoint") == esc0 + 1


class TestGuardianHang:
    """Tentpole e2e: a hung step (sleep@step.dispatch beyond the adaptive
    deadline) produces a postmortem bundle with all-thread stacks and a
    clean EXIT_DRAINED — within deadline + grace, not after the sleep."""

    def test_hang_dumps_bundle_and_exits_drained(self, tmp_path):
        import subprocess
        import sys as _sys
        script = os.path.join(os.path.dirname(__file__),
                              "guardian_train_script.py")
        run_dir = str(tmp_path)
        env = dict(os.environ,
                   DSTPU_RUN_DIR=run_dir,
                   DSTPU_HANG_AT="8",
                   # the wedged step sleeps 120 s — a process that waits it
                   # out fails the wall-clock bound below
                   DSTPU_FAULTS="sleep@step.dispatch:120+7",
                   JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        t0 = time.time()
        # the child starts, compiles one train step, runs seven and hangs
        # in the eighth; the watchdog then has it out within deadline +
        # grace (about 30 s in all on a loaded sandbox, nearly all of it
        # start-up and the compile).  The limit is that and under a minute
        # more: a child that sat out its 120 s sleep would not fit in it.
        proc = subprocess.run([_sys.executable, script], env=env,
                              capture_output=True, text=True, timeout=75)
        wall = time.time() - t0
        assert proc.returncode == resilience_EXIT_DRAINED, proc.stderr[-2000:]
        # the watchdog reacted at deadline+grace, it did not sit out the
        # sleep: bound (exit - the hanging step's dispatch stamp).  The
        # deadline is ~2x the EMA step time (sub-second post-compile) and
        # grace is 0.5 s; 30 s covers slow-CI noise with a 4x margin while
        # still proving the 120 s sleep was not awaited.
        with open(os.path.join(run_dir, "armed_at.txt")) as f:
            armed_at = float(f.read())
        assert (t0 + wall) - armed_at < 30.0
        pm = os.path.join(run_dir, "pm")
        bundles = [d for d in os.listdir(pm) if d.endswith("-hang")]
        assert bundles, os.listdir(pm)
        bundle = os.path.join(pm, bundles[0])
        stacks = open(os.path.join(bundle, "stacks.txt")).read()
        assert "ds-guardian-watchdog" in stacks    # all threads captured
        assert "train_batch" in stacks             # incl. the wedged one
        assert os.path.exists(os.path.join(bundle, "records.jsonl"))
        # hangs_total reached the bundle's own metric snapshot
        prom = open(os.path.join(bundle, "snapshot.prom")).read()
        assert "hangs_total" in prom
