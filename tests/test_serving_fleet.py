"""Serving-fleet chaos suite: supervised replicas, failure-tolerant
routing, request migration (deepspeed_tpu/serving/ + the engine's
drain/export hooks).

The invariants these tests pin, in order of importance:

1. **Token-exactness** — a request that survives a replica death, a
   drain, or any number of migrations completes with output byte-equal to
   a single no-failure engine's (greedy decoding + identical params +
   host-known-prefix folding).
2. **No lost or duplicated requests** — every request completes exactly
   once, whatever dies.
3. **Bounded failure** — retry-budget exhaustion surfaces a typed
   ``RequestFailed`` (reason, attempts), never a hang; the backoff
   schedule is pinned under the injected clock/seed.
4. **Determinism of the chaos itself** — ``runtime/faults.py`` sites +
   the new ``fired/armed/sites/reset`` introspection.

Everything is CPU-fast (tiny fp32 model, shared compile cache across
fleets) and in-process — no process isolation needed.
"""

import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import EngineDrained, InferenceEngineV2
from deepspeed_tpu.models import GPTConfig
from deepspeed_tpu.runtime import faults
from deepspeed_tpu.serving import (POLICIES, AdmissionConfig,
                                   AdmissionController, FleetDrained,
                                   FleetRequest, NoHealthyReplicas,
                                   RequestFailed, Router, RouterConfig,
                                   ServingFleet)
from deepspeed_tpu.telemetry.registry import MetricRegistry

VOCAB, SEQ = 97, 64
V2CFG = {"dtype": "fp32",
         "state_manager": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 64,
                           "kv_block_size": 8, "max_q_per_seq": 16}}
# jitted-step cache shared across every engine in this module: the fleet
# tests construct many fleets, and each program only needs to compile once
MODULE_STEPS = {}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)


@pytest.fixture(scope="module")
def params(cfg):
    eng = _engine(cfg)
    return eng.params


def _engine(cfg, params=None):
    return InferenceEngineV2(cfg, config=V2CFG, params=params, seed=0,
                             steps_cache=MODULE_STEPS)


@pytest.fixture(scope="module")
def workload():
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, size=int(rng.integers(4, 16)))
               .astype(np.int32) for _ in range(8)]
    budgets = [int(b) for b in rng.integers(6, 14, size=8)]
    return prompts, budgets


@pytest.fixture(scope="module")
def reference(cfg, params, workload):
    prompts, budgets = workload
    return _engine(cfg, params).generate(prompts, max_new_tokens=budgets)


def make_fleet(cfg, params, fleet_cfg):
    """Fleet whose replicas share MODULE_STEPS (compile once per module)
    and one registry (per-replica telemetry labels)."""
    reg = MetricRegistry()

    def factory(name):
        ecfg = dict(V2CFG)
        ecfg["telemetry"] = {"replica": name}
        return InferenceEngineV2(cfg, ecfg, params=params,
                                 steps_cache=MODULE_STEPS,
                                 telemetry_registry=reg)
    return ServingFleet(engine_factory=factory, config=fleet_cfg,
                        registry=reg)


# ---------------------------------------------------------------------------
# faults.py introspection (satellite)
# ---------------------------------------------------------------------------

class TestFaultsIntrospection:
    def test_fired_armed_sites_and_reset(self):
        faults.inject("replica.mid_decode", "exc", count=2)
        faults.inject("router.dispatch", "exc")
        assert faults.armed("replica.mid_decode") == 2
        assert faults.armed() == 3
        with pytest.raises(faults.InjectedFault):
            faults.fire("replica.mid_decode")
        assert faults.fired("replica.mid_decode") == 1
        assert faults.fired() == 1
        snap = faults.sites()
        assert snap["replica.mid_decode"] == {"armed": 1, "fired": 1}
        assert snap["router.dispatch"] == {"armed": 1, "fired": 0}
        faults.reset()
        assert faults.fired() == 0 and faults.armed() == 0
        assert faults.sites() == {}
        faults.fire("replica.mid_decode")      # disarmed: no-op

    def test_fired_count_survives_one_shot_disarm(self):
        faults.inject("admission.decide", "exc")
        with pytest.raises(faults.InjectedFault):
            faults.fire("admission.decide")
        faults.fire("admission.decide")        # disarmed now
        assert faults.fired("admission.decide") == 1
        assert faults.armed("admission.decide") == 0


# ---------------------------------------------------------------------------
# router: pinned backoff, policies
# ---------------------------------------------------------------------------

def _mk_router(reg=None, **cfg):
    return Router(RouterConfig(**cfg), clock=time.monotonic,
                  registry=reg or MetricRegistry())


class _FakeReplica:
    def __init__(self, name, state="healthy"):
        self.name = name
        self.state = state
        self.enqueued = []

    def enqueue(self, req):
        self.enqueued.append(req)


class TestRouterBackoff:
    def test_backoff_schedule_pinned_by_seed(self):
        """The retry schedule is fully deterministic: same seed -> the
        exact delays, matching the documented formula."""
        cfg = dict(seed=7, backoff_base_s=0.05, backoff_factor=2.0,
                   backoff_max_s=2.0, backoff_jitter=0.5)
        r = _mk_router(**cfg)
        want_rng = np.random.default_rng(7)
        for k in range(1, 9):
            want = (min(2.0, 0.05 * 2.0 ** (k - 1))
                    * (1.0 + 0.5 * float(want_rng.random())))
            assert r.backoff(k) == pytest.approx(want, rel=0, abs=0)
        r2 = _mk_router(**cfg)
        r3 = _mk_router(**cfg)
        assert [r2.backoff(k) for k in range(1, 6)] == \
            [r3.backoff(k) for k in range(1, 6)]

    def test_backoff_caps_at_max(self):
        r = _mk_router(seed=0, backoff_base_s=0.1, backoff_factor=10.0,
                       backoff_max_s=0.5, backoff_jitter=0.0)
        assert r.backoff(1) == pytest.approx(0.1)
        assert r.backoff(4) == pytest.approx(0.5)
        assert r.backoff(9) == pytest.approx(0.5)

    def test_retry_budget_exhaustion_is_typed(self):
        """fail_attempt past max_retries lands in router.failed as a
        RequestFailed carrying reason + attempts — the not-a-hang
        contract at the router level."""
        r = _mk_router(max_retries=2, backoff_base_s=0.0,
                       backoff_jitter=0.0)
        req = FleetRequest(index=5, prompt=np.zeros(4, np.int32),
                           max_new_tokens=4)
        r.submit(req)
        rep = _FakeReplica("r0")
        for attempt in range(3):
            (got,) = r.take_dispatchable(time.monotonic() + 10)
            assert got is req
            r.dispatch(req, rep, now=0.0)
            r.fail_attempt(req, now=0.0, reason="dispatch_error")
        assert 5 in r.failed
        err = r.failed[5]
        assert isinstance(err, RequestFailed)
        assert err.reason == "dispatch_error" and err.attempts == 3
        assert r.settled() is False or not r.pending  # nothing re-queued


class TestRouterPolicies:
    def test_least_outstanding_balances(self):
        r = _mk_router()
        a, b = _FakeReplica("r0"), _FakeReplica("r1")
        req0 = FleetRequest(index=0, prompt=np.zeros(10, np.int32),
                            max_new_tokens=10)
        r.submit(req0)
        r.dispatch(req0, a, now=0.0)
        req1 = FleetRequest(index=1, prompt=np.zeros(4, np.int32),
                            max_new_tokens=4)
        assert r.pick(req1, [a, b]) is b      # r0 carries 20 tokens
        assert r.outstanding_tokens("r0") == 20
        assert r.outstanding_tokens("r1") == 0

    def test_round_robin_cycles(self):
        r = _mk_router(policy="round_robin")
        reps = [_FakeReplica(f"r{i}") for i in range(3)]
        req = FleetRequest(index=0, prompt=np.zeros(4, np.int32),
                           max_new_tokens=4)
        picks = [r.pick(req, reps).name for _ in range(6)]
        assert picks == ["r0", "r1", "r2", "r0", "r1", "r2"]

    def test_prefix_affinity_residency_and_fallback(self):
        """prefix_affinity routes on ACTUAL radix residency (PR 15, PR 7
        stub closed): the replica whose engine reports the longest cached
        prefix wins; probe-less replicas report 0 and the policy degrades
        to deterministic least-outstanding routing."""
        class _Eng:
            def __init__(self, resident):
                self._n = resident

            def prefix_cached_tokens(self, prompt):
                return min(self._n, len(prompt))
        r = _mk_router(policy="prefix_affinity")
        reps = [_FakeReplica(f"r{i}") for i in range(3)]
        reps[1].engine = _Eng(16)
        reps[2].engine = _Eng(8)
        p = np.arange(20, dtype=np.int32)
        reqs = [FleetRequest(index=i, prompt=p.copy(), max_new_tokens=4)
                for i in range(4)]
        picks = {r.pick(q, reps).name for q in reqs}
        assert picks == {"r1"}          # most resident prefix wins
        # the favorite dying -> next-best survivor, never an error
        healthy = [x for x in reps if x.name != "r1"]
        assert r.pick(reqs[0], healthy).name == "r2"
        # cache-cold/probe-less fleet: deterministic fallback pick
        bare = [_FakeReplica(f"b{i}") for i in range(3)]
        assert r.pick(reqs[0], bare).name == "b0"

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="unknown routing policy"):
            _mk_router(policy="nope")
        assert set(POLICIES) >= {"least_outstanding_tokens", "round_robin",
                                 "prefix_affinity"}

    def test_no_healthy_replicas_raises(self):
        r = _mk_router()
        req = FleetRequest(index=0, prompt=np.zeros(4, np.int32),
                           max_new_tokens=4)
        with pytest.raises(NoHealthyReplicas):
            r.pick(req, [])


# ---------------------------------------------------------------------------
# admission controller: hysteresis, rejection, chaos site
# ---------------------------------------------------------------------------

class _TickClock:
    """Deterministic clock for the admission controller: the test advances
    ``t`` explicitly, so kv-failure RATES (per second) are exact."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt
        return self.t


class TestAdmission:
    def _ctl(self, **kw):
        base = dict(high_queue_depth=10, low_queue_depth=3,
                    high_kv_failures_per_s=1e9,
                    low_kv_failures_per_s=0.0, retry_after_s=0.1)
        base.update(kw)
        clk = _TickClock()
        return AdmissionController(AdmissionConfig(**base),
                                   registry=MetricRegistry(),
                                   clock=clk), clk

    def test_hysteresis_band_does_not_flap(self):
        ac, clk = self._ctl()
        assert ac.update(5) is False
        clk.tick()
        assert ac.update(11) is True          # trips above high
        # hovering INSIDE the band keeps the current state — no flapping
        for depth in (9, 5, 8, 4, 10):
            clk.tick()
            assert ac.update(depth) is True
        clk.tick()
        assert ac.update(3) is False          # releases at/below low
        for depth in (5, 9, 10):              # inside band again: stays off
            clk.tick()
            assert ac.update(depth) is False

    def test_kv_failure_rate_trips_shedding(self):
        ac, clk = self._ctl(high_kv_failures_per_s=5.0,
                            low_kv_failures_per_s=1.0)
        # 1 s ticks: rate == delta
        assert ac.update(0, kv_failures_total=0.0) is False
        clk.tick()
        assert ac.update(0, kv_failures_total=3.0) is False   # 3/s < 5
        clk.tick()
        assert ac.update(0, kv_failures_total=10.0) is True   # 7/s >= 5
        # queue is fine but the rate must drop below low to release
        clk.tick()
        assert ac.update(0, kv_failures_total=14.0) is True   # 4/s
        clk.tick()
        assert ac.update(0, kv_failures_total=14.5) is False  # 0.5/s

    def test_kv_threshold_normalized_by_elapsed_time(self):
        """The PR 8 finding: the same counter delta over a STRETCHED tick
        (exactly what a loaded dispatcher produces) is a lower rate and
        must NOT trip — and a short tick with the same delta must."""
        ac, clk = self._ctl(high_kv_failures_per_s=5.0,
                            low_kv_failures_per_s=1.0)
        ac.update(0, kv_failures_total=0.0)
        clk.tick(4.0)                         # slow tick: 12 over 4 s = 3/s
        assert ac.update(0, kv_failures_total=12.0) is False
        clk.tick(0.5)                         # fast tick: 12 over .5 s = 24/s
        assert ac.update(0, kv_failures_total=24.0) is True

    def test_subsecond_ticks_use_minimum_rate_window(self):
        """Dispatcher ticks are EVENT-driven and can land back-to-back:
        one isolated failure between two <1 ms ticks must not read as an
        instantaneous thousands/s burst and trip fleet-wide shedding —
        the rate is measured over at least ``rate_window_s``."""
        ac, clk = self._ctl(high_kv_failures_per_s=5.0,
                            low_kv_failures_per_s=1.0)
        assert ac.update(0, kv_failures_total=0.0) is False
        clk.tick(0.001)                      # back-to-back event tick
        assert ac.update(0, kv_failures_total=1.0) is False  # not 1000/s
        clk.tick(0.3)                        # window matures: ~3.3/s < 5
        assert ac.update(0, kv_failures_total=1.0) is False
        # a sustained burst still trips once its window matures
        clk.tick(0.3)
        assert ac.update(0, kv_failures_total=4.0) is True   # 10/s

    def test_legacy_per_tick_keys_rejected(self):
        with pytest.raises(ValueError, match="per_s"):
            AdmissionConfig(high_kv_failures_per_tick=5.0)
        with pytest.raises(ValueError, match="rate_window_s"):
            AdmissionConfig(rate_window_s=0.0)

    def test_rejection_counts_and_retry_after(self):
        ac, clk = self._ctl()
        req = FleetRequest(index=0, prompt=np.zeros(4, np.int32),
                           max_new_tokens=4)
        ok, ra = ac.decide(req)
        assert ok and ra == 0.0
        clk.tick()
        ac.update(11)
        ok, ra = ac.decide(req)
        assert not ok and ra == pytest.approx(0.1)
        assert req.rejections == 1
        assert ac.c_rejections.value() == 1.0
        assert ac.g_shedding.value() == 1.0

    def test_inverted_band_rejected(self):
        with pytest.raises(ValueError, match="hysteresis band inverted"):
            self._ctl(low_queue_depth=20)

    def test_decide_fires_chaos_site(self):
        ac, _ = self._ctl()
        req = FleetRequest(index=0, prompt=np.zeros(4, np.int32),
                           max_new_tokens=4)
        faults.inject("admission.decide", "exc")
        with pytest.raises(faults.InjectedFault):
            ac.decide(req)
        assert faults.fired("admission.decide") == 1

    def test_fleet_fails_open_on_admission_fault(self, cfg, params,
                                                 workload, reference):
        """An injected admission failure must not gate correctness: the
        fleet admits (fail open) and every request completes."""
        prompts, budgets = workload
        faults.inject("admission.decide", "exc", count=3)
        fleet = make_fleet(cfg, params, {"num_replicas": 1})
        try:
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=300)
        finally:
            fleet.shutdown()
        for o, want in zip(outs, reference):
            np.testing.assert_array_equal(o, want)


# ---------------------------------------------------------------------------
# engine-level drain/export hooks (single-threaded, deterministic)
# ---------------------------------------------------------------------------

class TestEngineMigrationHooks:
    def test_death_export_and_requeue_token_exact(self, cfg, params,
                                                  workload, reference):
        """Replica death mid-decode: export the host state, re-serve the
        pending requests on a fresh engine, stitch — byte-equal to the
        no-failure run."""
        prompts, budgets = workload
        e1 = _engine(cfg, params)
        faults.inject("replica.mid_decode", "exc", after=2)
        with pytest.raises(faults.InjectedFault):
            e1.generate(prompts, max_new_tokens=budgets)
        assert faults.fired("replica.mid_decode") == 1
        completed, pending = e1.export_pending_requests()
        assert len(completed) + len(pending) == len(prompts)
        faults.reset()
        e2 = _engine(cfg, params)
        outs = e2.generate([p["prompt"] for p in pending],
                           max_new_tokens=[p["max_new_tokens"]
                                           for p in pending])
        final = dict(completed)
        for rec, out in zip(pending, outs):
            pre = np.asarray(rec["generated"], np.int32)
            final[rec["index"]] = (np.concatenate([pre, out])
                                   if pre.size else out)
        for i, want in enumerate(reference):
            np.testing.assert_array_equal(final[i], want)

    def test_death_after_materialize_folds_progress(self, cfg, params):
        """With an EOS configured the engine materializes every 16 steps;
        a death later than that must export a non-empty generated prefix
        FOLDED into the prompt (the survivor re-prefills, it does not
        re-decode) — and the stitched output still matches."""
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, VOCAB, size=6).astype(np.int32)
                   for _ in range(2)]
        budgets = [40, 40]
        eos = VOCAB + 7                        # never sampled: only enables
        #                                        the periodic materialize
        ref = _engine(cfg, params).generate(prompts, max_new_tokens=budgets,
                                            eos_token_id=eos)
        e1 = _engine(cfg, params)
        # rounds: admit+first-token, 16-step burst (materialize), die at
        # the third round's top — 17 tokens/request are host-known by then
        faults.inject("replica.mid_decode", "exc", after=2)
        with pytest.raises(faults.InjectedFault):
            e1.generate(prompts, max_new_tokens=budgets, eos_token_id=eos)
        completed, pending = e1.export_pending_requests()
        assert pending, "expected in-flight requests at the injected death"
        assert any(len(p["generated"]) > 0 for p in pending), \
            "death past a materialize point must export host-known progress"
        for rec in pending:
            orig = prompts[rec["index"]]
            got = rec["prompt"]
            np.testing.assert_array_equal(got[:len(orig)], orig)
            np.testing.assert_array_equal(
                got[len(orig):], np.asarray(rec["generated"], np.int32))
        faults.reset()
        e2 = _engine(cfg, params)
        outs = e2.generate([p["prompt"] for p in pending],
                           max_new_tokens=[p["max_new_tokens"]
                                           for p in pending],
                           eos_token_id=eos)
        final = dict(completed)
        for rec, out in zip(pending, outs):
            pre = np.asarray(rec["generated"], np.int32)
            final[rec["index"]] = (np.concatenate([pre, out])
                                   if pre.size else out)
        for i, want in enumerate(ref):
            np.testing.assert_array_equal(final[i], want)

    def test_drain_interrupts_and_engine_reusable(self, cfg, params,
                                                  workload):
        prompts, budgets = workload
        eng = _engine(cfg, params)
        t = threading.Timer(0.15, eng.request_drain)
        t.start()
        with pytest.raises(EngineDrained):
            eng.generate(prompts, max_new_tokens=[40] * len(prompts))
        t.join()
        completed, pending = eng.export_pending_requests()
        assert len(completed) + len(pending) == len(prompts)
        # drained engine: sequences flushed, reusable after clear_drain
        assert eng.state.free_sequence_slots == \
            V2CFG["state_manager"]["max_tracked_sequences"]
        eng.clear_drain()
        outs = eng.generate(prompts[:2], max_new_tokens=4)
        assert len(outs) == 2

    def test_shared_steps_cache_namespaced_by_config(self, cfg, params):
        """One shared cache dict handed to differently-configured engines
        must give them DISJOINT sub-caches: the program keys encode only
        schedule shapes, the model/block-size live in the closures."""
        shared = {}
        e8 = InferenceEngineV2(cfg, config=V2CFG, params=params,
                               steps_cache=shared)
        cfg16 = {**V2CFG, "state_manager": {**V2CFG["state_manager"],
                                            "kv_block_size": 16}}
        e16 = InferenceEngineV2(cfg, config=cfg16, params=params,
                                steps_cache=shared)
        assert e8._steps is not e16._steps
        assert len(shared) == 2               # two config fingerprints
        # same config -> same sub-cache (the fleet-sharing fast path)
        e8b = InferenceEngineV2(cfg, config=V2CFG, params=params,
                                steps_cache=shared)
        assert e8b._steps is e8._steps
        # and both engines decode correctly against the shared dict
        rng = np.random.default_rng(2)
        p = [rng.integers(0, VOCAB, size=8).astype(np.int32)]
        np.testing.assert_array_equal(
            e8.generate(p, max_new_tokens=6)[0],
            e16.generate(p, max_new_tokens=6)[0])

    def test_clean_generate_exports_nothing(self, cfg, params, workload):
        prompts, budgets = workload
        eng = _engine(cfg, params)
        eng.generate(prompts[:2], max_new_tokens=4)
        assert eng.export_pending_requests() == ({}, [])


# ---------------------------------------------------------------------------
# fleet end-to-end (threads, real engines)
# ---------------------------------------------------------------------------

class TestFleetServing:
    def test_matches_single_engine(self, cfg, params, workload, reference):
        prompts, budgets = workload
        with make_fleet(cfg, params, {"num_replicas": 2}) as fleet:
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=300)
            for o, want in zip(outs, reference):
                np.testing.assert_array_equal(o, want)
            assert len(fleet.request_log) == len(prompts)
            # per-replica telemetry labels over the SHARED registry
            m = fleet.registry._metrics["serving_requests_total"]
            labels = {s[0].get("replica") for s in m.samples()}
            assert labels <= {"r0", "r1"} and labels

    def test_replica_death_mid_decode_token_exact(self, cfg, params,
                                                  workload, reference):
        """The acceptance-critical chaos leg: kill one replica mid-decode
        (no respawn), survivors absorb the migrated requests, and every
        output is byte-equal to the no-failure run — nothing lost,
        nothing duplicated."""
        prompts, budgets = workload
        faults.inject("replica.mid_decode", "exc", after=3)
        with make_fleet(cfg, params,
                        {"num_replicas": 2, "respawn": False}) as fleet:
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=300)
            reg = fleet.registry._metrics
            assert faults.fired("replica.mid_decode") == 1
            assert reg["fleet_replica_deaths_total"].value(
                reason="replica_death") == 1.0
            assert reg["requests_migrated_total"].value() > 0
            states = sorted(r.state for r in fleet.replicas.values())
            assert states == ["dead", "healthy"]
            # exactly one completion per request, token-exact
            assert len(fleet.router.done) == len(prompts)
            assert len(fleet.request_log) == len(prompts)
            for o, want in zip(outs, reference):
                np.testing.assert_array_equal(o, want)

    def test_death_respawns_with_warm_cache(self, cfg, params, workload,
                                            reference):
        prompts, budgets = workload
        faults.inject("replica.mid_decode", "exc", after=3)
        with make_fleet(cfg, params,
                        {"num_replicas": 2, "respawn": True,
                         "max_respawns": 1}) as fleet:
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=300)
            reg = fleet.registry._metrics
            assert reg["fleet_respawns_total"].value() == 1.0
            assert all(r.state == "healthy"
                       for r in fleet.replicas.values())
            assert reg["fleet_recovery_ms"].count() == 1
            for o, want in zip(outs, reference):
                np.testing.assert_array_equal(o, want)

    def test_respawn_factory_exception_books_dead_not_unwind(
            self, cfg, params, workload, reference):
        """PR 8 review finding: a respawn-factory exception must book THE
        replica dead and keep the dispatcher alive — a fleet that cannot
        rebuild one replica degrades to N-1, it does not unwind the whole
        control plane."""
        prompts, budgets = workload
        faults.inject("replica.mid_decode", "exc", after=3)
        faults.inject("fleet.respawn_factory", "exc")
        with make_fleet(cfg, params,
                        {"num_replicas": 2, "respawn": True,
                         "max_respawns": 2}) as fleet:
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=300)
            reg = fleet.registry._metrics
            assert faults.fired("fleet.respawn_factory") == 1
            assert reg["fleet_replica_deaths_total"].value(
                reason="respawn_failed") == 1.0
            states = sorted(r.state for r in fleet.replicas.values())
            assert states == ["dead", "healthy"]
            # no lost work, no unwind: the survivor finished everything
            for o, want in zip(outs, reference):
                np.testing.assert_array_equal(o, want)

    def test_drain_replica_migrates_and_respawns(self, cfg, params,
                                                 workload):
        prompts = workload[0] * 2
        budgets = [40] * len(prompts)
        ref = _engine(cfg, params).generate(prompts, max_new_tokens=budgets)
        with make_fleet(cfg, params, {"num_replicas": 2}) as fleet:
            t = threading.Timer(0.01, fleet.drain_replica, args=("r0",))
            t.start()
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=300)
            t.join()
            reg = fleet.registry._metrics
            assert reg["fleet_replica_deaths_total"].value(
                reason="drain") == 1.0
            # drain migrations burn no retry budget
            assert reg["router_retries_total"].value(reason="drain") == 0.0
            assert fleet.replicas["r0"].state == "healthy"   # respawned
            for o, want in zip(outs, ref):
                np.testing.assert_array_equal(o, want)

    def test_retry_budget_exhaustion_raises_typed(self, cfg, params,
                                                  workload):
        """Every dispatch faulted: the request must surface RequestFailed
        with the exact attempt count — and within bounded wall time."""
        prompts, _ = workload
        faults.inject("router.dispatch", "exc", count=99)
        fleet = make_fleet(cfg, params,
                           {"num_replicas": 1,
                            "router": {"max_retries": 2,
                                       "backoff_base_s": 0.01,
                                       "backoff_max_s": 0.05}})
        try:
            t0 = time.monotonic()
            with pytest.raises(RequestFailed) as ei:
                fleet.serve(prompts[:1], max_new_tokens=4, max_wall_s=60)
            assert time.monotonic() - t0 < 30
            assert ei.value.reason == "dispatch_error"
            assert ei.value.attempts == 3          # 1 first + 2 retries
            assert ei.value.index == 0
            reg = fleet.registry._metrics
            assert reg["router_retries_total"].value(
                reason="dispatch_error") == 2.0
        finally:
            fleet.shutdown()

    def test_poison_request_fails_request_not_replica(self, cfg, params,
                                                      workload, reference):
        """A client input error (context overflow) must surface as a typed
        RequestFailed for THAT request — the replicas stay healthy, burn no
        respawn budget, and the valid requests around it still complete
        token-exact."""
        prompts, budgets = workload
        poison = np.zeros(10, np.int32)
        with make_fleet(cfg, params, {"num_replicas": 2}) as fleet:
            outs = fleet.serve(list(prompts) + [poison],
                               max_new_tokens=list(budgets) + [SEQ],
                               raise_on_failure=False, max_wall_s=300)
            err = fleet.last_failures[len(prompts)]
            assert isinstance(err, RequestFailed)
            assert err.reason == "invalid_request"
            assert outs[len(prompts)] is None
            reg = fleet.registry._metrics
            assert sum(v for _, v in
                       reg["fleet_replica_deaths_total"].samples()) == 0
            assert all(r.state == "healthy"
                       for r in fleet.replicas.values())
            for o, want in zip(outs[:len(prompts)], reference):
                np.testing.assert_array_equal(o, want)

    def test_open_loop_arrivals_token_exact(self, cfg, params, workload,
                                            reference):
        prompts, budgets = workload
        arrivals = np.linspace(0.0, 0.5, len(prompts))
        with make_fleet(cfg, params, {"num_replicas": 2}) as fleet:
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               arrival_times=arrivals, max_wall_s=300)
            for o, want in zip(outs, reference):
                np.testing.assert_array_equal(o, want)
            # arrivals were honored: nothing completed before it arrived
            for rec in fleet.request_log:
                assert rec["t_done"] >= rec["t_arrival"]

    def test_replica_state_gauge_one_hot(self, cfg, params, workload):
        prompts, budgets = workload
        with make_fleet(cfg, params,
                        {"num_replicas": 2, "respawn": False}) as fleet:
            g = fleet.registry._metrics["fleet_replica_state"]
            for name in ("r0", "r1"):
                vec = {s: g.value(replica=name, state=s)
                       for s in ("spawning", "healthy", "draining", "dead")}
                assert vec["healthy"] == 1.0 and sum(vec.values()) == 1.0
            faults.inject("replica.mid_decode", "exc", after=2)
            fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=300)
            dead = [n for n in ("r0", "r1")
                    if g.value(replica=n, state="dead") == 1.0]
            assert len(dead) == 1
            assert g.value(replica=dead[0], state="healthy") == 0.0

    def test_preemption_notice_drains_fleet(self, cfg, params, workload):
        """A preemption notice mid-serve drains every replica; serve()
        surfaces FleetDrained with completed outputs + migration-folded
        pending requests (original arrivals intact) for a successor."""
        from deepspeed_tpu.runtime.resilience import PreemptionHandler
        prompts = workload[0] * 2
        budgets = [40] * len(prompts)
        handler = PreemptionHandler(signals=())
        reg = MetricRegistry()

        def factory(name):
            ecfg = dict(V2CFG)
            ecfg["telemetry"] = {"replica": name}
            return InferenceEngineV2(cfg, ecfg, params=params,
                                     steps_cache=MODULE_STEPS,
                                     telemetry_registry=reg)
        fleet = ServingFleet(engine_factory=factory,
                             config={"num_replicas": 2}, registry=reg,
                             preemption_handler=handler)
        try:
            t = threading.Timer(0.02, handler.request, args=("manual",))
            t.start()
            with pytest.raises(FleetDrained) as ei:
                fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=300)
            t.join()
            drained = ei.value
            indices = set(drained.completed) | {
                r.index for r in drained.pending}
            assert indices == set(range(len(prompts)))
            assert all(r.state == "dead" for r in fleet.replicas.values())
        finally:
            fleet.shutdown()


# ---------------------------------------------------------------------------
# heartbeat warm-up gate (PR 8 review finding)
# ---------------------------------------------------------------------------

class _ColdStartEngine:
    """Fake engine whose FIRST generate stalls ``cold_s`` (modelling the
    on-the-fly XLA compile — no heartbeats land during it) and whose later
    generates stall ``warm_s``."""

    def __init__(self, cold_s, warm_s=0.0):
        self.cold_s = cold_s
        self.warm_s = warm_s
        self.calls = 0
        self.heartbeat_fn = lambda: None

    def clear_drain(self):
        pass

    def request_drain(self):
        pass

    def export_pending_requests(self):
        return {}, []

    def generate(self, prompts, max_new_tokens):
        delay = self.cold_s if self.calls == 0 else self.warm_s
        self.calls += 1
        time.sleep(delay)
        self.heartbeat_fn()
        return [np.arange(int(m), dtype=np.int32) for m in max_new_tokens]


class TestHeartbeatWarmupGate:
    def _fleet(self, engine, **over):
        cfg = dict(num_replicas=1, respawn=False,
                   heartbeat_deadline_s=0.2, warmup_deadline_s=5.0,
                   poll_interval_s=0.005)
        cfg.update(over)
        return ServingFleet(engine_factory=lambda name: engine, config=cfg,
                            registry=MetricRegistry())

    def test_cold_first_call_survives_steady_deadline(self):
        """A first generate stalling WAY past heartbeat_deadline_s (the
        compile) must complete under the warm-up budget — a cold replica
        is never booked dead (the finding bench papered over with 120s)."""
        eng = _ColdStartEngine(cold_s=0.6)
        with self._fleet(eng) as fleet:
            outs = fleet.serve([np.zeros(4, np.int32)], max_new_tokens=4,
                               max_wall_s=60)
            assert len(outs[0]) == 4
            reg = fleet.registry._metrics
            assert reg["fleet_replica_deaths_total"].value(
                reason="heartbeat_timeout") == 0.0
            assert fleet.replicas["r0"].warmed

    def test_respawn_with_populated_shared_cache_is_warm(self):
        """A respawned incarnation reusing an already-populated shared
        compile cache performs no first-call compile: it must run under
        the steady-state deadline immediately — the warm-up budget would
        hide a wedged respawn (and its queued requests) for
        warmup_deadline_s with no compile to excuse it."""
        eng = _ColdStartEngine(cold_s=0.0)
        with self._fleet(eng) as fleet:
            rep = fleet.replicas["r0"]
            # the cache maps fingerprint → program dict; engines create
            # their sub-dict EAGERLY at construction, so an empty sub-dict
            # means the first incarnation died before compiling anything —
            # the replacement still pays the compile and must stay on the
            # warm-up budget
            fleet._steps_cache["fp"] = {}
            fleet._spawn(rep, is_respawn=True)
            assert not rep.warmed
            fleet._steps_cache["fp"]["sig"] = object()   # compiled program
            fleet._spawn(rep, is_respawn=True)
            assert rep.warmed
            fleet._steps_cache.clear()             # torn cache: assume cold
            fleet._spawn(rep, is_respawn=True)
            assert not rep.warmed

    def test_warmed_replica_still_deadlined(self):
        """The gate covers ONLY the cold call: once warm, the same stall
        is a real hang and the steady-state deadline books it dead."""
        eng = _ColdStartEngine(cold_s=0.0, warm_s=0.8)
        with self._fleet(eng) as fleet:
            fleet.serve([np.zeros(4, np.int32)], max_new_tokens=4,
                        max_wall_s=60)          # warms the incarnation
            outs = fleet.serve([np.zeros(4, np.int32)], max_new_tokens=4,
                               raise_on_failure=False, max_wall_s=60)
            assert outs == [None]
            reg = fleet.registry._metrics
            assert reg["fleet_replica_deaths_total"].value(
                reason="heartbeat_timeout") == 1.0
            assert fleet.last_failures[0].reason == "no_healthy_replicas"


# ---------------------------------------------------------------------------
# chaos under open-loop load + lint wiring
# ---------------------------------------------------------------------------

class TestBenchFleetLeg:
    def test_chaos_leg_goodput_degrades_gracefully(self, cfg, params,
                                                   workload, reference):
        """The acceptance criterion, CPU-sized: kill 1 of 2 replicas
        mid-load with respawn off; post-kill goodput stays >=
        0.7*(N-1)/N of the healthy fleet's and every request completes
        exactly once (the killed replica's in-flight requests migrate)."""
        import threading

        prompts, budgets = workload
        prompts, budgets = prompts * 2, budgets * 2     # enough load to
        #                                                 straddle the kill
        # under capacity for (N-1) replicas: the survivors must absorb the
        # offered load, so post-recovery goodput ~ offered rate — CPU-sized
        # "degrades gracefully, does not cliff"
        rate, n = 10.0, 2
        # the SAME seeded open-loop arrivals for the healthy and the
        # killed fleet
        arrivals = np.cumsum(np.random.default_rng(11).exponential(
            1.0 / rate, size=len(prompts)))
        with make_fleet(cfg, params, {"num_replicas": n}) as fleet:
            fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=300)
            t0 = fleet.clock()
            fleet.serve(prompts, max_new_tokens=budgets,
                        arrival_times=arrivals, max_wall_s=300)
            healthy = sum(r["generated_tokens"]
                          for r in fleet.request_log) \
                / (fleet.clock() - t0)

        kill_at = 0.35 * float(arrivals[-1])            # mid-load
        timer = threading.Timer(
            kill_at, lambda: faults.inject("replica.mid_decode", "exc"))
        with make_fleet(cfg, params, {
                "num_replicas": n, "respawn": False,
                "warmup_deadline_s": 600.0, "heartbeat_deadline_s": 60.0,
                "router": {"max_retries": n + 1}}) as fleet:
            fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=300)
            t0 = fleet.clock()
            timer.start()
            try:
                outs = fleet.serve(prompts, max_new_tokens=budgets,
                                   arrival_times=arrivals, max_wall_s=300)
            finally:
                timer.cancel()      # (the autouse fixture resets faults)
            t_end = fleet.clock()
        assert all(o is not None for o in outs), "fleet lost a request"
        reg = fleet.registry._metrics
        log = fleet.request_log
        t_kill = t0 + kill_at
        # recovered window: from the first post-kill completion to the end
        first_after = min(r["t_done"] for r in log if r["t_done"] > t_kill)
        goodput_after = sum(r["generated_tokens"] for r in log
                            if r["t_done"] >= first_after) \
            / max(t_end - first_after, 1e-3)
        assert reg["fleet_replica_deaths_total"].value(
            reason="replica_death") == 1.0
        assert reg["requests_migrated_total"].value() > 0
        assert len(log) == len(prompts)
        assert goodput_after >= 0.7 * (n - 1) / n * healthy

    def test_check_no_sync_covers_router_loop(self):
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "check_no_sync", os.path.join(
                os.path.dirname(__file__), os.pardir, "scripts",
                "check_no_sync.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        paths = [p for p, _, _, _ in mod.SCAN_TARGETS]
        assert mod.ROUTER_PATH in paths and mod.FLEET_PATH in paths
        assert "dispatch" in mod.ROUTER_FUNCS
        assert "_tick" in mod.FLEET_FUNCS
        assert mod.main([]) == 0

    def test_check_no_sync_catches_router_violation(self, tmp_path):
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "check_no_sync", os.path.join(
                os.path.dirname(__file__), os.pardir, "scripts",
                "check_no_sync.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        bad = tmp_path / "router.py"
        bad.write_text(
            "class Router:\n"
            "    def dispatch(self, req, replica, now):\n"
            "        jax.block_until_ready(req.prompt)\n")
        v = mod.check_file(str(bad), mod.ROUTER_FUNCS,
                           mod.TRANSFER_PATTERN, mod.ALLOW_PATTERN)
        assert len(v) == 1 and "dispatch" in v[0]
