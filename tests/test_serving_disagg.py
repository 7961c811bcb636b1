"""Disaggregated prefill/decode fleet suite: KV block handoff,
phase-aware routing with residency probes, cross-request batched
speculative decode, and signal-driven pool autoscaling.

The invariants, in order of importance:

1. **Byte-identical outputs** — a disaggregated serve (prefill pool +
   handoff + decode pool, any number of mid-transfer aborts) produces
   exactly the tokens a unified fleet / single engine produces.
2. **No refcount leaks** — handoff pins are released on completion and
   on every abort path; the pool allocator and radix invariants hold
   after chaos.
3. **Batched spec is an optimization, not a decoder** — one
   cross-request dispatch is token-identical to per-request dispatches
   AND to non-spec greedy, with strictly fewer dispatches per token.
4. **Autoscaler moves are warm** — a role flip respawns against the
   shared compile cache; the recompile watchdog pins that no new
   program is compiled by one, and no request is lost or duplicated
   across a flip.
"""

import math

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import GPTConfig
from deepspeed_tpu.runtime import faults
from deepspeed_tpu.serving import (AutoscaleConfig, FleetRequest,
                                   PoolAutoscaler, Router, RouterConfig,
                                   ServingFleet)
from deepspeed_tpu.telemetry.registry import MetricRegistry

VOCAB, SEQ = 97, 64
V2CFG = {"dtype": "fp32",
         "state_manager": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 64,
                           "kv_block_size": 8, "max_q_per_seq": 16,
                           "prefix_cache": True}}
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
    return _engine(cfg).params


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
    """Disagg-capable fleet: replicas share MODULE_STEPS and one registry;
    the engine config carries the prefix cache the handoff pins against."""
    reg = MetricRegistry()

    def factory(name):
        ecfg = {k: (dict(v) if isinstance(v, dict) else v)
                for k, v in V2CFG.items()}
        ecfg["telemetry"] = {"replica": name}
        return InferenceEngineV2(cfg, ecfg, params=params,
                                 steps_cache=MODULE_STEPS,
                                 telemetry_registry=reg)
    return ServingFleet(engine_factory=factory, config=fleet_cfg,
                        registry=reg)


DISAGG_CFG = {"num_replicas": 2, "prefill_replicas": 1,
              "disaggregated": True, "respawn": False,
              "warmup_deadline_s": 600.0, "heartbeat_deadline_s": 60.0}


def _assert_no_leaks(fleet):
    """Every replica's pool must hold references ONLY through its radix
    cache after all requests completed and slots flushed — a handoff pin
    still live would show up as a refcount the radix can't explain."""
    assert not fleet._handoffs, f"handoff pins leaked: {fleet._handoffs}"
    for rep in fleet.replicas.values():
        eng = rep.engine
        if eng is None or getattr(eng, "state", None) is None:
            continue
        state = eng.state
        if state.radix is not None:
            state.radix.check_invariants()
            radix_held = {n.block for n in state.radix._nodes()}
        else:
            radix_held = set()
        for b, refs in enumerate(state.allocator._ref):
            if refs > 0:
                assert b in radix_held, \
                    f"{rep.name}: block {b} holds {refs} refs outside " \
                    f"the radix (leaked handoff pin)"


# ---------------------------------------------------------------------------
# tentpole: disaggregated serve is byte-identical and hands KV off
# ---------------------------------------------------------------------------

class TestDisaggregatedFleet:
    def test_byte_identical_to_unified_with_handoffs(self, cfg, params,
                                                     workload, reference):
        prompts, budgets = workload
        fleet = make_fleet(cfg, params, DISAGG_CFG)
        try:
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=600)
            for out, ref in zip(outs, reference):
                assert np.array_equal(np.asarray(out), np.asarray(ref))
            reg = fleet.registry._metrics
            # every multi-token request went through exactly one handoff
            multi = sum(1 for b in budgets if b > 1)
            assert reg["fleet_handoffs_total"].value(outcome="ok") == multi
            assert reg["kv_handoff_bytes_total"].value() > 0
            # phases advanced: nothing is still in its prefill phase
            assert all(r.phase == "decode"
                       for r in fleet.router.requests.values()
                       if r.max_new_tokens > 1)
            # fleet-observed first-token time is set by the handoff
            assert all(r["t_first"] is not None for r in fleet.request_log)
            assert all(r["t_first"] <= r["t_done"]
                       for r in fleet.request_log)
            _assert_no_leaks(fleet)
        finally:
            fleet.shutdown()

    def test_roles_and_phase_dispatch(self, cfg, params, workload):
        prompts, budgets = workload
        fleet = make_fleet(cfg, params, DISAGG_CFG)
        try:
            roles = {r.name: r.role for r in fleet.replicas.values()}
            assert roles == {"r0": "prefill", "r1": "decode"}
            fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=600)
            reg = fleet.registry._metrics
            # the prefill replica served prompts, the decode replica the
            # tails: per-phase token counters prove the split happened
            tok = reg["serving_tokens_total"]
            assert tok.value(phase="prefill", replica="r0") \
                == sum(len(p) for p in prompts)
            assert tok.value(phase="decode", replica="r0") == 0
            assert tok.value(phase="decode", replica="r1") \
                >= sum(budgets) - len(prompts)
        finally:
            fleet.shutdown()

    def test_one_token_budget_skips_handoff(self, cfg, params):
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, VOCAB, size=8).astype(np.int32)
                   for _ in range(3)]
        fleet = make_fleet(cfg, params, DISAGG_CFG)
        try:
            outs = fleet.serve(prompts, max_new_tokens=1, max_wall_s=600)
            assert all(len(o) == 1 for o in outs)
            reg = fleet.registry._metrics
            assert reg["fleet_handoffs_total"].value(outcome="ok") == 0
            _assert_no_leaks(fleet)
        finally:
            fleet.shutdown()

    def test_disagg_config_validation(self, cfg, params):
        with pytest.raises(ValueError, match="prefill_replicas"):
            make_fleet(cfg, params, {"num_replicas": 2,
                                     "prefill_replicas": 2,
                                     "disaggregated": True})


# ---------------------------------------------------------------------------
# satellite: handoff.mid_transfer chaos — no leak, token-exact re-entry
# ---------------------------------------------------------------------------

class TestHandoffChaos:
    def test_mid_transfer_abort_releases_pins_token_exact(
            self, cfg, params, workload, reference):
        prompts, budgets = workload
        fleet = make_fleet(cfg, params, DISAGG_CFG)
        try:
            # warm pass (also primes the radix caches)
            fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=600)
            faults.inject("handoff.mid_transfer", "exc", count=3)
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=600)
            assert faults.fired("handoff.mid_transfer") == 3
            for out, ref in zip(outs, reference):
                assert np.array_equal(np.asarray(out), np.asarray(ref))
            reg = fleet.registry._metrics
            assert reg["fleet_handoffs_total"].value(outcome="aborted") == 3
            # aborts re-enter via the migration fold, not the retry path:
            # no retry budget burned
            assert sum(v for _, v in
                       reg["requests_migrated_total"].samples()) >= 3
            _assert_no_leaks(fleet)
        finally:
            fleet.shutdown()

    def test_replica_death_mid_serve_token_exact(
            self, cfg, params, workload, reference):
        """The real death (not just the fault site): a replica dies
        mid-serve in disaggregated mode, its requests migrate (prefill
        pool falls back to the unified policy if it emptied), and the
        survivors finish everything byte-identically."""
        prompts, budgets = workload
        fleet = make_fleet(cfg, params, {**DISAGG_CFG, "num_replicas": 3,
                                         "router": {"max_retries": 4}})
        try:
            fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=600)
            faults.inject("replica.mid_decode", "exc", after=1)
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=600)
            for out, ref in zip(outs, reference):
                assert np.array_equal(np.asarray(out), np.asarray(ref))
            reg = fleet.registry._metrics
            deaths = sum(v for _, v in
                         reg["fleet_replica_deaths_total"].samples())
            assert deaths == 1
            _assert_no_leaks(fleet)
        finally:
            fleet.shutdown()


# ---------------------------------------------------------------------------
# satellite: router handoff semantics + residency probe cache
# ---------------------------------------------------------------------------

class _ProbeEngine:
    """Counts residency probes; returns a fixed per-name residency."""

    def __init__(self, tokens):
        self.tokens = tokens
        self.probes = 0

    def prefix_cached_tokens(self, prompt):
        self.probes += 1
        return self.tokens


class _FakeReplica:
    def __init__(self, name, role=None, engine=None):
        self.name = name
        self.state = "healthy"
        self.role = role
        self.engine = engine

    def enqueue(self, req):
        pass


def _mk_router(**cfg):
    return Router(RouterConfig.parse(cfg), clock=lambda: 0.0,
                  registry=MetricRegistry())


class TestRouterHandoff:
    def _submit(self, router, n=1, phase="prefill"):
        reqs = []
        for i in range(n):
            r = FleetRequest(index=i, prompt=np.arange(8, dtype=np.int32),
                             max_new_tokens=6, phase=phase)
            router.submit(r)
            reqs.append(r)
        return reqs

    def test_handoff_folds_and_requeues(self):
        router = _mk_router(disaggregated=True)
        (req,) = self._submit(router)
        rep = _FakeReplica("p0", role="prefill")
        router.dispatch(req, rep, 0.0)
        epoch = req.epoch
        tokens = np.array([42], np.int32)
        out = router.handoff(req.index, epoch, tokens, 1.0)
        assert out is req
        assert req.phase == "decode"
        assert req.epoch == epoch + 1
        assert req.generated == [42]
        assert req.prompt[-1] == 42 and len(req.prompt) == 9
        assert req.remaining == 5
        assert req.index not in router.inflight
        assert req in router.pending
        assert not router.settled()

    def test_handoff_is_strictly_epoch_gated(self):
        """Unlike complete() (first result wins), a STALE prefill result
        must never fold into a request a live attempt owns — the live
        attempt would double-serve the folded tokens."""
        router = _mk_router(disaggregated=True)
        (req,) = self._submit(router)
        rep = _FakeReplica("p0", role="prefill")
        router.dispatch(req, rep, 0.0)
        stale = req.epoch
        router.fail_attempt(req, 0.0, "timeout")       # epoch bumps
        assert router.handoff(req.index, stale,
                              np.array([42], np.int32), 1.0) is None
        assert req.phase == "prefill" and req.generated == []

    def test_disagg_pick_routes_by_phase(self):
        router = _mk_router(disaggregated=True)
        pre = _FakeReplica("p0", role="prefill")
        dec = _FakeReplica("d0", role="decode")
        req_p = FleetRequest(index=0, prompt=np.arange(4, dtype=np.int32),
                             max_new_tokens=4, phase="prefill")
        req_d = FleetRequest(index=1, prompt=np.arange(4, dtype=np.int32),
                             max_new_tokens=4, phase="decode")
        assert router.pick(req_p, [pre, dec]) is pre
        assert router.pick(req_d, [pre, dec]) is dec
        # empty pool degrades to the unified policy over whoever is healthy
        assert router.pick(req_p, [dec]) is dec

    def test_residency_cache_probes_once_per_replica(self):
        router = _mk_router(disaggregated=True)
        engs = [_ProbeEngine(0), _ProbeEngine(16)]
        reps = [_FakeReplica("d0", role="decode", engine=engs[0]),
                _FakeReplica("d1", role="decode", engine=engs[1])]
        prompt = np.arange(16, dtype=np.int32)
        picks = []
        for i in range(10):
            req = FleetRequest(index=i, prompt=prompt, max_new_tokens=4,
                               phase="decode")
            router.submit(req)
            picks.append(router.pick(req, reps))
        # routing is O(1) per request: ten same-prompt picks cost ONE
        # probe per replica, not ten
        assert engs[0].probes == 1 and engs[1].probes == 1
        assert all(p is reps[1] for p in picks)   # residency wins
        # invalidation (migration/death/dispatch) forces a re-probe
        router.invalidate_residency("d1")
        req = FleetRequest(index=99, prompt=prompt, max_new_tokens=4,
                           phase="decode")
        router.submit(req)
        router.pick(req, reps)
        assert engs[1].probes == 2

    def test_dispatch_invalidates_target_residency(self):
        router = _mk_router(disaggregated=True)
        eng = _ProbeEngine(8)
        rep = _FakeReplica("d0", role="decode", engine=eng)
        prompt = np.arange(8, dtype=np.int32)
        r1 = FleetRequest(index=0, prompt=prompt, max_new_tokens=4,
                          phase="decode")
        router.submit(r1)
        assert router.pick(r1, [rep]) is rep
        router.dispatch(r1, rep, 0.0)     # residency about to change
        r2 = FleetRequest(index=1, prompt=prompt, max_new_tokens=4,
                          phase="decode")
        router.submit(r2)
        router.pick(r2, [rep])
        assert eng.probes == 2            # dispatch cleared the cache

    def test_probe_failure_does_not_poison_cache(self):
        class _Boom:
            probes = 0

            def prefix_cached_tokens(self, prompt):
                _Boom.probes += 1
                raise RuntimeError("probe died")

        router = _mk_router(disaggregated=True)
        rep = _FakeReplica("d0", role="decode", engine=_Boom())
        req = FleetRequest(index=0, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=4, phase="decode")
        router.submit(req)
        assert router.pick(req, [rep]) is rep     # degrades to residency 0
        assert router.residency(rep, req) == 0    # retried, still safe
        assert _Boom.probes == 2                  # failures are NOT cached


# ---------------------------------------------------------------------------
# satellite: cross-request batched speculative decode
# ---------------------------------------------------------------------------

SPEC_SM = {"max_tracked_sequences": 4, "max_ragged_batch_size": 128,
           "kv_block_size": 8, "max_q_per_seq": 32}


@pytest.fixture(scope="module")
def spec_setup():
    import dataclasses

    import jax.numpy as jnp
    tcfg = GPTConfig.llama(num_layers=2, hidden=128, heads=4,
                           vocab_size=VOCAB, max_seq_len=128, dtype=None)
    tcfg = dataclasses.replace(tcfg, dtype=jnp.float32)
    dcfg = GPTConfig.llama(num_layers=1, hidden=64, heads=2,
                           vocab_size=VOCAB, max_seq_len=128, dtype=None)
    dcfg = dataclasses.replace(dcfg, dtype=jnp.float32)
    tparams = InferenceEngineV2(
        tcfg, {"state_manager": dict(SPEC_SM), "dtype": "fp32"},
        seed=0, steps_cache=MODULE_STEPS).params
    dparams = InferenceEngineV2(
        dcfg, {"state_manager": dict(SPEC_SM), "dtype": "fp32"},
        seed=1, steps_cache=MODULE_STEPS).params
    return tcfg, tparams, dcfg, dparams


def _spec_engine(spec_setup, batch_across_requests, spec_extra=None):
    tcfg, tparams, dcfg, dparams = spec_setup
    spec = {"batch_across_requests": batch_across_requests}
    spec.update(spec_extra or {})
    return InferenceEngineV2(
        tcfg, {"state_manager": dict(SPEC_SM), "dtype": "fp32",
               "generation": {"do_sample": False}, "speculative": spec},
        params=tparams, draft_model=dcfg, draft_params=dparams,
        steps_cache=MODULE_STEPS)


class TestBatchedSpec:
    # mixed budgets: request 1 completes mid-verify (budget 4 < gamma+1
    # per outer round x outer), the rest keep decoding in the same batch
    PROMPTS_SEED, BUDGETS = 3, [9, 4, 13, 7]

    def _workload(self):
        rng = np.random.default_rng(self.PROMPTS_SEED)
        return [rng.integers(0, VOCAB, size=int(rng.integers(8, 20)))
                .astype(np.int32) for _ in range(len(self.BUDGETS))]

    def test_batched_token_exact_vs_per_request_and_greedy(self,
                                                           spec_setup):
        prompts = self._workload()
        tcfg, tparams, _, _ = spec_setup
        greedy = InferenceEngineV2(
            tcfg, {"state_manager": dict(SPEC_SM), "dtype": "fp32",
                   "generation": {"do_sample": False}},
            params=tparams, steps_cache=MODULE_STEPS)
        outs_g = greedy.generate(prompts, max_new_tokens=self.BUDGETS)

        eb = _spec_engine(spec_setup, True)
        outs_b = eb.generate(prompts, max_new_tokens=self.BUDGETS)
        sb = eb.telemetry.spec_summary()

        ep = _spec_engine(spec_setup, False)
        outs_p = ep.generate(prompts, max_new_tokens=self.BUDGETS)
        sp = ep.telemetry.spec_summary()

        for b, p, g, budget in zip(outs_b, outs_p, outs_g, self.BUDGETS):
            assert len(b) == budget
            assert np.array_equal(np.asarray(b), np.asarray(p)), \
                "batched spec diverged from per-request spec"
            assert np.array_equal(np.asarray(b), np.asarray(g)), \
                "speculative decoding diverged from greedy"
        # the whole point: same tokens, strictly fewer dispatches.  Both
        # engines hit the SAME compiled ("spec", outer, gamma) programs —
        # the batch dimension is slot-wide, not request-count-sized
        assert sb["spec_dispatches"] > 0 and sp["spec_dispatches"] > 0
        assert sb["spec_dispatches"] < sp["spec_dispatches"]
        assert (sb["spec_dispatches"] / max(sb["emitted"], 1)
                < sp["spec_dispatches"] / max(sp["emitted"], 1)), \
            "batched spec must emit more tokens per dispatch"

    def test_mixed_accept_lengths_in_one_batch(self, spec_setup):
        """Deterministic mixed accept lengths inside one fused dispatch:
        with the draft SET TO the target (every proposal accepted), the
        only thing limiting a lane's emission is its own budget — so
        budgets [9, 4, 13, 7] against gamma=4 put a lane that completes
        mid-verify (budget 4 < gamma+1) in the same batch as lanes that
        accept the full window.  Outputs must still equal greedy's."""
        tcfg, tparams, _, _ = spec_setup
        prompts = self._workload()
        eb = InferenceEngineV2(
            tcfg, {"state_manager": dict(SPEC_SM), "dtype": "fp32",
                   "generation": {"do_sample": False},
                   "speculative": {"batch_across_requests": True}},
            params=tparams, draft_model=tcfg, draft_params=tparams,
            steps_cache=MODULE_STEPS)
        outs = eb.generate(prompts, max_new_tokens=self.BUDGETS)
        greedy = InferenceEngineV2(
            tcfg, {"state_manager": dict(SPEC_SM), "dtype": "fp32",
                   "generation": {"do_sample": False}},
            params=tparams, steps_cache=MODULE_STEPS)
        outs_g = greedy.generate(prompts, max_new_tokens=self.BUDGETS)
        for o, g, budget in zip(outs, outs_g, self.BUDGETS):
            assert len(o) == budget
            assert np.array_equal(np.asarray(o), np.asarray(g))
        st = eb.telemetry.spec_summary()
        assert st["accepted"] > 0, "self-draft must accept proposals"
        # speculation overshoots the per-lane budgets (counters see the
        # scheduled window, emission truncates) — the budget clip itself
        # is pinned by the exact lengths asserted above, the acceptance
        # by the counter here
        assert st["emitted"] >= sum(self.BUDGETS)
        assert st["emitted_per_outer"] > 1.0   # not the reject-all floor


# ---------------------------------------------------------------------------
# satellite: pool autoscaler — pure decisions + a deterministic fleet move
# ---------------------------------------------------------------------------

class TestAutoscalerDecisions:
    def _scaler(self, **cfg):
        return PoolAutoscaler(AutoscaleConfig.parse({"enabled": True,
                                                     **cfg}),
                              registry=MetricRegistry(), clock=lambda: 0.0)

    def test_skew_directions(self):
        s = self._scaler(min_requests=1)
        base = {"requests": 10, "shedding": False, "shed_rate": 0.0}
        assert s.decide({**base, "ttft_p99_ms": 1000.0,
                         "tpot_p99_ms": 2.0}) == "to_prefill"
        assert s.decide({**base, "ttft_p99_ms": 3.0,
                         "tpot_p99_ms": 2.0}) == "to_decode"
        assert s.decide({**base, "ttft_p99_ms": 40.0,
                         "tpot_p99_ms": 2.0}) is None     # in band

    def test_shedding_tightens_thresholds(self):
        s = self._scaler(min_requests=1, skew_to_prefill=50.0,
                         shed_tighten=2.0)
        sig = {"requests": 10, "ttft_p99_ms": 80.0, "tpot_p99_ms": 2.0,
               "shed_rate": 3.0}
        # ratio 40 < 50: calm fleet waits...
        assert s.decide({**sig, "shedding": False}) is None
        # ...but under active shedding the same skew acts now (50/2=25)
        assert s.decide({**sig, "shedding": True}) == "to_prefill"

    def test_signal_mass_and_nan_floors(self):
        s = self._scaler(min_requests=4)
        assert s.decide({"requests": 2, "ttft_p99_ms": 1000.0,
                         "tpot_p99_ms": 1.0}) is None
        assert s.decide({"requests": 10, "ttft_p99_ms": float("nan"),
                         "tpot_p99_ms": 1.0}) is None
        assert s.decide({"requests": 10, "ttft_p99_ms": 10.0,
                         "tpot_p99_ms": 0.0}) is None

    def test_evaluate_rate_limits_and_floors(self):
        t = [0.0]
        s = PoolAutoscaler(
            AutoscaleConfig.parse({"enabled": True, "min_requests": 0,
                                   "interval_s": 1.0, "cooldown_s": 5.0}),
            registry=MetricRegistry(), clock=lambda: t[0])
        reg = s.registry
        h = reg.histogram("serving_ttft_ms", "t")
        h2 = reg.histogram("serving_tpot_ms", "t")
        for _ in range(8):
            h.observe(1000.0, replica="r0")
            h2.observe(1.0, replica="r1")
        pools = {"prefill": 1, "decode": 2}
        assert s.evaluate(10.0, pools) == "to_prefill"
        # inside interval_s: no evaluation at all
        assert s.evaluate(10.5, pools) is None
        s.record_move("to_prefill", 11.0)
        # outside interval, inside cooldown: decision suppressed
        assert s.evaluate(13.0, pools) is None
        # donor at its floor: no move even with the skew persisting
        assert s.evaluate(30.0, {"prefill": 2, "decode": 1}) is None
        # gauge stays fresh regardless
        assert reg._metrics["pool_replicas"].value(role="decode") == 1.0

    def test_fleet_p99_aggregates_across_replica_labels(self):
        s = self._scaler()
        h = s.registry.histogram("serving_ttft_ms", "t")
        h.observe(10.0, replica="r0")
        h.observe(500.0, replica="r1")
        worst, count = s._fleet_p99("serving_ttft_ms")
        assert count == 2
        assert worst == pytest.approx(500.0)    # max across label sets
        assert math.isnan(s._fleet_p99("no_such_metric")[0])


class TestAutoscalerFleetMove:
    def test_warm_role_flip_under_skew_no_lost_requests(
            self, cfg, params, workload, reference):
        """Deterministic end-to-end move: synthetic skew seeded into the
        shared registry dominates the live histograms, the autoscaler
        flips the idle decode replica to prefill mid-serve, and the
        serve completes byte-identically — zero lost or duplicated
        requests, and the flip is WARM (the recompile watchdog pins that
        no new program was compiled)."""
        prompts, budgets = workload
        fleet = make_fleet(cfg, params, {
            **DISAGG_CFG, "num_replicas": 3,
            "autoscale": {"enabled": True, "interval_s": 0.0,
                          "cooldown_s": 1e9, "min_requests": 1,
                          "min_decode": 1, "skew_to_prefill": 50.0}})
        try:
            roles = lambda: sorted(  # noqa: E731
                (r.name, r.role) for r in fleet.replicas.values())
            assert roles() == [("r0", "prefill"), ("r1", "decode"),
                               ("r2", "decode")]
            # warm pass: every program both roles run compiles here
            fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=600)
            # synthetic skew: TTFT p99 >> 50x TPOT p99 — prefill-starved
            reg = fleet.registry
            h_ttft = reg.histogram("serving_ttft_ms", "t")
            h_tpot = reg.histogram("serving_tpot_ms", "t")
            for _ in range(64):
                h_ttft.observe(10_000.0, replica="synthetic")
                h_tpot.observe(1.0, replica="synthetic")
            watch = {fp: set(sub) for fp, sub in MODULE_STEPS.items()}
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               max_wall_s=600)
            # the move happened: one decode replica became prefill
            moved = reg._metrics["pool_rebalances_total"].value(
                direction="to_prefill")
            assert moved == 1.0
            assert [role for _, role in roles()].count("prefill") == 2
            # warm flip: the shared compile cache gained NO new programs
            after = {fp: set(sub) for fp, sub in MODULE_STEPS.items()}
            assert after == watch, "role flip recompiled a program"
            # the flipped replica is marked warm (no warm-up deadline)
            assert all(r.warmed for r in fleet.replicas.values()
                       if r.state == "healthy")
            # zero lost/duplicated: every request exactly once, byte-equal
            assert len(fleet.request_log) == len(prompts)
            assert sorted(r["index"] for r in fleet.request_log) \
                == list(range(len(prompts)))
            for out, ref in zip(outs, reference):
                assert np.array_equal(np.asarray(out), np.asarray(ref))
            # no respawn budget burned, no death booked by the flip
            assert reg._metrics["fleet_replica_deaths_total"].samples() \
                == [] or sum(v for _, v in reg._metrics[
                    "fleet_replica_deaths_total"].samples()) == 0
            _assert_no_leaks(fleet)
        finally:
            fleet.shutdown()

    def test_autoscaler_disabled_never_moves(self, cfg, params, workload):
        prompts, budgets = workload
        fleet = make_fleet(cfg, params, {**DISAGG_CFG, "num_replicas": 3})
        try:
            reg = fleet.registry
            h_ttft = reg.histogram("serving_ttft_ms", "t")
            h_tpot = reg.histogram("serving_tpot_ms", "t")
            for _ in range(64):
                h_ttft.observe(10_000.0, replica="synthetic")
                h_tpot.observe(1.0, replica="synthetic")
            fleet.serve(prompts, max_new_tokens=budgets, max_wall_s=600)
            assert reg._metrics["pool_rebalances_total"].samples() == []
            assert sorted(r.role for r in fleet.replicas.values()) \
                == ["decode", "decode", "prefill"]
        finally:
            fleet.shutdown()
