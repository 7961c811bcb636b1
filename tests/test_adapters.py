"""Multi-tenant LoRA adapter serving (PR 20): paged adapter pool +
batched-gather LoRA matmul (serving/adapters.py, ops/lora_matmul.py,
the v2 engine's ``adapters`` block, fleet adapter routing).

The invariants these tests pin, in order of importance:

1. **Exactness** — a mixed-adapter ragged batch is byte-equal to running
   every request alone with its adapter (the batched gather is exact,
   not approximately right), and id 0 rides the identity slot
   byte-equal to an adapter-less engine.
2. **One pool, no leaks** — adapter pages and KV blocks share the
   BlockedAllocator; after any serve (including eviction churn and a
   replica death) every pin is released and free + resident accounts
   for the whole pool.
3. **Cross-tenancy eviction policy** — cold adapters go LRU-first,
   pinned adapters never; an adapter that can NEVER fit fails the
   REQUEST typed (engine ValueError → fleet ``invalid_request``), not
   the replica.
4. **Compiled-step hygiene** — the adapters config is part of the
   shared steps-cache fingerprint, so adapter-enabled and base engines
   handed one cache never dispatch each other's programs.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu import ops
from deepspeed_tpu.inference.v2 import BlockedAllocator, InferenceEngineV2
from deepspeed_tpu.models import GPTConfig
import importlib

# the package exports a lora_matmul FUNCTION that shadows the submodule on
# attribute-style imports — resolve the module itself for trace_counts
lora_mod = importlib.import_module("deepspeed_tpu.ops.lora_matmul")
from deepspeed_tpu.runtime import faults
from deepspeed_tpu.serving import RequestFailed, ServingFleet
from deepspeed_tpu.serving.adapters import (AdapterPool,
                                            random_adapter_weights)
from deepspeed_tpu.telemetry.registry import MetricRegistry

VOCAB, SEQ = 97, 64
SM = {"max_tracked_sequences": 8, "max_ragged_batch_size": 64,
      "kv_block_size": 8, "max_q_per_seq": 16}
ADP = {"enabled": True, "rank": 4, "alpha": 8.0, "slots": 10}
# shared jitted-step cache: every identically-configured engine in this
# module compiles once (fingerprint-namespaced, asserted below)
MODULE_STEPS = {}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)


def _engine(cfg, params=None, adapters=ADP, registry=None, **sm_over):
    v2 = {"dtype": "fp32", "state_manager": {**SM, **sm_over}}
    if adapters:
        v2["adapters"] = adapters
    if registry is not None:
        v2["telemetry"] = {"replica": "r?"}
    return InferenceEngineV2(cfg, config=v2, params=params, seed=0,
                             steps_cache=MODULE_STEPS,
                             telemetry_registry=registry)


@pytest.fixture(scope="module")
def params(cfg):
    return _engine(cfg, adapters=None).params


@pytest.fixture(scope="module")
def workload():
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, VOCAB, size=int(rng.integers(4, 14)))
               .astype(np.int32) for _ in range(8)]
    budgets = [int(b) for b in rng.integers(6, 12, size=8)]
    return prompts, budgets


def _tenant_weights(aid, init_scale=0.5):
    """Big-delta weights so distinct adapters visibly steer greedy argmax
    (the pool's default 0.02 init is numerically real but too small to
    flip tokens on the tiny test model)."""
    return random_adapter_weights(2, 32, ADP["rank"], 32, 32, seed=aid,
                                  init_scale=init_scale)


@pytest.fixture(scope="module")
def adapter_engine(cfg, params):
    eng = _engine(cfg, params)
    for aid in range(1, 9):
        eng.register_adapter(aid, _tenant_weights(aid))
    return eng


@pytest.fixture(scope="module")
def solo_reference(cfg, adapter_engine, workload):
    """Each request served ALONE with its adapter (id = 1 + i % 8) — the
    exactness ground truth for every mixed/fleet/churn run below."""
    prompts, budgets = workload
    outs = []
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        outs.append(adapter_engine.generate(
            [p], max_new_tokens=[b], adapter_ids=[1 + i % 8])[0])
    return outs


# ---------------------------------------------------------------------------
# ops/lora_matmul.py: the batched gather is numerically exact
# ---------------------------------------------------------------------------

class TestLoRAMatmulOp:
    S, M, H, R, O = 4, 16, 256, 4, 128

    def _case(self, seed=0, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(self.M, self.H)), dtype)
        a = jnp.asarray(rng.normal(size=(self.S, self.H, self.R)), dtype)
        b = jnp.asarray(rng.normal(size=(self.S, self.R, self.O)), dtype)
        # slot 0 is the identity lane: zero pages, zero scale
        a = a.at[0].set(0.0)
        b = b.at[0].set(0.0)
        scales = jnp.asarray([0.0, 2.0, 0.5, 1.0], jnp.float32)
        ids = jnp.asarray(rng.integers(0, self.S, size=self.M), jnp.int32)
        return x, a, b, ids, scales

    def test_xla_matches_per_request_loop(self):
        x, a, b, ids, scales = self._case()
        got = np.asarray(ops.lora_matmul(x, a, b, ids, scales, impl="xla"))
        for i in range(self.M):
            s = int(ids[i])
            want = (np.asarray(x[i]) @ np.asarray(a[s])
                    @ np.asarray(b[s])) * float(scales[s])
            # fp32 vs numpy accumulation order: same math, different sums
            np.testing.assert_allclose(got[i], want, rtol=1e-3, atol=1e-3)

    def test_identity_rows_are_exact_zero(self):
        x, a, b, _, scales = self._case()
        ids = jnp.zeros((self.M,), jnp.int32)
        y = np.asarray(ops.lora_matmul(x, a, b, ids, scales, impl="xla"))
        assert not y.any()

    @staticmethod
    def _summation_order_tol(x, a, b, ids, scales):
        """Per-element bound on what two f32 summation orders of the same
        H-term rank product may differ by: the rounding error of an n-term
        sum grows as sqrt(n)·eps·rms(terms) (4× for margin; measured worst
        0.16 of it over four seeds), carried through B and the scale.  The
        kernel contracts a dense [bm, H] x [H, r] dot, the reference a
        per-row gathered einsum; against float64 the kernel is the closer
        of the two (3.3e-5 vs 1.7e-4 at |y| to 250), and the elements that
        a flat 2e-5 refused were cancellations (|y| 0.2-0.4 from terms of
        ~100)."""
        x, a, b = (np.asarray(v, np.float64) for v in (x, a, b))
        ids, s = np.asarray(ids), np.asarray(scales, np.float64)
        u_err = 4 * np.sqrt(x.shape[1]) * np.finfo(np.float32).eps \
            * np.sqrt(np.einsum("mh,mhr->mr", x ** 2, a[ids] ** 2))
        return np.einsum("mr,mro->mo", u_err, np.abs(b[ids])) \
            * s[ids][:, None]

    def _assert_same_to_summation_order(self, got, want, *case):
        tol = self._summation_order_tol(*case)
        diff = np.abs(np.asarray(got, np.float64) - np.asarray(want))
        assert (diff <= tol).all(), (diff / np.maximum(tol, 1e-30)).max()
        # the bound stays a bound: at most 1e-5 of the outputs' scale
        assert tol.max() < 1e-5 * np.abs(np.asarray(want)).max()

    def test_pallas_kernel_matches_xla(self):
        """Interpret-mode kernel vs the gather reference, and the staging
        counter proves the KERNEL ran (not the silent fallback)."""
        x, a, b, ids, scales = self._case(seed=3)
        before = lora_mod.trace_counts["lora"]
        got = ops.lora_matmul(x, a, b, ids, scales, impl="pallas")
        assert lora_mod.trace_counts["lora"] == before + 1
        want = ops.lora_matmul(x, a, b, ids, scales, impl="xla")
        self._assert_same_to_summation_order(got, want, x, a, b, ids,
                                             scales)

    def test_pallas_pads_ragged_row_counts(self):
        """Decode rounds hand the kernel M that doesn't tile to the
        sublane — the pad rows carry id -1 (matches no slot) and are
        stripped from the output."""
        x, a, b, ids, scales = self._case(seed=5)
        m = 13
        got = ops.lora_matmul(x[:m], a, b, ids[:m], scales, impl="pallas")
        want = ops.lora_matmul(x[:m], a, b, ids[:m], scales, impl="xla")
        assert got.shape == (m, self.O)
        self._assert_same_to_summation_order(got, want, x[:m], a, b,
                                             ids[:m], scales)

    def test_unsupported_layout_falls_back_not_crash(self):
        x, a, b, ids, scales = self._case()
        bad_ids = ids[: self.M - 1]                  # ids/rows mismatch
        assert not lora_mod.lora_supported(x, a, b, bad_ids, scales)
        y = lora_mod.pallas_lora_matmul(x, a, b,
                                        jnp.pad(bad_ids, (0, 1)), scales)
        assert y.shape == (self.M, self.O)


# ---------------------------------------------------------------------------
# serving/adapters.py: pool residency, eviction policy, supply accounting
# ---------------------------------------------------------------------------

def _pool(num_blocks=4, slots=4, block_bytes=128, telemetry=None):
    """Tiny pool: L=1, H=8, r=2, q=v=8 → 256 B/adapter → 2 blocks each."""
    alloc = BlockedAllocator(num_blocks)
    return AdapterPool(alloc, slots=slots, rank=2, hidden=8, num_layers=1,
                       q_dim=8, v_dim=8, block_bytes=block_bytes,
                       scale=2.0, telemetry=telemetry)


class TestAdapterPool:
    def test_register_validation_and_idempotence(self):
        pool = _pool()
        with pytest.raises(ValueError, match="reserved base-model"):
            pool.register(0)
        with pytest.raises(ValueError, match="missing projection"):
            pool.register(1, {"a_q": np.zeros((1, 8, 2), np.float32)})
        pool.register(1)
        pool.register(1)                 # duplicate register = overwrite
        assert pool.registered(1) and pool.registered(0)
        assert not pool.registered(2)
        assert pool.blocks_per_adapter == 2

    def test_miss_hit_evict_reload_cycle(self):
        pool = _pool(num_blocks=4)       # capacity: exactly 2 adapters
        for aid in (1, 2, 3):
            pool.register(aid)
        pool.ensure([1])
        pool.ensure([1])
        assert (pool.hits, pool.misses) == (1, 1)
        pool.ensure([2])
        assert pool.allocator.free_blocks == 0
        pool.ensure([3])                 # LRU victim is 1
        assert pool.evictions == 1
        assert not pool.is_resident(1)
        assert pool.is_resident(2) and pool.is_resident(3)
        pool.ensure([1])                 # reload after eviction
        st = pool.stats()
        assert st["resident_adapters"] == 2
        assert st["resident_blocks"] == 4 and st["pinned_blocks"] == 0
        assert st["hit_rate"] == pytest.approx(1 / 5)   # 1 hit, 4 misses
        pool.check_invariants()

    def test_pinned_adapter_never_evicted(self):
        pool = _pool(num_blocks=4)
        for aid in (1, 2, 3):
            pool.register(aid)
        pool.ensure([1, 2])
        pool.acquire(1)                  # in-flight request pins 1
        pool.ensure([3])                 # must evict 2 (cold), not 1 (LRU)
        assert pool.is_resident(1) and not pool.is_resident(2)
        assert pool.stats()["pinned_blocks"] == 2
        assert pool.evictable_blocks() == 2          # only adapter 3
        pool.release(1)
        assert pool.evictable_blocks() == 4
        pool.check_invariants()

    def test_all_slots_pinned_raises_retryable(self):
        pool = _pool(num_blocks=8, slots=3)          # 2 tenant slots
        for aid in (1, 2, 3):
            pool.register(aid)
        pool.ensure([1, 2])
        pool.acquire(1)
        pool.acquire(2)
        with pytest.raises(RuntimeError, match="slots exhausted"):
            pool.ensure([3])
        pool.release(1)
        pool.ensure([3])                 # a released pin unblocks the load
        pool.check_invariants()

    def test_spill_reclaims_beyond_cold_adapters(self):
        """Cold adapters first, then the caller's spill (the state manager
        hands radix eviction through this hook)."""
        pool = _pool(num_blocks=5)
        pool.register(1)
        pool.register(2)
        pool.ensure([1])
        pool.acquire(1)                  # not evictable
        radix = pool.allocator.allocate(2)           # "radix" holds 2
        calls = []

        def spill(n):
            calls.append(n)
            freed = pool.allocator.release(radix[:n])
            del radix[:n]
            return len(freed)

        pool.ensure([2], spill=spill)
        assert calls == [1]              # free was 1, short exactly 1
        assert pool.is_resident(1) and pool.is_resident(2)
        pool.check_invariants()

    def test_unfittable_reasons(self):
        pool = _pool()
        assert pool.unfittable_reason(0) is None
        assert "never registered" in pool.unfittable_reason(9)
        tiny = _pool(num_blocks=1)
        tiny.register(1)
        assert "pool only has" in tiny.unfittable_reason(1)
        slotless = _pool(slots=1)
        slotless.register(1)
        assert "no tenant slots" in slotless.unfittable_reason(1)

    def test_identity_slot_and_cross_thread_peeks(self):
        pool = _pool()
        pool.register(1)
        assert pool.is_resident(0) and pool.slot_of(0) == 0
        assert pool.resident_count([0, 1, 2]) == 0
        pool.ensure([1])
        assert pool.resident_count([0, 1, 1, 2]) == 1
        t = pool.tables()
        assert not np.asarray(t["a_q"][0]).any()     # identity pages zero
        assert float(t["scale"][0]) == 0.0
        assert float(t["scale"][pool.slot_of(1)]) == 2.0

    def test_churn_keeps_invariants_and_books_telemetry(self):
        reg = MetricRegistry()
        from deepspeed_tpu.telemetry.serving import ServingTelemetry
        stel = ServingTelemetry(registry=reg)
        pool = _pool(num_blocks=4, telemetry=stel)
        for aid in range(1, 7):
            pool.register(aid)
        rng = np.random.default_rng(0)
        for _ in range(40):
            aid = int(rng.integers(1, 7))
            pool.ensure([aid])
            pool.acquire(aid)
            pool.release(aid)
            pool.check_invariants()
        m = reg._metrics["adapter_loads_total"]
        by = {s["outcome"]: v for s, v in m.samples()}
        assert by.get("miss", 0) >= 1 and by.get("reload", 0) >= 1
        assert by.get("hit", 0) == pool.hits
        assert reg._metrics["adapter_evictions_total"].value() \
            == pool.evictions > 0


# ---------------------------------------------------------------------------
# engine: mixed-adapter exactness, identity, admission, fingerprint
# ---------------------------------------------------------------------------

class TestEngineAdapters:
    def test_mixed_8_adapter_batch_byte_equal(self, adapter_engine,
                                              workload, solo_reference):
        """The tentpole invariant: 8 tenants in ONE fused ragged dispatch,
        every output byte-equal to its solo single-adapter run, and the
        pool fully unpinned afterwards."""
        prompts, budgets = workload
        ids = [1 + i % 8 for i in range(len(prompts))]
        outs = adapter_engine.generate(prompts, max_new_tokens=budgets,
                                       adapter_ids=ids)
        for o, want in zip(outs, solo_reference):
            np.testing.assert_array_equal(o, want)
        st = adapter_engine.adapters.stats()
        assert st["pinned_blocks"] == 0
        alloc = adapter_engine.state.allocator
        assert alloc.free_blocks + st["resident_blocks"] == alloc.num_blocks
        assert adapter_engine.adapter_resident(ids) == 8
        adapter_engine.adapters.check_invariants()

    def test_adapters_actually_steer_tokens(self, adapter_engine, workload,
                                            solo_reference):
        """Sanity against a no-op LoRA path: a big-delta adapter must
        diverge from the base model's greedy tokens."""
        prompts, budgets = workload
        base = adapter_engine.generate([prompts[0]],
                                       max_new_tokens=[budgets[0]])[0]
        assert not np.array_equal(base, solo_reference[0])

    def test_id0_byte_equal_to_adapterless_engine(self, cfg, params,
                                                  adapter_engine, workload):
        """Identity lane: explicit id 0, omitted adapter_ids, and a
        pool-less engine all produce the same bytes."""
        prompts, budgets = workload
        base = _engine(cfg, params, adapters=None)
        want = base.generate(prompts, max_new_tokens=budgets)
        for got in (adapter_engine.generate(prompts, max_new_tokens=budgets),
                    adapter_engine.generate(prompts, max_new_tokens=budgets,
                                            adapter_ids=[0] * len(prompts))):
            for o, w in zip(got, want):
                np.testing.assert_array_equal(o, w)

    def test_eviction_churn_stays_exact(self, cfg, params, workload,
                                        solo_reference):
        """slots=3 leaves TWO tenant slots for 8 adapters: serving the
        mixed workload sequentially forces eviction + reload churn, and
        every reloaded adapter still produces its solo bytes."""
        eng = _engine(cfg, params, adapters={**ADP, "slots": 3})
        for aid in range(1, 9):
            eng.register_adapter(aid, _tenant_weights(aid))
        prompts, budgets = workload
        for i, want in enumerate(solo_reference):
            out = eng.generate([prompts[i]], max_new_tokens=[budgets[i]],
                               adapter_ids=[1 + i % 8])[0]
            np.testing.assert_array_equal(out, want)
        st = eng.adapters.stats()
        assert st["evictions"] > 0 and st["pinned_blocks"] == 0
        eng.adapters.check_invariants()

    def test_client_errors_are_typed_valueerrors(self, cfg, params,
                                                 adapter_engine):
        p = np.arange(6, dtype=np.int32)
        with pytest.raises(ValueError, match="must match prompts"):
            adapter_engine.generate([p], max_new_tokens=[4],
                                    adapter_ids=[1, 2])
        with pytest.raises(ValueError, match="never registered"):
            adapter_engine.generate([p], max_new_tokens=[4],
                                    adapter_ids=[99])
        base = _engine(cfg, params, adapters=None)
        with pytest.raises(ValueError, match="no adapter"):
            base.generate([p], max_new_tokens=[4], adapter_ids=[1])
        base.generate([p], max_new_tokens=[4], adapter_ids=[0])  # id 0 ok

    def test_combined_kv_plus_adapter_capacity_rejected(self, cfg, params):
        """A request whose KV *would* fit alone but not next to its own
        pinned adapter pages is unservable at any load — reject at
        dispatch, don't livelock admission."""
        eng = _engine(cfg, params, num_kv_blocks=6)
        eng.register_adapter(1)
        need_all = eng.state.block_size * 6
        prompt = np.zeros(need_all - 4, np.int32)
        eng_ok = eng.generate([prompt], max_new_tokens=[4])  # base fits
        assert len(eng_ok) == 1
        with pytest.raises(ValueError, match="adapter-page"):
            eng.generate([prompt], max_new_tokens=[4], adapter_ids=[1])

    def test_register_requires_pool_and_spec_is_rejected(self, cfg, params):
        base = _engine(cfg, params, adapters=None)
        with pytest.raises(ValueError, match="no adapter pool"):
            base.register_adapter(1)
        assert base.adapter_resident([1, 2]) == 0
        with pytest.raises(NotImplementedError, match="speculative"):
            InferenceEngineV2(cfg, config={
                "dtype": "fp32", "state_manager": SM, "adapters": ADP},
                params=params, draft_model=cfg, draft_params=params,
                seed=0)

    def test_steps_cache_fingerprint_namespaces_adapters(self, cfg, params):
        """Adapter-enabled programs take extra operands and bake rank
        geometry into traced shapes — base / enabled / different-rank
        engines sharing one cache must land in DISJOINT sub-caches."""
        cache = {}
        mk = lambda adp: InferenceEngineV2(
            cfg, config={"dtype": "fp32", "state_manager": SM,
                         **({"adapters": adp} if adp else {})},
            params=params, seed=0, steps_cache=cache)
        mk(None)
        assert len(cache) == 1
        mk(ADP)
        assert len(cache) == 2
        mk({**ADP, "rank": 8})
        assert len(cache) == 3
        mk(ADP)                          # same config → same sub-cache
        assert len(cache) == 3


# ---------------------------------------------------------------------------
# fleet: adapter routing, typed failures, registry replay across respawn
# ---------------------------------------------------------------------------

def _make_fleet(cfg, params, fleet_cfg, adapters=ADP):
    reg = MetricRegistry()

    def factory(name):
        v2 = {"dtype": "fp32", "state_manager": SM,
              "telemetry": {"replica": name}}
        if adapters:
            v2["adapters"] = adapters
        return InferenceEngineV2(cfg, v2, params=params,
                                 steps_cache=MODULE_STEPS,
                                 telemetry_registry=reg)
    return ServingFleet(engine_factory=factory, config=fleet_cfg,
                        registry=reg)


class TestFleetAdapters:
    def test_fleet_serve_token_exact(self, cfg, params, workload,
                                     solo_reference):
        prompts, budgets = workload
        ids = [1 + i % 8 for i in range(len(prompts))]
        with _make_fleet(cfg, params, {"num_replicas": 2}) as fleet:
            for aid in range(1, 9):
                fleet.register_adapter(aid, _tenant_weights(aid))
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               adapter_ids=ids, max_wall_s=300)
            for o, want in zip(outs, solo_reference):
                np.testing.assert_array_equal(o, want)
            with pytest.raises(ValueError, match="must match prompts"):
                fleet.serve(prompts, max_new_tokens=budgets,
                            adapter_ids=ids[:-1])

    def test_replica_death_migrates_adapters_token_exact(
            self, cfg, params, workload, solo_reference):
        """Chaos leg: a replica dies mid-decode with adapter requests in
        flight.  The respawned replica replays the fleet's adapter
        registry, migrated requests complete byte-equal, and NO replica
        leaks a block or a pin."""
        prompts, budgets = workload
        ids = [1 + i % 8 for i in range(len(prompts))]
        faults.inject("replica.mid_decode", "exc", after=3)
        with _make_fleet(cfg, params,
                         {"num_replicas": 2, "respawn": True,
                          "max_respawns": 1}) as fleet:
            for aid in range(1, 9):
                fleet.register_adapter(aid, _tenant_weights(aid))
            outs = fleet.serve(prompts, max_new_tokens=budgets,
                               adapter_ids=ids, max_wall_s=300)
            reg = fleet.registry._metrics
            assert faults.fired("replica.mid_decode") == 1
            assert reg["requests_migrated_total"].value() > 0
            for o, want in zip(outs, solo_reference):
                np.testing.assert_array_equal(o, want)
            for rep in fleet.replicas.values():
                if rep.state != "healthy":
                    continue
                eng = rep.engine
                st = eng.adapters.stats()
                assert st["pinned_blocks"] == 0
                alloc = eng.state.allocator
                assert alloc.free_blocks + st["resident_blocks"] \
                    == alloc.num_blocks
                eng.adapters.check_invariants()

    def test_unfittable_adapter_fails_request_not_replica(
            self, cfg, params, workload, solo_reference):
        """An unknown adapter id is a CLIENT error: typed invalid_request,
        zero deaths, zero respawn budget burned, and the valid adapter
        requests around it still complete byte-equal."""
        prompts, budgets = workload
        ids = [1 + i % 8 for i in range(len(prompts))]
        with _make_fleet(cfg, params, {"num_replicas": 2}) as fleet:
            for aid in range(1, 9):
                fleet.register_adapter(aid, _tenant_weights(aid))
            outs = fleet.serve(list(prompts) + [prompts[0]],
                               max_new_tokens=list(budgets) + [4],
                               adapter_ids=ids + [404],
                               raise_on_failure=False, max_wall_s=300)
            err = fleet.last_failures[len(prompts)]
            assert isinstance(err, RequestFailed)
            assert err.reason == "invalid_request"
            assert "never registered" in str(err)
            assert outs[len(prompts)] is None
            reg = fleet.registry._metrics
            assert sum(v for _, v in
                       reg["fleet_replica_deaths_total"].samples()) == 0
            assert all(r.state == "healthy"
                       for r in fleet.replicas.values())
            for o, want in zip(outs[:len(prompts)], solo_reference):
                np.testing.assert_array_equal(o, want)

    def test_base_only_fleet_rejects_adapter_requests(self, cfg, params,
                                                      workload):
        prompts, budgets = workload
        with _make_fleet(cfg, params, {"num_replicas": 1},
                         adapters=None) as fleet:
            outs = fleet.serve([prompts[0]], max_new_tokens=[4],
                               adapter_ids=[1], raise_on_failure=False,
                               max_wall_s=300)
            err = fleet.last_failures[0]
            assert isinstance(err, RequestFailed)
            assert err.reason == "invalid_request"
            assert "base model only" in str(err)
            assert outs[0] is None


class TestRouterAdapterAffinity:
    def _router(self):
        import time
        from deepspeed_tpu.serving import Router, RouterConfig
        return Router(RouterConfig(policy="prefix_affinity"),
                      clock=time.monotonic, registry=MetricRegistry())

    class _Rep:
        def __init__(self, name, resident=None, broken=False):
            self.name = name
            self.state = "healthy"
            self.enqueued = []
            if resident is not None:
                rep = self

                class _Eng:
                    def adapter_resident(self, ids):
                        if broken:
                            raise RuntimeError("probe on a dying replica")
                        return sum(1 for a in ids if a in resident)
                self.engine = _Eng()

        def enqueue(self, req):
            self.enqueued.append(req)

    def test_adapter_residency_is_second_signal(self, workload):
        """Radix residency ranks first; with prefixes cold, the replica
        already holding the request's adapter pages wins the tie."""
        from deepspeed_tpu.serving import FleetRequest
        r = self._router()
        reps = [self._Rep("r0", resident={2}), self._Rep("r1", resident={7})]
        req = FleetRequest(index=0, prompt=np.arange(8, dtype=np.int32),
                           max_new_tokens=4, adapter=7)
        assert r.pick(req, reps).name == "r1"
        # base-model requests never probe: deterministic name-order pick
        base = FleetRequest(index=1, prompt=np.arange(8, dtype=np.int32),
                            max_new_tokens=4)
        assert r.pick(base, reps).name == "r0"
        # probe-less replicas degrade to 0, never error
        bare = [self._Rep("b0"), self._Rep("b1")]
        assert r.pick(req, bare).name == "b0"

    def test_probe_failure_and_cache_invalidation(self, workload):
        from deepspeed_tpu.serving import FleetRequest
        r = self._router()
        dying = self._Rep("r0", resident={7}, broken=True)
        req = FleetRequest(index=0, prompt=np.arange(8, dtype=np.int32),
                           max_new_tokens=4, adapter=7)
        assert r.adapter_residency(dying, req) == 0    # never raises
        warm = self._Rep("r1", resident={7})
        assert r.adapter_residency(warm, req) == 1
        assert r._adapter_residency["r1"][7] == 1      # cached
        r.invalidate_residency("r1")
        assert "r1" not in r._adapter_residency
