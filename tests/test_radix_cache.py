"""Radix shared-prefix KV cache + SLA serving scheduler (PR 15,
[serving_scale]): refcounted allocator invariants, trie share/COW/eviction
invariants, cache-on == cache-off greedy token-exactness, SplitFuse
chunked-prefill fairness, SLA-aware admission/preemption, and the
DSStateManager deque satellite."""

import numpy as np
import pytest
from conftest import v2_engine

from deepspeed_tpu.inference.v2 import (BlockedAllocator, DSStateManager,
                                        RadixKVCache)
from deepspeed_tpu.models import GPTConfig


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig.tiny(vocab_size=97, max_seq_len=64)


BASE_SM = {"max_tracked_sequences": 4, "max_ragged_batch_size": 64,
           "kv_block_size": 8, "max_q_per_seq": 16}


def mk_engine(cfg, seed=0, **sm_overrides):
    return v2_engine(cfg, config={
        "dtype": "fp32",
        "state_manager": dict(BASE_SM, **sm_overrides)}, seed=seed)


class TestRefcountedAllocator:
    def test_acquire_release_cycle(self):
        a = BlockedAllocator(8)
        blocks = a.allocate(3)
        assert a.free_blocks == 5
        a.acquire(blocks)                       # second holder
        assert a.release(blocks) == []          # first release frees nothing
        assert a.free_blocks == 5
        assert a.release(blocks) == blocks      # last holder frees
        assert a.free_blocks == 8

    def test_release_underflow_raises(self):
        a = BlockedAllocator(4)
        b = a.allocate(1)
        a.release(b)
        with pytest.raises(RuntimeError, match="underflow"):
            a.release(b)

    def test_acquire_dead_block_raises(self):
        a = BlockedAllocator(4)
        with pytest.raises(RuntimeError, match="dead block"):
            a.acquire([0])

    def test_free_alias_back_compat(self):
        a = BlockedAllocator(4)
        b = a.allocate(2)
        a.free(b)
        assert a.free_blocks == 4


class TestStateManagerDeque:
    def test_free_lists_are_deques(self):
        """PR 15 satellite: create/flush used list.pop(0)/insert(0, ...) —
        O(S) per request; both free lists must be deques now (O(1))."""
        from collections import deque
        st = DSStateManager(max_tracked_sequences=4, num_blocks=8,
                            block_size=8, max_seq_len=64)
        assert isinstance(st._free_slots, deque)
        assert isinstance(st.allocator._free, deque)
        # flush returns the slot to the FRONT (LIFO reuse, as before)
        s = st.create(1)
        slot = s.slot
        st.flush(1)
        assert st.create(2).slot == slot


class TestRadixIndex:
    """Host-only trie semantics: share, dedup, LRU eviction, and the
    never-negative / never-dangling refcount invariants."""

    BS = 4

    def mk(self, blocks=16):
        a = BlockedAllocator(blocks)
        return a, RadixKVCache(a, self.BS)

    def toks(self, *vals):
        return np.asarray(vals, np.int32)

    def test_insert_match_share(self):
        a, r = self.mk()
        seq_blocks = a.allocate(2)
        content = self.toks(*range(8))
        assert r.insert(content, seq_blocks) == 2
        blocks, matched = r.match(content)
        assert matched == 8 and blocks == seq_blocks
        # acquire as a matching sequence would; blocks now shared
        a.acquire(blocks)
        assert a.refcount(blocks[0]) == 3       # owner + radix + sharer
        r.check_invariants()

    def test_insert_dedup_keeps_existing_node(self):
        a, r = self.mk()
        b1 = a.allocate(1)
        content = self.toks(1, 2, 3, 4)
        r.insert(content, b1)
        b2 = a.allocate(1)                      # same content, private copy
        assert r.insert(content, b2) == 0       # dedup: no new node
        assert a.refcount(b2[0]) == 1           # radix took NO hold on it
        blocks, _ = r.match(content)
        assert blocks == b1
        r.check_invariants()

    def test_lru_eviction_order_and_refcount_guard(self):
        a, r = self.mk(blocks=8)
        cold = a.allocate(1)
        r.insert(self.toks(1, 2, 3, 4), cold)
        warm = a.allocate(1)
        r.insert(self.toks(5, 6, 7, 8), warm)
        a.release(cold)                         # only the radix holds both
        a.release(warm)
        r.match(self.toks(1, 2, 3, 4))          # freshen "cold" -> now MRU
        assert r.evict(1) == 1                  # LRU leaf = the other one
        assert r.peek(self.toks(1, 2, 3, 4)) == 4
        assert r.peek(self.toks(5, 6, 7, 8)) == 0
        # a block still held by a sequence is never evictable
        held, _ = r.match(self.toks(1, 2, 3, 4))
        a.acquire(held)
        assert r.evictable_blocks() == 0
        assert r.evict(5) == 0
        r.check_invariants()

    def test_deep_chain_evicts_leaf_first(self):
        a, r = self.mk()
        blocks = a.allocate(3)
        content = self.toks(*range(12))
        r.insert(content, blocks)
        a.release(blocks)
        assert r.evictable_blocks() == 3
        assert r.evict(1) == 1                  # leaf only
        assert r.peek(content) == 8             # prefix chain intact
        assert r.evict(10) == 2                 # drains parent then root child
        assert r.peek(content) == 0
        assert a.free_blocks == 16
        r.check_invariants()

    def test_pool_accounting_exact_through_share_evict(self):
        a, r = self.mk(blocks=12)
        s1 = a.allocate(3)
        c1 = self.toks(*range(12))
        r.insert(c1, s1)
        m, n = r.match(c1)
        a.acquire(m)                            # a second sequence aliases
        a.release(s1)                           # first sequence flushes
        a.release(m)                            # second flushes
        # every block now held ONLY by the radix; totals must reconcile
        assert a.free_blocks + r.node_count == 12
        r.evict(3)
        assert a.free_blocks == 12
        r.check_invariants()


class TestPrefixCacheEngine:
    """Engine-level tentpole invariants: exactness, prefill skipping,
    eviction under pressure, accounting."""

    def shared_prompts(self, rng, shared_len=16, n=3):
        shared = rng.integers(0, 97, (shared_len,)).astype(np.int32)
        return [np.concatenate([shared,
                                rng.integers(0, 97, (4 + i,)).astype(np.int32)])
                for i in range(n)]

    def test_cache_on_off_token_exact_and_hits(self, cfg, rng):
        prompts = self.shared_prompts(rng)
        want = mk_engine(cfg).generate(prompts, max_new_tokens=8)
        eng = mk_engine(cfg, prefix_cache=True)
        got = eng.generate(prompts, max_new_tokens=8)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        # a SECOND serve hits the now-resident prefix for every request and
        # must still be byte-identical
        got2 = eng.generate(prompts, max_new_tokens=8)
        for w, g in zip(want, got2):
            np.testing.assert_array_equal(w, g)
        t = eng.telemetry
        assert t.value("kv_prefix_lookups_total") >= 6
        # each of the 3 second-pass requests aliases the 16-token prefix
        assert t.value("kv_prefix_hit_tokens_total") >= 3 * 16
        eng.state.radix.check_invariants()

    def test_prefill_actually_skipped(self, cfg, rng):
        prompts = self.shared_prompts(rng, shared_len=24)
        eng = mk_engine(cfg, prefix_cache=True)
        eng.generate(prompts, max_new_tokens=4)
        before = eng.telemetry.value("serving_tokens_total", phase="prefill")
        eng.generate(prompts, max_new_tokens=4)
        prefilled = (eng.telemetry.value("serving_tokens_total",
                                         phase="prefill") - before)
        total = sum(len(p) for p in prompts)
        # ≥ 24 tokens/request served from the cache -> scheduled prefill
        # shrinks by at least that much
        assert prefilled <= total - 3 * 24

    def test_put_matched_logits_equal_full_forward(self, cfg, rng):
        import jax.numpy as jnp
        from deepspeed_tpu.models.gpt import GPTLogits
        eng = mk_engine(cfg, prefix_cache=True)
        ids = rng.integers(0, 97, (20,)).astype(np.int32)
        eng.put([1], [ids[:16]])
        eng.put([1], [ids[16:]])
        eng.flush([1])
        # 16 tokens (2 full blocks) now cached: a 20-token one-shot put is
        # LEGAL (effective 4 ≤ max_q_per_seq) and must match the
        # cache-free forward
        logits = eng.put([2], [ids])
        assert eng.telemetry.value("kv_prefix_hit_tokens_total") == 16
        lm = GPTLogits(eng.model_config)
        want = np.asarray(lm.apply({"params": eng.params},
                                   jnp.asarray(ids[None], jnp.int32)))[0, -1]
        np.testing.assert_allclose(logits[0], want, atol=1e-4, rtol=1e-4)

    def test_eviction_under_pool_pressure_stays_exact(self, cfg, rng):
        prompts = self.shared_prompts(rng, shared_len=16)
        want = mk_engine(cfg).generate(prompts, max_new_tokens=12)
        # 7-block pool: cached prefixes must be evicted and re-prefilled
        # mid-serve; output must not change
        eng = mk_engine(cfg, prefix_cache=True, num_kv_blocks=7)
        got = eng.generate(prompts, max_new_tokens=12)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        eng.state.radix.check_invariants()

    def test_preemption_foldback_composes_with_cache(self, cfg, rng):
        """Recompute preemption + radix cache: the preempted victim's
        re-prefill may hit its own previously-cached prefix — output must
        still match the uncontended run exactly."""
        prompts = [rng.integers(0, 97, (20,)).astype(np.int32)
                   for _ in range(2)]
        want = [mk_engine(cfg).generate([p], max_new_tokens=12)[0]
                for p in prompts]
        eng = mk_engine(cfg, prefix_cache=True, num_kv_blocks=6)
        got = eng.generate(prompts, max_new_tokens=12)
        total_preempts = sum(eng.preempt_stats.values())
        assert total_preempts > 0       # the pool forces preemption
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    def test_block_accounting_exact_after_serve(self, cfg, rng):
        eng = mk_engine(cfg, prefix_cache=True)
        prompts = self.shared_prompts(rng)
        eng.generate(prompts, max_new_tokens=6)
        alloc = eng.state.allocator
        # free + radix-resident == total, and everything left is evictable
        assert alloc.free_blocks + eng.state.radix.node_count \
            == alloc.num_blocks
        assert eng.state.available_blocks == alloc.num_blocks
        q = eng.query()
        assert q["cached_kv_blocks"] == eng.state.radix.node_count
        assert q["available_kv_blocks"] == alloc.num_blocks
        # refcounts: every cached block held exactly once (by the radix)
        node_blocks = []
        stack = list(eng.state.radix.root.children.values())
        while stack:
            nd = stack.pop()
            node_blocks.append(nd.block)
            stack.extend(nd.children.values())
        assert all(alloc.refcount(b) == 1 for b in node_blocks)
        eng.state.radix.check_invariants()

    def test_sampled_generate_runs_with_cache(self, cfg, rng):
        """do_sample with the cache on: same seed + same cache state must
        reproduce (the matched prefix changes scheduling, not the rng
        threading)."""
        prompts = self.shared_prompts(rng)
        mk = lambda: mk_engine(cfg, prefix_cache=True)
        a = mk().generate(prompts, max_new_tokens=10, seed=3,
                          do_sample=True, temperature=1.0)
        b = mk().generate(prompts, max_new_tokens=10, seed=3,
                          do_sample=True, temperature=1.0)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


class TestChunkedPrefillFairness:
    def test_decode_not_starved_by_long_prefill(self, cfg, rng):
        """Continuous chunked-prefill load must not starve running
        decoders: short requests admitted alongside a long prompt finish
        BEFORE the long prompt even produces its first token (decode
        priority + chunk bound), and the chunk counter books the stream."""
        eng = v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": dict(BASE_SM, max_q_per_seq=8,
                                  prefill_chunk_tokens=8)}, seed=0)
        clk = [0.0]

        def now():
            clk[0] += 1.0
            return clk[0]
        # shorts FIRST (FIFO): they are mid-decode when the long prompt's
        # chunks start streaming through the same rounds
        long_p = rng.integers(0, 97, (48,)).astype(np.int32)
        shorts = [rng.integers(0, 97, (4,)).astype(np.int32)
                  for _ in range(3)]
        outs = eng.generate(shorts + [long_p], max_new_tokens=[8, 8, 8, 4],
                            now_fn=now, eos_token_id=None)
        assert [len(o) for o in outs] == [8, 8, 8, 4]
        t = eng.telemetry
        # one 48-token prompt in 8-token chunks -> ≥ 6 chunks booked
        assert t.value("prefill_chunks_total") >= 6
        recs = {r["uid"]: r for r in t.request_log}
        long_rec = recs[-4]
        # decode-priority + chunk bound: every decoder emits its first
        # token before the long prefill completes AND retires before the
        # long request — a scheduler that let the long prompt monopolize
        # rounds would push the shorts' decode behind its whole prefill
        # (e2e is <=: once the long prompt turns decode-ready the fused
        # burst can retire a short's last token and the long's in the SAME
        # dispatch, giving them one timestamp)
        for uid in (-1, -2, -3):
            assert recs[uid]["ttft_ms"] < long_rec["ttft_ms"], (uid, recs)
            assert recs[uid]["e2e_ms"] <= long_rec["e2e_ms"], (uid, recs)

    def test_chunk_cap_bounds_per_round_prefill(self, cfg, rng):
        """No round schedules more prefill tokens than the cap (asserted
        via the mixed-dispatch bucket: with cap 8 + ≤4 decodes the padded
        bucket never exceeds 64, so no full-budget prefill round ran)."""
        eng = v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": dict(BASE_SM, max_q_per_seq=16,
                                  prefill_chunk_tokens=8)}, seed=0)
        prompts = [rng.integers(0, 97, (30,)).astype(np.int32)
                   for _ in range(3)]
        want = mk_engine(cfg, max_q_per_seq=16).generate(
            prompts, max_new_tokens=5)
        got = eng.generate(prompts, max_new_tokens=5)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)     # chunking never changes
        #                                             tokens, only batching
        n_chunks = eng.telemetry.value("prefill_chunks_total")
        assert n_chunks >= sum(-(-len(p) // 8) for p in prompts)


class TestSLAScheduler:
    SLA_CFG = {"sla_classes": {
        "batch": {"priority": 0},
        "gold": {"priority": 10, "ttft_slo_ms": 1.0}}}

    def mk(self, cfg, **sm):
        return v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": dict(BASE_SM, **sm),
            "scheduler": self.SLA_CFG}, seed=0)

    def test_unknown_class_rejected(self, cfg, rng):
        eng = self.mk(cfg)
        with pytest.raises(ValueError, match="unknown SLA class"):
            eng.generate([rng.integers(0, 97, (6,)).astype(np.int32)],
                         max_new_tokens=2, sla=["platinum"])

    def test_priority_admission_order(self, cfg, rng):
        """With one slot and simultaneous arrivals, the high-priority
        request is admitted first regardless of list order."""
        eng = self.mk(cfg, max_tracked_sequences=1,
                      max_ragged_sequence_count=1)
        clk = [0.0]

        def now():
            clk[0] += 0.01
            return clk[0]
        prompts = [rng.integers(0, 97, (6,)).astype(np.int32)
                   for _ in range(2)]
        eng.generate(prompts, max_new_tokens=4, now_fn=now,
                     arrival_times=[0.0, 0.0], sla=["batch", "gold"])
        recs = {r["uid"]: r for r in eng.telemetry.request_log}
        assert recs[-2]["ttft_ms"] < recs[-1]["ttft_ms"]    # gold first

    def test_sla_preemption_fires_and_stays_token_exact(self, cfg, rng):
        """A gold arrival mid-decode preempts the batch request (the
        serving_preemptions_total policy trigger) and BOTH outputs match
        uncontended runs exactly (fold-back invariant)."""
        eng = self.mk(cfg, max_tracked_sequences=1,
                      max_ragged_sequence_count=1)
        clk = [0.0]

        def now():
            clk[0] += 0.05
            return clk[0]
        p_lo = rng.integers(0, 97, (8,)).astype(np.int32)
        p_hi = rng.integers(0, 97, (6,)).astype(np.int32)
        got = eng.generate([p_lo, p_hi], max_new_tokens=[40, 4],
                           now_fn=now, arrival_times=[0.0, 0.2],
                           sla=["batch", "gold"])
        t = eng.telemetry
        assert t.value("serving_sla_preemptions_total", sla="batch") >= 1
        assert t.value("serving_preemptions_total",
                       kind="decode_ready") >= 1
        assert t.value("serving_admissions_total", sla="gold",
                       decision="preempted_for") >= 1
        assert t.value("serving_admissions_total", sla="gold",
                       decision="admitted") == 1
        ref = mk_engine(cfg)
        np.testing.assert_array_equal(
            got[0], ref.generate([p_lo], max_new_tokens=40)[0])
        np.testing.assert_array_equal(
            got[1], ref.generate([p_hi], max_new_tokens=4)[0])
        # gold met its latency goal: first token well before batch retired
        recs = {r["uid"]: r for r in t.request_log}
        assert recs[-2]["preempts"] == 0
        assert recs[-1]["preempts"] >= 1

    def test_default_class_keeps_legacy_behavior(self, cfg, rng):
        """No sla argument -> byte-identical to an engine without the
        scheduler block (the SLA machinery must not engage)."""
        prompts = [rng.integers(0, 97, (9 + i,)).astype(np.int32)
                   for i in range(3)]
        want = mk_engine(cfg).generate(prompts, max_new_tokens=8)
        got = self.mk(cfg).generate(prompts, max_new_tokens=8)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)


class TestResidencyRouting:
    """serving/router.py prefix_affinity: real radix residency (PR 7 stub
    closed)."""

    def mk_router(self):
        from deepspeed_tpu.serving.router import Router, RouterConfig
        from deepspeed_tpu.telemetry.registry import MetricRegistry
        return Router(RouterConfig(policy="prefix_affinity"),
                      clock=lambda: 0.0, registry=MetricRegistry())

    class Rep:
        def __init__(self, name, engine=None):
            self.name = name
            self.engine = engine

        def enqueue(self, req):
            pass

    class Eng:
        def __init__(self, resident):
            self._n = resident

        def prefix_cached_tokens(self, prompt):
            return min(self._n, len(prompt))

    def test_routes_to_longest_resident_prefix(self):
        from deepspeed_tpu.serving.router import FleetRequest
        r = self.mk_router()
        reps = [self.Rep("r0", self.Eng(0)), self.Rep("r1", self.Eng(16)),
                self.Rep("r2", self.Eng(8)), self.Rep("r3")]
        req = FleetRequest(index=0, prompt=np.arange(32, dtype=np.int32),
                           max_new_tokens=4)
        assert r.pick(req, reps).name == "r1"
        # the favorite dying -> next-best survivor, never an error
        assert r.pick(req, [x for x in reps if x.name != "r1"]).name == "r2"

    def test_residency_tie_breaks_least_outstanding(self):
        from deepspeed_tpu.serving.router import FleetRequest
        r = self.mk_router()
        a, b = self.Rep("a", self.Eng(8)), self.Rep("b", self.Eng(8))
        busy = FleetRequest(index=0, prompt=np.arange(32, dtype=np.int32),
                            max_new_tokens=4)
        r.submit(busy)
        r.dispatch(busy, a, now=0.0)
        req = FleetRequest(index=1, prompt=np.arange(32, dtype=np.int32),
                           max_new_tokens=4)
        assert r.pick(req, [a, b]).name == "b"

    def test_probe_exception_degrades_gracefully(self):
        from deepspeed_tpu.serving.router import FleetRequest

        class BadEng:
            def prefix_cached_tokens(self, prompt):
                raise RuntimeError("mid-death probe")
        r = self.mk_router()
        reps = [self.Rep("r0", BadEng()), self.Rep("r1", self.Eng(4))]
        req = FleetRequest(index=0, prompt=np.arange(8, dtype=np.int32),
                           max_new_tokens=4)
        assert r.pick(req, reps).name == "r1"


class TestFleetPrefixCache:
    def test_migration_reprefills_uncached_suffix_token_exact(self, cfg, rng):
        """Replica death with prefix caches on: migrated requests land on
        the survivor (whose radix may hold their shared prefix from its own
        traffic), re-prefill only what is uncached there, and the outputs
        stay byte-identical to a no-failure single engine."""
        from deepspeed_tpu.runtime import faults
        from deepspeed_tpu.serving import ServingFleet
        ecfg = {"dtype": "fp32",
                "state_manager": dict(BASE_SM, prefix_cache=True)}
        shared = rng.integers(0, 97, (16,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.integers(0, 97, (3 + i,)).astype(np.int32)])
            for i in range(4)]
        want = mk_engine(cfg, prefix_cache=True).generate(
            prompts, max_new_tokens=10)
        faults.reset()
        fleet = ServingFleet(cfg, engine_config=ecfg,
                             config={"num_replicas": 2, "respawn": False,
                                     "router": {
                                         "policy": "prefix_affinity",
                                         "max_retries": 3}})
        try:
            fleet.serve(prompts, max_new_tokens=10, max_wall_s=600)  # warm
            faults.inject("replica.mid_decode", "exc")
            outs = fleet.serve(prompts, max_new_tokens=10, max_wall_s=600)
        finally:
            faults.reset()
            fleet.shutdown()
        deaths = fleet.registry._metrics[
            "fleet_replica_deaths_total"].value(reason="replica_death")
        assert deaths >= 1
        for w, g in zip(want, outs):
            np.testing.assert_array_equal(w, g)
