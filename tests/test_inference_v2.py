"""Inference v2 (ragged/paged serving) tests — reference pattern:
tests/unit/inference/v2/{ragged,model_implementations}."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import v2_engine

from deepspeed_tpu.inference.v2 import (BlockedAllocator, DSStateManager,
                                        InferenceEngineV2)
from deepspeed_tpu.models import GPTConfig
from deepspeed_tpu.models.gpt import GPTLogits


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig.tiny(vocab_size=97, max_seq_len=64)


@pytest.fixture(scope="module")
def v2cfg():
    return {"dtype": "fp32",
            "state_manager": {"max_tracked_sequences": 4,
                              "max_ragged_batch_size": 64,
                              "kv_block_size": 8, "max_q_per_seq": 16}}


@pytest.fixture()
def engine(cfg, v2cfg):
    return v2_engine(cfg, config=v2cfg, seed=0)


# one program a (model, length), not one a primitive of an eager forward
_forward = jax.jit(lambda lm, params, ids: lm.apply({"params": params}, ids),
                   static_argnums=0)


def full_logits(cfg, engine, ids):
    """Ground truth: cache-free full forward on the same params."""
    return np.asarray(_forward(GPTLogits(engine.model_config), engine.params,
                               jnp.asarray(ids, jnp.int32)))


class TestAllocator:
    def test_allocate_free_cycle(self):
        a = BlockedAllocator(10)
        b1 = a.allocate(4)
        assert a.free_blocks == 6
        a.free(b1)
        assert a.free_blocks == 10
        with pytest.raises(RuntimeError, match="exhausted"):
            a.allocate(11)

    def test_state_manager_slots(self):
        st = DSStateManager(max_tracked_sequences=2, num_blocks=8,
                            block_size=8, max_seq_len=64)
        st.create(1)
        st.create(2)
        with pytest.raises(RuntimeError, match="capacity"):
            st.create(3)
        st.flush(1)
        st.create(3)


# ------------------------------------------- one-row slots of a mixed step

def _mixed_program(engine):
    """The engine's mixed step program, jitted apart from ``engine._steps``:
    the prefill route below patches what its trace reads."""
    import functools

    from deepspeed_tpu.inference.v2.model import ragged_forward
    sm = engine.config.state_manager
    return jax.jit(functools.partial(
        ragged_forward, cfg=engine.model_config,
        block_size=engine._block_size, max_q_per_seq=sm.max_q_per_seq,
        **engine._model_static))


def _mixed_step(engine, uids, toks, step=None):
    """What ``put`` does, but always through the mixed program (``put``
    hands a step of one-row slots to the decode program): logits of the
    scheduled slots.  ``step``: the engine's ``_mixed_program``, traced once
    for all its steps; without it, the step as it was before one-row slots
    went to the paged decode op (a program of its own a step: the patched
    ops close over that step's schedule)."""
    from deepspeed_tpu.inference.v2.ragged import build_ragged_batch
    sm = engine.config.state_manager
    schedule = []
    for uid, t in zip(uids, toks):
        seq = engine.state.get(uid) or engine.state.create(uid)
        engine.state.ensure_blocks(seq, len(t))
        schedule.append((seq, np.asarray(t, np.int32)))
    rb = build_ragged_batch(schedule, engine.state, sm.max_ragged_batch_size,
                            sm.max_q_per_seq)
    batch = jax.tree_util.tree_map(jnp.asarray, {
        "tokens": rb.tokens, "token_slot": rb.token_slot,
        "token_pos": rb.token_pos,
        **rb.table_operands(), "kv_len": rb.kv_len})
    with pytest.MonkeyPatch.context() as m:
        if step is None:
            _prefill_route_for_every_slot(m, rb.kv_len, rb.q_len)
            step = _mixed_program(engine)
        logits, engine.cache = step(engine.params, engine.cache, batch)[:2]
    for seq, t in schedule:
        seq.seen_tokens += len(t)
    return np.asarray(logits)[rb.logits_slots]


def _prefill_route_for_every_slot(monkeypatch, kv_len, q_len):
    """The ragged prefill reference attends every slot by the rows the
    SCHEDULE gives it (``kv_len``, ``q_len``: whatever lengths and counts
    the model hands the ops), a one-row slot as a chunk of one row."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops.paged_attention import xla_ragged_prefill
    kv_len, q_len = jnp.asarray(kv_len), jnp.asarray(q_len)

    def prefill(q, k, v, table, kv_lens, q_starts, q_counts, row_starts, *,
                impl=None, **kw):
        return xla_ragged_prefill(q, k, v, table, kv_len, kv_len - q_len,
                                  q_len, row_starts, **kw)

    def decode(q, k, v, table, kv_lens, *, impl=None, **kw):
        return xla_ragged_prefill(q, k, v, table, kv_len, kv_len - q_len,
                                  jnp.minimum(q_len, 1),
                                  jnp.arange(q.shape[0]), max_q=1, **kw)
    monkeypatch.setattr(ops, "ragged_prefill_attention", prefill)
    monkeypatch.setattr(ops, "paged_attention", decode)


def _one_row_engine(cfg=None, seed=0, **sm):
    if cfg is None:         # heads of 128: standard (row-major) pages of 8
        cfg = GPTConfig.llama(num_layers=2, hidden=256, heads=2,
                              num_kv_heads=1, vocab_size=97, max_seq_len=64)
    manager = {"max_tracked_sequences": 4, "max_ragged_batch_size": 64,
               "kv_block_size": 8, "max_q_per_seq": 16, **sm}
    return lambda: v2_engine(
        cfg, config={"dtype": "fp32", "state_manager": manager}, seed=seed)


def _afmoe_engine(window):
    import test_trinity as tt
    cfg, params = tt.model(tt.sizes(window=window))
    return lambda: tt.engine(cfg, params)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, (n,)).astype(np.int32)


# case -> (engine factory, [(uids, token lists) a step]); pages hold 8
# tokens (4 in the afmoe model, 128 where the layout demands it)
ONE_ROW_CASES = {
    # contexts 11, 15 and 16: the riders' rows end mid-page, on a page's
    # last row and on the first row of a new page
    "riders-beside-a-chunk": (_one_row_engine(), [
        ([1, 2, 3], [_ids(11, 1), _ids(15, 2), _ids(16, 3)]),
        ([1, 2, 3, 4], [[5], [6], [7], _ids(13, 4)]),
        ([4, 1, 2, 3], [_ids(16, 5), [8], [9], [10]])]),
    "one-token-tail-and-one-token-prompt": (_one_row_engine(), [
        ([1, 2], [_ids(16, 1), _ids(9, 2)]),
        ([1, 3, 2], [_ids(1, 3), [42], _ids(7, 4)]),
        ([1, 3, 4], [[5], [6], _ids(12, 5)])]),
    "riders-only-beside-an-empty-slot": (_one_row_engine(), [
        ([1, 2, 3], [_ids(8, 1), _ids(13, 2), _ids(3, 3)]),
        ([1, 2, 3], [[5], [6], [7]]),
        ([3, 1], [[8], [9]])]),
    "gqa-riders": (_one_row_engine(GPTConfig.llama(
        num_layers=2, hidden=512, heads=4, num_kv_heads=2, vocab_size=97,
        max_seq_len=64)), [
        ([1, 2], [_ids(16, 1), _ids(7, 2)]),
        ([1, 2, 3], [[5], [6], _ids(10, 3)])]),
    # tiny heads of 8: token-on-lanes pages of 128
    "kv-major-pages": (_one_row_engine(
        GPTConfig.tiny(vocab_size=97, max_seq_len=64)), [
        ([1, 2], [_ids(16, 1), _ids(7, 2)]),
        ([1, 2, 3], [[5], [6], _ids(10, 3)]),
        ([3, 1, 2], [_ids(1, 4), [7], [8]])]),
    "int8-kv-pool": (_one_row_engine(kv_quant="int8"), [
        ([1, 2], [_ids(16, 1), _ids(7, 2)]),
        ([1, 2, 3], [[5], [6], _ids(10, 3)]),
        ([3, 1, 2], [_ids(1, 4), [7], [8]])]),
    "int8-kv-major-pool": (_one_row_engine(
        GPTConfig.tiny(vocab_size=97, max_seq_len=64), kv_quant="int8"), [
        ([1, 2], [_ids(16, 1), _ids(7, 2)]),
        ([1, 2, 3], [[5], [6], _ids(10, 3)])]),
    # the afmoe model's two page groups (pages of 4, chunks of 8): a rider
    # at context 19 past a window of 6 reads across released pages, one at
    # 5 still inside it
    "window-and-global-page-groups": (_afmoe_engine(6), [
        ([1], [_ids(8, 1)]), ([1], [_ids(8, 2)]),
        ([1, 2], [_ids(3, 3), _ids(5, 4)]),
        ([1, 2, 3], [[5], [6], _ids(8, 5)]),
        ([1, 2, 3], [[7], [8], _ids(2, 6)]),
        ([1, 2, 3], [[9], [10], [11]])]),
    "window-wider-than-the-riders": (_afmoe_engine(12), [
        ([1], [_ids(8, 1)]), ([1, 2], [_ids(3, 3), _ids(5, 4)]),
        ([1, 2, 3], [[5], [6], _ids(8, 5)]),
        ([1, 2, 3], [[7], [8], [9]])]),
}


class TestRaggedForward:
    def test_single_seq_prefill_matches_full_forward(self, cfg, engine, rng):
        ids = rng.integers(0, 97, (12,)).astype(np.int32)
        logits = engine.put([7], [ids])
        want = full_logits(cfg, engine, ids[None])[0, -1]
        np.testing.assert_allclose(logits[0], want, atol=1e-4, rtol=1e-4)

    def test_decode_steps_match_full_forward(self, cfg, engine, rng):
        ids = rng.integers(0, 97, (10,)).astype(np.int32)
        engine.put([1], [ids])
        # two incremental decode tokens
        l1 = engine.put([1], [np.asarray([5], np.int32)])
        want1 = full_logits(cfg, engine,
                            np.concatenate([ids, [5]])[None])[0, -1]
        np.testing.assert_allclose(l1[0], want1, atol=1e-4, rtol=1e-4)
        l2 = engine.put([1], [np.asarray([9], np.int32)])
        want2 = full_logits(cfg, engine,
                            np.concatenate([ids, [5, 9]])[None])[0, -1]
        np.testing.assert_allclose(l2[0], want2, atol=1e-4, rtol=1e-4)

    def test_ragged_mixed_batch_matches_separate(self, cfg, engine, rng):
        """Prefill of one seq + decode of another in ONE ragged forward."""
        a = rng.integers(0, 97, (9,)).astype(np.int32)
        b = rng.integers(0, 97, (13,)).astype(np.int32)
        engine.put([1], [a])                    # a in cache
        logits = engine.put([1, 2], [np.asarray([3], np.int32), b])
        want_a = full_logits(cfg, engine,
                             np.concatenate([a, [3]])[None])[0, -1]
        want_b = full_logits(cfg, engine, b[None])[0, -1]
        np.testing.assert_allclose(logits[0], want_a, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(logits[1], want_b, atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("case", sorted(ONE_ROW_CASES))
    def test_one_row_slots_match_the_prefill_route(self, case):
        """A mixed step sends each slot that holds ONE row to the paged
        decode op and the others to the ragged prefill op.  Every step's
        logits and the pages it wrote equal the route before that (every
        slot through ``xla_ragged_prefill``)."""
        make, steps = ONE_ROW_CASES[case]
        new, old = make(), make()       # the same weights, from the seed
        program = _mixed_program(new)
        for uids, toks in steps:
            got = _mixed_step(new, uids, toks, program)
            want = _mixed_step(old, uids, toks)
            np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
        for g, w in zip(new.cache, old.cache):
            assert (g is None) == (w is None)
            if w is not None:
                np.testing.assert_allclose(
                    np.asarray(g, np.float32), np.asarray(w, np.float32),
                    atol=1e-4, rtol=1e-4)

    def test_split_prompt_matches_one_shot(self, cfg, engine, rng):
        """SplitFuse chunking: a prompt fed in 3 chunks gives the same final
        logits as the one-shot prefill."""
        ids = rng.integers(0, 97, (30,)).astype(np.int32)
        engine.put([1], [ids[:16]])
        engine.put([1], [ids[16:24]])
        logits = engine.put([1], [ids[24:]])
        want = full_logits(cfg, engine, ids[None])[0, -1]
        np.testing.assert_allclose(logits[0], want, atol=1e-4, rtol=1e-4)

    def test_budget_and_chunk_guards(self, cfg, engine, rng):
        """One forward's guards stand where the forward is built; ``put()``
        runs a call that holds more than a forward as chunks."""
        with pytest.raises(ValueError, match="max_q_per_seq"):
            engine._put_device([1], [np.zeros(17, np.int32)])
        with pytest.raises(ValueError, match="budget"):
            engine._put_device([1, 2, 3, 4, 5],
                               [np.zeros(16, np.int32)] * 5)
        ids = rng.integers(0, 97, (2, 40)).astype(np.int32)
        got = engine.put([1, 2], list(ids))     # 80 tokens: 16-row chunks
        np.testing.assert_allclose(got, full_logits(cfg, engine, ids)[:, -1],
                                   atol=1e-4, rtol=1e-4)


class TestQueryFlush:
    def test_query_and_flush_accounting(self, engine, rng):
        free0 = engine.query()["free_kv_blocks"]
        engine.put([1], [rng.integers(0, 97, (12,)).astype(np.int32)])
        q = engine.query()
        assert q["free_kv_blocks"] == free0 - 2   # 12 tokens / block 8 -> 2
        assert engine.can_schedule([2], [16])
        engine.flush([1])
        assert engine.query()["free_kv_blocks"] == free0

    def test_can_schedule_limits(self, engine):
        assert not engine.can_schedule([1, 2], [40, 40])  # > 64 budget


class TestContinuousBatching:
    def test_generate_matches_v1_engine(self, cfg, v2cfg, rng):
        """Greedy continuous-batching output == v1 static-cache output, with
        more prompts than sequence slots (forces admission control)."""
        import deepspeed_tpu
        engine = v2_engine(cfg, config=v2cfg, seed=0)
        prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
                   for n in (9, 23, 5, 30, 12, 7)]   # 6 prompts, 4 slots
        got = engine.generate(prompts, max_new_tokens=6)
        v1 = deepspeed_tpu.init_inference(cfg, config={"dtype": "fp32"})
        # same seed 0 -> same params as the v2 engine
        for p, g in zip(prompts, got):
            want = v1.generate(p[None], max_new_tokens=6)[0]
            np.testing.assert_array_equal(want, g)

    def test_burst_path_matches_v1(self, cfg, v2cfg, rng):
        """max_new_tokens >= 8 with no waiting prompts engages the fused
        decode burst; output must equal the v1 static-cache engine."""
        import deepspeed_tpu
        engine = v2_engine(cfg, config=v2cfg, seed=0)
        prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
                   for n in (9, 14)]
        got = engine.generate(prompts, max_new_tokens=16)
        v1 = deepspeed_tpu.init_inference(cfg, config={"dtype": "fp32"})
        for p, g in zip(prompts, got):
            want = v1.generate(p[None], max_new_tokens=16)[0]
            np.testing.assert_array_equal(want, g)

    def test_oversubscribed_kv_pool_defers_instead_of_crashing(self, cfg, rng):
        """A KV pool too small for all requests at once must page: requests
        queue/defer until finished sequences free blocks (this crashed with
        'KV cache exhausted' before block reservation moved to schedule
        time)."""
        engine = v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": {"max_tracked_sequences": 4,
                              "max_ragged_batch_size": 64,
                              "kv_block_size": 8, "max_q_per_seq": 16,
                              "num_kv_blocks": 6}}, seed=0)
        # each request needs 24 tokens = 3 blocks; pool holds 6 -> 2 at a time
        prompts = [rng.integers(0, 97, (14,)).astype(np.int32)
                   for _ in range(3)]
        out = engine.generate(prompts, max_new_tokens=10)
        assert all(len(o) == 10 for o in out)
        # pool fully freed afterwards
        assert engine.query()["free_kv_blocks"] == 6

    def test_put_capacity_validation_leaves_state_clean(self, cfg, v2cfg):
        engine = v2_engine(cfg, config=v2cfg, seed=0)
        with pytest.raises(RuntimeError, match="free slots"):
            engine.put([1, 2, 3, 4, 5], [np.zeros(1, np.int32)] * 5)
        assert engine.state.free_sequence_slots == 4  # nothing leaked

    def test_generate_eos_stops(self, cfg, v2cfg, rng):
        engine = v2_engine(cfg, config=v2cfg, seed=0)
        p = rng.integers(0, 97, (8,)).astype(np.int32)
        ref = engine.generate([p], max_new_tokens=6)[0]
        engine2 = v2_engine(cfg, config=v2cfg, seed=0)
        got = engine2.generate([p], max_new_tokens=6,
                               eos_token_id=int(ref[0]))[0]
        assert len(got) == 1 and got[0] == ref[0]


class TestInt8KVCache:
    """kv_quant="int8": per-token symmetric KV quantization (ZeRO-Inference's
    memory trade applied to the KV side) — halves cache bytes, and the
    mixed/decode/burst paths all read through the dequant fallback."""

    def mk(self, cfg, v2cfg, quant):
        sm = dict(v2cfg["state_manager"], kv_quant=quant)
        return v2_engine(cfg, config={**v2cfg, "state_manager": sm},
                         seed=0)

    def test_cache_bytes_halved(self, cfg, v2cfg):
        full = self.mk(cfg, v2cfg, None)
        q8 = self.mk(cfg, v2cfg, "int8")
        fb = full.cache.k.nbytes + full.cache.v.nbytes
        qb = sum(a.nbytes for a in (q8.cache.k, q8.cache.v,
                                    q8.cache.k_scale, q8.cache.v_scale))
        # fp32 cache in the test config: int8 payload is 4x smaller and the
        # fp32 per-token scales add 4/head_dim (tiny cfg: hd=8 -> 0.375)
        assert qb < 0.4 * fb, (qb, fb)

    def test_put_logits_close_to_unquantized(self, cfg, v2cfg, rng):
        full = self.mk(cfg, v2cfg, None)
        q8 = v2_engine(
            cfg, config={**v2cfg, "state_manager": dict(
                v2cfg["state_manager"], kv_quant="int8")},
            params=full.params)
        ids = rng.integers(0, 97, (14,)).astype(np.int32)
        a = full.put([1], [ids])[0]
        b = q8.put([1], [ids])[0]
        rel = np.linalg.norm(a - b) / np.linalg.norm(a)
        assert rel < 0.05, rel

    def test_generate_runs_all_paths_and_tracks_greedy(self, cfg, v2cfg, rng):
        """generate() drives mixed + decode + burst programs over the
        quantized cache; greedy output should mostly agree with the
        unquantized engine (near-tie flips from quant noise allowed)."""
        full = self.mk(cfg, v2cfg, None)
        q8 = v2_engine(
            cfg, config={**v2cfg, "state_manager": dict(
                v2cfg["state_manager"], kv_quant="int8")},
            params=full.params)
        prompts = [rng.integers(0, 97, (16 + i,)).astype(np.int32)
                   for i in range(3)]
        a = full.generate(prompts, max_new_tokens=12)
        b = q8.generate(prompts, max_new_tokens=12)
        agree = sum(int(np.sum(np.asarray(x) == np.asarray(y)))
                    for x, y in zip(a, b))
        total = sum(len(x) for x in a)
        assert all(len(x) == len(y) for x, y in zip(a, b))
        assert agree / total > 0.7, (agree, total)


def _scatter_write(flat_k, flat_v, flat_ks, flat_vs, k, v, plan, base, km,
                   mesh=None):
    """The plain write, the reference for ``model._kv_write``: a scatter of
    [nkv, hd] windows at (page, :, offset), one row at a time, as the model
    wrote until the pool had to stay row-major (it made the compiler re-lay
    the pool around every step program; the values are the same)."""
    from deepspeed_tpu.inference.v2.model import quantize_kv_token
    big = jnp.iinfo(jnp.int32).max
    if km:      # per-page plan -> (page, offset, row) of every written token
        page, start, lo, hi = plan.unit, plan.start, plan.lo, plan.hi
        bs = flat_k.shape[3]
        r = jnp.arange(bs)[None, :]
        live = ((r >= lo[:, None]) & (r < hi[:, None])).reshape(-1)
        row = jnp.clip((start[:, None] + r).reshape(-1), 0, k.shape[0] - 1)
        k, v = k[row], v[row]
        page, off = jnp.repeat(page, bs), jnp.tile(jnp.arange(bs), len(lo))
    else:
        page, off, live = plan.rows
    page = jnp.where(live, base + page, big)
    if flat_ks is not None:
        k, ks = quantize_kv_token(k)
        v, vs = quantize_kv_token(v)
        flat_ks = flat_ks.at[page, :, off].set(ks, mode="drop")
        flat_vs = flat_vs.at[page, :, off].set(vs, mode="drop")
    at = (page, slice(None), slice(None), off) if km else (
        page, slice(None), off)
    return (flat_k.at[at].set(k.astype(flat_k.dtype), mode="drop"),
            flat_v.at[at].set(v.astype(flat_v.dtype), mode="drop"),
            flat_ks, flat_vs)


class TestKVWrite:
    """``_write_plan`` + ``_kv_write`` against a numpy loop over the rows:
    the same values in the same places, pads and inactive slots dropped,
    every other token of the pool untouched — for the three shapes a step
    has (one row a slot; a packed ragged batch with runs that start and end
    inside pages; the dense verify layout), both page layouts, int8, and
    with the kv heads sharded over a ``tp`` mesh."""

    S, MB, NB, L, nkv, hd, bs = 4, 4, 16, 2, 2, 8, 8

    def _step(self, shape):
        """(row_slot [N] with S for dropped rows, row_pos [N],
        rows_per_slot)."""
        S = self.S
        if shape == "decode":        # slot 1 inactive; last token of a page
            return (np.array([0, S, 2, 3]), np.array([0, 5, 15, 19]), 1)
        if shape == "mixed":
            # slot 0: 7 rows from 5 (crosses a page, ends inside one);
            # slot 1: nothing; slot 2: exactly one whole page; slot 3: 3 rows
            # over a boundary; 6 pad rows at the end
            runs = [(0, 5, 7), (2, 0, 8), (3, 14, 3)]
            slot = np.concatenate([np.full(n, s) for s, _, n in runs]
                                  + [np.full(6, S)])
            pos = np.concatenate([np.arange(p, p + n) for _, p, n in runs]
                                 + [np.zeros(6, int)])
            return slot, pos, 8
        G = 3                        # verify: dense [S, G], slot 2 inactive
        active = np.array([True, True, False, True])
        pos0 = np.array([6, 0, 3, 13])
        slot = np.repeat(np.where(active, np.arange(S), S), G)
        pos = (pos0[:, None] + np.arange(G)[None]).reshape(-1)
        return slot, pos, G

    @pytest.mark.parametrize("tp", [1, 2], ids=["tp1", "tp2"])
    @pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8kv"])
    @pytest.mark.parametrize("km", [False, True], ids=["std", "kvmajor"])
    @pytest.mark.parametrize("shape", ["decode", "mixed", "verify"])
    def test_matches_numpy_reference(self, rng, shape, km, quant, tp):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu.inference.v2.model import (_kv_write, _write_plan,
                                                      quantize_kv_token)
        from deepspeed_tpu.parallel import mesh as mesh_lib
        mesh = None if tp == 1 else mesh_lib.build_mesh(
            mesh_lib.MeshSpec(tp=tp, dp=1, fsdp=1))

        def place(a):       # the pool as the engine holds it: heads sharded
            if a is None or mesh is None:
                return None if a is None else jnp.asarray(a)
            return jax.device_put(a, NamedSharding(mesh, P(
                None, "tp", *(None,) * (a.ndim - 2))))
        S, NB, L, nkv, hd, bs = (self.S, self.NB, self.L, self.nkv, self.hd,
                                 self.bs)
        slot, pos, per_slot = self._step(shape)
        N = len(slot)
        bt = rng.permutation(NB)[:S * self.MB].reshape(S, self.MB)
        page_shape = (nkv, hd, bs) if km else (nkv, bs, hd)
        dt = np.int8 if quant else np.float32
        pools = [rng.integers(-9, 9, (L * NB,) + page_shape).astype(dt)
                 for _ in range(2)]
        scales = ([rng.random((L * NB, nkv, bs)).astype(np.float32)
                   for _ in range(2)] if quant else [None, None])
        k, v = (rng.standard_normal((N, nkv, hd)).astype(np.float32)
                for _ in range(2))
        li = 1
        plan = _write_plan(jnp.asarray(bt, jnp.int32),
                           jnp.asarray(slot, jnp.int32),
                           jnp.asarray(pos, jnp.int32), bs, per_slot, km)
        got = _kv_write(*(place(a) for a in pools + scales), jnp.asarray(k),
                        jnp.asarray(v), plan, li * NB, km, mesh=mesh)
        if mesh is not None:
            assert got[0].sharding.spec[1] == "tp"

        want = [a.copy() for a in pools] + [
            None if a is None else a.copy() for a in scales]
        for x, pool, sc in ((k, want[0], want[2]), (v, want[1], want[3])):
            rows, row_scales = x, None
            if quant:
                rows, row_scales = (np.asarray(a) for a in
                                    quantize_kv_token(jnp.asarray(x)))
            for n in range(N):
                if slot[n] >= S:
                    continue
                pg, off = li * NB + bt[slot[n], pos[n] // bs], pos[n] % bs
                if km:
                    pool[pg, :, :, off] = rows[n]
                else:
                    pool[pg, :, off, :] = rows[n]
                if quant:
                    sc[pg, :, off] = row_scales[n]
        for g, w in zip(got, want):
            assert (g is None) == (w is None)
            if w is not None:
                np.testing.assert_array_equal(np.asarray(g), w)

    @pytest.mark.parametrize("path", ["mixed", "burst", "speculative",
                                      "hd128"])
    def test_generation_identical_to_the_plain_scatter(self, cfg, v2cfg, rng,
                                                       monkeypatch, path):
        """Greedy tokens through the engine with the model's write and with
        the plain per-row scatter in its place: mixed SplitFuse steps (more
        prompts than slots, prompts longer than a chunk), fused decode
        bursts, and speculative draft + verify over both pools.  The tiny
        model's heads of 8 take kv-major pages; ``hd128`` runs mixed steps
        and bursts over standard pages."""
        from deepspeed_tpu.inference.v2 import model as v2model
        lens, new = {"mixed": ((9, 23, 5, 30, 12, 7), 6),
                     "burst": ((9, 14), 16),
                     "speculative": ((10, 13, 16), 18),
                     "hd128": ((9, 23, 5, 30, 12, 7), 16)}[path]
        if path == "hd128":
            cfg = GPTConfig.llama(num_layers=2, hidden=128, heads=1,
                                  vocab_size=97, max_seq_len=64)
            assert not v2model.kv_major_layout(cfg)
        prompts = [rng.integers(0, 97, (n,)).astype(np.int32) for n in lens]

        def run(engine):
            eng = engine(cfg, config=v2cfg, seed=0)
            if path == "speculative":
                eng = engine(cfg, config=v2cfg, params=eng.params,
                             draft_model=cfg)
            out = eng.generate(prompts, max_new_tokens=new)
            if path == "speculative":
                assert eng.telemetry.spec_summary()["outer_steps"] > 0
            return out
        got = run(v2_engine)
        monkeypatch.setattr(v2model, "_kv_write", _scatter_write)
        # private engines: the patch is read when the step programs trace
        want = run(InferenceEngineV2)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)


class TestSpeculative:
    """Greedy draft-and-verify decoding: acceptance is exact token match, so
    for ANY draft the output must be token-identical to target-only greedy
    decoding — the invariant every test here pins."""

    def test_identical_draft_exact_and_accepts(self, cfg, v2cfg, rng):
        prompts = [rng.integers(0, 97, (10 + 3 * i,)).astype(np.int32)
                   for i in range(3)]
        base = v2_engine(cfg, config=v2cfg, seed=0)
        want = base.generate(prompts, max_new_tokens=18)
        spec = v2_engine(cfg, config=v2cfg, params=base.params,
                         draft_model=cfg, draft_params=base.params)
        got = spec.generate(prompts, max_new_tokens=18)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        st = spec.telemetry.spec_summary()
        assert st["outer_steps"] > 0          # the spec path actually ran
        # identical weights: the draft should track the target closely
        # (decode vs verify run different-but-equivalent fp32 programs, so
        # rare near-tie divergence is tolerated)
        gamma = spec.config.speculative.gamma
        assert st["emitted_per_outer"] > 0.8 * (gamma + 1), st
        # proposed/accepted/emitted counters are mutually consistent
        assert st["emitted"] == st["accepted"] + st["outer_steps"]
        assert 0.0 <= st["accept_ratio"] <= 1.0

    def test_random_draft_still_exact(self, cfg, v2cfg, rng):
        prompts = [rng.integers(0, 97, (12 + i,)).astype(np.int32)
                   for i in range(3)]
        base = v2_engine(cfg, config=v2cfg, seed=0)
        want = base.generate(prompts, max_new_tokens=15)
        # draft_params=None -> fresh random draft (low acceptance)
        spec = v2_engine(cfg, config=v2cfg, params=base.params,
                         draft_model=cfg)
        got = spec.generate(prompts, max_new_tokens=15)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert spec.telemetry.spec_summary()["outer_steps"] > 0

    def test_eos_and_heterogeneous_budgets(self, cfg, v2cfg, rng):
        prompts = [rng.integers(0, 97, (11 + i,)).astype(np.int32)
                   for i in range(3)]
        budgets = [7, 13, 18]
        base = v2_engine(cfg, config=v2cfg, seed=0)
        want = base.generate(prompts, max_new_tokens=budgets)
        eos = int(want[2][4])                  # force an early stop on seq 2
        want_eos = base.generate(prompts, max_new_tokens=budgets,
                                 eos_token_id=eos)
        spec = v2_engine(cfg, config=v2cfg, params=base.params,
                         draft_model=cfg, draft_params=base.params)
        got = spec.generate(prompts, max_new_tokens=budgets,
                            eos_token_id=eos)
        for w, g in zip(want_eos, got):
            np.testing.assert_array_equal(w, g)


class TestSpeculativeSampled:
    """Rejection-sampling speculative decoding: every emitted token must be
    exactly target-distributed for any draft (Leviathan et al.)."""

    def test_spec_accept_preserves_target_distribution(self):
        """Monte Carlo over the pure accept math: 200k vectorized trials of
        fixed q/p; the first emitted token's empirical distribution must
        match softmax(p_0), and the second (where reached) softmax(p_1)."""
        from deepspeed_tpu.inference.v2.model import spec_accept
        V, gamma, N = 6, 3, 200_000
        rng = np.random.default_rng(0)
        q_log = jnp.asarray(rng.standard_normal((1, gamma, V)), jnp.float32)
        p_log = jnp.asarray(rng.standard_normal((1, gamma + 1, V)),
                            jnp.float32)
        qN = jnp.broadcast_to(q_log, (N, gamma, V))
        pN = jnp.broadcast_to(p_log, (N, gamma + 1, V))
        kd, ka = jax.random.split(jax.random.PRNGKey(0))
        d = jax.random.categorical(kd, qN, axis=-1).astype(jnp.int32)
        emit, counts = jax.jit(spec_accept)(ka, qN, pN, d)
        emit, counts = np.asarray(emit), np.asarray(counts)
        p0 = np.asarray(jax.nn.softmax(p_log[0, 0]))
        freq0 = np.bincount(emit[:, 0], minlength=V) / N
        np.testing.assert_allclose(freq0, p0, atol=0.01)
        m = counts >= 2           # second token emitted (first draft accepted)
        p1 = np.asarray(jax.nn.softmax(p_log[0, 1]))
        freq1 = np.bincount(emit[m, 1], minlength=V) / m.sum()
        np.testing.assert_allclose(freq1, p1, atol=0.02)

    def test_near_greedy_limit_matches_greedy(self, cfg, v2cfg, rng):
        """temperature→0 sampling degenerates to greedy; the sampled spec
        path must then reproduce the target-only greedy output exactly —
        a deterministic end-to-end exercise of the rejection machinery."""
        prompts = [rng.integers(0, 97, (10 + 3 * i,)).astype(np.int32)
                   for i in range(3)]
        base = v2_engine(cfg, config=v2cfg, seed=0)
        want = base.generate(prompts, max_new_tokens=14)
        spec = v2_engine(cfg, config=v2cfg, params=base.params,
                         draft_model=cfg)   # random draft
        got = spec.generate(prompts, max_new_tokens=14, do_sample=True,
                            temperature=1e-5)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert spec.telemetry.spec_summary()["outer_steps"] > 0

    def test_same_seed_reproduces(self, cfg, v2cfg, rng):
        prompts = [rng.integers(0, 97, (12 + i,)).astype(np.int32)
                   for i in range(2)]
        mk = lambda: v2_engine(cfg, config=v2cfg, seed=0,
                               draft_model=cfg)
        a = mk().generate(prompts, max_new_tokens=16, seed=5,
                          do_sample=True, temperature=1.0)
        b = mk().generate(prompts, max_new_tokens=16, seed=5,
                          do_sample=True, temperature=1.0)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


class TestSampledGenerate:
    def test_same_seed_reproduces_from_same_state(self, cfg, v2cfg, rng):
        """do_sample=True with the device-resident rng: same seed + same
        engine state must give identical outputs (rng threads through the
        step/burst programs deterministically); different seeds diverge.
        Draws are keyed per SLOT, so the guarantee is state-identical
        reproducibility — re-running on a used engine may assign different
        slots and legitimately re-draw (scheduling-dependent, as in the
        reference's ragged serving)."""
        prompts = [rng.integers(0, 97, (12 + i,)).astype(np.int32)
                   for i in range(3)]
        mk = lambda: v2_engine(cfg, config=v2cfg, seed=0)
        a = mk().generate(prompts, max_new_tokens=24, seed=7,
                          do_sample=True, temperature=1.0)
        b = mk().generate(prompts, max_new_tokens=24, seed=7,
                          do_sample=True, temperature=1.0)
        c = mk().generate(prompts, max_new_tokens=24, seed=8,
                          do_sample=True, temperature=1.0)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        assert any(not np.array_equal(x, y) for x, y in zip(a, c)), \
            "different seeds produced identical samples"


class TestPreemption:
    def test_recompute_preemption_roundtrip(self, cfg, rng):
        """Two requests whose combined contexts exceed the pool (each fits
        alone): one must be preempted by recompute mid-generation and resumed
        after the other finishes — output must match an uncontended run."""
        mk = lambda: v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": {"max_tracked_sequences": 4,
                              "max_ragged_batch_size": 64,
                              "kv_block_size": 8, "max_q_per_seq": 16,
                              "num_kv_blocks": 6}}, seed=0)
        prompts = [rng.integers(0, 97, (20,)).astype(np.int32)
                   for _ in range(2)]
        # each needs ceil(32/8)=4 blocks; 2*4 > 6 -> preemption must fire
        got = mk().generate(prompts, max_new_tokens=12)
        big = v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": {"max_tracked_sequences": 4,
                              "max_ragged_batch_size": 64,
                              "kv_block_size": 8, "max_q_per_seq": 16}},
            seed=0)
        for p, g in zip(prompts, got):
            want = big.generate([p], max_new_tokens=12)[0]
            np.testing.assert_array_equal(want, g)

    def test_repeated_preemption_thrash_roundtrip(self, cfg, rng):
        """Three requests thrashing a pool that fits ~1.5 of them, with
        chunked prompts (max_q_per_seq < prompt length) so preemption can
        strike a victim whose RE-prefill is still in flight — a second
        preemption must preserve the held continuation token and fold state
        (double-preemption regression; the fold must never be re-applied)."""
        mk = lambda nb: v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": {"max_tracked_sequences": 4,
                              "max_ragged_batch_size": 64,
                              "kv_block_size": 8, "max_q_per_seq": 8,
                              "num_kv_blocks": nb}}, seed=0)
        prompts = [rng.integers(0, 97, (18 + 3 * i,)).astype(np.int32)
                   for i in range(3)]
        big = mk(None)          # uncontended: one request at a time
        want = [big.generate([p], max_new_tokens=14)[0] for p in prompts]
        mid_prefill_hits = 0
        for nb in (6, 7, 8):    # several pressure levels -> several
            eng = mk(nb)
            got = eng.generate(prompts, max_new_tokens=14)
            for w, g in zip(want, got):      # preemption interleavings
                np.testing.assert_array_equal(w, g)
            mid_prefill_hits += eng.preempt_stats["mid_prefill"]
        # the workload must actually strike a victim mid-(re-)prefill, or the
        # double-preemption fold-preservation path was never exercised
        assert mid_prefill_hits > 0

    def test_single_sequence_too_big_for_pool_raises(self, cfg, rng):
        engine = v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": {"max_tracked_sequences": 2,
                              "max_ragged_batch_size": 64,
                              "kv_block_size": 8, "max_q_per_seq": 16,
                              "num_kv_blocks": 2}}, seed=0)
        with pytest.raises(ValueError, match="num_kv_blocks"):
            engine.generate([rng.integers(0, 97, (30,)).astype(np.int32)],
                            max_new_tokens=10)
