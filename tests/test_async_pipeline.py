"""Asynchronous step pipeline tests (ISSUE 5): background device prefetch
(runtime/prefetch.py), overlapped ZeRO-Offload host step
(offload_optimizer.overlap_step — delayed-one-step-update semantics), and
async checkpoint I/O (in-progress marker, commit-ordered 'latest',
wait_for_checkpoint fence, crash-mid-write survivability).

Reference analog: DeepSpeed's delayed parameter update tests
(tests/unit/runtime/zero/test_zero_offload*) + decoupled checkpointing.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.checkpoint import (IN_PROGRESS_FILE, in_progress,
                                      mark_in_progress)
from deepspeed_tpu.runtime.offload import HostStepWorker
from deepspeed_tpu.runtime.prefetch import (PreparedBatch, PrefetchIterator,
                                            _InlinePrefetch)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ helpers

def _init_fn(rng, batch):
    return {"scale": jnp.ones((8,)), "bias": jnp.zeros((8,))}


def _apply_fn(params, batch, rng):
    feat = jnp.tanh(batch["x"]).mean(axis=-1, keepdims=True)      # [B, 1]
    pred = (feat * params["scale"] + params["bias"]).mean(axis=-1)
    return jnp.mean((pred - batch["y"]) ** 2)


def _engine(offload=False, overlap=True, fp16=False, telemetry=False,
            prefetch_depth=None, lr=1e-2):
    zero = {"stage": 2}
    if offload:
        zero["offload_optimizer"] = {"device": "cpu",
                                     "overlap_step": overlap}
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": lr}},
        "zero_optimization": zero,
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        "telemetry": {"enabled": telemetry},
    }
    if fp16:
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 4}
    if prefetch_depth is not None:
        cfg["data_pipeline"] = {"prefetch_depth": prefetch_depth}
    example = {"x": np.zeros((1, 16), np.float32),
               "y": np.zeros((1,), np.float32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=(_init_fn, _apply_fn), config=cfg, example_batch=example)
    return engine


def _data(n, bs, seed=0, nan_at=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        b = {"x": rng.normal(size=(bs, 16)).astype(np.float32),
             "y": rng.normal(size=(bs,)).astype(np.float32)}
        if nan_at is not None and i == nan_at:
            b["x"][0, 0] = np.nan
        out.append(b)
    return out


# ------------------------------------------------- prefetch iterator unit

class TestPrefetchIterator:
    def test_ordering_and_exhaustion(self):
        with PrefetchIterator(range(17), lambda x: x * 3, depth=3) as pf:
            assert list(pf) == [x * 3 for x in range(17)]
            with pytest.raises(StopIteration):
                next(pf)

    def test_depth_validation(self):
        with pytest.raises(ValueError, match="depth"):
            PrefetchIterator(range(4), lambda x: x, depth=0)

    def test_backpressure_bounds_prepared_batches(self):
        """At most depth batches queue + one sits in the blocked put — the
        worker must not run ahead of the consumer unboundedly."""
        prepared = []
        with PrefetchIterator(range(100), lambda x: prepared.append(x) or x,
                              depth=2) as pf:
            deadline = time.time() + 5.0
            while len(prepared) < 3 and time.time() < deadline:
                time.sleep(0.01)
            time.sleep(0.1)          # give a runaway worker rope
            assert len(prepared) <= 3    # depth queued + 1 blocked on put
            assert next(pf) == 0
            deadline = time.time() + 5.0
            while len(prepared) < 4 and time.time() < deadline:
                time.sleep(0.01)
            time.sleep(0.1)
            assert len(prepared) <= 4    # consuming one admits one more

    def test_source_exception_after_buffered_batches(self):
        """A source failure re-raises from __next__ AFTER everything
        prepared before the failure has been consumed."""
        def src():
            yield from range(3)
            raise ValueError("tape ran out")

        pf = PrefetchIterator(src(), lambda x: x + 10, depth=2)
        got = [next(pf), next(pf), next(pf)]
        assert got == [10, 11, 12]
        with pytest.raises(ValueError, match="tape ran out"):
            next(pf)
        with pytest.raises(StopIteration):    # terminal after the error
            next(pf)

    def test_prepare_exception_propagates(self):
        def boom(x):
            if x == 2:
                raise RuntimeError("device_put failed")
            return x

        pf = PrefetchIterator(range(5), boom, depth=2)
        assert [next(pf), next(pf)] == [0, 1]
        with pytest.raises(RuntimeError, match="device_put failed"):
            next(pf)

    def test_close_mid_stream_stops_worker(self):
        def forever():
            i = 0
            while True:
                yield i
                i += 1

        pf = PrefetchIterator(forever(), lambda x: x, depth=2)
        assert next(pf) == 0
        pf.close()
        pf.close()                               # idempotent
        assert not pf._worker.is_alive()
        with pytest.raises(StopIteration):
            next(pf)

    def test_starvation_counted_after_warmup(self):
        """A post-warmup pop that finds the queue empty is the bubble the
        pipeline exists to remove — it must be counted, and the first
        ``depth`` pops (worker still filling the queue for the first time)
        must not be."""
        pf = PrefetchIterator(range(4), lambda x: time.sleep(0.05) or x,
                              depth=1)
        assert list(pf) == list(range(4))
        assert pf.starvation_count >= 1          # slow producer, fast consumer
        fast = PrefetchIterator(range(1), lambda x: x, depth=1)
        assert list(fast) == [0]
        assert fast.starvation_count == 0        # first pop is warmup
        # depth > 1: the whole fill phase is warmup — a slow producer must
        # not register ramp-up pops as steady-state starvation
        ramp = PrefetchIterator(range(3), lambda x: time.sleep(0.05) or x,
                                depth=3)
        assert list(ramp) == list(range(3))
        assert ramp.starvation_count == 0

    def test_inline_prefetch_same_surface(self):
        with _InlinePrefetch(range(5), lambda x: x * 2) as pf:
            assert list(pf) == [0, 2, 4, 6, 8]


# ------------------------------------------------- engine prefetch path

class TestEnginePrefetch:
    def test_losses_match_plain_path(self):
        plain = _engine()
        batches = _data(5, bs=plain.train_batch_size)
        l_plain = [float(plain.train_batch(b).loss) for b in batches]
        pref = _engine(telemetry=True)
        # (the registry is the process's: count from where an earlier test
        # of this worker left the counter)
        handed = pref.telemetry.registry.counter("prefetch_batches_total")
        before = handed.value(loader="train")
        with pref.prefetch_loader(iter(batches)) as pf:
            l_pref = [float(pref.train_batch(pb).loss) for pb in pf]
            assert pf.batches == len(batches)
        assert l_pref == l_plain                 # bitwise: same math, same order
        assert handed.value(loader="train") - before == len(batches)

    def test_prepared_batch_carries_tokens_and_step(self):
        eng = _engine()
        pb = eng.prepare_batch(_data(1, bs=eng.train_batch_size)[0])
        assert isinstance(pb, PreparedBatch)
        assert pb.step_enqueued == 0
        m = eng.train_batch(pb)
        assert np.isfinite(float(m.loss))

    def test_depth_zero_is_inline(self):
        eng = _engine(prefetch_depth=0)
        batches = _data(4, bs=eng.train_batch_size)
        pf = eng.prefetch_loader(iter(batches))
        assert isinstance(pf, _InlinePrefetch)
        losses = [float(eng.train_batch(pb).loss) for pb in pf]
        ref = _engine()
        l_ref = [float(ref.train_batch(b).loss) for b in batches]
        assert losses == l_ref

    def test_dataloader_prefetch_method(self):
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
        rng = np.random.default_rng(0)
        eng = _engine()
        n_batches, bs = 4, eng.train_batch_size
        examples = [{"x": rng.normal(size=(16,)).astype(np.float32),
                     "y": np.float32(rng.normal())}
                    for _ in range(n_batches * bs)]
        loader = DeepSpeedDataLoader(examples, micro_batch_size_per_gpu=bs,
                                     gradient_accumulation_steps=1,
                                     dp_world_size=1)
        with loader.prefetch(eng) as pf:
            losses = [float(eng.train_batch(pb).loss) for pb in pf]
        assert len(losses) == 4 and all(np.isfinite(losses))


# ----------------------------------------------- overlapped host step

class TestOverlapHostStep:
    def test_off_path_bitwise_reproducible(self):
        a = _engine(offload=True, overlap=False)
        batches = _data(4, bs=a.train_batch_size)
        b = _engine(offload=True, overlap=False)
        la = [float(a.train_batch(x).loss) for x in batches]
        lb = [float(b.train_batch(x).loss) for x in batches]
        assert la == lb
        assert a._host_worker is None            # off-path spawns no worker

    def test_delayed_one_step_semantics_exact(self):
        """Documented staleness: under overlap_step the grads of step k run
        against the params of update k-2 (the step-(k-1) host Adam is still
        in flight), so loss_on[k] == loss(params_{k-2}, batch_k).  Checked
        EXACTLY against fresh serial engines fed the right prefix."""
        on = _engine(offload=True, overlap=True)
        batches = _data(3, bs=on.train_batch_size)
        assert on._overlap_step and on._host_worker is not None
        l_on = [float(on.train_batch(b).loss) for b in batches]

        off = _engine(offload=True, overlap=False)
        l_off = [float(off.train_batch(b).loss) for b in batches]

        # step 1: no update in flight yet — bitwise identical to serial
        assert l_on[0] == l_off[0]
        # step 2 ran against params0 (update 1 still in flight): equals a
        # fresh serial engine's FIRST step on batch2
        fresh = _engine(offload=True, overlap=False)
        assert l_on[1] == float(fresh.train_batch(batches[1]).loss)
        # step 3 ran against params1 (= serial params after batch1 only):
        # equals a serial engine fed [b1, b3]'s second loss — update 1 is
        # identical on both paths (same grads at params0)
        fresh2 = _engine(offload=True, overlap=False)
        fresh2.train_batch(batches[0])
        assert l_on[2] == float(fresh2.train_batch(batches[2]).loss)

    def test_join_commits_all_updates(self):
        on = _engine(offload=True, overlap=True)
        batches = _data(4, bs=on.train_batch_size)
        for b in batches:
            on.train_batch(b)
        assert on._host_worker.busy              # last update still in flight
        on._join_host_step()
        assert not on._host_worker.busy
        assert on.offload_opt.step_count == len(batches)
        off = _engine(offload=True, overlap=False)
        for b in batches:
            off.train_batch(b)
        assert off.offload_opt.step_count == len(batches)

    def test_eval_batch_fences_in_flight_step(self):
        on = _engine(offload=True, overlap=True)
        batches = _data(2, bs=on.train_batch_size)
        on.train_batch(batches[0])
        assert on._host_worker.busy
        on.eval_batch(batches[1])                # must see committed params
        assert not on._host_worker.busy

    def test_overflow_skips_identically_on_both_paths(self):
        """The overflow/skip interaction: a non-finite grad step is skipped
        (no Adam submitted, nothing stale) and the loss-scale machine
        advances identically with overlap on and off."""
        on = _engine(offload=True, overlap=True, fp16=True)
        batches = _data(4, bs=on.train_batch_size, nan_at=1)
        off = _engine(offload=True, overlap=False, fp16=True)
        m_on = [on.train_batch(b) for b in batches]
        m_off = [off.train_batch(b) for b in batches]
        on._join_host_step()
        assert int(m_on[1].skipped_steps) == 1
        assert [int(m.skipped_steps) for m in m_on] == \
               [int(m.skipped_steps) for m in m_off]
        assert [float(m.loss_scale) for m in m_on] == \
               [float(m.loss_scale) for m in m_off]
        assert on.offload_opt.step_count == off.offload_opt.step_count == 3

    def test_worker_submit_while_busy_raises(self):
        w = HostStepWorker()
        release = threading.Event()
        w.submit(lambda: (release.wait(5.0), 42)[1])
        assert w.busy
        with pytest.raises(RuntimeError, match="in flight"):
            w.submit(lambda: None)
        release.set()
        assert w.join() == 42
        assert w.join() is None                  # nothing pending
        w.shutdown()

    def test_worker_failure_reraises_at_join(self):
        w = HostStepWorker()

        def boom():
            raise RuntimeError("host adam died")

        w.submit(boom)
        with pytest.raises(RuntimeError, match="host adam died"):
            w.join()
        w.shutdown()


# ------------------------------------------------- async checkpoint I/O

class TestAsyncCheckpoint:
    def test_async_save_fence_and_resume(self, tmp_path):
        eng = _engine()
        batches = _data(4, bs=eng.train_batch_size)
        eng.train_batch(batches[0])
        eng.train_batch(batches[1])
        tag = eng.save_checkpoint(str(tmp_path), async_save=True)
        l_ref = [float(eng.train_batch(b).loss) for b in batches[2:]]
        eng.wait_for_checkpoint()
        # committed: marker gone, 'latest' points at the tag
        assert not in_progress(str(tmp_path), tag)
        with open(tmp_path / "latest") as f:
            assert f.read().strip() == tag
        eng2 = _engine()
        t2, _ = eng2.load_checkpoint(str(tmp_path))
        assert t2 == tag and eng2.global_steps == 2
        l_resume = [float(eng2.train_batch(b).loss) for b in batches[2:]]
        assert l_resume == l_ref

    def test_offload_async_save_roundtrip(self, tmp_path):
        eng = _engine(offload=True, overlap=True)
        batches = _data(4, bs=eng.train_batch_size)
        eng.train_batch(batches[0])
        eng.train_batch(batches[1])
        # save_checkpoint fences the in-flight host step first, so the
        # snapshot carries BOTH committed updates
        tag = eng.save_checkpoint(str(tmp_path), async_save=True)
        eng.wait_for_checkpoint()
        eng2 = _engine(offload=True, overlap=True)
        eng2.load_checkpoint(str(tmp_path))
        assert eng2.offload_opt.step_count == 2
        l_ref = [float(eng.train_batch(b).loss) for b in batches[2:]]
        l_resume = [float(eng2.train_batch(b).loss) for b in batches[2:]]
        assert l_resume == l_ref

    def test_crash_mid_write_previous_checkpoint_loads(self, tmp_path):
        """A simulated crash mid-async-write (in-progress marker left
        behind) must leave 'latest' at the previous committed tag, which
        still loads; restoring the torn tag fails loudly."""
        eng = _engine()
        eng.train_batch(_data(1, bs=eng.train_batch_size)[0])
        tag_ok = eng.save_checkpoint(str(tmp_path))          # committed
        # crash simulation: a later save died after its first byte
        torn = "global_step99"
        mark_in_progress(str(tmp_path), torn)
        (tmp_path / torn / "state").mkdir(parents=True, exist_ok=True)
        assert in_progress(str(tmp_path), torn)
        with open(tmp_path / "latest") as f:
            assert f.read().strip() == tag_ok                # never moved
        eng2 = _engine()
        t2, _ = eng2.load_checkpoint(str(tmp_path))          # follows latest
        assert t2 == tag_ok
        with pytest.raises(RuntimeError, match=IN_PROGRESS_FILE):
            eng2.load_checkpoint(str(tmp_path), tag=torn)

    def test_wait_for_checkpoint_without_pending_is_noop(self):
        _engine().wait_for_checkpoint()


class TestInfinityAsyncCheckpoint:
    def _build(self):
        from deepspeed_tpu.models import GPT, GPTConfig
        cfg = GPTConfig(num_layers=2, num_heads=4, head_dim=8,
                        hidden_size=32, mlp_ratio=2, vocab_size=64,
                        max_seq_len=16)
        ds = {"train_micro_batch_size_per_gpu": 2,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
              "zero_optimization": {"stage": 3,
                                    "offload_param": {"device": "cpu"}},
              "mesh": {"dp": 1, "fsdp": -1}, "steps_per_print": 0}
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=GPT(cfg), config=ds,
            example_batch={"input_ids": np.zeros((1, 16), np.int32)})
        return eng

    def _batches(self, n, bs):
        rng = np.random.default_rng(0)
        return [{"input_ids": rng.integers(0, 64, size=(bs, 16))
                 .astype(np.int32)} for _ in range(n)]

    def test_async_save_roundtrip(self, tmp_path):
        eng = self._build()
        data = self._batches(3, eng.train_batch_size)
        eng.train_batch(data[0])
        tag = eng.save_checkpoint(str(tmp_path), async_save=True)
        l_ref = [float(eng.train_batch(b).loss) for b in data[1:]]
        eng.wait_for_checkpoint()
        assert not in_progress(str(tmp_path), tag)
        eng2 = self._build()
        t2, _ = eng2.load_checkpoint(str(tmp_path))
        assert t2 == tag and eng2.global_steps == 1
        l_resume = [float(eng2.train_batch(b).loss) for b in data[1:]]
        np.testing.assert_allclose(l_resume, l_ref, rtol=1e-5)

    def test_torn_tag_refused(self, tmp_path):
        eng = self._build()
        eng.train_batch(self._batches(1, eng.train_batch_size)[0])
        eng.save_checkpoint(str(tmp_path))
        mark_in_progress(str(tmp_path), "global_step7")
        with pytest.raises(RuntimeError, match=IN_PROGRESS_FILE):
            eng.load_checkpoint(str(tmp_path), tag="global_step7")

    def test_writer_failure_reraises_at_fence(self, tmp_path, monkeypatch):
        eng = self._build()
        eng.train_batch(self._batches(1, eng.train_batch_size)[0])

        def boom(*a, **kw):
            raise OSError("disk full")

        import deepspeed_tpu.runtime.infinity as inf_mod
        monkeypatch.setattr(inf_mod.np, "savez", boom)
        eng.save_checkpoint(str(tmp_path), async_save=True)
        monkeypatch.undo()
        with pytest.raises(OSError, match="disk full"):
            eng.wait_for_checkpoint()
        # the failed tag never committed: marker still present, no 'latest'
        assert in_progress(str(tmp_path), f"global_step{eng.global_steps}")
        assert not os.path.exists(tmp_path / "latest")
