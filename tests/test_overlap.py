"""Device-side compute–collective overlap (ISSUE 8 tentpole).

Four proofs, all CPU-runnable:

1. config + flag plumbing: the ``overlap`` block validates, composes the
   scheduler flags, exports them to libtpu's own argument variable on a TPU
   target only (jaxlib aborts on a TPU flag name in XLA_FLAGS), and is echoed
   into env_report, the telemetry snapshot, and the postmortem bundle.
2. chunked ZeRO-3 collectives: ``runtime/zero.chunked_param_gather`` is
   bitwise-exact vs the flat gather at every chunk count, its autodiff
   transpose is the chunked reduce-scatter, and the engine's compiled
   stage-3 step shows exactly the per-layer-group chunk train
   (``comm.hlo_overlap_stats`` finds compute scheduled between the
   chunks).
3. ring collective-matmul fusions (``ops/collective_matmul.py``): exact vs
   the unfused XLA reference for all three ops, registry-selected, and the
   model wiring (gpt.py / linear.py) is loss-identical with the flag on.
4. satellites: wire-bytes logging convention, flash block overrides +
   sweep script, the scheduler regime in every snapshot.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import CONSUMER_ORDER

import deepspeed_tpu
from deepspeed_tpu.config import OverlapConfig, parse_config
from deepspeed_tpu.models import GPT, GPTConfig
from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh
from deepspeed_tpu.runtime.overlap import (apply_overlap_flags,
                                           compose_xla_flags,
                                           overlap_snapshot)

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
VOCAB, SEQ = 64, 16


def _build_engine(stage=3, chunks=1, mesh_kw=None, extra_zero=None,
                  overlap_extra=None, telemetry=False, seed=7, model_cfg=None):
    overlap = {"enabled": True, "num_chunks": chunks}
    overlap.update(overlap_extra or {})
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": dict({"stage": stage}, **(extra_zero or {})),
        "overlap": overlap,
        "mesh": mesh_kw or {"dp": 1, "fsdp": -1},
        "steps_per_print": 0,
        "seed": seed,
    }
    if telemetry:
        cfg["telemetry"] = {"enabled": True, "trace_enabled": False,
                            "snapshot_interval": 0}
    model = GPT(model_cfg or GPTConfig.tiny(vocab_size=VOCAB,
                                            max_seq_len=SEQ))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg,
        example_batch={"input_ids": np.zeros((2, SEQ), np.int32)})
    return engine


def _batch(engine, seed=5):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(
        0, VOCAB, size=(engine.train_batch_size, SEQ)).astype(np.int32)}


def _step_hlo(engine, compiler_options=None):
    batch = engine._shard_batch(engine._reshape_gas(_batch(engine)),
                                leading_gas=True)
    with engine.mesh:
        return jax.jit(engine._train_batch_fn).lower(
            engine.state, batch).compile(
                compiler_options=compiler_options).as_text()


# ===================================================================== config

class TestOverlapConfig:
    def test_defaults_off_and_inert(self):
        cfg = OverlapConfig()
        assert not cfg.enabled and cfg.num_chunks == 1
        assert compose_xla_flags(cfg) == []
        assert apply_overlap_flags(cfg) == []

    def test_flag_composition(self):
        cfg = OverlapConfig(enabled=True, scheduler_rerun=3,
                            scheduler_memory_limit_pct=90,
                            extra_xla_flags=["--xla_foo=1"])
        flags = compose_xla_flags(cfg)
        assert "--xla_latency_hiding_scheduler_rerun=3" in flags
        assert "--xla_tpu_scheduler_percent_shared_memory_limit=90" in flags
        assert any(f.startswith("--xla_tpu_enable_async_collective_fusion=")
                   for f in flags)
        assert flags[-1] == "--xla_foo=1"
        # knob gating: each lever removes its flags
        off = compose_xla_flags(OverlapConfig(
            enabled=True, async_collectives=False,
            latency_hiding_scheduler=False))
        assert off == []

    @pytest.mark.parametrize("bad", [
        {"num_chunks": 0},
        {"scheduler_rerun": -1},
        {"scheduler_memory_limit_pct": 0},
        {"extra_xla_flags": ["not_a_flag"]},
        {"extra_xla_flags": ["--xla_missing_value"]},
    ])
    def test_validation_rejects(self, bad):
        with pytest.raises(Exception):
            OverlapConfig(enabled=True, **bad)
        with pytest.raises(Exception):
            parse_config({"overlap": dict({"enabled": True}, **bad)})

    def test_cpu_process_never_exports_tpu_flags(self, monkeypatch):
        """jaxlib hard-aborts on the scheduler flags in XLA_FLAGS
        (parse_flags_from_env FATAL) — they are never written there, and
        off-TPU they are composed and recorded but exported nowhere."""
        xla = "--xla_force_host_platform_device_count=8"
        monkeypatch.setenv("XLA_FLAGS", xla)
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        added = apply_overlap_flags(OverlapConfig(enabled=True))
        assert added == []
        assert os.environ["XLA_FLAGS"] == xla
        assert os.environ["LIBTPU_INIT_ARGS"] == ""

    def test_tpu_target_exports_and_user_flags_win(self, monkeypatch):
        xla = "--xla_force_host_platform_device_count=8"
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv("XLA_FLAGS", xla)
        monkeypatch.setenv(
            "LIBTPU_INIT_ARGS", "--xla_latency_hiding_scheduler_rerun=5")
        added = apply_overlap_flags(OverlapConfig(enabled=True))
        # TPU compiler flags go to libtpu's variable, never to XLA_FLAGS
        assert os.environ["XLA_FLAGS"] == xla
        # the user's rerun=5 survives; the async flags were added
        flags = os.environ["LIBTPU_INIT_ARGS"]
        assert "--xla_latency_hiding_scheduler_rerun=5" in flags
        assert "--xla_latency_hiding_scheduler_rerun=1" not in flags
        assert any(f.startswith("--xla_tpu_enable_async_collective_fusion=")
                   for f in added)
        # idempotent: a second apply adds nothing
        assert apply_overlap_flags(OverlapConfig(enabled=True)) == []

    def test_snapshot_shape(self):
        cfg = OverlapConfig(enabled=True, num_chunks=4)
        snap = overlap_snapshot(cfg)
        assert snap["config"]["num_chunks"] == 4
        assert isinstance(snap["composed_flags"], list)
        assert "effective_xla_flags" in snap


# ============================================================ chunked gather

class TestChunkedGather:
    def _leaves_and_shardings(self, mesh):
        rng = np.random.default_rng(0)
        leaves = {
            "a": jnp.asarray(rng.normal(size=(16, 6)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(4, 32)), jnp.float32),
            "c": jnp.asarray(rng.normal(size=(8, 8)), jnp.bfloat16),
            "scalar": jnp.float32(3.0),
        }
        specs = {"a": P("fsdp", None), "b": P("tp", "fsdp"),
                 "c": P("fsdp", None), "scalar": P()}
        shardings = {k: NamedSharding(mesh, s) for k, s in specs.items()}
        placed = {k: jax.device_put(v, shardings[k])
                  for k, v in leaves.items()}
        return placed, shardings

    @pytest.mark.parametrize("chunks", [1, 2, 3, 4, 8])
    def test_chunked_equals_flat_all_counts(self, devices, chunks):
        """The gather is pure data movement: bitwise-equal to the input
        (already-global view) at EVERY chunk count, mixed dtypes and
        tp-co-sharded leaves included."""
        from deepspeed_tpu.runtime.zero import chunked_param_gather
        mesh = build_mesh(MeshSpec(dp=1, fsdp=4, tp=2))
        params, shardings = self._leaves_and_shardings(mesh)
        out = jax.jit(lambda p: chunked_param_gather(
            p, shardings, mesh, chunks))(params)
        for k in params:
            assert np.array_equal(np.asarray(out[k], np.float32),
                                  np.asarray(params[k], np.float32)), k

    def test_vjp_is_chunked_reduce_scatter(self, devices):
        """The transpose program: grads w.r.t. the sharded leaves equal the
        flat path's (the chunked flat reduce-scatter sums the same
        cotangents)."""
        from deepspeed_tpu.runtime.zero import chunked_param_gather
        mesh = build_mesh(MeshSpec(dp=1, fsdp=4, tp=2))
        params, shardings = self._leaves_and_shardings(mesh)

        def loss(p, gather):
            q = (chunked_param_gather(p, shardings, mesh, 3) if gather
                 else p)
            return sum((q[k].astype(jnp.float32) ** 2).sum()
                       for k in ("a", "b", "c"))

        g1 = jax.jit(jax.grad(lambda p: loss(p, True)))(params)
        g2 = jax.jit(jax.grad(lambda p: loss(p, False)))(params)
        for k in ("a", "b", "c"):
            np.testing.assert_allclose(np.asarray(g1[k], np.float32),
                                       np.asarray(g2[k], np.float32),
                                       rtol=1e-6, atol=1e-6)

    def test_engine_loss_parity_and_chunk_train(self, devices):
        """Engine-level: chunked vs flat stage-3 training is loss-identical,
        and the compiled chunked step shows EXACTLY the per-layer-group
        chunk train (num_chunks all-gathers + num_chunks reduce-scatters,
        vs one implicit gather per consumer on the flat step) with compute
        scheduled between chunks wherever the scheduler orders by consumer
        (``CONSUMER_ORDER``): no chunk's compute waits for the next chunk."""
        import re
        flat = _build_engine(chunks=1)
        ch = _build_engine(chunks=4)
        batch = _batch(flat)
        lf = [float(flat.train_batch(batch).loss) for _ in range(4)]
        lc = [float(ch.train_batch(batch).loss) for _ in range(4)]
        np.testing.assert_allclose(lc, lf, rtol=1e-6)

        txt = _step_hlo(ch, CONSUMER_ORDER)
        ags = [ln for ln in txt.splitlines()
               if re.search(r" all-gather(-start)?\(", ln)]
        rss = [ln for ln in txt.splitlines()
               if re.search(r" reduce-scatter(-start)?\(", ln)]
        assert len(ags) == 4, f"expected 4 chunk all-gathers, got {len(ags)}"
        assert len(rss) == 4, f"expected 4 chunk reduce-scatters, got {len(rss)}"
        flat_txt = _step_hlo(flat, CONSUMER_ORDER)
        flat_ags = [ln for ln in flat_txt.splitlines()
                    if re.search(r" all-gather(-start)?\(", ln)]
        assert len(flat_ags) > len(ags), (len(flat_ags), len(ags))

        # the CPU-verifiable overlap assertion: compute scheduled between
        # the decomposed chunk collectives
        from deepspeed_tpu.comm.comm import hlo_overlap_stats
        stats = hlo_overlap_stats(txt)
        assert _has_overlap_evidence(stats, min_chunks=2), stats
        assert stats["per_kind_interleaved"].get("all-gather", 0) >= 2, stats
        assert stats["exposed_ratio"] < 1.0

    def test_chunked_tag_in_collective_counters(self, devices):
        """The chunk train is tagged: trace-time counters carry the
        ``all_gather_chunked`` kind so byte assertions can separate the
        explicit chunks from XLA's implicit collectives."""
        from deepspeed_tpu.telemetry.registry import (COLLECTIVE_CALLS,
                                                      default_registry)
        default_registry.reset()
        ch = _build_engine(chunks=2, seed=11)
        ch.train_batch(_batch(ch))
        calls = default_registry.counter(COLLECTIVE_CALLS)
        assert calls.value(kind="all_gather_chunked", axis="fsdp") >= 2
        default_registry.reset()

    def test_gates(self, devices):
        # stage < 3: inert warning, engine still trains
        eng = _build_engine(stage=2, chunks=4, mesh_kw={"dp": -1})
        assert eng._gather_chunks == 0
        losses = [float(eng.train_batch(_batch(eng)).loss)
                  for _ in range(3)]
        assert np.isfinite(losses).all()

    def test_qwz_composes_with_chunks(self, devices):
        """The former hard conflict (ISSUE 14): chunking and the qwZ int8
        gather now COMPOSE on one pipeline — the compiled step shows a
        chunk train of s8 all-gathers, and the engine trains."""
        import re
        eng = _build_engine(chunks=4,
                            extra_zero={"zero_quantized_weights": True})
        assert eng._pipeline_active and eng._gather_chunks == 4
        assert eng._wire_plan.weight_bits == 8
        losses = [float(eng.train_batch(_batch(eng)).loss)
                  for _ in range(3)]
        assert np.isfinite(losses).all()
        txt = _step_hlo(eng)
        s8_ags = [ln for ln in txt.splitlines()
                  if re.search(r" all-gather(-start)?\(", ln)
                  and "s8[" in ln]
        assert len(s8_ags) >= 4, f"expected >=4 s8 chunk gathers, got {len(s8_ags)}"

    def test_num_chunks_clamped_to_leaf_count(self, devices):
        """More chunks than gatherable leaves: every group still gathers
        (layer_groups clamps), training works."""
        eng = _build_engine(chunks=64)
        loss = float(eng.train_batch(_batch(eng)).loss)
        assert np.isfinite(loss)

    def test_layer_groups_partition(self):
        from deepspeed_tpu.parallel.partition import layer_groups
        sizes = [10, 10, 10, 10, 10, 10, 10, 10]
        groups = layer_groups(sizes, 4)
        assert [len(g) for g in groups] == [2, 2, 2, 2]
        assert [i for g in groups for i in g] == list(range(8))
        assert len(layer_groups([5, 5], 8)) == 2      # clamped
        assert len(layer_groups(sizes, 1)) == 1
        # regression (review): tail-skewed sizes (a late wte-sized leaf)
        # must still materialize every requested group — a static
        # total/num_groups target never closed any early group
        assert layer_groups([1, 1, 1, 100], 2) == ((0, 1, 2), (3,))
        assert len(layer_groups([1, 1, 1, 1, 100], 3)) == 3
        # head-skew keeps the early close
        assert layer_groups([100, 1, 1, 1], 2) == ((0,), (1, 2, 3))


# ======================================================== collective matmul

class TestCollectiveMatmul:
    @pytest.fixture(scope="class")
    def mesh(self, devices):
        return build_mesh(MeshSpec(dp=2, fsdp=1, tp=4))

    @pytest.fixture(scope="class")
    def xw(self):
        rng = np.random.default_rng(0)
        return (jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32),
                jnp.asarray(rng.normal(size=(16, 12)), jnp.float32))

    @pytest.mark.parametrize("op", ["all_gather_matmul",
                                    "matmul_reduce_scatter",
                                    "row_parallel_matmul"])
    def test_ring_exact_vs_unfused_and_dense(self, mesh, xw, op):
        from deepspeed_tpu import ops
        x, w = xw
        fn = getattr(ops, op)
        ref = jax.jit(lambda a, b: fn(a, b, mesh, impl="xla"))(x, w)
        ring = jax.jit(lambda a, b: fn(a, b, mesh, impl="pallas"))(x, w)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                                   rtol=1e-6, atol=1e-5)
        dense = x @ w
        if op == "row_parallel_matmul" or op == "all_gather_matmul":
            np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                                       rtol=1e-5, atol=1e-5)

    def test_grads_match(self, mesh, xw):
        from deepspeed_tpu import ops
        x, w = xw

        def loss(impl):
            return jax.jit(jax.grad(
                lambda a, b: (ops.row_parallel_matmul(
                    a, b, mesh, impl=impl) ** 2).sum(), argnums=(0, 1)))
        gx1, gw1 = loss("xla")(x, w)
        gx2, gw2 = loss("pallas")(x, w)
        np.testing.assert_allclose(np.asarray(gx2), np.asarray(gx1),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gw2), np.asarray(gw1),
                                   rtol=1e-4, atol=1e-4)

    def test_registered_in_op_registry(self):
        from deepspeed_tpu.ops.registry import list_ops
        reg = list_ops()
        for name in ("all_gather_matmul", "matmul_reduce_scatter",
                     "row_parallel_matmul"):
            assert name in reg and reg[name].pallas is not None

    def test_divisibility_raises(self, mesh, xw):
        from deepspeed_tpu import ops
        x, w = xw
        with pytest.raises(ValueError, match="not divisible"):
            ops.row_parallel_matmul(x[:, :6], w, mesh)       # T=6, tp=4
        with pytest.raises(ValueError, match="not divisible"):
            ops.matmul_reduce_scatter(x[:, :, :10], w[:10], mesh)

    def test_model_wiring_loss_identical(self, devices):
        """gpt.py MLP down-proj + attention out_proj routed through the
        row-parallel ring under a tp=2 mesh: losses identical to the plain
        einsum path, and the engine pushes the flag from the overlap
        block."""
        def build(cm):
            return _build_engine(
                stage=2, chunks=1, mesh_kw={"dp": 4, "tp": 2},
                overlap_extra={"collective_matmul": bool(cm)}, seed=3)
        b0, b1 = build(False), build(True)
        assert b1.model.cfg.tp_collective_matmul
        assert not b0.model.cfg.tp_collective_matmul
        batch = _batch(b0)
        l0 = [float(b0.train_batch(batch).loss) for _ in range(4)]
        l1 = [float(b1.train_batch(batch).loss) for _ in range(4)]
        np.testing.assert_allclose(l1, l0, rtol=1e-6)

    def test_cache_decode_stays_inert(self, devices):
        """Regression (review): the fusion gate must be inert on the
        KV-cache path — decode's T=1 never divides tp, and raising there
        would crash serving for any model trained with the flag on.  Both
        MLP and attention receive use_cache."""
        import dataclasses
        from deepspeed_tpu.models.gpt import GPTBackbone
        mesh = build_mesh(MeshSpec(dp=4, fsdp=1, tp=2))
        cfg = dataclasses.replace(
            GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ),
            tp_collective_matmul=True)
        model = GPTBackbone(cfg, mesh=mesh)
        ids = np.zeros((4, 1), np.int32)
        pos = np.zeros((4, 1), np.int32)
        with mesh:
            vars_ = model.init(jax.random.PRNGKey(0), ids,
                               deterministic=True, positions=pos,
                               use_cache=True)
            (hidden, _emb, _aux), _ = model.apply(
                vars_, ids, deterministic=True, positions=pos,
                use_cache=True, mutable=["cache"])
        assert hidden.shape == (4, 1, cfg.hidden_size)

    def test_sp_combination_rejected(self, devices):
        import dataclasses
        mcfg = dataclasses.replace(
            GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ),
            sequence_parallel=True)
        with pytest.raises(ValueError, match="not wired"):
            _build_engine(stage=2, mesh_kw={"dp": 2, "sp": 2, "tp": 2},
                          overlap_extra={"collective_matmul": True},
                          model_cfg=mcfg)

    def test_linear_row_parallel(self, devices):
        """linear.OptimizedLinear: a row-parallel base (input axis mapped
        to tp) routed through the ring matches the dense path."""
        from deepspeed_tpu.linear import OptimizedLinear
        mesh = build_mesh(MeshSpec(dp=2, fsdp=1, tp=4))
        x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 8, 32)),
                        jnp.float32)
        kw = dict(input_dim=32, output_dim=16,
                  axis_names=("mlp", "embed"))
        plain = OptimizedLinear(**kw)
        ring = OptimizedLinear(mesh=mesh, collective_matmul=True, **kw)
        params = plain.init(jax.random.PRNGKey(0), x)
        with mesh:
            y0 = plain.apply(params, x)
            y1 = ring.apply(params, x)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                                   rtol=1e-5, atol=1e-5)

    def test_linear_column_parallel_inert(self, devices):
        """Column-parallel placement (default axes): no boundary collective
        to fuse — the flag must be inert, not an error."""
        from deepspeed_tpu.linear import OptimizedLinear
        mesh = build_mesh(MeshSpec(dp=2, fsdp=1, tp=4))
        x = jnp.ones((2, 8, 32), jnp.float32)
        lin = OptimizedLinear(input_dim=32, output_dim=16, mesh=mesh,
                              collective_matmul=True)
        params = lin.init(jax.random.PRNGKey(0), x)
        with mesh:
            y = lin.apply(params, x)
        assert y.shape == (2, 8, 16)


# ======================================================= hlo_overlap_stats

def _has_overlap_evidence(stats: dict, min_chunks: int = 2) -> bool:
    """True when at least one overlap signal is present."""
    if stats["async_pairs_with_compute"] >= 1:
        return True
    return any(cnt >= min_chunks
               for cnt in stats["per_kind_interleaved"].values())


class TestCheckOverlap:
    def test_parser_async_pair_with_compute(self):
        from deepspeed_tpu.comm.comm import hlo_overlap_stats
        hlo = """
ENTRY %main (p0: f32[8,16]) -> f32[16,16] {
  %ags = (f32[8,16], f32[16,16]) all-gather-start(f32[8,16] %p0), replica_groups={{0,1}}
  %f0 = f32[16,16] fusion(f32[16,16] %x), kind=kLoop
  %agd = f32[16,16] all-gather-done((f32[8,16], f32[16,16]) %ags)
}
"""
        s = hlo_overlap_stats(hlo)
        assert s["async_pairs"] == 1
        assert s["async_pairs_with_compute"] == 1
        assert s["exposed_ratio"] == 0.0

    def test_parser_async_pair_without_compute_is_exposed(self):
        from deepspeed_tpu.comm.comm import hlo_overlap_stats
        hlo = """
ENTRY %main (p0: f32[8,16]) -> f32[16,16] {
  %ags = (f32[8,16], f32[16,16]) all-gather-start(f32[8,16] %p0)
  %agd = f32[16,16] all-gather-done((f32[8,16], f32[16,16]) %ags)
  %f0 = f32[16,16] fusion(f32[16,16] %agd), kind=kLoop
}
"""
        s = hlo_overlap_stats(hlo)
        assert s["async_pairs"] == 1
        assert s["async_pairs_with_compute"] == 0
        assert s["exposed_ratio"] == 1.0

    def test_parser_chunk_train(self):
        from deepspeed_tpu.comm.comm import hlo_overlap_stats
        hlo = """
ENTRY %main () -> f32[] {
  %g0 = f32[4,8] all-gather(f32[1,8] %a)
  %f0 = f32[4,8] fusion(f32[4,8] %g0), kind=kLoop
  %g1 = f32[4,8] all-gather(f32[1,8] %b)
  %f1 = f32[4,8] fusion(f32[4,8] %g1), kind=kLoop
  %g2 = f32[4,8] all-gather(f32[1,8] %c)
}
"""
        s = hlo_overlap_stats(hlo)
        assert s["sync_collectives"] == 3
        assert s["per_kind_interleaved"]["all-gather"] == 2
        assert 0 < s["exposed_ratio"] < 1

    def test_check_gate(self):
        assert _has_overlap_evidence({"async_pairs_with_compute": 1,
                                      "per_kind_interleaved": {}})
        assert _has_overlap_evidence(
            {"async_pairs_with_compute": 0,
             "per_kind_interleaved": {"all-gather": 3}})
        assert not _has_overlap_evidence(
            {"async_pairs_with_compute": 0,
             "per_kind_interleaved": {"all-gather": 1}})

    def test_snapshot_env_records_scheduler_regime(self, devices):
        """Every telemetry snapshot records the scheduler regime (resolved
        overlap config + effective XLA_FLAGS), and the compile analysis
        still books the chunk train's collective bytes."""
        from deepspeed_tpu.telemetry.registry import default_registry
        default_registry.reset()
        eng = _build_engine(chunks=4, telemetry=True, seed=13)
        eng.train_batch(_batch(eng))
        snap = eng.telemetry.export(write=False)
        assert snap["env"]["config"]["num_chunks"] == 4
        assert "effective_xla_flags" in snap["env"]
        exe = snap["executables"]["train_batch"]
        assert exe["collectives"]["all-gather"]["count"] >= 4
        default_registry.reset()


# ================================================================ wire bytes

class TestWireBytes:
    def test_wire_byte_convention(self, devices):
        """Normalized accounting (collectives.py docstring): every wrapper
        logs the per-participant ring wire bytes, so cross-op ratios
        compare like with like.  (test_qgz's compiled-HLO byte assertions
        are independent of this trace-time convention.)"""
        from deepspeed_tpu.comm import collectives as cc
        from deepspeed_tpu.telemetry.registry import (COLLECTIVE_BYTES,
                                                      default_registry)
        from jax import shard_map
        default_registry.reset()
        mesh = build_mesh(MeshSpec(dp=4, fsdp=2))

        def body(x):
            r = cc.all_reduce(x, "dp")                 # [1, 64] per shard
            g = cc.all_gather(x, "dp")
            s = cc.reduce_scatter(g, "dp")
            b = cc.broadcast(x, "dp")
            return r + s + b

        x = jnp.ones((8, 64), jnp.float32)
        with mesh:
            out = jax.jit(shard_map(
                body, mesh=mesh, in_specs=P(("dp", "fsdp")),
                out_specs=P(("dp", "fsdp")), check_vma=False))(x)
        jax.device_get(out)
        shard = 64 * 4            # one [1, 64] f32 row per dp×fsdp shard
        n = 4
        bc = default_registry.counter(COLLECTIVE_BYTES)
        assert bc.value(kind="all_reduce", axis="dp") == \
            2 * shard * (n - 1) // n
        assert bc.value(kind="all_gather", axis="dp") == shard * (n - 1)
        # reduce_scatter input is the GATHERED [4, 64] block
        assert bc.value(kind="reduce_scatter", axis="dp") == \
            (shard * n) * (n - 1) // n
        assert bc.value(kind="broadcast", axis="dp") == \
            shard * (n - 1) // n
        default_registry.reset()


# ============================================================= flash blocks

class TestFlashBlockOverrides:
    def setup_method(self):
        from deepspeed_tpu.ops.flash_attention import configure_flash_blocks
        configure_flash_blocks({})

    def teardown_method(self):
        from deepspeed_tpu.ops.flash_attention import configure_flash_blocks
        configure_flash_blocks(None)

    def test_override_wins_and_resets(self, monkeypatch):
        from deepspeed_tpu.ops.flash_attention import (_block_pair,
                                                       configure_flash_blocks)
        default = _block_pair(1024)
        configure_flash_blocks({1024: (256, 512)})
        assert _block_pair(1024) == (256, 512)
        monkeypatch.delenv("DSTPU_FLASH_BLOCKS", raising=False)
        configure_flash_blocks(None)
        assert _block_pair(1024) == default

    def test_env_spec_parsing(self, monkeypatch):
        from deepspeed_tpu.ops.flash_attention import (_block_pair,
                                                       _parse_block_spec,
                                                       configure_flash_blocks)
        assert _parse_block_spec("4096:512x1024, 8192:512") == {
            4096: (512, 1024), 8192: (512, 512)}
        monkeypatch.setenv("DSTPU_FLASH_BLOCKS", "2048:256x1024")
        configure_flash_blocks(None)
        assert _block_pair(2048) == (256, 1024)

    def test_invalid_rejected(self):
        from deepspeed_tpu.ops.flash_attention import (_block_pair,
                                                       _parse_block_spec,
                                                       configure_flash_blocks)
        with pytest.raises(ValueError, match=">= 8"):
            configure_flash_blocks({128: (4, 8)})
        with pytest.raises(ValueError, match="bad flash block spec"):
            _parse_block_spec("4096=512")
        configure_flash_blocks({100: (32, 32)})
        with pytest.raises(ValueError, match="must divide"):
            _block_pair(100)

    def test_env_path_validated_like_dict_path(self):
        """Regression (review): a typo'd env spec ('4096:0') must raise the
        clear ValueError the dict path raises, not a ZeroDivisionError
        inside kernel tracing.  (Env handled manually: monkeypatch
        finalizes AFTER teardown_method, which re-reads the env.)"""
        from deepspeed_tpu.ops.flash_attention import configure_flash_blocks
        old = os.environ.get("DSTPU_FLASH_BLOCKS")
        os.environ["DSTPU_FLASH_BLOCKS"] = "4096:0"
        try:
            with pytest.raises(ValueError, match=">= 8"):
                configure_flash_blocks(None)
        finally:
            if old is None:
                os.environ.pop("DSTPU_FLASH_BLOCKS", None)
            else:
                os.environ["DSTPU_FLASH_BLOCKS"] = old

    def test_numerics_with_override(self):
        """An overridden tiling is still the same math: interpret-mode flash
        with a forced non-default block pair matches the XLA reference."""
        from deepspeed_tpu import ops
        from deepspeed_tpu.ops.flash_attention import configure_flash_blocks
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 8)) * 0.3,
                               jnp.float32) for _ in range(3))
        ref = ops.causal_attention(q, k, v, impl="xla")
        configure_flash_blocks({64: (16, 32)})
        out = ops.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_sweep_script_smoke(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import sweep_flash_blocks
        finally:
            sys.path.pop(0)
        assert sweep_flash_blocks.default_candidates(1024)
        assert sweep_flash_blocks.parse_candidates("16x32, 64") == [
            (16, 32), (64, 64)]
        rc = sweep_flash_blocks.main(
            ["--seq", "32", "--batch", "1", "--heads", "2", "--head-dim",
             "8", "--iters", "1", "--fwd-only", "--smoke",
             "--candidates", "8x8"])
        assert rc == 0


# ============================================================== env report

class TestEnvEcho:
    def test_env_report_carries_xla_flags(self):
        from deepspeed_tpu.env_report import env_report
        rep = env_report(color=False)
        assert "XLA_FLAGS" in rep

    def test_postmortem_bundle_records_regime(self, devices, tmp_path):
        """The flight-recorder bundle's env.txt names the resolved overlap
        block — a postmortem must say which scheduler regime the run
        compiled under."""
        from deepspeed_tpu.config import parse_config
        from deepspeed_tpu.telemetry import StepTelemetry
        cfg = parse_config({
            "overlap": {"enabled": True, "num_chunks": 4},
            "telemetry": {"output_path": str(tmp_path),
                          "health": {"enabled": True, "crash_dump": False}},
        })
        tel = StepTelemetry(cfg)
        tel._write_bundle_env(str(tmp_path))
        txt = open(os.path.join(str(tmp_path), "env.txt")).read()
        assert "overlap.num_chunks=4" in txt
        assert "overlap.composed_xla_flags=" in txt
