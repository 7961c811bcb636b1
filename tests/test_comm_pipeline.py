"""Composable quantized collective pipeline (ISSUE 14 tentpole).

The stage-3 gather/reduce is ONE pipeline with three orthogonal layers —
chunking × block quantization × hierarchy (runtime/zero.py) — and the
engine's former conflict gates (chunks × qwZ, chunks × qgZ) are gone.
Proof obligations, per the acceptance bar:

1. chunk-only mode is BITWISE identical to PR 4's gather (and its vjp);
2. quantized modes stay within documented error bounds, forward and vjp,
   at both int8 and int4, and the qwZ-only transpose is exact;
3. short-run loss trajectory of the composed engine tracks bf16
   collectives;
4. wire bytes: the composed int4 pipeline moves ≥3× fewer gather/scatter
   bytes than the bf16-chunked baseline while the exposed ratio stays in
   the same regime (the T3 claim: quantization must not un-hide wire);
5. hierarchy: intra-host axes keep full width, host-crossing axes
   quantize (simulated host map, comm/collectives.set_link_process_fn);
6. the quantized wire is byte-accounted at WIRE width under tagged kinds
   (all_gather_q8 / all_to_all_q8), and hlo_overlap_stats' companion
   logic keeps the exposed-ratio gauge sighted on quantized trains.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import CONSUMER_ORDER

import deepspeed_tpu
from deepspeed_tpu.models import GPT, GPTConfig
from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh
from deepspeed_tpu.runtime.zero import (WirePlan, chunked_param_gather,
                                        pipeline_grad_reduce,
                                        pipeline_param_gather,
                                        resolve_wire_bits)

VOCAB, SEQ = 64, 16


def _leaves_and_shardings(mesh):
    rng = np.random.default_rng(0)
    leaves = {
        "a": jnp.asarray(rng.normal(size=(64, 8)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(4, 32)), jnp.float32),
        "c": jnp.asarray(rng.normal(size=(16, 8)), jnp.bfloat16),
        "scalar": jnp.float32(3.0),
    }
    specs = {"a": P("fsdp", None), "b": P("tp", "fsdp"),
             "c": P("fsdp", None), "scalar": P()}
    shardings = {k: NamedSharding(mesh, s) for k, s in specs.items()}
    placed = {k: jax.device_put(v, shardings[k]) for k, v in leaves.items()}
    return placed, shardings


def _build_engine(stage=3, chunks=4, qwz=False, qgz=False, mesh_kw=None,
                  zpp=None, seed=7):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage,
                              "zero_quantized_weights": qwz,
                              "zero_quantized_gradients": qgz,
                              **({"zeropp": zpp} if zpp else {})},
        "overlap": {"enabled": True, "num_chunks": chunks},
        "mesh": mesh_kw or {"dp": 1, "fsdp": -1},
        "steps_per_print": 0,
        "seed": seed,
    }
    model = GPT(GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ))
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


# ================================================== hierarchy / plan resolve

class TestWirePlanResolution:
    def test_non_hierarchical_passthrough(self, devices):
        mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
        plan = WirePlan(weight_bits=8, grad_bits=4)
        assert resolve_wire_bits(plan, mesh, "fsdp") == (8, 4)
        assert resolve_wire_bits(WirePlan(), mesh, "fsdp") == (0, 0)

    def test_hierarchical_single_host_stays_full_width(self, devices):
        """All-ICI axis (one host): the hierarchy layer keeps full width —
        intra-host bandwidth is cheap and numerics stay exact."""
        mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
        plan = WirePlan(weight_bits=8, grad_bits=8, hierarchical=True)
        assert resolve_wire_bits(plan, mesh, "fsdp") == (0, 0)
        assert resolve_wire_bits(plan, mesh, "dp") == (0, 0)

    def test_hierarchical_cross_host_quantizes(self, devices):
        """Simulated 2-host fleet (dp crosses hosts, fsdp stays inside):
        only the host-crossing axis quantizes — the hpZ placement."""
        from deepspeed_tpu.comm import collectives as cc
        mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
        devs = list(mesh.devices.flatten())
        host_of = {d: i // 4 for i, d in enumerate(devs)}
        cc.set_link_process_fn(lambda d: host_of[d])
        try:
            plan = WirePlan(weight_bits=8, grad_bits=8, hierarchical=True)
            assert cc.axis_dcn_fraction("dp", mesh=mesh) > 0.0
            assert cc.axis_dcn_fraction("fsdp", mesh=mesh) == 0.0
            assert resolve_wire_bits(plan, mesh, "dp") == (8, 8)
            assert resolve_wire_bits(plan, mesh, "fsdp") == (0, 0)
        finally:
            cc.set_link_process_fn(None)


# ========================================================== gather pipeline

class TestPipelineGather:
    @pytest.mark.parametrize("chunks", [1, 3])
    def test_chunk_only_bitwise_vs_pr4(self, devices, chunks):
        """Quantization off: the pipeline IS PR 4's chunked gather —
        bitwise on every leaf, mixed dtypes and tp-co-sharded included."""
        mesh = build_mesh(MeshSpec(dp=1, fsdp=4, tp=2))
        params, shardings = _leaves_and_shardings(mesh)
        new = jax.jit(lambda p: pipeline_param_gather(
            p, shardings, mesh, WirePlan(num_chunks=chunks)))(params)
        old = jax.jit(lambda p: chunked_param_gather(
            p, shardings, mesh, chunks))(params)
        for k in params:
            assert np.array_equal(np.asarray(new[k], np.float32),
                                  np.asarray(old[k], np.float32)), k
            assert np.array_equal(np.asarray(new[k], np.float32),
                                  np.asarray(params[k], np.float32)), k

    @pytest.mark.parametrize("bits,bound", [(8, 0.02), (4, 0.15)])
    def test_quantized_gather_error_bounds(self, devices, bits, bound):
        """Documented bounds (docs/performance.md): blockwise symmetric
        quantization error is ~0.5%/block relative at int8, ~7% at int4 —
        the per-leaf relative L2 must stay inside them."""
        mesh = build_mesh(MeshSpec(dp=1, fsdp=4, tp=2))
        params, shardings = _leaves_and_shardings(mesh)
        plan = WirePlan(num_chunks=2, weight_bits=bits, grad_bits=bits,
                        block_size=64)
        out = jax.jit(lambda p: pipeline_param_gather(
            p, shardings, mesh, plan))(params)
        for k in ("a", "b", "c"):
            a = np.asarray(params[k], np.float32)
            b = np.asarray(out[k], np.float32)
            rel = np.linalg.norm(a - b) / np.linalg.norm(a)
            assert rel < bound, (k, bits, rel)

    def test_qwz_only_transpose_is_exact(self, devices):
        """weight_bits quantizes only the FORWARD wire: for a linear loss
        d/dx sum(gather(x) * w) must equal w exactly (weight quantization
        never biases gradients — the qwZ contract)."""
        mesh = build_mesh(MeshSpec(dp=1, fsdp=4, tp=2))
        params, shardings = _leaves_and_shardings(mesh)
        w = jax.tree_util.tree_map(jnp.ones_like, params)
        plan = WirePlan(num_chunks=2, weight_bits=8, grad_bits=0,
                        block_size=64)

        def loss(p):
            q = pipeline_param_gather(p, shardings, mesh, plan)
            return sum((q[k].astype(jnp.float32) * w[k].astype(jnp.float32)
                        ).sum() for k in ("a", "b", "c"))

        g = jax.jit(jax.grad(loss))(params)
        for k in ("a", "b", "c"):
            np.testing.assert_allclose(np.asarray(g[k], np.float32),
                                       np.ones_like(np.asarray(g[k],
                                                               np.float32)),
                                       rtol=1e-6)

    def test_quantized_vjp_within_bounds_and_s8_wire(self, devices):
        """grad_bits quantizes the transpose reduce-scatter: grads stay
        within the int8 bound vs the exact transpose, and the compiled
        backward carries the s8 all-to-all."""
        mesh = build_mesh(MeshSpec(dp=1, fsdp=4, tp=2))
        params, shardings = _leaves_and_shardings(mesh)

        def loss(p, plan):
            q = pipeline_param_gather(p, shardings, mesh, plan)
            return sum((q[k].astype(jnp.float32) ** 2).sum()
                       for k in ("a", "b", "c"))

        exact = jax.jit(jax.grad(
            lambda p: loss(p, WirePlan(num_chunks=2))))(params)
        planq = WirePlan(num_chunks=2, weight_bits=8, grad_bits=8,
                         block_size=64)
        quant = jax.jit(jax.grad(lambda p: loss(p, planq)))(params)
        for k in ("a", "b", "c"):
            a = np.asarray(exact[k], np.float32)
            b = np.asarray(quant[k], np.float32)
            rel = np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-9)
            assert rel < 0.05, (k, rel)
        txt = jax.jit(jax.grad(
            lambda p: loss(p, planq))).lower(params).compile().as_text()
        lines = txt.splitlines()
        assert any("s8[" in ln and "all-gather" in ln for ln in lines)
        assert any("s8[" in ln and "all-to-all" in ln for ln in lines)

    def test_hierarchical_on_one_host_is_bitwise(self, devices):
        """Hierarchy on a single host resolves every axis to full width:
        the quantized plan degrades to the bitwise chunk-only program."""
        mesh = build_mesh(MeshSpec(dp=1, fsdp=4, tp=2))
        params, shardings = _leaves_and_shardings(mesh)
        plan = WirePlan(num_chunks=3, weight_bits=8, grad_bits=8,
                        hierarchical=True)
        out = jax.jit(lambda p: pipeline_param_gather(
            p, shardings, mesh, plan))(params)
        for k in params:
            assert np.array_equal(np.asarray(out[k], np.float32),
                                  np.asarray(params[k], np.float32)), k


# ====================================================== data-axis reduce

class TestPipelineGradReduce:
    def test_quantized_allreduce_and_scatter(self, devices):
        """Stacked per-replica grads reduce to the mean within the int8
        bound; a leaf whose target shards over the reduce axis lands
        scattered (qgZ), replicated leaves take the EQuARX allreduce, and
        the wire is s8."""
        mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
        rng = np.random.default_rng(1)
        stacked = {
            "w": jnp.asarray(rng.normal(size=(2, 64, 16)), jnp.float32),
            "r": jnp.asarray(rng.normal(size=(2, 32, 8)), jnp.float32),
            "s": jnp.asarray(rng.normal(size=(2,)), jnp.float32),
        }
        target = {"w": NamedSharding(mesh, P(("fsdp", "dp"), None)),
                  "r": NamedSharding(mesh, P("fsdp", None)),
                  "s": NamedSharding(mesh, P())}
        placed = {
            "w": jax.device_put(stacked["w"],
                                NamedSharding(mesh, P("dp", "fsdp", None))),
            "r": jax.device_put(stacked["r"],
                                NamedSharding(mesh, P("dp", "fsdp", None))),
            "s": jax.device_put(stacked["s"], NamedSharding(mesh, P("dp"))),
        }
        plan = WirePlan(grad_bits=8, block_size=64)
        fn = jax.jit(lambda g: pipeline_grad_reduce(
            g, target, mesh, "dp", plan))
        red = fn(placed)
        for k in ("w", "r"):
            ref = np.asarray(stacked[k]).mean(0)
            got = np.asarray(red[k])
            assert got.shape == ref.shape
            rel = np.linalg.norm(ref - got) / np.linalg.norm(ref)
            assert rel < 0.02, (k, rel)
        assert abs(float(red["s"]) - float(np.asarray(
            stacked["s"]).mean())) < 1e-6
        txt = fn.lower(placed).compile().as_text()
        assert any("s8[" in ln and "all-to-all" in ln
                   for ln in txt.splitlines())

    def test_world1_unstacks(self, devices):
        mesh = build_mesh(MeshSpec(dp=1, fsdp=8))
        g = {"w": jnp.ones((1, 8, 8), jnp.float32)}
        target = {"w": NamedSharding(mesh, P())}
        red = pipeline_grad_reduce(g, target, mesh, "dp", WirePlan())
        assert red["w"].shape == (8, 8)


# ======================================================== engine: the matrix

class TestEngineComposition:
    def test_composed_wire_reduction_and_exposed_ratio(self, devices):
        """The acceptance criterion, CPU-sized: chunking + int4
        quantization together move ≥3× fewer gather/scatter bytes than the
        bf16-chunked baseline, while the compiled step's exposed ratio
        stays in the same regime (within 0.15 absolute) — quantization
        must not un-hide the wire.  Both steps are compiled in consumer
        order (``conftest.CONSUMER_ORDER``): XLA:CPU's default scheduler
        issues either train's gathers back to back whatever the program
        allows between them."""
        from deepspeed_tpu.comm.comm import hlo_overlap_stats, hlo_wire_bytes
        base = _build_engine(chunks=4)
        comp = _build_engine(chunks=4, qwz=True, qgz=True,
                             zpp={"weight_bits": 4, "grad_bits": 4,
                                  "block_size": 128})
        base_txt = _step_hlo(base, CONSUMER_ORDER)
        comp_txt = _step_hlo(comp, CONSUMER_ORDER)
        bw, cw = hlo_wire_bytes(base_txt), hlo_wire_bytes(comp_txt)
        assert cw["quantized"] > 0
        reduction = bw["gather_scatter"] / cw["gather_scatter"]
        assert reduction >= 3.0, (bw, cw)
        r0 = hlo_overlap_stats(base_txt)["exposed_ratio"]
        r1 = hlo_overlap_stats(comp_txt)["exposed_ratio"]
        assert abs(r1 - r0) < 0.15, (r0, r1)
        # the chunk train survives quantization: interleaved s8 gathers
        s = hlo_overlap_stats(comp_txt)
        assert s["per_kind_interleaved"].get("all-gather", 0) >= 2, s

    def test_loss_trajectory_parity_vs_bf16_comms(self, devices):
        """Short-run loss parity: the composed q8 pipeline tracks the
        full-width chunked engine (the ZeRO++ no-degradation claim)."""
        base = _build_engine(chunks=4, seed=3)
        comp = _build_engine(chunks=4, qwz=True, qgz=True, seed=3)
        # memorizable pool (same regime test_qgz uses): 8 fixed sequences
        rng = np.random.default_rng(9)
        pool = rng.integers(0, VOCAB, size=(8, SEQ)).astype(np.int32)
        batches = [{"input_ids": pool[rng.integers(
            0, len(pool), size=(base.train_batch_size,))]}
            for _ in range(20)]
        lb = [float(base.train_batch(b).loss) for b in batches]
        lc = [float(comp.train_batch(b).loss) for b in batches]
        assert lc[-1] < lc[0] * 0.8, "composed engine failed to learn"
        assert abs(lc[-1] - lb[-1]) / max(lb[-1], 1e-6) < 0.10, (lb, lc)

    @pytest.mark.parametrize("qwz,qgz", [(False, False), (True, False),
                                         (False, True), (True, True)])
    def test_vjp_covered_in_every_mode(self, devices, qwz, qgz):
        """The reduce-scatter transpose runs (and trains) in each of the
        four wire modes — grads flow, losses finite, s8 present iff
        quantized."""
        eng = _build_engine(chunks=2, qwz=qwz, qgz=qgz, seed=11)
        loss = float(eng.train_batch(_batch(eng)).loss)
        assert np.isfinite(loss)
        if qwz or qgz:
            txt = _step_hlo(eng)
            assert any("s8[" in ln for ln in txt.splitlines()
                       if "all-gather" in ln or "all-to-all" in ln)

    def test_equarx_stage1_quantized_allreduce(self, devices):
        """zeropp.quantized_allreduce opens the stage-0/1 dp grad path
        (full-width today → EQuARX block-quantized): the engine learns a
        memorizable pool as the full-width stage-1 engine does, and the
        compiled step moves s8 on the data axis.  (Fresh uniform-random
        batches teach neither engine anything: both stay at ln(VOCAB).)"""
        eng = _build_engine(stage=1, chunks=1, mesh_kw={"dp": -1},
                            zpp={"quantized_allreduce": True})
        full = _build_engine(stage=1, chunks=1, mesh_kw={"dp": -1})
        assert eng._qgz_axis is not None and full._qgz_axis is None
        rng = np.random.default_rng(9)
        pool = rng.integers(0, VOCAB, size=(8, SEQ)).astype(np.int32)
        batches = [{"input_ids": pool[rng.integers(
            0, len(pool), size=(eng.train_batch_size,))]}
            for _ in range(20)]
        lq = [float(eng.train_batch(b).loss) for b in batches]
        lf = [float(full.train_batch(b).loss) for b in batches]
        assert lq[-1] < lq[0] * 0.8, lq
        assert abs(lq[-1] - lf[-1]) / lf[-1] < 0.10, (lf, lq)
        txt = _step_hlo(eng)
        assert any("s8[" in ln and "all-to-all" in ln
                   for ln in txt.splitlines())

    def test_hierarchical_engine_quantizes_only_cross_host(self, devices):
        """Simulated 2-host mesh (dp crosses, fsdp inside): hierarchical
        qwZ+qgZ keeps the fsdp gather full-width (no s8 all-gather) while
        the cross-host dp grad exchange still moves s8."""
        from deepspeed_tpu.comm import collectives as cc
        mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
        devs = list(mesh.devices.flatten())
        host_of = {d: i // 4 for i, d in enumerate(devs)}
        cc.set_link_process_fn(lambda d: host_of[d])
        try:
            eng = _build_engine(chunks=2, qwz=True, qgz=True,
                                mesh_kw={"dp": 2, "fsdp": 4},
                                zpp={"hierarchical": True})
            assert eng._wire_plan.hierarchical
            loss = float(eng.train_batch(_batch(eng)).loss)
            assert np.isfinite(loss)
            txt = _step_hlo(eng)
            lines = txt.splitlines()
            # the fsdp (intra-host) gather train stays full-width: its
            # bf16/f32 all-gather payload dominates; the only s8
            # all-gathers are the small dp-side EQuARX return legs
            def ag_bytes(pred):
                total = 0
                for ln in lines:
                    m = re.search(r"=\s*([a-z0-9]+)\[([0-9,]*)\]\S*\s+"
                                  r"all-gather(?:-start)?\(", ln)
                    if m and pred(m.group(1)):
                        n = 1
                        for d in m.group(2).split(","):
                            if d:
                                n *= int(d)
                        total += n
                return total
            full_ag = ag_bytes(lambda dt: dt in ("f32", "bf16")) * 2
            s8_ag = ag_bytes(lambda dt: dt == "s8")
            assert full_ag > 4 * s8_ag, (full_ag, s8_ag)
            assert any("s8[" in ln and "all-to-all" in ln
                       for ln in lines), "dp exchange must quantize"
        finally:
            cc.set_link_process_fn(None)


# ===================================================== wire-byte accounting

class TestWireByteAccounting:
    def test_quantized_kinds_logged_at_wire_width(self, devices):
        """collective_bytes_total carries all_gather_q8 / all_to_all_q8
        series whose bytes are the int8+scales wire payload — well under
        the bf16-equivalent volume of the same exchange."""
        from deepspeed_tpu.telemetry.registry import (COLLECTIVE_BYTES,
                                                      default_registry)
        default_registry.reset()
        eng = _build_engine(chunks=2, qwz=True, qgz=True, seed=13)
        eng.train_batch(_batch(eng))
        bc = default_registry.counter(COLLECTIVE_BYTES)
        q_ag = bc.value(kind="all_gather_q8", axis="fsdp")
        q_a2a = bc.value(kind="all_to_all_q8", axis="fsdp")
        assert q_ag > 0 and q_a2a > 0
        # wire width: the q8 gather of P params over world n moves about
        # (n-1)·P·(1 + scales) bytes per trace — far below bf16's 2·(n-1)·P
        n = eng.mesh.shape["fsdp"]
        p = eng.num_parameters
        assert q_ag < 2 * (n - 1) * p, (q_ag, p)
        # the ici/dcn split sums to the total for the tagged kinds too
        ici = bc.value(kind="all_gather_q8", axis="fsdp", link="ici")
        dcn = bc.value(kind="all_gather_q8", axis="fsdp", link="dcn")
        assert ici + dcn == q_ag
        default_registry.reset()

    def test_hlo_wire_bytes_classifier(self):
        from deepspeed_tpu.comm.comm import hlo_wire_bytes
        hlo = """
ENTRY %main () -> f32[] {
  %g0 = s8[4,256] all-gather(s8[1,256] %a)
  %s0 = f32[4,2] all-gather(f32[1,2] %b)
  %r0 = f32[64] reduce-scatter(f32[256] %c)
  %ar = f32[8] all-reduce(f32[8] %d)
}
"""
        w = hlo_wire_bytes(hlo)
        assert w["quantized"] == 4 * 256
        assert w["full"] == 4 * 2 * 4 + 64 * 4 + 8 * 4
        assert w["total"] == w["quantized"] + w["full"]
        assert w["gather_scatter"] == w["total"] - 8 * 4


# ================================================== overlap-stats companions

class TestOverlapCompanions:
    def test_scale_leg_rides_values_window(self):
        """A tiny same-kind collective back-to-back after a big one (the
        fp32 scale leg of a quantized chunk) counts as a companion, not
        exposed — the gauge stays sighted under quantization."""
        from deepspeed_tpu.comm.comm import hlo_overlap_stats
        hlo = """
ENTRY %main () -> f32[] {
  %g0 = s8[4,256] all-gather(s8[1,256] %a)
  %s0 = f32[4,2] all-gather(f32[1,2] %sa)
  %f0 = f32[4,8] fusion(f32[4,8] %g0), kind=kLoop
  %g1 = s8[4,256] all-gather(s8[1,256] %b)
  %s1 = f32[4,2] all-gather(f32[1,2] %sb)
  %f1 = f32[4,8] fusion(f32[4,8] %g1), kind=kLoop
  %g2 = s8[4,256] all-gather(s8[1,256] %c)
  %s2 = f32[4,2] all-gather(f32[1,2] %sc)
}
"""
        s = hlo_overlap_stats(hlo)
        assert s["companion_collectives"] == 3
        assert s["companion_bytes"] == 3 * 4 * 2 * 4
        assert s["per_kind_interleaved"]["all-gather"] == 2
        # only the first values gather is exposed (no predecessor)
        assert s["exposed_bytes"] == 4 * 256

    def test_async_empty_window_companion(self):
        from deepspeed_tpu.comm.comm import hlo_overlap_stats
        hlo = """
ENTRY %main () -> f32[] {
  %v = s8[4,256] all-gather(s8[1,256] %a)
  %f0 = f32[4,8] fusion(f32[4,8] %v), kind=kLoop
  %w = s8[4,256] all-gather(s8[1,256] %b)
  %ss = (f32[1,2], f32[4,2]) all-gather-start(f32[1,2] %sa)
  %sd = f32[4,2] all-gather-done((f32[1,2], f32[4,2]) %ss)
}
"""
        s = hlo_overlap_stats(hlo)
        # the empty-window async scales pair rides the preceding values op
        assert s["companion_collectives"] == 1
        assert s["async_pairs"] == 1

    def test_big_empty_window_pair_still_exposed(self):
        """Companion logic must not grant amnesty to a real exposed
        collective: a full-size empty-window pair stays exposed."""
        from deepspeed_tpu.comm.comm import hlo_overlap_stats
        hlo = """
ENTRY %main () -> f32[] {
  %v = f32[4,256] all-gather(f32[1,256] %a)
  %ss = (f32[1,256], f32[4,256]) all-gather-start(f32[1,256] %b)
  %sd = f32[4,256] all-gather-done((f32[1,256], f32[4,256]) %ss)
}
"""
        s = hlo_overlap_stats(hlo)
        assert s["companion_collectives"] == 0
        assert s["exposed_ratio"] == 1.0


# ============================================================ gates removed

class TestGatesRemoved:
    def test_all_three_layers_compose_in_one_engine(self, devices):
        """The ROADMAP [comms] item verbatim: quantized wire AND hidden
        wire from one engine — chunks=4 × qwZ × qgZ builds (both former
        gates raised here), trains, and shows an interleaved s8 chunk
        train."""
        from deepspeed_tpu.comm.comm import hlo_overlap_stats
        eng = _build_engine(chunks=4, qwz=True, qgz=True, seed=5)
        assert eng._pipeline_active
        assert eng._wire_plan.num_chunks == 4
        assert eng._wire_plan.weight_bits == 8
        assert eng._wire_plan.grad_bits == 8
        loss = float(eng.train_batch(_batch(eng)).loss)
        assert np.isfinite(loss)
        txt = _step_hlo(eng)
        s8_ags = [ln for ln in txt.splitlines()
                  if re.search(r" all-gather(-start)?\(", ln)
                  and "s8[" in ln]
        assert len(s8_ags) >= 4
        assert hlo_overlap_stats(txt)["per_kind_interleaved"].get(
            "all-gather", 0) >= 2

    def test_stage3_dp_qgz_composes_with_chunks(self, devices):
        """chunks × qgZ with a real dp axis (the formerly
        NotImplementedError combination): the manual data-axis region now
        consumes pre-gathered params, so the chunk shard_maps never nest
        inside it."""
        eng = _build_engine(chunks=2, qgz=True,
                            mesh_kw={"dp": 2, "fsdp": 4}, seed=5)
        assert eng._qgz_axis == "dp"
        assert eng._pipeline_active
        losses = [float(eng.train_batch(_batch(eng, seed=60 + i)).loss)
                  for i in range(3)]
        assert np.isfinite(losses).all()
