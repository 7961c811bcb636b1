"""MoE expert-parallel fast-path tests (quantized + overlapped a2a).

Pins the PR's structural claims: the k==1 indexed gating is bitwise-equal
to the dense one-hot reference; ep=1 is a2a-free no matter which wire/chunk
knobs are set; chunking the dispatch→FFN→combine chain changes scheduling
only (outputs identical); the int4 wire moves ≥3× fewer a2a bytes than the
bf16-equivalent at a flat exposed-comm ratio; `all_to_all_q8`/`q4` byte
accounting satisfies ici + dcn == total (the docs/observability.md
contract); and the quantized wire preserves the training loss trajectory.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.comm import hlo_collective_bytes, hlo_overlap_stats
from deepspeed_tpu.models import GPT, GPTConfig
from deepspeed_tpu.moe import MoE
from deepspeed_tpu.moe.comm import resolve_a2a_bits
from deepspeed_tpu.moe.sharded_moe import _topk_gating_dense, topk_gating
from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh

VOCAB, SEQ = 64, 16


# ===================================================== k==1 indexed gating

class TestIndexedGating:
    """topk_gating(k=1) routes through the index-based fast path — same
    outputs BITWISE as the dense one-hot algebra it replaced."""

    @pytest.mark.parametrize("cf", [1.0, 1.25, 4.0])
    @pytest.mark.parametrize("seed", [0, 3])
    def test_bitwise_matches_dense_reference(self, cf, seed):
        logits = jax.random.normal(jax.random.PRNGKey(seed), (96, 8))
        aux_i, comb_i, disp_i = topk_gating(logits, 1, cf)
        aux_d, comb_d, disp_d = _topk_gating_dense(logits, 1, cf)
        np.testing.assert_array_equal(np.asarray(comb_i), np.asarray(comb_d))
        np.testing.assert_array_equal(np.asarray(disp_i), np.asarray(disp_d))
        np.testing.assert_array_equal(np.asarray(aux_i), np.asarray(aux_d))

    def test_bitwise_under_heavy_imbalance(self):
        """Tight capacity + skewed router (most tokens drop): the indexed
        path's clamp-and-mask scatter must reproduce the dense drop
        pattern exactly."""
        logits = jnp.asarray(
            np.random.default_rng(7).standard_normal((64, 4)), jnp.float32)
        logits = logits.at[:, 0].add(4.0)       # expert 0 wins almost always
        aux_i, comb_i, disp_i = topk_gating(logits, 1, 1.0, 4)
        aux_d, comb_d, disp_d = _topk_gating_dense(logits, 1, 1.0, 4)
        np.testing.assert_array_equal(np.asarray(comb_i), np.asarray(comb_d))
        np.testing.assert_array_equal(np.asarray(disp_i), np.asarray(disp_d))
        assert int(disp_i.sum()) < logits.shape[0]      # drops did happen


# ========================================================= ep=1 inertness

class TestEp1Inert:
    def test_no_a2a_and_knobs_inert_without_ep(self, rng):
        """mesh=None (ep=1): wire/chunk knobs must be dead code — the
        compiled HLO contains NO all-to-all, and the output is bitwise the
        plain einsum path's."""
        x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
        plain = MoE(hidden_size=16, num_experts=4, k=1, mlp_ratio=2)
        knobs = MoE(hidden_size=16, num_experts=4, k=1, mlp_ratio=2,
                    wire_bits=8, wire_block=64, num_chunks=4,
                    hierarchical=True)
        v = plain.init(jax.random.PRNGKey(0), x)
        y0, aux0 = plain.apply(v, x)
        y1, aux1 = knobs.apply(v, x)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
        assert float(aux0) == float(aux1)
        txt = jax.jit(knobs.apply).lower(v, x).compile().as_text()
        assert "all-to-all" not in txt


# ===================================================== chunk-only semantics

class TestChunking:
    """num_chunks tiles the dispatch-a2a → FFN → combine-a2a chain; it may
    only change scheduling, never values."""

    def _params_x(self, rng, drop=False):
        x = jnp.asarray(rng.standard_normal((4, 16, 32)), jnp.float32)
        m = MoE(hidden_size=32, num_experts=8, k=2, capacity_factor=2.0,
                mlp_ratio=2, dropless=drop)
        return m, m.init(jax.random.PRNGKey(1), x), x

    def test_capacity_route_chunked_equals_unchunked(self, rng, devices):
        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        m, v, x = self._params_x(rng)
        one = m.clone(mesh=mesh, num_chunks=1)
        two = m.clone(mesh=mesh, num_chunks=2)
        with mesh:
            y1, aux1 = jax.jit(one.apply)(v, x)
            y2, aux2 = jax.jit(two.apply)(v, x)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
        assert float(aux1) == float(aux2)

    def test_dropless_route_chunked_equals_unchunked(self, rng, devices):
        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        m, v, x = self._params_x(rng, drop=True)
        one = m.clone(mesh=mesh, num_chunks=1)
        two = m.clone(mesh=mesh, num_chunks=2)
        with mesh:
            y1, aux1 = jax.jit(one.apply)(v, x)
            y2, aux2 = jax.jit(two.apply)(v, x)
        # same rows through the same experts, but a chunk's grouped GEMM
        # sees half the rows and the backend blocks a matmul's accumulation
        # by its shape: equal up to float32 rounding, not bit for bit (the
        # capacity route above batches per expert, not per row, and is)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-5, atol=1e-7)
        assert float(aux1) == float(aux2)

    def test_non_divisor_chunk_count_degrades_gracefully(self, rng, devices):
        """num_chunks that doesn't tile E_local resolves to the largest
        divisor (never crashes, never changes values)."""
        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        m, v, x = self._params_x(rng)
        odd = m.clone(mesh=mesh, num_chunks=3)      # E_local=2 → nc=1
        ref = m.clone(mesh=mesh, num_chunks=1)
        with mesh:
            yo, _ = jax.jit(odd.apply)(v, x)
            yr, _ = jax.jit(ref.apply)(v, x)
        np.testing.assert_array_equal(np.asarray(yo), np.asarray(yr))


# ===================================================== quantized a2a wire

def _a2a_bytes(txt):
    return hlo_collective_bytes(txt).get("all-to-all", {}).get("bytes", 0)


def _bf16_equiv_a2a_bytes(txt):
    """a2a payload bytes normalized to a bf16 wire: XLA:CPU's float
    normalization rewrites bf16 compute to f32, so full-width a2a payloads
    compile at 4 B/el here vs 2 B/el on TPU — halve when no bf16 a2a
    survived."""
    b = _a2a_bytes(txt)
    if not re.search(r"bf16\[[0-9,]*\][^ ]*\s+all-to-all", txt):
        b //= 2
    return b


class TestQuantizedWire:
    def _grad_hlo(self, mesh, m, v, x):
        def loss(vv, xx):
            y, aux = m.clone(mesh=mesh).apply(vv, xx)
            return jnp.sum(y.astype(jnp.float32) ** 2) + aux
        with mesh:
            return jax.jit(jax.grad(loss)).lower(v, x).compile().as_text()

    def test_int4_wire_3x_below_bf16_at_flat_exposure(self, rng, devices):
        """Acceptance gate: composed int4 dispatch+combine a2a bytes ≥3×
        below the bf16-equivalent full-width wire, with the exposed-comm
        ratio no worse — measured structurally on compiled HLO of the
        full fwd+bwd route."""
        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        x = jnp.asarray(rng.standard_normal((4, 16, 64)), jnp.bfloat16)
        base = MoE(hidden_size=64, num_experts=8, k=1, capacity_factor=1.25,
                   mlp_ratio=2, num_chunks=2)
        v = base.init(jax.random.PRNGKey(0), x)
        base_txt = self._grad_hlo(mesh, base, v, x)
        q4_txt = self._grad_hlo(
            mesh, base.clone(wire_bits=4, wire_block=64), v, x)
        bf16_b = _bf16_equiv_a2a_bytes(base_txt)
        q4_b = _a2a_bytes(q4_txt)
        assert bf16_b > 0 and q4_b > 0
        assert bf16_b / q4_b >= 3.0, (bf16_b, q4_b)
        exp0 = hlo_overlap_stats(base_txt)["exposed_ratio"]
        exp4 = hlo_overlap_stats(q4_txt)["exposed_ratio"]
        assert exp4 <= exp0 + 0.05, (exp0, exp4)

    def test_int8_wire_preserves_route_output(self, rng, devices):
        """int8 codes + fp32 block scales on the wire: the routed output
        stays within blockwise-quantization error of the full-width route,
        and gradients stay finite."""
        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        x = jnp.asarray(rng.standard_normal((4, 16, 32)), jnp.float32)
        full = MoE(hidden_size=32, num_experts=8, k=2, capacity_factor=2.0,
                   mlp_ratio=2, mesh=mesh)
        q8 = full.clone(wire_bits=8, wire_block=64)
        # init and backward as a program each, not one a primitive
        v = jax.jit(full.init)(jax.random.PRNGKey(2), x)
        with mesh:
            yf, _ = jax.jit(full.apply)(v, x)
            yq, _ = jax.jit(q8.apply)(v, x)

            def loss(vv):
                y, aux = q8.apply(vv, x)
                return jnp.sum(y ** 2) + aux
            g = jax.jit(jax.grad(loss))(v)
        yf, yq = np.asarray(yf), np.asarray(yq)
        rel = np.linalg.norm(yq - yf) / np.linalg.norm(yf)
        assert rel < 0.05, rel
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(g))

    def test_hierarchical_policy_resolves_per_mesh(self, devices):
        """resolve_a2a_bits: all-ICI ep rings stay full width under the
        hierarchical policy; simulated host-crossing rings quantize."""
        from deepspeed_tpu.comm import collectives as cc
        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        assert resolve_a2a_bits(0, hierarchical=False, mesh=mesh) == 0
        assert resolve_a2a_bits(8, hierarchical=False, mesh=mesh) == 8
        # single host (CPU CI): hierarchical keeps the wire full width
        assert resolve_a2a_bits(8, hierarchical=True, mesh=mesh) == 0
        devs = list(mesh.devices.flatten())
        host_of = {d: i // 2 for i, d in enumerate(devs)}   # ep rings cross
        cc.set_link_process_fn(lambda d: host_of[d])
        try:
            assert resolve_a2a_bits(8, hierarchical=True, mesh=mesh) == 8
            assert resolve_a2a_bits(4, hierarchical=True, mesh=mesh) == 4
        finally:
            cc.set_link_process_fn(None)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_tagged_kind_ici_dcn_split_sums_to_total(self, rng, devices,
                                                     bits):
        """docs/observability.md contract: `all_to_all_q8`/`q4` byte series
        carry the ici/dcn link split and ici + dcn == total EXACTLY."""
        from deepspeed_tpu.comm import collectives as cc
        from deepspeed_tpu.telemetry.registry import (COLLECTIVE_BYTES,
                                                      default_registry)
        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        devs = list(mesh.devices.flatten())
        host_of = {d: i // 2 for i, d in enumerate(devs)}   # 4 hosts of 2
        cc.set_link_process_fn(lambda d: host_of[d])
        default_registry.reset()
        try:
            x = jnp.asarray(rng.standard_normal((4, 16, 32)), jnp.float32)
            m = MoE(hidden_size=32, num_experts=8, k=1, mlp_ratio=2,
                    mesh=mesh, wire_bits=bits, wire_block=64)
            # the lowering needs the variables' shapes, not their values
            v = jax.eval_shape(m.init, jax.random.PRNGKey(0), x)
            with mesh:
                jax.jit(m.apply).lower(v, x)    # bytes log at trace time
            bc = default_registry.counter(COLLECTIVE_BYTES)
            kind = f"all_to_all_q{bits}"
            total = bc.value(kind=kind, axis="ep")
            ici = bc.value(kind=kind, axis="ep", link="ici")
            dcn = bc.value(kind=kind, axis="ep", link="dcn")
            assert total > 0
            assert dcn > 0                      # the simulated hosts split
            assert ici + dcn == total, (ici, dcn, total)
        finally:
            cc.set_link_process_fn(None)
            default_registry.reset()


# ============================================== engine-level loss behavior

def _moe_engine(moe_block=None, num_experts=4, seed=11):
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "mesh": {"dp": 1, "fsdp": 2, "ep": 2, "tp": 2},
        "steps_per_print": 0,
        "seed": seed,
        **({"moe": moe_block} if moe_block else {}),
    }
    model = GPT(GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ,
                               num_experts=num_experts,
                               moe_k=2 if num_experts else 1))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg,
        example_batch={"input_ids": np.zeros((4, SEQ), np.int32)})
    return engine


def _memorize(engine, steps=20):
    rng = np.random.default_rng(0)
    pool = rng.integers(0, VOCAB, size=(8, SEQ)).astype(np.int32)
    losses = []
    for _ in range(steps):
        idx = rng.integers(0, 8, size=(engine.train_batch_size,))
        losses.append(float(engine.train_batch({"input_ids": pool[idx]}).loss))
    return losses


class TestEngineLossBehavior:
    def test_quantized_wire_loss_trajectory_tracks_full_width(self, devices):
        """Same data, same seed: the int8 a2a wire must track the
        full-width run's loss trajectory (blockwise error only) and still
        memorize — and the compiled step must actually move s8 on an
        all-to-all."""
        full = _moe_engine()
        lf = _memorize(full)
        del full
        q = _moe_engine({"wire_bits": 8, "block_size": 64, "num_chunks": 2})
        lq = _memorize(q)
        assert all(np.isfinite(lq))
        assert lq[-1] < lq[0] * 0.8, lq
        # trajectory bound vs bf16: quantization may not change the
        # optimization story, only perturb it
        diffs = [abs(a - b) for a, b in zip(lf, lq)]
        assert max(diffs) < 0.5, (max(diffs), lf, lq)
        batch = q._shard_batch(q._reshape_gas(
            {"input_ids": np.zeros((q.train_batch_size, SEQ), np.int32)}),
            leading_gas=True)
        with q.mesh:
            txt = jax.jit(q._train_batch_fn).lower(
                q.state, batch).compile().as_text()
        assert any("s8[" in ln and "all-to-all" in ln
                   for ln in txt.splitlines()), "wire must carry s8 codes"

    def test_moe_loss_parity_vs_dense_equivalent(self, devices):
        """Short memorization run: the MoE model must reach the same loss
        neighborhood as its dense-equivalent (num_experts=0) twin — the
        routed experts add capacity, they must not break optimization."""
        dense = _moe_engine(num_experts=0)
        ld = _memorize(dense)
        del dense
        moe = _moe_engine()
        lm = _memorize(moe)
        assert ld[-1] < ld[0] * 0.8, ld
        assert lm[-1] < lm[0] * 0.8, lm
        assert abs(lm[-1] - ld[-1]) < 0.6, (lm[-1], ld[-1])
