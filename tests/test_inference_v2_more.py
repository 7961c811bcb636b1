"""Inference v2 (ragged/paged serving) tests, second file: tensor parallelism,
prefill buckets, quantized weights, the kernels' reach and MoE decode.  Moved
out of ``test_inference_v2.py`` as they stood (PR 46) so that the two halves
run on two workers (``--dist loadfile``): the one file was the suite's
longest, 1,128 s of a 1,528 s run.  Fixtures and the ground truth are the
first file's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import v2_engine
from test_inference_v2 import cfg, engine, full_logits, v2cfg  # noqa: F401

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import GPTConfig


class TestTensorParallel:
    """v2 ragged serving TP (reference inference/v2/model_implementations/
    sharding/): tp=2 must be token-exact vs tp=1 on the CPU mesh."""

    @pytest.mark.parametrize("hd", [8, 128], ids=["kvmajor", "hd128"])
    def test_tp2_generate_token_exact_vs_tp1(self, cfg, rng, hd):
        """Both page layouts: heads of 8 take kv-major pages and the page
        write, heads of 128 standard pages and the row write."""
        import dataclasses
        from deepspeed_tpu.inference.v2.model import kv_major_layout
        cfg2 = dataclasses.replace(cfg, num_heads=4, num_kv_heads=2,
                                   head_dim=hd)
        assert kv_major_layout(cfg2) == (hd == 8)
        v2cfg = {"dtype": "fp32",
                 "state_manager": {"max_tracked_sequences": 4,
                                   "max_ragged_batch_size": 64,
                                   "kv_block_size": 8, "max_q_per_seq": 16},
                 "generation": {"do_sample": False}}
        e1 = v2_engine(cfg2, config=v2cfg, seed=0)
        e2 = v2_engine(cfg2, config={**v2cfg,
                                     "tensor_parallel": {"tp_size": 2}},
                       params={"params": e1.params}, seed=0)
        assert e2.mesh is not None and e2.mesh.shape["tp"] == 2
        prompts = [rng.integers(0, 97, size=n).astype(np.int32)
                   for n in (5, 11, 3)]
        want = e1.generate(prompts, max_new_tokens=8)
        got = e2.generate(prompts, max_new_tokens=8)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    def test_tp_rejects_indivisible_kv_heads(self, cfg):
        import dataclasses
        cfg3 = dataclasses.replace(cfg, num_heads=3, num_kv_heads=3)
        with pytest.raises(ValueError, match="not divisible"):
            InferenceEngineV2(cfg3,
                              config={"tensor_parallel": {"tp_size": 2}})

    def test_pallas_kernel_sharded_matches_xla(self, rng):
        """shard_map-wrapped Pallas kernel (interpret mode) == XLA path."""
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        from deepspeed_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=2, dp=1, fsdp=1))
        S, nkv, g, hd, NB, bs, MB = 3, 2, 2, 8, 8, 8, 2
        q = rng.standard_normal((S, nkv, g, hd)).astype(np.float32)
        k = rng.standard_normal((NB, nkv, bs, hd)).astype(np.float32)
        v = rng.standard_normal((NB, nkv, bs, hd)).astype(np.float32)
        bt = np.array([[0, 1], [2, 3], [4, 5]], np.int32)
        lens = np.array([10, 16, 0], np.int32)
        want = xla_paged_attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), jnp.asarray(bt),
                                   jnp.asarray(lens))
        got = jax.jit(lambda *a: pallas_paged_attention(
            *a, interpret=True, mesh=mesh))(q, k, v, bt, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


class TestPrefillBuckets:
    def test_chunked_prefill_crosses_buckets_token_exact(self, cfg, v2cfg):
        """A prompt long enough that successive SplitFuse chunks land in
        different power-of-two block-table buckets must still match the
        cache-free forward exactly (the bucket slice only removes NEVER-USED
        pages)."""
        # a private engine: the case counts the programs in ``eng._steps``
        eng = InferenceEngineV2(cfg, config=v2cfg, seed=0)
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, 97, size=(50,)).astype(np.int32)  # 7 blocks
        uid = 11
        # feed in max_q_per_seq chunks like generate() does
        pos = 0
        while pos < len(prompt):
            chunk = prompt[pos:pos + 16]
            logits = eng.put([uid], [chunk])
            pos += len(chunk)
        # put() returns rows uid-ordered (one uid here → row 0)
        want = full_logits(cfg, eng, prompt[None])[0, -1]
        np.testing.assert_allclose(np.asarray(logits)[0], want,
                                   atol=2e-4, rtol=2e-4)
        # multiple prefill programs were compiled (different mb buckets)
        mixed_keys = [k for k in eng._steps if k[0] == "mixed"]
        assert len(mixed_keys) >= 2, mixed_keys


class TestQuantizedWeights:
    """v2 quantized weight serving (reference
    inference/v2/modules/implementations/linear/quantized_linear.py W6A16):
    int8 codes + group scales in HBM, per-use-site dequant in model.py
    _w/_embed — the bf16 tree never exists at rest."""

    QCFG = {"enabled": True, "group_size": 32}

    def mk(self, cfg, v2cfg, params=None, extra=None):
        c = dict(v2cfg, quant=self.QCFG)
        if extra:
            c.update(extra)
        return v2_engine(cfg, config=c, params=params, seed=0)

    def test_store_is_int8_and_smaller(self, v2cfg):
        """Realistically-shaped config (divisible vocab, ≥16 heads-dim):
        every matmul weight quantizes and the store is ~¼ the fp32 bytes.
        (The shared tiny fixture's vocab=97 is PRIME — its embedding can
        never group-quantize, which is the fallback path, tested above.)"""
        qcfg = GPTConfig.llama(num_layers=2, hidden=64, heads=16,
                               vocab_size=128, max_seq_len=64)
        base = v2_engine(qcfg, config=v2cfg, seed=0)
        q = self.mk(qcfg, v2cfg, params=base.params)
        fp_bytes = sum(l.size * l.dtype.itemsize for l in
                       jax.tree_util.tree_leaves(base.params))
        q_bytes = sum(l.size * l.dtype.itemsize for l in
                      jax.tree_util.tree_leaves(q.params))
        assert q_bytes < 0.45 * fp_bytes       # fp32 fixture → ~4x smaller
        kinds = {l.dtype for l in jax.tree_util.tree_leaves(q.params)}
        assert np.dtype("int8") in kinds

    def test_logits_close_to_unquantized(self, cfg, v2cfg, rng):
        base = v2_engine(cfg, config=v2cfg, seed=0)
        q = self.mk(cfg, v2cfg, params=base.params)
        prompts = [rng.integers(0, 97, (15,)).astype(np.int32)]
        lb = base.put([1], prompts)[0]
        base.flush([1])
        lq = q.put([1], prompts)[0]
        q.flush([1])
        denom = np.max(np.abs(np.asarray(lb)))
        assert np.max(np.abs(np.asarray(lb) - np.asarray(lq))) < 0.15 * denom

    def test_generate_runs_all_paths(self, cfg, v2cfg, rng):
        """prefill + decode burst + retirement over the quantized store."""
        q = self.mk(cfg, v2cfg)
        prompts = [rng.integers(0, 97, (10 + 5 * i,)).astype(np.int32)
                   for i in range(6)]                 # oversubscribes 4 slots
        outs = q.generate(prompts, max_new_tokens=[7, 9, 11, 5, 8, 6])
        assert [len(o) for o in outs] == [7, 9, 11, 5, 8, 6]

    def test_quant_tp2_token_exact_vs_tp1(self, cfg, v2cfg, rng):
        """The quant × tp composition the round-3 verdict ordered: same int8
        codes sharded two ways must produce identical greedy tokens."""
        base = v2_engine(cfg, config=v2cfg, seed=0)
        prompts = [rng.integers(0, 97, (12 + 3 * i,)).astype(np.int32)
                   for i in range(3)]
        q1 = self.mk(cfg, v2cfg, params=base.params)
        got1 = q1.generate(prompts, max_new_tokens=12)
        q2 = self.mk(cfg, v2cfg, params=base.params,
                     extra={"tensor_parallel": {"tp_size": 2}})
        got2 = q2.generate(prompts, max_new_tokens=12)
        for a, b in zip(got1, got2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_speculative_composes(self, cfg, v2cfg, rng):
        """Greedy spec decoding over a quantized target must match the
        quantized target-only output (exact-match acceptance invariant)."""
        base = v2_engine(cfg, config=v2cfg, seed=0)
        prompts = [rng.integers(0, 97, (11,)).astype(np.int32)]
        q = self.mk(cfg, v2cfg, params=base.params)
        want = q.generate(prompts, max_new_tokens=10)
        qs = v2_engine(cfg, config=dict(v2cfg, quant=self.QCFG),
                       params=base.params, seed=0,
                       draft_model=cfg, draft_params=base.params)
        got = qs.generate(prompts, max_new_tokens=10)
        np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))

    def test_moe_serving_over_quantized_experts(self, v2cfg, rng):
        """Mixtral-style MoE serving with the quant block: expert stacks
        quantize along dim 1 and the dropless route consumes the dequant
        at its use site — generate must run and match the unquantized
        engine's output closely (greedy, trained-free fp32 fixture)."""
        import dataclasses
        mcfg = GPTConfig.llama(num_layers=2, hidden=64, heads=4,
                               vocab_size=128, max_seq_len=64)
        mcfg = dataclasses.replace(mcfg, num_experts=4, moe_k=2)
        base = v2_engine(mcfg, config=v2cfg, seed=0)
        q = self.mk(mcfg, v2cfg, params=base.params)
        assert any(l.dtype == np.dtype("int8")
                   for l in jax.tree_util.tree_leaves(q.params)), \
            "nothing quantized in the MoE tree"
        prompts = [rng.integers(0, 128, (10 + i,)).astype(np.int32)
                   for i in range(3)]
        got = q.generate(prompts, max_new_tokens=8)
        want = base.generate(prompts, max_new_tokens=8)
        agree = np.mean([np.mean(np.asarray(a) == np.asarray(b))
                         for a, b in zip(got, want)])
        assert agree > 0.5          # random weights: near-ties may flip

    def test_tied_unembed_kernel_path(self, v2cfg, rng):
        """Tied embeddings with a group-divisible vocab: the unembed rides
        wq_matmul_t over the same [V, H] store the embed gather reads —
        greedy generate must track the unquantized engine."""
        import dataclasses
        tcfg = GPTConfig.llama(num_layers=2, hidden=64, heads=4,
                               vocab_size=128, max_seq_len=64)
        tcfg = dataclasses.replace(tcfg, tie_embeddings=True)
        base = v2_engine(tcfg, config=v2cfg, seed=0)
        q = self.mk(tcfg, v2cfg, params=base.params)
        from deepspeed_tpu.ops.quantization import is_quantized_weight
        assert is_quantized_weight(q.params["backbone"]["wte"])
        prompts = [rng.integers(0, 128, (11 + i,)).astype(np.int32)
                   for i in range(3)]
        got = q.generate(prompts, max_new_tokens=8)
        want = base.generate(prompts, max_new_tokens=8)
        agree = np.mean([np.mean(np.asarray(a) == np.asarray(b))
                         for a, b in zip(got, want)])
        assert agree > 0.5              # random weights: near-ties flip


class TestKernelReach:
    """Round-4 verdict items 2/3/7: the quantized-weight kernels must engage
    on attention projections, under tensor parallelism, on packed int4
    stores, and on real (non-tiling) vocabs — asserted via the kernels'
    trace counters, not just output correctness (a silent dequant fallback
    produces the same numbers while reading 2× the HBM).  An engine whose
    traces a case counts is a private ``InferenceEngineV2``: on shared step
    programs a second engine of its configuration traces nothing."""

    KCFG = GPTConfig.llama(num_layers=2, hidden=128, heads=4,
                           vocab_size=128, max_seq_len=64)

    def _counts(self):
        from deepspeed_tpu.ops import wq_matmul as wqm
        return dict(wqm.trace_counts)

    def test_kernel_engages_everywhere_single_shard(self, v2cfg, rng):
        """hidden=128/hd=32/group 32: QKV (dim-0 3-D view), attn-out
        (dim-1 3-D view), MLP, and untied lm_head all ride the W8 kernel."""
        base = v2_engine(self.KCFG, config=v2cfg, seed=0)
        before = self._counts()
        q = InferenceEngineV2(
            self.KCFG, config=dict(v2cfg, quant={"enabled": True,
                                                 "group_size": 32}),
            params=base.params, seed=0)
        prompts = [rng.integers(0, 128, (11,)).astype(np.int32)]
        got = q.generate(prompts, max_new_tokens=8)
        after = self._counts()
        # per compiled program: 3 qkv + 1 attn-out per layer (2 layers),
        # 3 mlp (gated) per layer, 1 unembed — several programs compile
        # (prefill buckets + decode burst), so just require a healthy count
        assert after["w8"] - before["w8"] >= 10, (before, after)
        want = base.generate(prompts, max_new_tokens=8)
        agree = np.mean(np.asarray(got[0]) == np.asarray(want[0]))
        assert agree > 0.5

    def test_kernel_engages_under_tp2(self, v2cfg, rng):
        """The round-4 bypass ran tp>1 on the dequant path; the shard_map
        wrapper must keep the kernel engaged AND reproduce tp=1 tokens."""
        base = v2_engine(self.KCFG, config=v2cfg, seed=0)
        qc = {"enabled": True, "group_size": 32}
        q1 = v2_engine(self.KCFG, config=dict(v2cfg, quant=qc),
                       params=base.params, seed=0)
        prompts = [rng.integers(0, 128, (12 + 3 * i,)).astype(np.int32)
                   for i in range(3)]
        got1 = q1.generate(prompts, max_new_tokens=10)
        before = self._counts()
        q2 = InferenceEngineV2(
            self.KCFG, config=dict(v2cfg, quant=qc,
                                   tensor_parallel={"tp_size": 2}),
            params=base.params, seed=0)
        got2 = q2.generate(prompts, max_new_tokens=10)
        after = self._counts()
        assert after["w8"] - before["w8"] >= 10, (before, after)
        for a, b in zip(got1, got2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_w4_kernel_engages(self, v2cfg, rng):
        """bits=4 now serves through the packed W4A16 kernel (group 64)."""
        base = v2_engine(self.KCFG, config=v2cfg, seed=0)
        before = self._counts()
        q = InferenceEngineV2(
            self.KCFG, config=dict(v2cfg, quant={"enabled": True, "bits": 4,
                                                 "group_size": 64}),
            params=base.params, seed=0)
        prompts = [rng.integers(0, 128, (11,)).astype(np.int32)]
        outs = q.generate(prompts, max_new_tokens=8)
        after = self._counts()
        assert after["w4"] - before["w4"] >= 4, (before, after)
        assert len(outs[0]) == 8

    def test_w4_tp2_matches_tp1(self, v2cfg, rng):
        """Nibble packing no longer forces single-shard: pack-after-shard
        keeps pairs/groups intact over tp=2 and tokens must match tp=1."""
        base = v2_engine(self.KCFG, config=v2cfg, seed=0)
        qc = {"enabled": True, "bits": 4, "group_size": 64}
        prompts = [rng.integers(0, 128, (12,)).astype(np.int32)]
        q1 = v2_engine(self.KCFG, config=dict(v2cfg, quant=qc),
                       params=base.params, seed=0)
        got1 = q1.generate(prompts, max_new_tokens=8)
        q2 = v2_engine(
            self.KCFG, config=dict(v2cfg, quant=qc,
                                   tensor_parallel={"tp_size": 2}),
            params=base.params, seed=0)
        got2 = q2.generate(prompts, max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(got1[0]),
                                      np.asarray(got2[0]))

    def test_tied_odd_vocab_pads_and_serves(self, v2cfg, rng):
        """GPT-2-class odd vocabs (here 250) pad to the quantization group
        at store creation so the table quantizes and the transposed kernel
        tiles; logits slice back to vocab_size (round-4 verdict item 7)."""
        import dataclasses
        tcfg = GPTConfig.llama(num_layers=2, hidden=128, heads=4,
                               vocab_size=250, max_seq_len=64)
        tcfg = dataclasses.replace(tcfg, tie_embeddings=True)
        base = v2_engine(tcfg, config=v2cfg, seed=0)
        before = self._counts()
        q = InferenceEngineV2(
            tcfg, config=dict(v2cfg, quant={"enabled": True,
                                            "group_size": 128}),
            params=base.params, seed=0)
        from deepspeed_tpu.ops.quantization import is_quantized_weight
        wte = q.params["backbone"]["wte"]
        assert is_quantized_weight(wte)
        assert wte["v"].shape[0] == 256          # padded to the group
        prompts = [rng.integers(0, 250, (11 + i,)).astype(np.int32)
                   for i in range(3)]
        got = q.generate(prompts, max_new_tokens=8)
        after = self._counts()
        assert after["w8t"] - before["w8t"] >= 1, (before, after)
        want = base.generate(prompts, max_new_tokens=8)
        agree = np.mean([np.mean(np.asarray(a) == np.asarray(b))
                         for a, b in zip(got, want)])
        assert agree > 0.5
        for o in got:                            # padded ids never emitted
            assert np.all(np.asarray(o) < 250)


class TestMoEDecode:
    """MoE models through the v2 ragged engine (the training-side dropless
    route and the serving-side _ffn are the same gating + ragged grouped
    GEMM): decode must be token-exact against the training forward."""

    def _mcfg(self):
        import dataclasses
        mcfg = GPTConfig.llama(num_layers=2, hidden=64, heads=4,
                               vocab_size=128, max_seq_len=64)
        return dataclasses.replace(mcfg, num_experts=4, moe_k=2,
                                   moe_dropless=True)

    def test_prefill_and_decode_match_training_forward(self, v2cfg, rng):
        mcfg = self._mcfg()
        engine = v2_engine(mcfg, config=v2cfg, seed=0)
        ids = rng.integers(0, 128, (12,)).astype(np.int32)
        logits = engine.put([1], [ids])
        want = full_logits(mcfg, engine, ids[None])[0, -1]
        np.testing.assert_allclose(logits[0], want, atol=1e-4, rtol=1e-4)
        l1 = engine.put([1], [np.asarray([5], np.int32)])
        want1 = full_logits(mcfg, engine,
                            np.concatenate([ids, [5]])[None])[0, -1]
        np.testing.assert_allclose(l1[0], want1, atol=1e-4, rtol=1e-4)

    def test_greedy_generate_token_exact_vs_full_rollout(self, v2cfg, rng):
        """Greedy decode through the paged KV cache reproduces the exact
        token sequence of an argmax rollout over cache-free training-side
        forwards — MoE routing decisions survive serving bitwise enough to
        never flip a greedy pick (fp32 fixture)."""
        mcfg = self._mcfg()
        engine = v2_engine(mcfg, config=v2cfg, seed=0)
        prompts = [rng.integers(0, 128, (9 + 3 * i,)).astype(np.int32)
                   for i in range(2)]
        got = engine.generate(prompts, max_new_tokens=8)
        for p, out in zip(prompts, got):
            seq = list(p)
            for _ in range(8):
                nxt = int(np.argmax(full_logits(
                    mcfg, engine, np.asarray(seq, np.int32)[None])[0, -1]))
                seq.append(nxt)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(seq[len(p):]))
