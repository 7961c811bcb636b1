"""Numeric tests for the ops layer (reference pattern: tests/unit/ops/* compare
custom kernels against a torch reference; here Pallas-in-interpret-mode vs XLA)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import ops


@pytest.fixture()
def qkv(rng):
    B, T, N, D = 2, 128, 4, 64
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, N, D)), jnp.float32)
    return mk(), mk(), mk()


class TestFlashAttention:
    def test_forward_matches_xla(self, qkv):
        q, k, v = qkv
        ref = ops.causal_attention(q, k, v, impl="xla")
        out = ops.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=1e-4)

    def test_backward_matches_xla(self, qkv):
        q, k, v = qkv
        gr = jax.grad(lambda *a: jnp.sum(
            ops.causal_attention(*a, impl="xla") ** 2), argnums=(0, 1, 2))
        gf = jax.grad(lambda *a: jnp.sum(
            ops.flash_attention(*a, interpret=True) ** 2), argnums=(0, 1, 2))
        for a, b in zip(gr(q, k, v), gf(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)

    def test_gqa(self, qkv):
        q, k, v = qkv
        k, v = k[:, :, :2], v[:, :, :2]
        ref = ops.causal_attention(q, k, v, impl="xla")
        out = ops.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=1e-4)

    def test_block_pair_table(self):
        """Pin the on-chip-tuned (bq, bk) table (round-5 v5e sweep) so a
        refactor can't silently regress the measured fast pairs."""
        import importlib
        fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
        assert fa._block_pair(1024) == (1024, 1024)
        assert fa._block_pair(2048) == (512, 2048)
        assert fa._block_pair(4096) == (512, 1024)
        assert fa._block_pair(8192) == (512, 1024)
        assert fa._block_pair(512) == (512, 512)
        assert fa._block_pair(64) == (64, 64)
        # non-1024-multiple long T keeps the safe square fallback
        assert fa._block_pair(4608) == (512, 512)
        # sliding window keeps square tiles (whole-seq K defeats the
        # dead-tile skip that gives T*window scaling)
        assert fa._block_pair(1024, window=128) == (512, 512)
        assert fa._block_pair(4096, window=256) == (512, 512)
        # head_dim > 128 keeps square tiles (VMEM envelope only validated
        # to d=128; an over-full tile is a compile error, not a fallback)
        assert fa._block_pair(1024, d=256) == (512, 512)
        assert fa._block_pair(1024, d=128) == (1024, 1024)

    def test_rectangular_blocks(self, qkv, monkeypatch):
        """bq != bk (the T>=4096 on-chip fast pair, round 5) must stay
        exact through fwd AND both backward kernels — exercised at small T
        by pinning a rectangular pair."""
        import importlib
        # import_module, NOT `from deepspeed_tpu.ops import flash_attention`:
        # the package re-exports a FUNCTION of that name which shadows the
        # submodule on attribute access
        fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
        monkeypatch.setattr(fa, "_block_pair",
                            lambda t, d=64, window=None: (8, 16))
        q, k, v = qkv
        ref = ops.causal_attention(q, k, v, impl="xla")
        out = ops.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=1e-4)
        gr = jax.grad(lambda *a: jnp.sum(
            ops.causal_attention(*a, impl="xla") ** 2), argnums=(0, 1, 2))
        gf = jax.grad(lambda *a: jnp.sum(
            ops.flash_attention(*a, interpret=True) ** 2), argnums=(0, 1, 2))
        for a, b in zip(gr(q, k, v), gf(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)

    def test_gqa_backward_matches_xla(self, qkv):
        """dk/dv of the fused (q-head-in-group, q-block) kernel grid must sum
        contributions over the whole GQA group."""
        q, k, v = qkv
        k, v = k[:, :, :2], v[:, :, :2]      # 4 q heads over 2 kv heads
        gr = jax.grad(lambda *a: jnp.sum(
            ops.causal_attention(*a, impl="xla") ** 2), argnums=(0, 1, 2))
        gf = jax.grad(lambda *a: jnp.sum(
            ops.flash_attention(*a, interpret=True) ** 2), argnums=(0, 1, 2))
        for a, b in zip(gr(q, k, v), gf(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)

    def test_noncausal(self, qkv):
        q, k, v = qkv
        ref = ops.causal_attention(q, k, v, causal=False, impl="xla")
        out = ops.flash_attention(q, k, v, causal=False, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=1e-4)

    def test_supported_predicate(self, qkv):
        q, k, v = qkv
        import importlib
        fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
        assert fa.supported(q, k, v)
        assert not fa.supported(q[:, :100], k[:, :100], v[:, :100])  # 100 % 8 != 0
        assert not fa.supported(q, k[:, :64], v[:, :64])  # ragged kv len

    def test_registry_dispatch_cpu_falls_back(self, qkv):
        q, k, v = qkv
        out = ops.causal_attention(q, k, v)  # CPU -> xla path, must not raise
        assert out.shape == q.shape

    def test_window_forward_matches_xla(self, qkv):
        """Sliding window in-kernel (mistral/gpt-neo training; tile skipping
        means small windows never touch early K tiles)."""
        q, k, v = qkv
        for w in (5, 16, 40, 1000):
            ref = ops.causal_attention(q, k, v, window=w, impl="xla")
            out = ops.flash_attention(q, k, v, window=w, interpret=True)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       atol=2e-5, rtol=1e-4,
                                       err_msg=f"window={w}")

    def test_window_matches_mask_form(self, qkv):
        """window= must equal the model's legacy rel-position mask form."""
        q, k, v = qkv
        T = q.shape[1]
        pos = jnp.broadcast_to(jnp.arange(T), (q.shape[0], T))
        rel = pos[:, :, None] - pos[:, None, :]
        wmask = (rel >= 0) & (rel < 7)
        ref = ops.causal_attention(q, k, v, causal=False, mask=wmask,
                                   impl="xla")
        out = ops.causal_attention(q, k, v, window=7, impl="xla")
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-6)

    def test_window_backward_matches_xla(self, qkv):
        q, k, v = qkv
        gr = jax.grad(lambda *a: jnp.sum(ops.causal_attention(
            *a, window=9, impl="xla") ** 2), argnums=(0, 1, 2))
        gf = jax.grad(lambda *a: jnp.sum(ops.flash_attention(
            *a, window=9, interpret=True) ** 2), argnums=(0, 1, 2))
        for a, b in zip(gr(q, k, v), gf(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)

    def test_alibi_forward_matches_xla(self, qkv):
        q, k, v = qkv
        from deepspeed_tpu.models.gpt import alibi_slopes
        sl = jnp.asarray(alibi_slopes(q.shape[2]))
        ref = ops.causal_attention(q, k, v, alibi_slopes=sl, impl="xla")
        out = ops.flash_attention(q, k, v, alibi_slopes=sl, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=1e-4)

    def test_alibi_matches_bias_form(self, qkv):
        """alibi_slopes= must equal the legacy slope×kpos bias form."""
        q, k, v = qkv
        from deepspeed_tpu.models.gpt import alibi_slopes
        sl = jnp.asarray(alibi_slopes(q.shape[2]))
        T = q.shape[1]
        bias = sl[:, None, None] * jnp.arange(T, dtype=jnp.float32)
        ref = ops.causal_attention(q, k, v, bias=bias[None], impl="xla")
        out = ops.causal_attention(q, k, v, alibi_slopes=sl, impl="xla")
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-5)

    def test_alibi_backward_matches_xla(self, qkv):
        q, k, v = qkv
        from deepspeed_tpu.models.gpt import alibi_slopes
        sl = jnp.asarray(alibi_slopes(q.shape[2]))
        gr = jax.grad(lambda *a: jnp.sum(ops.causal_attention(
            *a, alibi_slopes=sl, impl="xla") ** 2), argnums=(0, 1, 2))
        gf = jax.grad(lambda *a: jnp.sum(ops.flash_attention(
            *a, alibi_slopes=sl, interpret=True) ** 2), argnums=(0, 1, 2))
        for a, b in zip(gr(q, k, v), gf(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)

    def test_alibi_window_gqa_combined(self, qkv):
        q, k, v = qkv
        k, v = k[:, :, :2], v[:, :, :2]
        from deepspeed_tpu.models.gpt import alibi_slopes
        sl = jnp.asarray(alibi_slopes(q.shape[2]))
        ref = ops.causal_attention(q, k, v, alibi_slopes=sl, window=21,
                                   impl="xla")
        out = ops.flash_attention(q, k, v, alibi_slopes=sl, window=21,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=1e-4)

    def test_window_alibi_now_kernel_supported(self, qkv):
        """VERDICT r2 item 3: supported() must accept alibi/window so the
        bloom/falcon/mistral/qwen2 slice of the zoo hits the kernel path."""
        import importlib
        fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
        q, k, v = qkv
        sl = np.ones(q.shape[2], np.float32)
        assert fa.supported(q, k, v, window=8)
        assert fa.supported(q, k, v, alibi_slopes=sl)
        assert fa.supported(q, k, v, window=8, alibi_slopes=sl)
        assert not fa.supported(q, k, v, causal=False, window=8)


class TestModelFusedAttentionPaths:
    """GPT training with alibi/sliding-window must produce identical loss and
    grads whether attention runs the Pallas kernel (interpret) or XLA — i.e.
    the fused_ok fast path is numerically transparent."""

    def _loss_and_grads(self, cfg_kw, impl):
        from deepspeed_tpu.models import GPT, GPTConfig
        cfg = GPTConfig.tiny(vocab_size=64, max_seq_len=32, attn_impl=impl,
                             **cfg_kw)
        model = GPT(cfg)
        r = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(r.integers(0, 64, (2, 32)),
                                          jnp.int32)}
        p = model.init(jax.random.PRNGKey(0), batch, deterministic=True)

        def loss(p_):
            return model.apply(p_, batch, deterministic=True)
        l, g = jax.value_and_grad(loss)(p)
        return float(l), g

    @pytest.mark.parametrize("kw", [
        {"use_alibi": True, "use_rope": False},
        {"sliding_window": 8},
        {"use_alibi": True, "use_rope": False, "sliding_window": 8},
        {"sliding_window": 8, "local_attn_layers": (1,)},
    ])
    def test_pallas_matches_xla(self, kw):
        l_x, g_x = self._loss_and_grads(kw, "xla")
        l_p, g_p = self._loss_and_grads(kw, "pallas")
        np.testing.assert_allclose(l_p, l_x, rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g_x),
                        jax.tree_util.tree_leaves(g_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-4)

    def test_remat_fused_path(self):
        """fused_ok threads through nn.remat as a static arg."""
        l_x, _ = self._loss_and_grads({"sliding_window": 8, "remat": True},
                                      "xla")
        l_p, _ = self._loss_and_grads({"sliding_window": 8, "remat": True},
                                      "pallas")
        np.testing.assert_allclose(l_p, l_x, rtol=1e-5)


class TestChunkedCrossEntropy:
    def test_matches_unchunked(self, rng):
        B, T, H, V = 2, 64, 32, 97
        x = jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((H, V)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
        mask = jnp.asarray(rng.integers(0, 2, (B, T)), jnp.float32)
        ref = ops.lm_cross_entropy(x, w, labels, mask, chunk_size=None)
        out = ops.lm_cross_entropy(x, w, labels, mask, chunk_size=24)  # pad path
        np.testing.assert_allclose(float(ref), float(out), rtol=1e-6)

    def test_grads_match(self, rng):
        B, T, H, V = 2, 32, 16, 53
        x = jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((H, V)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
        mask = jnp.ones((B, T), jnp.float32)
        g1 = jax.grad(lambda x_, w_: ops.lm_cross_entropy(
            x_, w_, labels, mask, chunk_size=None), argnums=(0, 1))(x, w)
        g2 = jax.grad(lambda x_, w_: ops.lm_cross_entropy(
            x_, w_, labels, mask, chunk_size=8), argnums=(0, 1))(x, w)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-5)

    def test_fused_loss_only_is_dced(self, rng):
        """Loss-only callers (eval_batch) of the FUSED path must not pay for
        the in-forward gx/dW gradient GEMMs — XLA scan DCE strips the unused
        carry/outputs.  Pin it with compiled cost analysis: fused loss-only
        FLOPs == non-fused loss-only FLOPs (ADVICE r3 #4 — if this ever
        breaks, route loss-only callers through fused=False instead)."""
        B, T, H, V = 4, 128, 64, 1000
        x = jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((H, V)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
        mask = jnp.ones((B, T), jnp.float32)

        def flops(fused):
            f = jax.jit(lambda x_, w_: ops.lm_cross_entropy(
                x_, w_, labels, mask, chunk_size=128, fused=fused))
            ca = f.lower(x, w).compile().cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            return ca["flops"]
        assert flops(True) <= flops(False) * 1.01

    def test_fused_matches_remat_with_bias(self, rng):
        """The fused in-forward-gradient path must match the jax.checkpoint
        remat path (loss AND x/w/bias grads), including the unembed bias."""
        B, T, H, V = 2, 32, 16, 53
        x = jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((H, V)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal((V,)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
        mask = jnp.asarray(rng.integers(0, 2, (B, T)), jnp.float32)

        def loss(fused):
            return lambda x_, w_, b_: ops.lm_cross_entropy(
                x_, w_, labels, mask, chunk_size=8, bias=b_, fused=fused)

        l1, g1 = jax.value_and_grad(loss(False), argnums=(0, 1, 2))(x, w, bias)
        l2, g2 = jax.value_and_grad(loss(True), argnums=(0, 1, 2))(x, w, bias)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-5)

    def test_fused_mask_grad_matches(self, rng):
        """d(loss)/d(mask) must match the autodiff paths (learned per-token
        loss weights differentiate through the mask)."""
        B, T, H, V = 2, 32, 16, 53
        x = jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((H, V)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
        mask = jnp.asarray(rng.uniform(0.2, 1.0, (B, T)), jnp.float32)
        gm_ref = jax.grad(lambda m: ops.lm_cross_entropy(
            x, w, labels, m, chunk_size=8, fused=False))(mask)
        gm = jax.grad(lambda m: ops.lm_cross_entropy(
            x, w, labels, m, chunk_size=8, fused=True))(mask)
        np.testing.assert_allclose(np.asarray(gm), np.asarray(gm_ref),
                                   atol=1e-6, rtol=1e-5)

    def test_fused_bf16_grads_dtype_and_close(self, rng):
        """bf16 params: fused path returns grads in the param dtype and close
        to the fp32 reference (fp32 accumulation inside)."""
        B, T, H, V = 2, 32, 16, 53
        x32 = rng.standard_normal((B, T, H)).astype(np.float32)
        w32 = rng.standard_normal((H, V)).astype(np.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
        mask = jnp.ones((B, T), jnp.float32)
        x, w = jnp.asarray(x32, jnp.bfloat16), jnp.asarray(w32, jnp.bfloat16)
        gx, gw = jax.grad(lambda x_, w_: ops.lm_cross_entropy(
            x_, w_, labels, mask, chunk_size=8, fused=True),
            argnums=(0, 1))(x, w)
        assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
        rx, rw = jax.grad(lambda x_, w_: ops.lm_cross_entropy(
            x_, w_, labels, mask, chunk_size=None),
            argnums=(0, 1))(jnp.asarray(x32), jnp.asarray(w32))
        np.testing.assert_allclose(np.asarray(gx, np.float32),
                                   np.asarray(rx), atol=0.05, rtol=0.1)
        np.testing.assert_allclose(np.asarray(gw, np.float32),
                                   np.asarray(rw), atol=0.05, rtol=0.1)

    def test_model_chunked_loss_matches(self, rng):
        from deepspeed_tpu.models import GPT, GPTChunkedLoss, GPTConfig
        cfg = GPTConfig.tiny(vocab_size=64, max_seq_len=32)
        ids = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
        batch = {"input_ids": ids}
        m1, m2 = GPT(cfg), GPTChunkedLoss(cfg)
        p = m1.init(jax.random.PRNGKey(0), batch, deterministic=True)
        l1 = m1.apply(p, batch, deterministic=True)
        l2 = m2.apply(p, batch, deterministic=True)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_op_report():
    rep = ops.op_report()
    assert "causal_attention" in rep


class TestDispatchLog:
    """No quiet fallback: every registry decision is recorded with its
    reason, and the kernels' own fall-backs are counted the same way."""

    def _log(self):
        from deepspeed_tpu.ops import registry
        return {(d["op"], d["impl"], d["reason"]): d["count"]
                for d in registry.dispatch_log()}

    def test_auto_and_forced_decisions_are_recorded(self):
        from deepspeed_tpu.ops import registry
        registry.reset_dispatch_log()
        q = jnp.ones((1, 16, 2, 8), jnp.float32)
        ops.causal_attention(q, q, q)                    # auto, on the CPU
        ops.causal_attention(q, q, q, impl="pallas")     # demanded
        ops.causal_attention(q, q, q, impl="xla")
        assert self._log() == {
            ("causal_attention", "xla", "backend is not tpu"): 1,
            ("causal_attention", "pallas", "forced"): 1,
            ("causal_attention", "xla", "forced"): 1}
        assert "forced (1)" in ops.op_report()

    def test_shape_predicate_refusal_is_recorded(self, monkeypatch):
        from deepspeed_tpu.ops import registry
        monkeypatch.setattr(registry, "_on_tpu", lambda: True)
        registry.reset_dispatch_log()
        q = jnp.ones((1, 15, 2, 8), jnp.float32)         # T=15: no block
        ops.causal_attention(q, q, q)
        assert self._log() == {
            ("causal_attention", "xla", "shape predicate refused"): 1}

    def test_kernel_side_fallback_is_counted(self):
        from deepspeed_tpu.ops import registry
        from deepspeed_tpu.ops.quantization import quantize_weight
        from deepspeed_tpu.ops.wq_matmul import wq_matmul
        registry.reset_dispatch_log()
        w = jnp.ones((64, 32), jnp.float32)
        store = quantize_weight(w, bits=8, group=16, dim=0)   # g % 32 != 0
        for _ in range(2):           # warned once, counted every time
            wq_matmul(jnp.ones((8, 64), jnp.float32), store)
        assert self._log() == {("wq_matmul", "xla", "layout refused"): 2}


class TestPagedAttention:
    """Pallas decode kernel (interpret mode) vs the XLA gather path
    (reference blocked_flash decode kernels)."""

    def _rand_case(self, rng, S=4, nkv=2, g=3, hd=16, NB=16, bs=8, MB=4):
        q = rng.standard_normal((S, nkv, g, hd)).astype(np.float32)
        k = rng.standard_normal((NB, nkv, bs, hd)).astype(np.float32)
        v = rng.standard_normal((NB, nkv, bs, hd)).astype(np.float32)
        # distinct physical pages per slot, deliberately out of order
        perm = rng.permutation(NB)[:S * MB].reshape(S, MB).astype(np.int32)
        # lens: inactive slot, partial page, exact page boundary, full
        lens = np.array([0, 5, bs * 2, bs * MB], np.int32)[:S]
        return q, k, v, perm, lens

    def test_kernel_matches_xla(self, rng):
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        args = [jnp.asarray(a) for a in self._rand_case(rng)]
        want = xla_paged_attention(*args)
        got = pallas_paged_attention(*args, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_kernel_bf16(self, rng):
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        q, k, v, bt, lens = self._rand_case(rng, hd=32, bs=16)
        q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
        want = xla_paged_attention(q, k, v, jnp.asarray(bt), jnp.asarray(lens))
        got = pallas_paged_attention(q, k, v, jnp.asarray(bt),
                                     jnp.asarray(lens), interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=2e-2)

    def test_kernel_int8_kv_matches_xla(self, rng):
        """In-kernel dequant: int8 pages + per-token scales DMA'd alongside,
        dequantized in VMEM before the dots — parity vs the XLA dequant
        path, both layouts."""
        from deepspeed_tpu.inference.v2.model import quantize_kv_token
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       supported,
                                                       xla_paged_attention)
        for kv_major in (False, True):
            # standard layout needs hd % 128 == 0; kv-major needs bs % 128
            # (and int8 tightens the sublane requirement to 32)
            hd = 128 if not kv_major else 32
            S, nkv, g, NB, bs, MB = 4, 2, 3, 16, 128, 2
            q = jnp.asarray(rng.standard_normal((S, nkv, g, hd)), jnp.float32)
            # quantize token-major KV then lay out pages per the layout flag
            kt = rng.standard_normal((NB, nkv, bs, hd)).astype(np.float32)
            vt = rng.standard_normal((NB, nkv, bs, hd)).astype(np.float32)
            kq, ks = quantize_kv_token(jnp.asarray(kt))     # [NB,nkv,bs,hd]
            vq, vs = quantize_kv_token(jnp.asarray(vt))
            if kv_major:
                kq, vq = (jnp.swapaxes(a, 2, 3) for a in (kq, vq))
            bt = jnp.asarray(rng.permutation(NB)[:S * MB].reshape(S, MB),
                             jnp.int32)
            lens = jnp.asarray([0, 7, bs, 2 * bs], jnp.int32)
            kw = dict(kv_major=kv_major, k_scale=ks, v_scale=vs)
            assert supported(q, kq, vq, bt, lens, **kw)
            want = xla_paged_attention(q, kq, vq, bt, lens, **kw)
            got = pallas_paged_attention(q, kq, vq, bt, lens,
                                         interpret=True, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=f"{kv_major=}")

    def test_kernel_alibi_matches_xla(self, rng):
        """Alibi slope×key-pos bias inside the online softmax (BLOOM /
        falcon-rw decode hits the kernel path now)."""
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       supported,
                                                       xla_paged_attention)
        q, k, v, bt, lens = (jnp.asarray(a) for a in self._rand_case(rng))
        nkv, g = q.shape[1], q.shape[2]
        slopes = jnp.asarray(
            np.geomspace(0.5, 1 / 256, nkv * g), jnp.float32)
        want = xla_paged_attention(q, k, v, bt, lens, alibi_slopes=slopes)
        got = pallas_paged_attention(q, k, v, bt, lens, alibi_slopes=slopes,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_kernel_window_matches_xla(self, rng):
        """Sliding window: masking matches the XLA path AND the DMA loop
        starts past pages wholly outside the window."""
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       supported,
                                                       xla_paged_attention)
        q, k, v, bt, lens = (jnp.asarray(a) for a in self._rand_case(rng))
        for window in (3, 8, 11, 100):
            want = xla_paged_attention(q, k, v, bt, lens, window=window)
            got = pallas_paged_attention(q, k, v, bt, lens, window=window,
                                         interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=f"window={window}")

    def test_kernel_window_skips_pages(self, rng):
        """Pages before the window must never be read: poison them with NaN
        and check the kernel output is still finite (the XLA fallback gathers
        every page, so only the kernel passes this)."""
        from deepspeed_tpu.ops.paged_attention import pallas_paged_attention
        q, k, v, bt, lens = self._rand_case(rng, S=1, MB=4, bs=8)
        lens = np.array([32], np.int32)          # 4 full pages
        window = 8                               # only the last page visible
        # poison pages 0..2 (wholly outside [lens-window, lens) = [24, 32))
        k = k.copy(); v = v.copy()
        for p in range(3):
            k[bt[0, p]] = np.nan
            v[bt[0, p]] = np.nan
        got = pallas_paged_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(bt),
            jnp.asarray(lens), window=window, interpret=True)
        assert np.isfinite(np.asarray(got)).all()

    # ---- the block pipeline (PR 32): P pages, every kv head, an iteration

    VARIANTS = ["plain", "kv-major", "int8", "alibi"]
    BS = 8                              # tokens a page of the block cases

    def _block_case(self, rng, variant, S, MB, layers=1, layer=0):
        """A pool of ``layers`` x (S * MB) pages of ``BS`` tokens, a table of
        distinct out-of-order pages in ``layer`` -> (q, k, v, bt, kw, blk)
        in ``variant``'s layout; ``blk`` is the tokens of a block of P pages,
        P as the kernel derives it."""
        from deepspeed_tpu.inference.v2.model import quantize_kv_token
        from deepspeed_tpu.ops.paged_attention import _block_pages
        nkv, g, hd, bs = 2, 3, 16, self.BS
        NB = S * MB
        q = jnp.asarray(rng.standard_normal((S, nkv, g, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((layers * NB, nkv, bs, hd)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((layers * NB, nkv, bs, hd)),
                        jnp.float32)
        bt = jnp.asarray(rng.permutation(NB).reshape(S, MB) + layer * NB,
                         jnp.int32)
        kw = {}
        if variant == "int8":
            (k, ks), (v, vs) = quantize_kv_token(k), quantize_kv_token(v)
            kw.update(k_scale=ks, v_scale=vs)
        if variant == "kv-major":
            k, v = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
            kw["kv_major"] = True
        if variant == "alibi":
            kw["alibi_slopes"] = jnp.asarray(
                np.geomspace(0.5, 1 / 256, nkv * g), jnp.float32)
        P = _block_pages([k, v] + ([kw["k_scale"], kw["v_scale"]]
                                   if variant == "int8" else []))
        assert P > 1, "the cases below need a block of several pages"
        return q, k, v, bt, kw, P * bs

    @classmethod
    def _poison_dead_pages(cls, k, v, kw, bt, lens, window):
        """NaN in every page outside a slot's [window's first page, pages of
        kv_len): the table's other entries and the pool's other pages.  int8
        codes cannot hold a NaN; their scale rows can."""
        bs = cls.BS
        live = np.zeros(k.shape[0], bool)
        for s, n in enumerate(np.asarray(lens)):
            first = 0 if window is None else max(int(n) - window, 0) // bs
            live[np.asarray(bt)[s, first:-(-int(n) // bs)]] = True
        dead = jnp.asarray(~live)

        def nan(a):
            return jnp.where(dead.reshape((-1,) + (1,) * (a.ndim - 1)),
                             jnp.nan, a)
        if "k_scale" in kw:
            return k, v, dict(kw, k_scale=nan(kw["k_scale"]),
                              v_scale=nan(kw["v_scale"]))
        return nan(k), nan(v), kw

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_block_boundary_contexts(self, rng, variant):
        """Contexts of 0, 1, one short of a block, a block, one past it and
        several blocks, none of whose dead pages is read."""
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        S, MB = 8, 28
        q, k, v, bt, kw, blk = self._block_case(rng, variant, S, MB)
        lens = jnp.asarray([0, 1, blk - 1, blk, blk + 1, 2 * blk,
                            2 * blk + self.BS + 3, 3 * blk + 5], jnp.int32)
        assert int(lens.max()) <= MB * self.BS
        want = xla_paged_attention(q, k, v, bt, lens, **kw)
        k, v, kw = self._poison_dead_pages(k, v, kw, bt, lens, None)
        got = pallas_paged_attention(q, k, v, bt, lens, interpret=True, **kw)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_block_window_starts(self, rng, variant):
        """A window whose first page is a block's first page in the table,
        one that starts blocks in, mid-page and on a page's first row; the
        pages before it are dead and poisoned like those past kv_len."""
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        S, MB = 6, 30
        q, k, v, bt, kw, blk = self._block_case(rng, variant, S, MB)
        window = blk + self.BS + 5           # a block, a page and five keys
        lens = jnp.asarray(
            [0, window - 2,                  # nothing is outside the window
             window + blk,                   # first key on page P's first row
             window + blk + 3,               # ... three rows into that page
             window + 2 * self.BS + 1,       # starts two pages into block 0
             3 * blk + 7], jnp.int32)
        assert int(lens.max()) <= MB * self.BS
        want = xla_paged_attention(q, k, v, bt, lens, window=window, **kw)
        k, v, kw = self._poison_dead_pages(k, v, kw, bt, lens, window)
        got = pallas_paged_attention(q, k, v, bt, lens, window=window,
                                     interpret=True, **kw)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_block_hand_over_between_slots(self, rng, variant):
        """A slot starts its successor's first block before its own last
        dots: empty slots between two live ones, a live last slot, a live
        slot after a run of empty ones, and a call with no live slot."""
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        S, MB = 8, 20
        q, k, v, bt, kw, blk = self._block_case(rng, variant, S, MB)
        for lens in ([blk + 9, 0, 0, 3, 2 * blk, 0, 0, 11],
                     [0, 0, 0, 0, 0, blk, 0, 0],
                     [0] * S):
            lens = jnp.asarray(lens, jnp.int32)
            want = xla_paged_attention(q, k, v, bt, lens, **kw)
            got = pallas_paged_attention(q, k, v, bt, lens, interpret=True,
                                         **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=str(lens))

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_block_flat_pool_base(self, rng, variant):
        """The flat pool of three layers with the middle layer's first page
        added to the table, at contexts of several blocks."""
        from deepspeed_tpu.ops.paged_attention import pallas_paged_attention
        S, MB, L, LI = 4, 20, 3, 1
        q, k, v, bt, kw, blk = self._block_case(rng, variant, S, MB,
                                                layers=L, layer=LI)
        lens = jnp.asarray([0, blk - 3, blk + 1, 2 * blk + 4], jnp.int32)
        NB = S * MB
        own = {n: a[LI * NB:(LI + 1) * NB] if n.endswith("scale") else a
               for n, a in kw.items()}
        want = pallas_paged_attention(
            q, k[LI * NB:(LI + 1) * NB], v[LI * NB:(LI + 1) * NB],
            bt - LI * NB, lens, window=blk + 2, interpret=True, **own)
        got = pallas_paged_attention(q, k, v, bt, lens, window=blk + 2,
                                     interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_kernel_alibi_window_combined(self, rng):
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        q, k, v, bt, lens = (jnp.asarray(a) for a in self._rand_case(rng))
        nkv, g = q.shape[1], q.shape[2]
        slopes = jnp.asarray(np.geomspace(0.5, 1 / 64, nkv * g), jnp.float32)
        want = xla_paged_attention(q, k, v, bt, lens, alibi_slopes=slopes,
                                   window=6)
        got = pallas_paged_attention(q, k, v, bt, lens, alibi_slopes=slopes,
                                     window=6, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_kv_major_matches_standard(self, rng):
        """Transposed [NB, nkv, hd, bs] pages (the layout hd%128!=0 models
        use on real TPU) must be numerically identical to the standard
        layout through both the XLA and Pallas paths."""
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        q, k, v, bt, lens = (jnp.asarray(a) for a in self._rand_case(rng))
        want = xla_paged_attention(q, k, v, bt, lens)
        kt, vt = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
        for fn, kw in ((xla_paged_attention, {}),
                       (pallas_paged_attention, {"interpret": True})):
            got = fn(q, kt, vt, bt, lens, kv_major=True, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=fn.__name__)

    def test_kv_major_alibi_window(self, rng):
        from deepspeed_tpu.ops.paged_attention import (pallas_paged_attention,
                                                       xla_paged_attention)
        q, k, v, bt, lens = (jnp.asarray(a) for a in self._rand_case(rng))
        nkv, g = q.shape[1], q.shape[2]
        slopes = jnp.asarray(np.geomspace(0.5, 1 / 64, nkv * g), jnp.float32)
        kt, vt = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
        for kw in ({"alibi_slopes": slopes}, {"window": 6},
                   {"alibi_slopes": slopes, "window": 6}):
            want = xla_paged_attention(q, k, v, bt, lens, **kw)
            got = pallas_paged_attention(q, kt, vt, bt, lens, kv_major=True,
                                         interpret=True, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=str(kw))

    def test_supported_reflects_tpu_dma_constraints(self):
        """The Mosaic DMA slab needs a 128-aligned lane dim: standard layout
        ⇒ hd % 128 == 0, kv-major ⇒ block_size % 128 == 0 (found on real
        v5e — interpret mode accepts anything, so the gate must not)."""
        from deepspeed_tpu.ops.paged_attention import supported
        bt = jnp.zeros((2, 4), jnp.int32)
        lens = jnp.zeros((2,), jnp.int32)

        def mk(nkv, a, b):
            return jnp.zeros((8, nkv, a, b), jnp.bfloat16)

        q128 = jnp.zeros((2, 2, 2, 128), jnp.bfloat16)
        q64 = jnp.zeros((2, 2, 2, 64), jnp.bfloat16)
        assert supported(q128, mk(2, 8, 128), mk(2, 8, 128), bt, lens)
        assert not supported(q64, mk(2, 8, 64), mk(2, 8, 64), bt, lens)
        assert supported(q64, mk(2, 64, 128), mk(2, 64, 128), bt, lens,
                         kv_major=True)
        assert not supported(q64, mk(2, 64, 64), mk(2, 64, 64), bt, lens,
                             kv_major=True)


def token_major(q, counts, order=None, pad=0):
    """Dense test queries [S, Q, ...] as the ragged prefill op takes them:
    (flat [N, ...], row_starts [S]), each slot's live rows one span of the
    flat batch, the slots in ``order`` (slot order if not said), ``pad``
    rows no slot owns at the end."""
    counts = np.asarray(counts)
    order = np.arange(len(counts)) if order is None else np.asarray(order)
    row_starts = np.zeros(len(counts), np.int32)
    spans, cursor = [], 0
    for s in order:
        row_starts[s] = cursor
        spans.append(q[s, :counts[s]])
        cursor += int(counts[s])
    spans.append(jnp.zeros((pad,) + q.shape[2:], q.dtype))
    return jnp.concatenate(spans), jnp.asarray(row_starts)


def slot_rows(o, counts, row_starts, Q):
    """The op's token-major result back as [S, Q, ...], a slot's rows past
    its count zero: what the kernel leaves unwritten is not compared."""
    counts, row_starts = np.asarray(counts), np.asarray(row_starts)
    out = np.zeros((len(counts), Q) + o.shape[1:], np.float32)
    for s, (n, r) in enumerate(zip(counts, row_starts)):
        out[s, :n] = np.asarray(o[r:r + n], np.float32)
    return out


class TestRaggedPrefill:
    """Ragged prefill flash kernel (interpret) vs the gather+masked-dense XLA
    path (reference blocked_flash + atom_builder).  Mixed decode (count=1) and
    prefill-chunk slots in one batch of token-major rows."""

    def _case(self, rng, S=4, Q=8, nkv=2, g=2, hd=16, NB=24, bs=8, MB=4):
        q = jnp.asarray(rng.standard_normal((S, Q, nkv, g, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((NB, nkv, bs, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((NB, nkv, bs, hd)), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB)[:S * MB].reshape(S, MB),
                         jnp.int32)
        # slot 0: inactive; slot 1: pure decode (1 row, long kv);
        # slot 2: prefill continuation (5 rows appended after 9 kv);
        # slot 3: fresh full prefill (Q rows)
        counts = jnp.asarray([0, 1, 5, Q], jnp.int32)[:S]
        lens = jnp.asarray([0, 19, 14, Q], jnp.int32)[:S]
        starts = lens - counts
        return q, k, v, bt, lens, starts, counts

    @staticmethod
    def _both(q, k, v, bt, lens, starts, counts, pallas_kv=None, **kw):
        """(want, got) as [S, Q, ...]: the XLA path and the interpreted
        kernel over the token-major form of the dense case ``q``;
        ``pallas_kv``: the kernel's pages if not ``k, v`` (another layout,
        poisoned pages)."""
        from deepspeed_tpu.ops.paged_attention import (pallas_ragged_prefill,
                                                       xla_ragged_prefill)
        Q = q.shape[1]
        flat, rows = token_major(q, counts, pad=3)
        want = xla_ragged_prefill(flat, k, v, bt, lens, starts, counts, rows,
                                  max_q=Q, **kw)
        got = pallas_ragged_prefill(flat, *(pallas_kv or (k, v)), bt, lens,
                                    starts, counts, rows, max_q=Q,
                                    interpret=True, **kw)
        return (slot_rows(want, counts, rows, Q),
                slot_rows(got, counts, rows, Q))

    def test_matches_xla(self, rng):
        want, got = self._both(*self._case(rng))
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_int8_kv_matches_xla(self, rng):
        """int8 pages + in-kernel dequant in the prefill kernel, both
        layouts, mixed decode/prefill slots."""
        from deepspeed_tpu.inference.v2.model import quantize_kv_token
        from deepspeed_tpu.ops.paged_attention import ragged_prefill_supported
        for kv_major in (False, True):
            hd = 128 if not kv_major else 32
            S, Q, nkv, g, NB, bs, MB = 4, 8, 2, 2, 12, 128, 2
            q = jnp.asarray(rng.standard_normal((S, Q, nkv, g, hd)),
                            jnp.float32)
            kt = rng.standard_normal((NB, nkv, bs, hd)).astype(np.float32)
            vt = rng.standard_normal((NB, nkv, bs, hd)).astype(np.float32)
            kq, ks = quantize_kv_token(jnp.asarray(kt))
            vq, vs = quantize_kv_token(jnp.asarray(vt))
            if kv_major:
                kq, vq = (jnp.swapaxes(a, 2, 3) for a in (kq, vq))
            bt = jnp.asarray(rng.permutation(NB)[:S * MB].reshape(S, MB),
                             jnp.int32)
            counts = jnp.asarray([0, 1, 5, Q], jnp.int32)
            lens = jnp.asarray([0, bs + 9, 14, Q], jnp.int32)
            starts = lens - counts
            kw = dict(kv_major=kv_major, k_scale=ks, v_scale=vs)
            flat, rows = token_major(q, counts)
            assert ragged_prefill_supported(flat, kq, vq, bt, lens, starts,
                                            counts, rows, **kw)
            want, got = self._both(q, kq, vq, bt, lens, starts, counts, **kw)
            np.testing.assert_allclose(got, want, atol=1e-5,
                                       err_msg=f"{kv_major=}")

    def test_alibi_and_window(self, rng):
        args = self._case(rng)
        nkv, g = args[0].shape[2], args[0].shape[3]
        slopes = jnp.asarray(np.geomspace(0.5, 1 / 64, nkv * g), jnp.float32)
        for kw in ({"alibi_slopes": slopes}, {"window": 6},
                   {"alibi_slopes": slopes, "window": 6}):
            want, got = self._both(*args, **kw)
            np.testing.assert_allclose(got, want, atol=1e-5, err_msg=str(kw))

    def test_skips_unreachable_pages(self, rng):
        """Pages past a slot's kv_len are never DMA'd: poison them with NaN;
        the XLA gather path would propagate the NaN through its masked
        softmax input, the kernel must stay finite."""
        from deepspeed_tpu.ops.paged_attention import pallas_ragged_prefill
        q, k, v, bt, lens, starts, counts = self._case(rng, S=1, Q=8, MB=4,
                                                       bs=8)
        counts = jnp.asarray([4], jnp.int32)
        lens = jnp.asarray([12], jnp.int32)      # pages 0,1 used; 2,3 unused
        starts = lens - counts
        k = np.array(k); v = np.array(v)
        for p in (2, 3):
            k[int(bt[0, p])] = np.nan
            v[int(bt[0, p])] = np.nan
        got = pallas_ragged_prefill(q[0], jnp.asarray(k), jnp.asarray(v), bt,
                                    lens, starts, counts,
                                    jnp.zeros((1,), jnp.int32),
                                    interpret=True)
        out = np.asarray(got)
        assert np.isfinite(out[:4]).all()
        # the rows past the slot's count are not the kernel's to write: the
        # interpreter's fresh output buffer is NaN and stays so
        assert np.isnan(out[4:]).all()

    def test_kv_major_matches_standard(self, rng):
        q, k, v, bt, lens, starts, counts = self._case(rng)
        nkv, g = q.shape[2], q.shape[3]
        slopes = jnp.asarray(np.geomspace(0.5, 1 / 64, nkv * g), jnp.float32)
        kt, vt = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
        for kw in ({}, {"alibi_slopes": slopes}, {"window": 6}):
            want, _ = self._both(q, k, v, bt, lens, starts, counts, **kw)
            for name, got in zip(
                    ("xla", "pallas"),
                    self._both(q, kt, vt, bt, lens, starts, counts,
                               kv_major=True, **kw)):
                np.testing.assert_allclose(got, want, atol=1e-5,
                                           err_msg=f"{name} {kw}")

    @pytest.mark.parametrize("variant", ["plain", "window", "alibi-window",
                                         "kv-major-window"])
    def test_one_row_slots_through_the_decode_kernel(self, rng, variant):
        """The mixed step's composition (model.py ``_mixed_attention``):
        slots with one row through the paged decode kernel (their first rows
        gathered), the others through the prefill kernel, each blind to the
        other's slots; a row takes its slot's kernel's result, and together
        they are the prefill reference over every slot.  The riders lie
        BETWEEN the chunks in the flat batch and the prefill kernel leaves
        their rows alone."""
        from deepspeed_tpu.ops.paged_attention import (
            pallas_paged_attention, pallas_ragged_prefill, xla_ragged_prefill)
        q, k, v, bt, _, _, _ = self._case(rng, S=6, NB=32)
        Q = q.shape[1]
        # riders at contexts that end mid-page, on a page's last row and on
        # the first row of a new page, beside two chunks and an empty slot
        counts = jnp.asarray([0, 1, 5, Q, 1, 1], jnp.int32)
        lens = jnp.asarray([0, 19, 14, Q, 16, 25], jnp.int32)
        starts = lens - counts
        flat, rows = token_major(q, counts, order=[1, 2, 4, 3, 5, 0], pad=2)
        kw = {}
        if "window" in variant:
            kw["window"] = 6
        if "alibi" in variant:
            kw["alibi_slopes"] = jnp.asarray(
                np.geomspace(0.5, 1 / 64, q.shape[2] * q.shape[3]),
                jnp.float32)
        want = xla_ragged_prefill(flat, k, v, bt, lens, starts, counts, rows,
                                  max_q=Q, **kw)
        if "kv-major" in variant:
            k, v = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
            kw["kv_major"] = True
        one = np.asarray(counts == 1)
        o_one = np.asarray(pallas_paged_attention(
            flat[rows], k, v, bt, jnp.where(one, lens, 0), interpret=True,
            **kw))
        o_many = np.asarray(pallas_ragged_prefill(
            flat, k, v, bt, lens, starts, jnp.where(one, 0, counts), rows,
            max_q=Q, interpret=True, **kw))
        np.testing.assert_array_equal(o_one[~one], 0)
        # slot of each flat row (-1: no slot's), as the model knows it
        slot = np.full(flat.shape[0], -1)
        for s, (n, r) in enumerate(zip(np.asarray(counts), np.asarray(rows))):
            slot[r:r + n] = s
        many = (slot >= 0) & ~one[slot]
        # written: exactly the rows of the slots the kernel was given
        np.testing.assert_array_equal(np.isnan(o_many).any((1, 2, 3)), ~many)
        got = np.where(many[:, None, None, None], o_many, 0)
        got[np.asarray(rows)[one]] = o_one[one]
        np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)

    # [(rows a slot, context after the step)] in slot order, the slots'
    # order in the flat batch, rows no slot owns at its end; ``cq`` is 8
    # (GQA form) or 32 (latent form), so these counts are in units of it
    HAZARDS = {
        # two slots whose spans end inside a chunk, one after the other: the
        # first's last chunk overhangs the second's rows
        "adjacent-tails": ([(1.5, 3), (2.25, 2.25), (0.5, 4)], None, 0),
        "riders-between": ([(1.25, 2), (0, 3), (2.5, 2.5), (0, 1), (1, 1)],
                           [3, 0, 1, 2, 4], 0),
        "every-slot-a-rider": ([(0, 2), (0, 0), (0, 5)], None, 0),
        "a-slot-of-exactly-q": ([(5, 5), (0.75, 1)], [1, 0], 0),
        "batch-not-whole-chunks": ([(1.125, 1.125), (2, 3)], None, 5),
    }

    @pytest.mark.parametrize("form", ["gqa", "gqa-window", "latent",
                                      "latent-window"])
    @pytest.mark.parametrize("hazard", sorted(HAZARDS))
    def test_token_major_rows(self, rng, hazard, form):
        """What the token-major layout could get wrong, each against
        ``xla_ragged_prefill`` and in the latent form (``v_dim`` 512, key
        width 640, chunks of 32) and under a window: EVERY slot's rows are
        compared, and every row the kernel was not given must come back
        untouched (NaN, the interpreter's fresh buffer).  A slot here with 0
        rows stands for a rider or an empty slot: a row of the flat batch is
        left for it where ``order`` puts it."""
        from deepspeed_tpu.ops.paged_attention import (
            _prefill_chunk, pallas_ragged_prefill, xla_ragged_prefill)
        latent = form.startswith("latent")
        nkv, g, hd, vd, bs = (1, 16, 640, 512, 32) if latent else \
            (2, 2, 16, 16, 8)
        spec, order, pad = self.HAZARDS[hazard]
        Q = 5 * (32 if latent else 8)   # five chunks: the largest that divide
        cq = _prefill_chunk(Q, g, vd)
        assert cq == (32 if latent else 8)
        counts = np.asarray([int(c * cq) for c, _ in spec], np.int32)
        lens = np.asarray([int(max(n, c) * cq) + 3 for c, n in spec],
                          np.int32)
        S = len(spec)
        order = np.arange(S) if order is None else np.asarray(order)
        rows, cursor = np.zeros(S, np.int32), 0
        for s in order:
            rows[s] = cursor
            cursor += max(int(counts[s]), 1)    # a rider's row stays its own
        N = cursor + pad
        MB = -(-int(lens.max()) // bs)
        q = jnp.asarray(rng.standard_normal((N, nkv, g, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((S * MB, nkv, bs, hd)),
                        jnp.float32)
        v = None if latent else jnp.asarray(
            rng.standard_normal(k.shape), jnp.float32)
        bt = jnp.asarray(rng.permutation(S * MB).reshape(S, MB), jnp.int32)
        kw = dict(max_q=Q, window=40 if "window" in form else None)
        if latent:
            kw.update(v_dim=vd, scale=192 ** -0.5)
        args = (q, k, v, bt, jnp.asarray(lens), jnp.asarray(lens - counts),
                jnp.asarray(counts), jnp.asarray(rows))
        want = np.asarray(xla_ragged_prefill(*args, **kw))
        got = np.asarray(pallas_ragged_prefill(*args, interpret=True, **kw))
        owned = np.zeros(N, bool)
        for n, r in zip(counts, rows):
            owned[r:r + n] = True
        np.testing.assert_array_equal(np.isnan(got).any((1, 2, 3)), ~owned)
        np.testing.assert_allclose(got[owned], want[owned], atol=2e-5)

    # What a BLOCK of pages adds, a slot each, as (context before the step,
    # rows) in units of ``u`` = a page = a chunk, at two pages a block:
    BLOCK_EDGES = [
        ((2, 0), (2, 0)),     # the context ends exactly on a block ...
        ((3, 0), (2, 0)),     # ... one page past it ...
        ((2, 1), (2, 0)),     # ... and one key past it
        ((2, -1), (1, 0)),    # the first row on a page's last key: the
                              # block under it ends AT its position
        ((2, -2), (1, 0)),    # ... one key earlier: the diagonal crosses it
        ((2, 0), (1, 0)),     # ... and on the next page's first key
        ((4, 0), (1, 3)),     # a last chunk of 3 rows beside a full one
        ((0, 0), (3, 0)),     # a fresh prompt
        ((8, 0), (2, 0)),     # far past a window of 5 pages: its start lies
                              # on a block's first key for the first item's
                              # first row and inside it for the others
    ]

    @pytest.mark.parametrize("pages", [1, 2, 5, 16])
    @pytest.mark.parametrize("window", [None, 5], ids=["global", "window"])
    @pytest.mark.parametrize("form", ["gqa", "latent", "int8", "alibi",
                                      "kv-major"])
    def test_block_edges(self, rng, monkeypatch, form, window, pages):
        """The page loop's block: ``BLOCK_EDGES`` in one flat batch against
        ``xla_ragged_prefill``, in every form of the pool, with the pages of
        a block forced to 1 (a page a loop step), 2, 5 (in the latent
        form 160 keys, no whole number of lane tiles) and more than any
        context holds (the whole context one block)."""
        from deepspeed_tpu.inference.v2.model import quantize_kv_token
        import importlib
        pa = importlib.import_module("deepspeed_tpu.ops.paged_attention")
        latent = form == "latent"
        nkv, g, hd, vd, u = (1, 16, 640, 512, 32) if latent else \
            (2, 2, 16, 16, 8)
        monkeypatch.setattr(pa, "_prefill_block_pages",
                            lambda *a, **k: pages)
        ctx = np.asarray([a * u + b for (a, b), _ in self.BLOCK_EDGES],
                         np.int32)
        counts = np.asarray([a * u + b for _, (a, b) in self.BLOCK_EDGES],
                            np.int32)
        S, Q = len(ctx), 3 * u
        assert pa._prefill_chunk(Q, g, vd) == u
        rows = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
        N = int(counts.sum()) + 5
        MB = -(-int((ctx + counts).max()) // u)
        q = jnp.asarray(rng.standard_normal((N, nkv, g, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((S * MB, nkv, u, hd)),
                        jnp.float32)
        v = None if latent else jnp.asarray(
            rng.standard_normal(k.shape), jnp.float32)
        kw = dict(max_q=Q, window=window and window * u)
        if latent:
            kw.update(v_dim=vd, scale=192 ** -0.5)
        if form == "int8":
            (k, ks), (v, vs) = quantize_kv_token(k), quantize_kv_token(v)
            kw.update(k_scale=ks, v_scale=vs)
        if form == "alibi":
            kw["alibi_slopes"] = jnp.asarray(
                np.geomspace(0.5, 1 / 64, nkv * g), jnp.float32)
        bt = jnp.asarray(rng.permutation(S * MB).reshape(S, MB), jnp.int32)
        args = [q, k, v, bt, jnp.asarray(ctx + counts), jnp.asarray(ctx),
                jnp.asarray(counts), jnp.asarray(rows)]
        want = np.asarray(pa.xla_ragged_prefill(*args, **kw))
        if form == "kv-major":
            args[1:3] = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
            kw["kv_major"] = True
        got = np.asarray(pa.pallas_ragged_prefill(*args, interpret=True,
                                                  **kw))
        owned = np.arange(N) < counts.sum()
        np.testing.assert_array_equal(np.isnan(got).any((1, 2, 3)), ~owned)
        np.testing.assert_allclose(got[owned], want[owned], atol=2e-5)

    @pytest.mark.parametrize("name,pools,rows,bs,vd,pages", [
        ("heads-of-128-g4", [((8, 128, 128), "bfloat16")] * 2, 512, 128, 128,
         8),
        ("heads-of-128-g6", [((8, 128, 128), "bfloat16")] * 2, 768, 128, 128,
         8),
        ("int8-with-scale-rows", [((8, 128, 128), "int8")] * 2
         + [((8, 128), "float32")] * 2, 512, 128, 128, 8),
        ("latent-640", [((1, 128, 640), "bfloat16")], 512, 128, 512, 8),
        ("latent-1152-pages-of-512", [((1, 512, 1152), "bfloat16")], 256,
         512, 1024, 1),
        ("pages-of-512", [((8, 512, 128), "bfloat16")] * 2, 768, 512, 128, 2),
        ("2048-score-rows", [((8, 128, 128), "bfloat16")] * 2, 2048, 128,
         128, 3),
    ])
    def test_block_pages_follow_the_shapes(self, name, pools, rows, bs, vd,
                                           pages):
        """P of the prefill kernel's block from static shapes alone: one kv
        head's pages within the block's bytes, the float32 score tile beside
        the accumulator within the tile's."""
        from deepspeed_tpu.ops.paged_attention import _prefill_block_pages
        pools = [jax.ShapeDtypeStruct((64,) + shape, dtype)
                 for shape, dtype in pools]
        assert _prefill_block_pages(pools, rows, bs, vd) == pages

    @pytest.mark.parametrize("G", [4, 5])
    def test_dense_slots_of_the_verify_program(self, rng, G):
        """The speculative verify program's layout (model.py
        ``_verify_core``): every slot owns the ``G`` rows from ``s * G`` and
        scores all of them or, inactive, none; ``G`` 5 divides by no chunk
        but 1."""
        from deepspeed_tpu.ops.paged_attention import (pallas_ragged_prefill,
                                                       xla_ragged_prefill)
        S, nkv, g, hd, bs, MB = 5, 2, 2, 16, 8, 4
        active = np.asarray([True, False, True, True, False])
        q = jnp.asarray(rng.standard_normal((S * G, nkv, g, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((S * MB, nkv, bs, hd)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal(k.shape), jnp.float32)
        bt = jnp.asarray(rng.permutation(S * MB).reshape(S, MB), jnp.int32)
        pos0 = jnp.asarray([3, 0, 17, 8, 0], jnp.int32)
        counts = jnp.where(active, G, 0)
        args = (q, k, v, bt, jnp.where(active, pos0 + G, 0), pos0, counts,
                jnp.arange(S, dtype=jnp.int32) * G)
        want = np.asarray(xla_ragged_prefill(*args, max_q=G))
        got = np.asarray(pallas_ragged_prefill(*args, max_q=G,
                                               interpret=True))
        rows = np.repeat(active, G)
        np.testing.assert_allclose(got[rows], want[rows], atol=1e-5)
        assert np.isnan(got[~rows]).all() and (want[~rows] == 0).all()

    @pytest.mark.parametrize("g,width,dtype,want", [
        (6, 128, jnp.bfloat16, (8, 128)),     # Trinity: a group of 6 in 8
        (4, 128, jnp.bfloat16, (4, 128)),     # Mistral
        (16, 640, jnp.bfloat16, (16, 640)),   # Moonlight's queries ...
        (16, 512, jnp.bfloat16, (16, 512)),   # ... and its output
        (1, 64, jnp.bfloat16, (2, 128)),      # GPT-2: bf16 packs two rows
        (12, 64, jnp.bfloat16, (16, 128)),
        (1, 64, jnp.float32, (1, 128)),
    ])
    def test_tile_pad(self, g, width, dtype, want):
        """What the kernel pads a token-major array's two minor dims to so
        that a copy may take one kv head's rows of it; the compiler agrees
        at every engine geometry (tests/test_chip_compile.py)."""
        from deepspeed_tpu.ops.paged_attention import _tile_pad
        assert _tile_pad(g, width, dtype) == want

    def test_engine_serving_token_exact_with_kernel(self, rng, monkeypatch):
        """Force the dispatch onto the Pallas (interpret) kernels and check
        the v2 engine generates the SAME tokens as the XLA path."""
        import dataclasses

        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        from deepspeed_tpu.models import GPTConfig
        from deepspeed_tpu.ops import registry as reg
        cfg = GPTConfig.tiny(vocab_size=128, max_seq_len=64)
        cfg = dataclasses.replace(cfg, use_rope=True, use_rmsnorm=True)
        sm = {"state_manager": {"max_tracked_sequences": 3,
                                "kv_block_size": 8},
              "generation": {"do_sample": False}}
        prompts = [np.asarray(rng.integers(0, 128, n), np.int32)
                   for n in (5, 17, 3)]
        eng = InferenceEngineV2(cfg, sm, seed=0)
        want = eng.generate(prompts, max_new_tokens=8)
        params = eng.params
        del eng
        monkeypatch.setattr(reg, "_on_tpu", lambda: True)
        eng2 = InferenceEngineV2(cfg, sm, params=params)
        got = eng2.generate(prompts, max_new_tokens=8)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFlatPool:
    """The serving step programs hand the attention ops the flat pool of all
    layers, [L * NB, ...], and the layer's first page ``li * NB`` in the
    block table (inference/v2/model.py) instead of a slice of the pool: both
    ops, in both implementations, must read exactly what they read from the
    layer's own pages."""

    L, LI, NB, S, MB, nkv, g, hd, bs = 3, 1, 12, 4, 3, 2, 2, 16, 8

    def _pool(self, rng):
        shape = (self.L * self.NB, self.nkv, self.bs, self.hd)
        k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        bt = jnp.asarray(rng.permutation(self.NB).reshape(self.S, self.MB),
                         jnp.int32)
        return k, v, bt

    @pytest.mark.parametrize("extra", ["plain", "window", "alibi"])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    @pytest.mark.parametrize("op", ["decode", "prefill"])
    def test_offset_table_equals_layer_pages(self, rng, op, impl, extra):
        from deepspeed_tpu.models.gpt import alibi_slopes
        from deepspeed_tpu.ops.paged_attention import (
            pallas_paged_attention, pallas_ragged_prefill,
            xla_paged_attention, xla_ragged_prefill)
        k, v, bt = self._pool(rng)
        lo, hi = self.LI * self.NB, (self.LI + 1) * self.NB
        kw = {"window": {"window": 5},
              "alibi": {"alibi_slopes": alibi_slopes(self.nkv * self.g,
                                                     self.hd)},
              "plain": {}}[extra]
        if impl == "pallas":
            kw["interpret"] = True
        if op == "decode":
            fn = {"xla": xla_paged_attention,
                  "pallas": pallas_paged_attention}[impl]
            q = jnp.asarray(rng.standard_normal(
                (self.S, self.nkv, self.g, self.hd)), jnp.float32)
            rest = (jnp.asarray([0, 5, 16, 24], jnp.int32),)
        else:
            fn = {"xla": xla_ragged_prefill,
                  "pallas": pallas_ragged_prefill}[impl]
            Q = 8
            counts = jnp.asarray([0, 1, 5, Q], jnp.int32)
            q, rows = token_major(jnp.asarray(rng.standard_normal(
                (self.S, Q, self.nkv, self.g, self.hd)), jnp.float32), counts)
            lens = jnp.asarray([0, 19, 14, Q], jnp.int32)
            rest = (lens, lens - counts, counts, rows)
            kw["max_q"] = Q
        want = fn(q, k[lo:hi], v[lo:hi], bt, *rest, **kw)
        got = fn(q, k, v, bt + lo, *rest, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestSparseAttention:
    """Block-sparse attention patterns (reference ops/sparse_attention/)."""

    def _qkv(self, rng, B=2, T=32, N=2, D=8):
        mk = lambda: jnp.asarray(  # noqa: E731
            rng.standard_normal((B, T, N, D)), jnp.float32)
        return mk(), mk(), mk()

    def test_dense_config_matches_causal(self, rng):
        from deepspeed_tpu.ops.sparse_attention import (DenseSparsityConfig,
                                                        sparse_attention)
        q, k, v = self._qkv(rng)
        got = sparse_attention(q, k, v, DenseSparsityConfig(block=8))
        want = ops.causal_attention(q, k, v, impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_fixed_pattern_masks_long_range(self, rng):
        from deepspeed_tpu.ops.sparse_attention import (FixedSparsityConfig,
                                                        expand_layout_mask,
                                                        sparse_attention,
                                                        sparsity_ratio)
        cfg = FixedSparsityConfig(block=8, num_local_blocks=2,
                                  num_global_blocks=1)
        lay = cfg.make_layout(64)
        assert lay.shape == (8, 8)
        assert lay[7, 7] and lay[0, 0]          # diagonal always active
        assert not lay[7, 4]                    # distant non-global masked
        assert sparsity_ratio(cfg, 64) < 1.0
        q, k, v = self._qkv(rng, T=64)
        out = sparse_attention(q, k, v, cfg)
        assert np.isfinite(np.asarray(out)).all()

    def test_longformer_and_bigbird_layouts(self):
        from deepspeed_tpu.ops.sparse_attention import (
            BigBirdSparsityConfig, BSLongformerSparsityConfig)
        lf = BSLongformerSparsityConfig(
            block=4, num_sliding_window_blocks=2, global_block_indices=(0,))
        lay = lf.make_layout(32)
        assert lay[:, 0].all() and lay[0, :].all()      # global block
        assert lay[5, 4] and not lay[5, 2]              # window of 2
        bb = BigBirdSparsityConfig(block=4, num_random_blocks=1,
                                   num_sliding_window_blocks=2,
                                   num_global_blocks=1)
        lay2 = bb.make_layout(32)
        assert lay2[:, 0].all()
        # deterministic layout (static under jit)
        np.testing.assert_array_equal(lay2, bb.make_layout(32))

    def test_bad_block_size_raises(self):
        from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
        with pytest.raises(ValueError, match="divisible"):
            FixedSparsityConfig(block=7).make_layout(32)

    # ---- block-SKIPPING kernel (round-3 VERDICT item 5) ----

    def _configs(self):
        from deepspeed_tpu.ops.sparse_attention import (
            BigBirdSparsityConfig, BSLongformerSparsityConfig,
            FixedSparsityConfig)
        return [
            FixedSparsityConfig(block=8, num_local_blocks=2,
                                num_global_blocks=1),
            BSLongformerSparsityConfig(block=8, num_sliding_window_blocks=2,
                                       global_block_indices=(0,)),
            BigBirdSparsityConfig(block=8, num_random_blocks=1,
                                  num_sliding_window_blocks=2,
                                  num_global_blocks=1),
        ]

    def test_kernel_matches_masked_dense(self, rng):
        from deepspeed_tpu.ops.sparse_attention import (block_sparse_flash,
                                                        sparse_attention)
        q, k, v = self._qkv(rng, T=64, D=16)
        for cfg in self._configs():
            want = sparse_attention(q, k, v, cfg, impl="xla")
            got = block_sparse_flash(q, k, v, cfg, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=type(cfg).__name__)

    def test_kernel_grads_match_masked_dense(self, rng):
        from deepspeed_tpu.ops.sparse_attention import (block_sparse_flash,
                                                        sparse_attention)
        q, k, v = self._qkv(rng, T=64, D=16)
        cfg = self._configs()[0]
        gr = jax.grad(lambda *a: jnp.sum(sparse_attention(
            *a, cfg, impl="xla") ** 2), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(lambda *a: jnp.sum(block_sparse_flash(
            *a, cfg, interpret=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)

    def test_kernel_gqa(self, rng):
        from deepspeed_tpu.ops.sparse_attention import (block_sparse_flash,
                                                        sparse_attention)
        q, k, v = self._qkv(rng, T=64, N=4, D=16)
        k, v = k[:, :, :2], v[:, :, :2]
        cfg = self._configs()[1]
        want = sparse_attention(q, k, v, cfg, impl="xla")
        got = block_sparse_flash(q, k, v, cfg, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_kernel_skips_dead_blocks(self, rng):
        """Dead K/V blocks must never be touched: poison them with NaN —
        masked-dense would read (and mask) them post-matmul, the kernel
        never loads them (the actual FLOP/bandwidth saving)."""
        from deepspeed_tpu.ops.sparse_attention import (FixedSparsityConfig,
                                                        block_sparse_flash,
                                                        expand_layout_mask)
        cfg = FixedSparsityConfig(block=8, num_local_blocks=2,
                                  num_global_blocks=1)
        T = 64
        lay = cfg.make_layout(T)
        lay_c = lay & np.tril(np.ones_like(lay))
        q, k, v = self._qkv(rng, T=T, D=16)
        k = np.array(k); v = np.array(v)
        dead_cols = np.flatnonzero(~lay_c.any(0))     # blocks no row reads
        # also poison per-column: any column j dead for ALL rows
        assert dead_cols.size > 0 or (~lay_c).sum() > 0
        for j in dead_cols:
            k[:, j * 8:(j + 1) * 8] = np.nan
            v[:, j * 8:(j + 1) * 8] = np.nan
        got = block_sparse_flash(q, jnp.asarray(k), jnp.asarray(v), cfg,
                                 interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        del expand_layout_mask

    def test_kernel_work_scales_with_density(self):
        """The kernel's grid is nb × max-active-blocks-per-row, not nb² —
        the static shape itself proves the FLOP saving."""
        from deepspeed_tpu.ops.sparse_attention import (
            BSLongformerSparsityConfig, _layout_tables, sparsity_ratio)
        cfg = BSLongformerSparsityConfig(block=16,
                                         num_sliding_window_blocks=2,
                                         global_block_indices=(0,))
        T = 1024
        lay = cfg.make_layout(T)
        nb = lay.shape[0]
        cols, nact_r, _, _ = _layout_tables(lay, True)
        # grid work = sum(nact) ≈ density · nb², far below dense nb²
        assert cols.shape[1] <= 4          # window 2 + global + diag
        assert int(nact_r.sum()) < 0.1 * nb * nb
        assert sparsity_ratio(cfg, T) < 0.12

    def test_dispatch_uses_kernel_on_tpu(self, rng, monkeypatch):
        from deepspeed_tpu.ops import registry as reg
        from deepspeed_tpu.ops.sparse_attention import (FixedSparsityConfig,
                                                        sparse_attention)
        monkeypatch.setattr(reg, "_on_tpu", lambda: True)
        q, k, v = self._qkv(rng, T=64, D=16)
        cfg = FixedSparsityConfig(block=8, num_local_blocks=2)
        got = sparse_attention(q, k, v, cfg)          # -> pallas (interpret)
        want = sparse_attention(q, k, v, cfg, impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


class TestEvoformer:
    """DS4Science evoformer attention (reference csrc/deepspeed4science/)."""

    def test_matches_naive_softmax(self, rng):
        from deepspeed_tpu.ops.evoformer import evoformer_attention
        B, N, S, H, D = 2, 3, 8, 2, 4
        q, k, v = (jnp.asarray(rng.standard_normal((B, N, S, H, D)),
                               jnp.float32) for _ in range(3))
        bias1 = jnp.asarray(rng.standard_normal((B, N, 1, 1, S)), jnp.float32)
        bias2 = jnp.asarray(rng.standard_normal((B, 1, H, S, S)), jnp.float32)
        got = evoformer_attention(q, k, v, bias1, bias2)
        # naive reference
        logits = jnp.einsum("bnqhd,bnkhd->bnhqk", q, k) * (D ** -0.5)
        logits = logits + bias1 + bias2
        want = jnp.einsum("bnhqk,bnkhd->bnqhd",
                          jax.nn.softmax(logits, -1), v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        assert got.shape == q.shape

    def test_mask_bias_excludes_keys(self, rng):
        from deepspeed_tpu.ops.evoformer import evoformer_attention
        B, N, S, H, D = 1, 1, 4, 1, 4
        q, k, v = (jnp.asarray(rng.standard_normal((B, N, S, H, D)),
                               jnp.float32) for _ in range(3))
        bias1 = jnp.zeros((B, N, 1, 1, S)).at[..., -1].set(-1e9)
        out = evoformer_attention(q, k, v, bias1)
        # last key masked → output equals attention over first S-1 keys
        want = evoformer_attention(q, k[:, :, :-1], v[:, :, :-1])
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5)

    def test_rank_check(self):
        from deepspeed_tpu.ops.evoformer import evoformer_attention
        with pytest.raises(ValueError, match="B, N, S, H, D"):
            evoformer_attention(jnp.zeros((2, 3, 4)), jnp.zeros((2, 3, 4)),
                                jnp.zeros((2, 3, 4)))

    def test_pallas_kernel_matches_xla(self, rng):
        """Blockwise kernel (round-3 verdict item 6) vs the einsum ground
        truth — forward AND every gradient (dq/dk/dv/dbias1/dbias2)."""
        from deepspeed_tpu.ops.evoformer import (_evoformer_xla,
                                                 evoformer_attention,
                                                 supported)
        B, N, S, H, D = 2, 3, 32, 2, 8
        q, k, v = (jnp.asarray(rng.standard_normal((B, N, S, H, D)),
                               jnp.float32) for _ in range(3))
        bias1 = jnp.asarray(rng.standard_normal((B, N, 1, 1, S)), jnp.float32)
        bias2 = jnp.asarray(rng.standard_normal((B, 1, H, S, S)), jnp.float32)
        assert supported(q, k, v)                 # really the Pallas path

        got = evoformer_attention(q, k, v, bias1, bias2)
        want = _evoformer_xla(q, k, v, bias1, bias2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

        def loss(fn):
            return lambda q_, k_, v_, b1, b2: jnp.sum(
                fn(q_, k_, v_, b1, b2) * 0.01)
        gp = jax.grad(loss(evoformer_attention), argnums=(0, 1, 2, 3, 4))(
            q, k, v, bias1, bias2)
        gx = jax.grad(loss(_evoformer_xla), argnums=(0, 1, 2, 3, 4))(
            q, k, v, bias1, bias2)
        for name, a, b in zip(("dq", "dk", "dv", "dbias1", "dbias2"), gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, err_msg=name)

    def test_pallas_bias_subsets(self, rng):
        """bias1-only, bias2-only, and no-bias variants all hit the kernel
        and match the ground truth."""
        from deepspeed_tpu.ops.evoformer import (_evoformer_xla,
                                                 evoformer_attention)
        B, N, S, H, D = 1, 2, 16, 2, 8
        q, k, v = (jnp.asarray(rng.standard_normal((B, N, S, H, D)),
                               jnp.float32) for _ in range(3))
        bias1 = jnp.asarray(rng.standard_normal((B, N, 1, 1, S)), jnp.float32)
        bias2 = jnp.asarray(rng.standard_normal((B, 1, H, S, S)), jnp.float32)
        for b1, b2 in ((bias1, None), (None, bias2), (None, None)):
            got = evoformer_attention(q, k, v, b1, b2)
            want = _evoformer_xla(q, k, v, b1, b2)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-5)

    def test_pallas_fully_masked_row(self, rng):
        """A row whose every key carries the -1e9 mask bias: softmax over
        uniformly masked logits is uniform (standard softmax semantics, and
        what the XLA path computes) — the kernel must agree and stay
        NaN-free in forward and grads (the exp rescaling guard)."""
        from deepspeed_tpu.ops.evoformer import (_evoformer_xla,
                                                 evoformer_attention)
        B, N, S, H, D = 1, 2, 16, 1, 8
        q, k, v = (jnp.asarray(rng.standard_normal((B, N, S, H, D)),
                               jnp.float32) for _ in range(3))
        bias1 = jnp.zeros((B, N, 1, 1, S)).at[:, 0].set(-1e9)  # row 0 all dead
        out = evoformer_attention(q, k, v, bias1)
        assert not np.any(np.isnan(np.asarray(out)))
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_evoformer_xla(q, k, v, bias1)),
                                   atol=2e-5)
        g = jax.grad(lambda q_: jnp.sum(evoformer_attention(q_, k, v, bias1)))(q)
        assert not np.any(np.isnan(np.asarray(g)))


class TestEvoformerPadding:
    """Odd-S MSA stacks (round-4 verdict item 6): S that doesn't block-tile
    pads to the grid instead of silently materializing the O(S²) einsum;
    the residual einsum fallbacks warn once."""

    def test_odd_s_pads_onto_kernel_and_matches(self, rng):
        from deepspeed_tpu.ops.evoformer import (_evoformer_xla,
                                                 evoformer_attention,
                                                 supported)
        B, N, S, H, D = 1, 2, 21, 2, 8            # 21 never tiles
        q, k, v = (jnp.asarray(rng.standard_normal((B, N, S, H, D)),
                               jnp.float32) for _ in range(3))
        bias1 = jnp.asarray(rng.standard_normal((B, N, 1, 1, S)), jnp.float32)
        bias2 = jnp.asarray(rng.standard_normal((B, 1, H, S, S)), jnp.float32)
        assert not supported(q, k, v)
        got = evoformer_attention(q, k, v, bias1, bias2)
        want = _evoformer_xla(q, k, v, bias1, bias2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        # gradients flow through the pad/slice to the ORIGINAL bias shapes
        def loss(fn):
            return lambda q_, b1, b2: jnp.sum(fn(q_, k, v, b1, b2) * 0.01)
        gp = jax.grad(loss(evoformer_attention), argnums=(0, 1, 2))(
            q, bias1, bias2)
        gx = jax.grad(loss(_evoformer_xla), argnums=(0, 1, 2))(
            q, bias1, bias2)
        for name, a, b in zip(("dq", "dbias1", "dbias2"), gp, gx):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, err_msg=name)

    def test_odd_s_no_bias(self, rng):
        """Padding with NO caller bias must still mask the padded keys
        (a synthetic bias1 carries the -1e9 tail)."""
        from deepspeed_tpu.ops.evoformer import (_evoformer_xla,
                                                 evoformer_attention)
        B, N, S, H, D = 1, 1, 13, 1, 8
        q, k, v = (jnp.asarray(rng.standard_normal((B, N, S, H, D)),
                               jnp.float32) for _ in range(3))
        got = evoformer_attention(q, k, v)
        want = _evoformer_xla(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_residual_fallback_warns_once(self, rng):
        """d % 8 != 0 cannot pad onto the kernel — einsum with ONE warning
        (wq_matmul's warn-once policy; the project logger doesn't
        propagate, so assert via the dedup set the warning keys off)."""
        from deepspeed_tpu.ops import evoformer as evo
        B, N, S, H, D = 1, 1, 16, 1, 7
        q, k, v = (jnp.asarray(rng.standard_normal((B, N, S, H, D)),
                               jnp.float32) for _ in range(3))
        evo._warned_fallback.clear()
        out1 = evo.evoformer_attention(q, k, v)
        assert len(evo._warned_fallback) == 1
        out2 = evo.evoformer_attention(q, k, v)
        assert len(evo._warned_fallback) == 1      # deduped, not re-warned
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))
        # and the odd-S path must NOT be in the fallback set (it pads)
        q8, k8, v8 = (jnp.asarray(rng.standard_normal((1, 1, 13, 1, 8)),
                                  jnp.float32) for _ in range(3))
        evo.evoformer_attention(q8, k8, v8)
        assert len(evo._warned_fallback) == 1
