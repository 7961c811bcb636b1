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
        """Pin the on-chip-tuned (bq, bk) table (the v5e sweep of PR 52, the
        blocks of the loop inside the kernels) so a refactor can't silently
        regress the measured fast pairs."""
        import importlib
        fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
        assert fa._block_pair(1024) == (512, 512)
        assert fa._block_pair(2048) == (1024, 1024)
        assert fa._block_pair(4096) == (1024, 1024)
        assert fa._block_pair(8192) == (1024, 1024)
        assert fa._block_pair(4096, d=128) == (1024, 1024)
        assert fa._block_pair(512) == (512, 512)
        assert fa._block_pair(64) == (64, 64)
        # a long T that 1,024 does not divide takes the next block down
        assert fa._block_pair(4608) == (512, 512)
        # a window's edge tiles are not walked in strips: 512
        assert fa._block_pair(1024, window=128) == (512, 512)
        assert fa._block_pair(4096, window=256) == (512, 512)
        # head_dim > 128 stays at 512 (as fast there, and known to fit)
        assert fa._block_pair(4096, d=256) == (512, 512)
        assert fa._block_pair(1024, d=128) == (512, 512)
        # a diagonal tile's strips: the backward from two, the forward
        # from four, never under a window or off a square pair
        g, z = fa._plan(1024, 64, None), fa._plan(4096, 128, None)
        assert fa._strips(g, True, None, least=2) == 2
        assert fa._strips(g, True, None, least=4) == 1
        assert fa._strips(z, True, None, least=4) == 4
        assert fa._strips(fa._plan(1024, 64, 128), True, 128, least=2) == 1
        assert fa._strips(g._replace(bk=1024), True, None, least=2) == 1
        assert fa._strips(g, False, None, least=2) == 1

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

    # the in-kernel loop over live blocks: (bq, bk) pairs whose diagonal
    # crosses a block off its corner both ways, under every variant, in
    # the three layouts a shape can take
    _VARIANTS = {
        "plain": {},
        "gqa": {"kv_heads": 2},
        "window": {"window": 21},
        "alibi": {"alibi": True},
        "combined": {"kv_heads": 2, "window": 40, "alibi": True},
        "noncausal": {"causal": False},
    }
    _LAYOUTS = {
        # one grid step a head, bounds static, keys and values resident
        "resident": {},
        # several query spans a head: bounds from the program ids
        "spans": {"_SPAN_ROWS": 32},
        # a square pair's diagonal tiles walked in four strips (or eight),
        # forward and backward; in two, the backward alone
        "strips": {"_STRIP": 8},
        "two_strips": {"_STRIP": 16},
        # keys, values and queries arrive in groups; dq from its own kernel
        "streamed": {"_RESIDENT_ROWS_X_DIM": 0, "_SPAN_ROWS": 32,
                     "_STREAM_ROWS": 64},
    }

    def _loop_case(self, qkv, monkeypatch, blocks, variant, layout):
        import importlib
        from deepspeed_tpu.models.gpt import alibi_slopes
        fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
        monkeypatch.setattr(fa, "_block_pair",
                            lambda t, d=64, window=None: blocks)
        for name, value in self._LAYOUTS[layout].items():
            monkeypatch.setattr(fa, name, value)
        opts = dict(self._VARIANTS[variant])
        q, k, v = (x[:1] for x in qkv)
        kv_heads = opts.pop("kv_heads", q.shape[2])
        k, v = k[:, :, :kv_heads], v[:, :, :kv_heads]
        if opts.pop("alibi", False):
            opts["alibi_slopes"] = jnp.asarray(alibi_slopes(q.shape[2]))
        return q, k, v, opts

    @pytest.mark.parametrize("layout", sorted(_LAYOUTS))
    @pytest.mark.parametrize("variant", sorted(_VARIANTS))
    @pytest.mark.parametrize("blocks", [(16, 64), (64, 16), (32, 32)])
    def test_live_block_loop_matches_xla(self, qkv, monkeypatch, blocks,
                                         variant, layout):
        q, k, v, opts = self._loop_case(qkv, monkeypatch, blocks, variant,
                                        layout)
        ref = ops.causal_attention(q, k, v, impl="xla", **opts)
        out = ops.flash_attention(q, k, v, interpret=True, **opts)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=1e-4)
        gr = jax.grad(lambda *a: jnp.sum(ops.causal_attention(
            *a, impl="xla", **opts) ** 2), argnums=(0, 1, 2))
        gf = jax.grad(lambda *a: jnp.sum(ops.flash_attention(
            *a, interpret=True, **opts) ** 2), argnums=(0, 1, 2))
        for a, b in zip(gr(q, k, v), gf(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)

    @pytest.mark.parametrize("layout", ["resident", "streamed"])
    def test_dead_blocks_are_not_computed(self, qkv, monkeypatch, layout):
        """NaN in the values of every key block past the first query
        block's diagonal: a block that was computed and masked would carry
        it into that block's output (0 * NaN) and dq."""
        q, k, v, _ = self._loop_case(qkv, monkeypatch, (32, 32), "plain",
                                     layout)
        v = v.at[:, 32:].set(jnp.nan)

        def first_block(q_, k_, v_):
            return ops.flash_attention(q_, k_, v_, interpret=True)[:, :32]

        out = first_block(q, k, v)
        dq = jax.grad(lambda *a: jnp.sum(first_block(*a) ** 2))(q, k, v)
        assert np.isfinite(np.asarray(out)).all()
        assert np.isfinite(np.asarray(dq[:, :32])).all()

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
        # init and the step as a program each, not one a primitive
        p = jax.jit(lambda k: model.init(k, batch, deterministic=True))(
            jax.random.PRNGKey(0))

        def loss(p_):
            return model.apply(p_, batch, deterministic=True)
        l, g = jax.jit(jax.value_and_grad(loss))(p)
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
            ("causal_attention", "xla", "forced"): 1,
            # the kernel's own note: the form that ran
            ("flash_attention", "pallas",
             "blocks 16x16, backward one-pass"): 1}
        assert "forced (1)" in ops.op_report()

    def test_shape_predicate_refusal_is_recorded(self, monkeypatch):
        from deepspeed_tpu.ops import registry
        monkeypatch.setattr(registry, "_on_tpu", lambda: True)
        registry.reset_dispatch_log()
        q = jnp.ones((1, 15, 2, 8), jnp.float32)         # T=15: no block
        ops.causal_attention(q, q, q)
        assert self._log() == {
            ("causal_attention", "xla", "shape predicate refused"): 1}

    def test_flash_note_says_blocks_and_backward_form(self):
        """Which kernel a cell's step used, from shapes alone: the two
        train cells' attention shapes, traced and never run."""
        from deepspeed_tpu.ops import registry
        registry.reset_dispatch_log()
        for q_shape, kv_heads in (((8, 1024, 16, 64), 16),
                                  ((2, 4096, 32, 128), 8)):
            q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
            kv = jax.ShapeDtypeStruct(
                q_shape[:2] + (kv_heads,) + q_shape[3:], jnp.bfloat16)
            jax.eval_shape(lambda q_, k_, v_: ops.flash_attention(
                q_, k_, v_, interpret=True), q, kv, kv)
        assert self._log() == {
            ("flash_attention", "pallas",
             "blocks 512x512, backward one-pass"): 1,
            ("flash_attention", "pallas",
             "blocks 1024x1024, backward one-pass"): 1}
        # a head whose rows no longer fit the budget streams
        import importlib
        fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
        assert fa.flash_plan(32768, 128)[2] == "streamed"
        assert fa.flash_plan(16384, 64)[2] == "one-pass"

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
