"""Numeric tests for the paged decode op: the Pallas kernel (interpret mode)
against the XLA gather path.  Moved out of ``test_ops.py`` as they stood
(PR 47; the ragged prefill op's are in ``test_ops_ragged.py``) so that the
three run on three workers (``--dist loadfile``): the one file was the
suite's longest, and its classes share nothing."""

import jax.numpy as jnp
import numpy as np
import pytest


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
