"""Numeric tests for the ragged prefill op and the flat pool of all layers:
the Pallas kernels (interpret mode) against the XLA paths.  Moved out of
``test_ops.py`` as they stood (PR 47), see ``test_ops_paged.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


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
        ``_verify_core``'s ``attend``, what the verify step supplies to the
        one layer body ``_layer``): every slot owns the ``G`` rows from ``s * G`` and
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
