"""A selecting layer's prompt chunk, both ways it can read its keys (PR 43):
the selection as bits (``ops.selection_mask``), the prefill kernel's masked
form against its XLA fallback and against the row gather on the same picks,
the no-mask kernel being the program it was, and the one rule that chooses
(``ops.sparse_index.masked_prefill``); and the same bits with no list (PR 54:
``ops.threshold_mask``, each row's k-th score by a search) against the sorted
path's, bit for bit.  Small shapes, the kernels interpreted.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import ops
from deepspeed_tpu.ops import sparse_index as si
from deepspeed_tpu.ops.paged_attention import (pallas_ragged_prefill,
                                               xla_ragged_prefill)

NH, P, VD, BS, MB, K = 4, 128, 128, 16, 8, 24      # C = 128 positions
S = 4


def _step(contexts, counts, pad=3, seed=0, ties=False):
    """A mixed step's flat rows: slot ``s`` holds ``counts[s]`` rows from
    context ``contexts[s]`` on, then ``pad`` rows of no slot; scores random
    over what each row may see (``ties``: from a few values only)."""
    rng = np.random.default_rng(seed)
    slot = np.concatenate([np.full(n, s) for s, n in enumerate(counts)]
                          + [np.full(pad, S)]).astype(np.int32)
    pos = np.concatenate([c + np.arange(n) for c, n in zip(contexts, counts)]
                         + [np.zeros(pad)]).astype(np.int32)
    N, C = len(slot), MB * BS
    raw = rng.integers(0, 5, size=(N, C)).astype(np.float32) if ties \
        else rng.normal(size=(N, C)).astype(np.float32)
    seen = (np.arange(C)[None] <= pos[:, None]) & (slot < S)[:, None]
    scores = jnp.asarray(np.where(seen, raw, -np.inf))
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (N, NH, P), jnp.float32)
    pages = jax.random.normal(kp, (S * MB, 1, BS, P), jnp.float32)
    table = jnp.asarray(rng.permutation(S * MB).reshape(S, MB), jnp.int32)
    return dict(slot=jnp.asarray(slot), pos=jnp.asarray(pos), scores=scores,
                q=q, pages=pages, table=table,
                counts=jnp.asarray(counts, jnp.int32),
                first=jnp.asarray(np.cumsum([0] + list(counts[:-1])),
                                  jnp.int32),
                kv_len=jnp.asarray(np.add(contexts, counts), jnp.int32))


def _bits(words, n):
    words = np.asarray(words)
    return (words[np.arange(n) // 32] >> (np.arange(n) % 32)[:, None]) & 1


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_the_mask_holds_each_rows_picks_and_nothing_else(ties):
    """Bit by bit the set ``index_select`` picked, the first ``min(pos + 1,
    k)`` of a row's list: a row that sees fewer keys than picks keeps all it
    sees, ties go to the lower position as the sort sends them, a row of no
    slot keeps nothing."""
    b = _step([0, 40, 90, 17], [40, 30, 1, 9], ties=ties)
    picked = ops.index_select(b["scores"], K)
    words = ops.selection_mask(b["scores"], picked)
    N = b["scores"].shape[0]
    assert words.shape == (-(-N // 32), MB * BS) and words.dtype == jnp.int32
    keep = _bits(words, N)
    for n in range(N):
        want = np.asarray(picked[n, :min(int(b["pos"][n]) + 1, K)]) \
            if int(b["slot"][n]) < S else []
        assert set(np.flatnonzero(keep[n])) == set(np.asarray(want)), n


def _scores(kind, rng, n, c):
    """``[n, c]`` float32 of one kind of trouble, all finite."""
    if kind == "distinct":
        return rng.permutation(n * c).reshape(n, c).astype(np.float32) - 9.5
    if kind == "ties":          # a few values: every threshold is a tie
        return rng.integers(-2, 3, size=(n, c)).astype(np.float32)
    if kind == "signed_zeros":  # the sort puts -0.0 under 0.0, == does not
        return rng.choice(np.asarray([-0.0, 0.0, -0.0, 0.0, 1.5, -1.5],
                                     np.float32), size=(n, c))
    if kind == "one_value":     # the k lowest positions of a row of equals
        return np.full((n, c), -3.25, np.float32)
    raise ValueError(kind)


@pytest.mark.parametrize("rows,width,C,k", [
    (70, None, 256, K), (70, 256, 256, K), (70, 100, 256, K),
    (32, 129, 256, K), (5, 64, 256, K), (40, 300, 1024, 600)],
    ids=["whole", "width_is_C", "narrow", "word_rows", "few_rows",
         "width_under_k"])
@pytest.mark.parametrize("kind", ["distinct", "ties", "signed_zeros",
                                  "one_value"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_threshold_gives_the_sorted_paths_bits(impl, kind, rows, width,
                                                   C, k):
    """``threshold_mask(scores, k, width=)`` is ``selection_mask(scores,
    index_select(scores, k, width=))`` word for word, in both forms: rows
    that see fewer keys than ``k`` (0, 5, ``k - 1``, ``k``, ``k + 1``
    positions and more), rows of no slot (all ``-inf``), scores of both
    signs, ties at the threshold, ``width`` narrower than the table (and
    than ``k``: the kernel's walk still covers ``k`` columns), equal to it
    and absent, row counts that are and are not whole words."""
    rng = np.random.default_rng(sum(map(ord, kind)) + rows)
    reach = C if width is None else width
    pos = rng.integers(0, reach, size=rows)
    pos[:5] = [0, 5, min(k, reach) - 2, min(k, reach) - 1,
               min(k, reach - 1)]
    pos[5:7] = reach - 1
    seen = np.arange(C)[None, :] <= pos[:, None]
    seen[-2:] = False                                   # rows of no slot
    scores = jnp.asarray(np.where(seen, _scores(kind, rng, rows, C),
                                  -np.inf))
    w = None if width is None else jnp.int32(width)
    want = jax.jit(lambda s, w: ops.selection_mask(
        s, ops.index_select(s, k, width=w)))(scores, w)
    got = jax.jit(lambda s, w: ops.threshold_mask(
        s, k, width=w, impl=impl))(scores, w)
    assert got.shape == want.shape == (-(-rows // 32), C)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    kept = _bits(got, rows).sum(axis=1)
    assert (kept[-2:] == 0).all() and kept[1] == 6 and kept[0] == 1
    if kind != "signed_zeros":      # (there ``==`` keeps a tie the sort
        assert (kept[:-2] == np.minimum(pos[:-2] + 1, k)).all()  # ranked lower)


def _three_ways(b, impl):
    """The chunk rows' attention by the masked kernel (``impl``), by its XLA
    fallback with the same bits, and by the gather on the same picks."""
    one_row = b["counts"] == 1
    picked = ops.index_select(b["scores"], K)
    keep = ops.selection_mask(b["scores"], picked)
    args = (b["q"][:, None], b["pages"], None, b["table"], b["kv_len"],
            b["kv_len"] - b["counts"], jnp.where(one_row, 0, b["counts"]),
            b["first"])
    kw = dict(max_q=64, scale=0.2, v_dim=VD, sel_mask=keep)
    kernel = ops.ragged_prefill_attention(*args, impl=impl, **kw)[:, 0]
    fallback = xla_ragged_prefill(*args, **kw)[:, 0]
    slot = jnp.minimum(b["slot"], S - 1)
    rows = jnp.take_along_axis(b["table"][slot], picked // BS, axis=1) * BS \
        + picked % BS
    gather = ops.selected_attention(
        b["q"], b["pages"], rows, jnp.minimum(b["pos"] + 1, K), v_dim=VD,
        scale=0.2)
    shared = np.asarray((b["slot"] < S) & ~one_row[slot])
    return (np.asarray(kernel)[shared], np.asarray(fallback)[shared],
            np.asarray(gather)[shared])


@pytest.mark.parametrize("contexts,counts", [
    ([0, 40, 90, 17], [40, 30, 1, 9]),      # a chunk that crosses the top-k
    ([64, 0, 100, 0], [64, 1, 5, 2]),       # chunks past it; 64 rows: 2 items
    ([3, 127, 50, 0], [33, 1, 1, 0]),       # riders beside it, an empty slot
], ids=["crossing", "past", "riders"])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_masked_prefill_is_the_fallback_and_the_gather(contexts, counts,
                                                       impl):
    kernel, fallback, gather = _three_ways(_step(contexts, counts, seed=5),
                                           impl)
    assert len(kernel) == sum(n for n in counts if n > 1)
    np.testing.assert_allclose(kernel, fallback, atol=2e-5)
    np.testing.assert_allclose(kernel, gather, atol=2e-5)


def test_a_mask_leaves_the_other_rows_as_they_were():
    """The masked form keeps the kernel's contract for rows it was told
    nothing of: a one-row slot's row and the pad come back untouched, so the
    caller may take them from elsewhere."""
    b = _step([3, 127, 50, 0], [33, 1, 1, 0])
    full = jnp.full((b["scores"].shape[0] // 32 + 1, MB * BS), -1, jnp.int32)
    args = (b["q"][:, None], b["pages"], None, b["table"], b["kv_len"],
            b["kv_len"] - b["counts"],
            jnp.where(b["counts"] == 1, 0, b["counts"]), b["first"])
    kw = dict(max_q=64, scale=0.2, v_dim=VD, interpret=True)
    with_mask = pallas_ragged_prefill(*args, sel_mask=full, **kw)
    without = pallas_ragged_prefill(*args, **kw)
    # every bit set: the causal kernel itself
    np.testing.assert_allclose(np.asarray(with_mask)[:33],
                               np.asarray(without)[:33], atol=1e-6)


# sha256 of the jaxpr of a call WITHOUT a mask, function addresses blanked,
# taken on the parent commit (5341a54): the static flag leaves today's
# program as it was for the models that never select.  A later edit to the
# kernel shows here: take the digest anew, on the tree before the edit's
# masked part, and say so.
NO_MASK_JAXPR = {
    False: "329639b273c44ea2c5602c7532af2ce20edf8299bd7569436dd577a4d9431ad0",
    True: "515a3e2b6f927a914d1680ff9e29793a5f350da8823e7aff383a3ba073c0c5b3",
}


def _jaxpr(latent, masked=False):
    N, MB_, bs, nkv, g, hd = 64, 4, 16, (1 if latent else 2), 4, 128
    q = jnp.zeros((N, nkv, g, hd), jnp.bfloat16)
    k = jnp.zeros((S * MB_, nkv, bs, hd), jnp.bfloat16)
    i = jnp.zeros((S,), jnp.int32)
    bt = jnp.zeros((S, MB_), jnp.int32)
    kw = dict(v_dim=64, scale=0.1) if latent else dict(window=24)
    if masked:
        kw["sel_mask"] = jnp.zeros((2, MB_ * bs), jnp.int32)
    text = str(jax.make_jaxpr(lambda q, k, bt, a, b, c, d: (
        pallas_ragged_prefill(q, k, None if latent else k, bt, a, b, c, d,
                              max_q=32, interpret=False, **kw)))(
        q, k, bt, i, i, i, i))
    return re.sub(r"0x[0-9a-f]+", "0x0", text)


@pytest.mark.parametrize("latent", [False, True], ids=["gqa", "latent"])
def test_a_call_without_a_mask_is_the_program_it_was(latent):
    text = _jaxpr(latent)
    assert hashlib.sha256(text.encode()).hexdigest() == NO_MASK_JAXPR[latent]
    masked = _jaxpr(latent, masked=True)
    assert masked != text and "shift_left" in masked \
        and "shift_left" not in text


def test_the_rule_at_below_and_above_the_constant(monkeypatch):
    """One function of one module constant, asked by the step program (a
    traced scalar) and by the engine's counter (a number): the same object
    in both modules."""
    from deepspeed_tpu.inference.v2 import engine_v2, model as v2model
    assert engine_v2.masked_prefill is si.masked_prefill
    assert "masked_prefill(reach)" in "".join(
        open(v2model.__file__).read().split())
    at = si.MASKED_REACH
    assert at == 32768
    asked = jax.jit(si.masked_prefill)
    for reach, want in ((0, True), (at - 1, True), (at, True),
                        (at + 1, False), (65536, False)):
        assert bool(si.masked_prefill(reach)) is want
        assert bool(asked(jnp.int32(reach))) is want
    monkeypatch.setattr(si, "MASKED_REACH", 0)
    assert not si.masked_prefill(1) and si.masked_prefill(0)
