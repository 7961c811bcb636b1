"""Learned key selection over paged index keys (a "lightning indexer"), and
attention over the rows it selects.

A layer with an indexer keeps, beside its page pool, ONE small index key a
token (``dI`` values) in pages of the same geometry, addressed by the same
block table.  A query row ``t`` carries ``nI`` index queries and as many
weights, and scores every key ``s <= t`` of its sequence::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)

It then attends over the ``k`` keys of largest ``I[t, .]`` only (all of them
while ``t < k``).  The selection is exact: the set ``lax.top_k`` gives over
the float32 scores, ties to the lower position.  Five ops, each registered
(``ops/registry.py``) and so in the dispatch log:

- ``index_scores``: ``[N, C]`` scores of a step's rows over their slots'
  pages (``C = table width x page``), ``-inf`` where the key is not the
  row's to see.  The kernel (``_score_kernel``) takes one block of rows of
  one slot against that slot's gathered keys and keeps the ``[rows, nI,
  keys]`` products in VMEM: in XLA they are an HBM array ``nI`` times the
  result's size.  It computes the block TRANSPOSED, keys on sublanes and
  rows on lanes, so that a head's weights are a sublane slice.
- ``index_select``: the ``k`` best positions a row, as rows of the pool.
- ``selected_attention``: softmax attention of each row over ITS list of pool
  rows (latent MQA form: one row a token, key its whole width and value its
  leading ``v_dim`` columns), rows gathered by index a block of query rows
  at a time.  XLA on every backend: a gather of 1.3 KB rows and two batched
  matmuls a block (PERF.md section 6, PR 36, has the chip's readings).
- ``selection_mask``: a sorted list as bits over the positions of a row's
  sequence, which ``ragged_prefill_attention`` takes as ``sel_mask``
  (``ops/paged_attention.py``): that kernel then reads a slot's pages ONCE
  for all the slot's rows where the gather reads ``k`` rows a query row.
- ``threshold_mask``: those bits from the scores alone, with no list and no
  sort: a row keeps what is at or above its ``k``-th largest score, found by
  an exact search over the scores' bits (32 counting passes; the kernel,
  ``_threshold_kernel``, holds a block of rows' scores in VMEM for all of
  them), and of the scores equal to it the lowest positions, as the sort
  would.  Bit for bit ``selection_mask(scores, index_select(scores, k))``.

Which rows take which (``inference/v2/model.py:_selected_attention``): a row
alone in its slot (every row of a decode step, the riders of a mixed step)
gathers, one row attending ``k`` rows being the gather's own case, and a
gather needs the list: ``index_select``.  A prompt chunk's rows take the
masked kernel on ``threshold_mask``'s bits while ``masked_prefill(reach)``
holds, ``reach`` the longest context among them, and past it sort their lists
and gather: the gather costs a chunk the same at any context and its sort up
to as much again, the kernel what the context holds.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30          # a masked attention score: finite, so an empty row's
#                      softmax is uniform and not NaN (its output is dropped)


def _slot_keys(k_pages, block_table):
    """Every slot's index keys, gathered: [S, C, dI]."""
    S, MB = block_table.shape
    bs, d = k_pages.shape[-2:]
    return k_pages[block_table].reshape(S, MB * bs, d)


def _mask_scores(scores, row_slot, row_pos, S):
    C = scores.shape[-1]
    seen = (jnp.arange(C, dtype=jnp.int32)[None, :] <= row_pos[:, None]) \
        & (row_slot < S)[:, None]
    return jnp.where(seen, scores, -jnp.inf)


def _one_row_scores(q, w, keys):
    """``q [S, nI, dI]``, ``w [S, nI]`` against ``keys [S, C, dI]``, a row a
    slot -> [S, C] float32."""
    x = jnp.einsum("sjd,scd->sjc", q, keys,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("sjc,sj->sc", jax.nn.relu(x), w.astype(jnp.float32))


def xla_index_scores(q, w, k_pages, block_table, row_slot, row_pos, *,
                     max_rows: int = 0, interpret=None):
    """Every row against its slot's gathered keys: ``[N, C, dI]`` of them,
    which is what the small sizes of a CPU run can afford."""
    del max_rows, interpret
    S = block_table.shape[0]
    keys = _slot_keys(k_pages, block_table)[jnp.minimum(row_slot, S - 1)]
    return _mask_scores(_one_row_scores(q, w, keys), row_slot, row_pos, S)


def _score_kernel(q_ref, w_ref, k_ref, o_ref):
    """One block of keys against one block of rows, transposed:
    ``o[s, t] = sum_j w[j, t] * relu(k[s] . q[j, t])``."""
    nI = q_ref.shape[0]
    step = 4 if nI % 4 == 0 else 1           # heads unrolled by hand

    def heads(i, acc):
        for j in range(step):
            x = jax.lax.dot_general(k_ref[...], q_ref[i * step + j],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(x, 0.0) * w_ref[pl.ds(i * step + j, 1), :]
        return acc
    o_ref[...] = jax.lax.fori_loop(
        0, nI // step, heads, jnp.zeros(o_ref.shape, jnp.float32))


SCORE_ROWS = 128      # rows of one slot a kernel call
SCORE_KEYS = 512      # keys a grid step


@functools.partial(jax.jit, static_argnames=("interpret",))
def _score_block(q, w, keys, *, interpret=False):
    """``q [nI, TQ, dI]``, ``w [nI, TQ]`` f32, ``keys [C, dI]`` ->
    ``[C, TQ]`` float32 (the block of scores, transposed)."""
    nI, TQ, d = q.shape
    C = keys.shape[0]
    tk = min(SCORE_KEYS, C)
    return pl.pallas_call(
        _score_kernel,
        grid=(C // tk,),
        in_specs=[pl.BlockSpec((nI, TQ, d), lambda i: (0, 0, 0)),
                  pl.BlockSpec((nI, TQ), lambda i: (0, 0)),
                  pl.BlockSpec((tk, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tk, TQ), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((C, TQ), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=48 << 20),
        interpret=interpret, name="index_score_kernel")(q, w, keys)


def pallas_index_scores(q, w, k_pages, block_table, row_slot, row_pos, *,
                        max_rows: int = 0, interpret=None):
    """Slots with one row (every slot of a decode step) take the batched
    product over their gathered keys; a slot with more is walked in blocks
    of ``SCORE_ROWS`` of its rows (one contiguous span of the step's rows,
    ragged.py), each block one kernel call over that slot's keys.  The walk
    is a loop over the LIVE blocks only."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N, nI, d = q.shape
    S, MB = block_table.shape
    bs = k_pages.shape[-2]
    C = MB * bs
    TQ = SCORE_ROWS
    w = w.astype(jnp.float32)
    scat = jnp.where(row_slot < S, row_slot, S)
    ar = jnp.arange(N, dtype=jnp.int32)
    counts = jnp.zeros((S,), jnp.int32).at[scat].add(1, mode="drop")
    first = jnp.full((S,), N - 1, jnp.int32).at[scat].min(ar, mode="drop")
    one = _one_row_scores(q[first], w[first], _slot_keys(k_pages,
                                                         block_table))
    slot = jnp.minimum(row_slot, S - 1)
    if max_rows == 1 or C % min(SCORE_KEYS, C):
        return _mask_scores(one[slot], row_slot, row_pos, S)
    # ---- the blocks of the slots that hold more than one row
    blocks = jnp.where(counts > 1, -(-counts // TQ), 0)
    ends = jnp.cumsum(blocks)
    qp = jnp.pad(q, ((0, TQ), (0, 0), (0, 0)))
    wp = jnp.pad(w, ((0, TQ), (0, 0)))

    def body(i, out):
        s = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
        s = jnp.minimum(s, S - 1)
        j = i - (ends[s] - blocks[s])
        start = first[s] + j * TQ
        live = jnp.clip(counts[s] - j * TQ, 0, TQ)
        qb = jax.lax.dynamic_slice_in_dim(qp, start, TQ)        # [TQ,nI,d]
        wb = jax.lax.dynamic_slice_in_dim(wp, start, TQ)        # [TQ,nI]
        keys = k_pages[block_table[s]].reshape(C, d)
        blk = _score_block(qb.transpose(1, 0, 2), wb.T, keys,
                           interpret=interpret).T               # [TQ, C]
        old = jax.lax.dynamic_slice_in_dim(out, start, TQ)
        keep = (jnp.arange(TQ) < live)[:, None]
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(keep, blk, old), start, 0)

    out = jnp.pad(one[slot], ((0, TQ), (0, 0)))
    out = jax.lax.fori_loop(0, ends[-1], body, out)[:N]
    return _mask_scores(out, row_slot, row_pos, S)


def index_scores_supported(q, w, k_pages, block_table, row_slot, row_pos, *,
                           max_rows: int = 0, interpret=None) -> bool:
    return (q.shape[-1] % 128 == 0 and q.shape[1] % 8 == 0
            and k_pages.shape[-2] % 8 == 0)


def index_scores(q, w, k_pages, block_table, row_slot, row_pos, *,
                 max_rows: int = 0, impl: Optional[str] = None,
                 interpret: Optional[bool] = None):
    """Registry entry: index queries ``q [N, nI, dI]`` with weights ``w [N,
    nI]`` over the index key pages ``k_pages [pages, 1, bs, dI]`` that
    ``block_table [S, MB]`` names -> ``[N, MB * bs]`` float32; row ``n`` is
    slot ``row_slot[n]``'s (``S`` or more: a pad, all ``-inf``) at position
    ``row_pos[n]`` and sees keys ``0 .. row_pos[n]``.  ``max_rows``: the
    most rows a slot can hold (1: a decode step)."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("index_scores", q, w, k_pages, block_table, row_slot,
                    row_pos, max_rows=max_rows, impl=impl,
                    interpret=interpret)


def _at_width(fn, scores, k: int, width):
    """``fn(scores[:, :w])`` at the narrowest power-of-two share ``w`` of the
    columns that holds ``width`` (and ``k``): one branch a width, taken at
    run time."""
    C = scores.shape[-1]
    if width is None or C <= k:
        return fn(scores)
    widths = [C]
    while widths[-1] % 2 == 0 and widths[-1] // 2 >= k:
        widths.append(widths[-1] // 2)
    fits = jnp.asarray(widths, jnp.int32) >= width
    which = jnp.maximum(jnp.sum(fits) - 1, 0)
    return jax.lax.switch(
        which, [(lambda s, w=w: fn(s[:, :w])) for w in widths], scores)


def xla_index_select(scores, k: int, width=None):
    with jax.named_scope("index_select"):
        # the sort is what costs (a row of 32 k scores ten times a row of
        # 8 k)
        return _at_width(
            lambda s: jax.lax.top_k(s, k)[1].astype(jnp.int32), scores, k,
            width)


def index_select(scores, k: int, *, width=None, impl: Optional[str] = None):
    """Registry entry: the positions of the ``k`` largest of each row of
    ``scores [N, C]``, best first, ties to the lower position -> ``[N, k]``
    int32.  Exact.  A row with fewer than ``k`` finite scores lists those
    first and masked positions after them.  ``width`` (a traced scalar):
    no score at or past that column is finite, so only the narrowest
    power-of-two share of the columns that holds ``width`` is sorted."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("index_select", scores, k, width=width, impl=impl)


def _pack_rows(keep):
    """``keep [N, C]`` bool -> ``[ceil(N / 32), C]`` int32, bit ``n % 32`` of
    word ``[n // 32, c]`` row ``n``'s."""
    N, C = keep.shape
    groups = -(-N // 32)
    keep = jnp.pad(keep, ((0, groups * 32 - N), (0, 0)))
    bits = jnp.left_shift(jnp.int32(1), jnp.arange(32, dtype=jnp.int32))
    return jax.lax.reduce(
        jnp.where(keep.reshape(groups, 32, C), bits[None, :, None], 0),
        jnp.int32(0), jax.lax.bitwise_or, (1,))


def _kept(scores, least, last, col):
    """What a row keeps, given its last pick's score and position: every
    score above that one and its ties up to that position (``index_select``:
    best first, ties to the lower position).  A row with fewer keys in sight
    than picks keeps them all: its last pick is a masked position,
    ``-inf``."""
    return (scores > least) | ((scores == least) & (col <= last)
                               & (scores > -jnp.inf))


def xla_selection_mask(scores, picked):
    with jax.named_scope("selection_mask"):
        last = picked[:, -1:]                                   # [N, 1]
        least = jnp.take_along_axis(scores, last, axis=1)
        col = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
        return _pack_rows(_kept(scores, least, last, col))


def selection_mask(scores, picked, *, impl: Optional[str] = None):
    """Registry entry: ``picked [N, k]`` (``index_select`` of ``scores [N,
    C]``, the same array) as bits -> ``[ceil(N / 32), C]`` int32: bit ``n %
    32`` of word ``[n // 32, c]`` is set where row ``n`` keeps position
    ``c``.  No scatter: a pick is a score no lower than the row's last
    pick's, so the mask is two compares a score (a pass over the scores, a
    thirty-second of their size written)."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("selection_mask", scores, picked, impl=impl)


# ---- the same bits without the list: a row's k-th largest score by a search
# over the scores' bits.  ``lax.top_k`` orders float32 as the int32 below does
# (a total order: -0.0 under 0.0), ties to the lower position; so a row's last
# pick is the k-th largest KEY, and of the keys equal to it the one at the
# ``k - #(key above)``-th lowest position.  Both are found exactly by counting:
# the key a bit a pass from the top (32 counts of the keys at or above a
# candidate), the position the same way over the ties' columns, which only a
# step with a row that does NOT take all its ties pays.  ``_kept`` then decides
# in float32, as ``selection_mask`` does (-0.0 and 0.0 equal there).
_INT_MIN = -2 ** 31


def _flip(bits):
    """A float32's bits <-> an int32 that sorts as the float does (its own
    inverse)."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _sort_key(scores):
    return _flip(jax.lax.bitcast_convert_type(scores, jnp.int32))


def _key_score(key):
    return jax.lax.bitcast_convert_type(_flip(key), jnp.float32)


def _last_pick(count, top, k: int, position_bits: int, shape):
    """The key and the position of each row's ``k``-th pick, from
    ``count(pred) -> [rows, 1]`` (the row's keys ``x`` at columns ``col``
    for which ``pred(x, col)`` holds) and ``top(t)`` (the highest column
    whose key is ``t``): shared by the XLA form and the kernel."""
    one = jnp.int32(1)

    def key_bit(i, t):
        cand = t ^ jnp.left_shift(one, 31 - i)
        return jnp.where(count(lambda x, col: x >= cand) >= k, cand, t)
    t = jax.lax.fori_loop(0, 32, key_bit,
                          jnp.full(shape, _INT_MIN, jnp.int32))
    need = k - count(lambda x, col: x > t)
    ties = count(lambda x, col: x == t)

    def search():
        def position_bit(i, q):
            cand = q | jnp.left_shift(one, position_bits - 1 - i)
            return jnp.where(
                count(lambda x, col: (x == t) & (col < cand)) < need, cand, q)
        return jax.lax.fori_loop(0, position_bits, position_bit,
                                 jnp.zeros(shape, jnp.int32))
    last = jax.lax.cond(jnp.max(ties - need) > 0, search, lambda: top(t))
    return t, last


def xla_threshold_mask(scores, k: int, width=None, *, interpret=None):
    del interpret

    def words(s):
        w = s.shape[1]
        key = _sort_key(s)
        col = jnp.arange(w, dtype=jnp.int32)[None, :]
        t, last = _last_pick(
            lambda pred: jnp.sum(pred(key, col), axis=1, keepdims=True,
                                 dtype=jnp.int32),
            lambda t: jnp.max(jnp.where(key == t, col, -1), axis=1,
                              keepdims=True),
            k, max(1, (w - 1).bit_length()), (s.shape[0], 1))
        out = _pack_rows(_kept(s, _key_score(t), last, col))
        return jnp.pad(out, ((0, 0), (0, scores.shape[1] - w)))
    with jax.named_scope("threshold_mask"):
        return _at_width(words, scores, k, width)


THRESHOLD_ROWS = 64    # rows a grid step: their scores stay in VMEM for all
#                        the passes (8 MB at 32 k columns, and as much of keys)
LANES = 128


def _threshold_kernel(width_ref, s_ref, o_ref, key_ref, *, k: int, n_rows: int,
                      cols: int):
    R, C = s_ref.shape
    # (at least ``k`` columns, as the sort's narrowest width: a row that sees
    # fewer keys finds ``-inf`` for its threshold, and keeps what is finite)
    steps = jnp.minimum(-(-jnp.maximum(width_ref[0], k) // cols), C // cols)
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 1)

    def walk(fn, init):
        """``fn(column offset, carry)`` over the 128-column slices below the
        width."""
        def chunk(j, acc):
            for c in range(cols // LANES):
                acc = fn(pl.multiple_of(j * cols + c * LANES, LANES), acc)
            return acc
        return jax.lax.fori_loop(0, steps, chunk, init)

    def to_key(off, _):
        key_ref[:, pl.ds(off, LANES)] = _sort_key(s_ref[:, pl.ds(off, LANES)])
        return 0
    walk(to_key, 0)

    def wide(x):
        return jnp.broadcast_to(x, (R, LANES))

    # (a row's numbers are kept across its lanes: nothing is broadcast
    # inside a pass)
    def count(pred):
        return wide(jnp.sum(walk(
            lambda off, acc: acc + pred(
                key_ref[:, pl.ds(off, LANES)], off + lane).astype(jnp.int32),
            jnp.zeros((R, LANES), jnp.int32)), axis=1, keepdims=True))

    def top(t):
        return wide(jnp.max(walk(
            lambda off, acc: jnp.maximum(acc, jnp.where(
                key_ref[:, pl.ds(off, LANES)] == t, off + lane, -1)),
            jnp.full((R, LANES), -1, jnp.int32)), axis=1, keepdims=True))

    t, last = _last_pick(count, top, k, max(1, (C - 1).bit_length()),
                         (R, LANES))
    least = _key_score(t)
    row = pl.program_id(0) * R + jax.lax.broadcasted_iota(
        jnp.int32, (R, LANES), 0)
    bit = jnp.where(row < n_rows, jnp.left_shift(jnp.int32(1), row % 32), 0)

    def pack(off, _):
        s = s_ref[:, pl.ds(off, LANES)]
        mine = jnp.where(_kept(s, least, last, off + lane), bit, 0)
        for g in range(R // 32):            # bits of 32 rows never collide
            o_ref[pl.ds(g, 1), pl.ds(off, LANES)] = jnp.sum(
                mine[g * 32:(g + 1) * 32], axis=0, keepdims=True)
        return 0
    walk(pack, 0)

    def clear(j, _):
        o_ref[:, pl.ds(pl.multiple_of(j * cols, cols), cols)] = jnp.zeros(
            (R // 32, cols), jnp.int32)
        return 0
    jax.lax.fori_loop(steps, C // cols, clear, 0)


def pallas_threshold_mask(scores, k: int, width=None, *, interpret=None):
    """A block of ``THRESHOLD_ROWS`` rows a grid step, read from HBM once and
    kept in VMEM as keys for the passes; only the columns below ``width``
    (and ``k``, to the loop step's multiple) are walked."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N, C = scores.shape
    R = THRESHOLD_ROWS
    blocks = -(-N // R)
    cols = math.gcd(C, 512)
    width = jnp.full((1,), C if width is None else width, jnp.int32)
    with jax.named_scope("threshold_mask"):
        out = pl.pallas_call(
            functools.partial(_threshold_kernel, k=k, n_rows=N, cols=cols),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(blocks,),
                in_specs=[pl.BlockSpec((R, C), lambda i, w: (i, 0))],
                out_specs=pl.BlockSpec((None, R // 32, C),
                                       lambda i, w: (i, 0, 0)),
                scratch_shapes=[pltpu.VMEM((R, C), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((blocks, R // 32, C), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=48 << 20),
            interpret=interpret, name="threshold_mask_kernel")(width, scores)
        return out.reshape(blocks * (R // 32), C)[:-(-N // 32)]


def threshold_mask_supported(scores, k: int, width=None, *,
                             interpret=None) -> bool:
    return scores.dtype == jnp.float32 and scores.shape[1] % LANES == 0


def threshold_mask(scores, k: int, *, width=None, impl: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """Registry entry: ``selection_mask(scores, index_select(scores, k,
    width=width))``, bit for bit, without the list: ``scores [N, C]`` float32
    -> ``[ceil(N / 32), C]`` int32.  A row that sees fewer than ``k`` keys
    keeps every finite score, a row of no slot (all ``-inf``) nothing."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("threshold_mask", scores, k, width, impl=impl,
                    interpret=interpret)


# A prompt chunk's rows take the masked prefill kernel while the longest
# context among them is at most this many tokens, and the row gather past it.
# Fitted on one v5e chip at dots3-note-prev's full layer, a chunk of 1,024
# rows over a table of 32,768 tokens (``scripts/selected_prefill_paths.py``;
# PERF.md section 6, PR 54, step 0, ``chiprun_out/pr54/step0_a.out``): the
# gather costs a chunk 43 ms at any context AND the sort of its list (36 ms
# past 16 k), the kernel what the context holds (58.8 ms at the table's width)
# and 2 ms of ``threshold_mask``: 78.6 against 60.6 ms at 32,768, so they do
# not cross below that table's width, and the constant is that width, as far
# as the run walked.  (Until PR 54 both paths paid the sort, and the kernel
# crossed the gather alone at ~22.5 k: 20,480.)
MASKED_REACH = 32768


def masked_prefill(reach):
    """Whether the rows that share their slots take the masked prefill
    kernel: ``reach``, the furthest context among them after the step, in
    tokens (a traced scalar in the step program, a number on the host: the
    engine's counter asks this same function)."""
    return reach <= MASKED_REACH


ATTEND_ROWS = 64      # query rows whose lists are gathered at a time


def xla_selected_attention(q, pages, rows, counts, *, v_dim: int,
                           scale: float):
    with jax.named_scope("selected_attention"):
        return _selected_attention(q, pages, rows, counts, v_dim, scale)


def _selected_attention(q, pages, rows, counts, v_dim, scale):
    N, nh, P = q.shape
    k = rows.shape[1]
    pool = pages.reshape(-1, pages.shape[-1])
    rb = min(ATTEND_ROWS, N)
    nb = -(-N // rb)
    pad = nb * rb - N
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, rb, nh, P)
    ib = jnp.pad(rows, ((0, pad), (0, 0))).reshape(nb, rb, k)
    cb = jnp.pad(counts, ((0, pad),)).reshape(nb, rb)

    def block(args):
        qi, ii, ci = args
        kv = pool[ii]                                       # [rb, k, P]
        s = jnp.einsum("tnp,tkp->tnk", qi, kv,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(jnp.arange(k)[None, None, :] < ci[:, None, None], s,
                      NEG)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("tnk,tkv->tnv", p, kv[..., :v_dim],
                          preferred_element_type=jnp.float32).astype(q.dtype)

    out = block((qb[0], ib[0], cb[0]))[None] if nb == 1 else \
        jax.lax.map(block, (qb, ib, cb))
    return out.reshape(nb * rb, nh, v_dim)[:N]


def selected_attention(q, pages, rows, counts, *, v_dim: int, scale: float,
                     impl: Optional[str] = None):
    """Registry entry: ``q [N, nh, P]`` over latent pages ``[pages, 1, bs,
    P]``; row ``n`` attends over the pool rows ``rows[n, :counts[n]]``
    (``page * bs + offset``), key the row's whole width and value its
    leading ``v_dim`` columns -> ``[N, nh, v_dim]``."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("selected_attention", q, pages, rows, counts, v_dim=v_dim,
                    scale=scale, impl=impl)
