"""Learned key selection over paged index keys (a "lightning indexer"), and
attention over the rows it selects.

A layer with an indexer keeps, beside its page pool, ONE small index key a
token (``dI`` values) in pages of the same geometry, addressed by the same
block table.  A query row ``t`` carries ``nI`` index queries and as many
weights, and scores every key ``s <= t`` of its sequence::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)

It then attends over the ``k`` keys of largest ``I[t, .]`` only (all of them
while ``t < k``).  The selection is exact: ``lax.top_k`` over the float32
scores, ties to the lower position.  Four ops, each registered
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
- ``selection_mask``: the same selection as bits over the positions of a
  row's sequence, which ``ragged_prefill_attention`` takes as ``sel_mask``
  (``ops/paged_attention.py``): that kernel then reads a slot's pages ONCE
  for all the slot's rows where the gather reads ``k`` rows a query row.

Which rows take which (``inference/v2/model.py:_selected_attention``): a row
alone in its slot (every row of a decode step, the riders of a mixed step)
gathers, one row attending ``k`` rows being the gather's own case; a prompt
chunk's rows take the masked kernel while ``masked_prefill(reach)`` holds,
``reach`` the longest context among them, and gather past it: the gather
costs a chunk the same at any context, the kernel what the context holds.
"""

from __future__ import annotations

import functools
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


def xla_index_select(scores, k: int, width=None):
    with jax.named_scope("index_select"):
        C = scores.shape[-1]
        if width is None or C <= k:
            return jax.lax.top_k(scores, k)[1].astype(jnp.int32)
        # the sort is what costs (a row of 32 k scores ten times a row of
        # 8 k): one branch a power-of-two width, taken at run time
        widths = [C]
        while widths[-1] % 2 == 0 and widths[-1] // 2 >= k:
            widths.append(widths[-1] // 2)
        fits = jnp.asarray(widths, jnp.int32) >= width
        which = jnp.maximum(jnp.sum(fits) - 1, 0)
        return jax.lax.switch(which, [
            (lambda s, w=w: jax.lax.top_k(s[:, :w], k)[1].astype(jnp.int32))
            for w in widths], scores)


def index_select(scores, k: int, *, width=None, impl: Optional[str] = None):
    """Registry entry: the positions of the ``k`` largest of each row of
    ``scores [N, C]``, best first, ties to the lower position -> ``[N, k]``
    int32.  Exact.  A row with fewer than ``k`` finite scores lists those
    first and masked positions after them.  ``width`` (a traced scalar):
    no score at or past that column is finite, so only the narrowest
    power-of-two share of the columns that holds ``width`` is sorted."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("index_select", scores, k, width=width, impl=impl)


def xla_selection_mask(scores, picked):
    with jax.named_scope("selection_mask"):
        N, C = scores.shape
        # the last of a row's picks is the least of them and, of the
        # positions that tie with it, the highest one taken (``index_select``:
        # best first, ties to the lower position): so what the row keeps is
        # every score above that one and its ties up to that position.  A
        # row with fewer keys in sight than picks keeps them all: its last
        # pick is a masked position, ``-inf``.
        last = picked[:, -1:]                                   # [N, 1]
        least = jnp.take_along_axis(scores, last, axis=1)
        col = jnp.arange(C, dtype=jnp.int32)[None, :]
        keep = (scores > least) | ((scores == least) & (col <= last)
                                   & (scores > -jnp.inf))
        groups = -(-N // 32)
        keep = jnp.pad(keep, ((0, groups * 32 - N), (0, 0)))
        bits = jnp.left_shift(jnp.int32(1), jnp.arange(32, dtype=jnp.int32))
        return jax.lax.reduce(
            jnp.where(keep.reshape(groups, 32, C), bits[None, :, None], 0),
            jnp.int32(0), jax.lax.bitwise_or, (1,))


def selection_mask(scores, picked, *, impl: Optional[str] = None):
    """Registry entry: ``picked [N, k]`` (``index_select`` of ``scores [N,
    C]``, the same array) as bits -> ``[ceil(N / 32), C]`` int32: bit ``n %
    32`` of word ``[n // 32, c]`` is set where row ``n`` keeps position
    ``c``.  No scatter: a pick is a score no lower than the row's last
    pick's, so the mask is two compares a score (a pass over the scores, a
    thirty-second of their size written)."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("selection_mask", scores, picked, impl=impl)


# A prompt chunk's rows take the masked prefill kernel while the longest
# context among them is at most this many tokens, and the row gather past it.
# Fitted on one v5e chip at dots3-note-prev's full layer, a chunk of 1,024
# rows over a table of 32,768 tokens (PERF.md section 6, PR 43, step 0): the
# gather costs a chunk the same at any context, the kernel what the context
# holds, and they cross between 20 k and 22 k.
MASKED_REACH = 20480


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
