"""Selection of keys by BLOCKS from pooled keys (InfLLM-V2, MiniCPM4's and
MiniCPM-SALA's sparse attention layers), and attention over the kept blocks.

A layer that selects by blocks keeps, beside its keys, one POOLED key for
every ``stride`` positions: ``kbar_j = mean(k[stride j : stride j + kernel])``
a KV head, complete spans only, which a row at position ``t`` may see once its
last key is behind it (``stride j + kernel - 1 <= t``).  A row whose context
(``t + 1``) is at most ``dense_len`` attends to every earlier key.  Past it,
each KV head ``n`` of the row chooses for all its ``g`` query heads alike::

    p_h[t, j] = softmax_j(q_h[t] . kbar_j * scale)       over the visible j,
                                                          float32, exact
    P[t, n, j] = sum of p_h over the g heads of n
    score[t, n, b] = max P[t, n, j] over the pooled keys whose span overlaps
                     block b (keys block b .. block b + block - 1)

and keeps block 0 .. ``init - 1``, the ``window / block`` blocks that end at
the row's own block, and the best-scoring others up to ``topk`` blocks in all,
ties to the lower block.  Attention is then a softmax over the keys ``s <= t``
of the kept blocks.

The forced blocks enter the choice as scores above any real one (``FORCED``)
and blocks a row cannot see as ``-inf``, so that the choice is ONE exact
top-``topk`` over a row's block scores: ``ops.threshold_mask`` (bits, for the
rows of a prompt chunk, which read their keys through the masked prefill
kernel) or ``ops.index_select`` (a list, for a one-row slot, which gathers its
kept blocks), both of ``ops/sparse_index.py`` and both exact with ties to the
lower position, as ``kept_blocks`` below is for the dense forms.

The scores and the marks are XLA on every backend (``block_scores`` is
registered, ``ops/registry.py``, and so in the dispatch log); the attention
itself runs through the two paged kernels (``ops/paged_attention.py``): a
prompt chunk through the prefill kernel's masked form on the choice's bits a
KV head, a one-row slot through the decode kernel over a view of the pool
whose pages are single blocks of a single kv head (``block_pages``,
``kept_block_table``): PERF.md section 6, PR 57, has the chip's readings that
decided it.  The sizes
(``kernel`` a multiple of ``stride``, ``block`` a multiple of ``stride``) are
``BlockGeometry``'s, checked once.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
FORCED = float(jnp.finfo(jnp.float32).max)   # a forced block's score
NEG = -1e30          # a masked attention score (``ops/sparse_index.py``)


class BlockGeometry(NamedTuple):
    """The sizes of a selection by blocks (``GPTConfig.block_geometry``)."""
    kernel: int       # keys a pooled key averages
    stride: int       # positions between two pooled keys
    block: int        # keys a block
    topk: int         # blocks a row keeps in all, the forced ones counted
    window: int       # keys of the local run (whole blocks)
    init: int         # leading blocks always kept
    dense_len: int    # contexts up to this read every key

    def check(self):
        if (self.kernel % self.stride or self.block % self.stride
                or self.window % self.block or min(self) <= 0):
            raise ValueError(
                f"a selection by blocks needs kernel and block whole "
                f"multiples of stride and window of block, got {self}")
        if self.init + self.window // self.block > self.topk:
            raise ValueError(
                f"the forced blocks ({self.init} + {self.window} / "
                f"{self.block}) outnumber topk {self.topk}")
        if self.dense_len < self.topk * self.block:
            raise ValueError(
                f"dense_len {self.dense_len} is under topk x block = "
                f"{self.topk * self.block}: a selecting row would see fewer "
                f"blocks than it keeps")
        return self

    @property
    def per_block(self) -> int:
        """Pooled keys that START inside one block."""
        return self.block // self.stride

    @property
    def extra(self) -> int:
        """Pooled keys that start before a block and still overlap it."""
        return self.kernel // self.stride - 1


def pooled_keys(k, geo: BlockGeometry):
    """``k [..., S, nkv, d]`` -> ``[..., S // stride, nkv, d]`` in ``k``'s
    type: pooled key ``j`` is the float32 mean of keys ``stride j .. stride j
    + kernel - 1``.  The trailing ones whose span runs past ``S`` hold
    whatever the zeros behind ``S`` make of them: no row ever sees them."""
    S = k.shape[-3]
    m = geo.kernel // geo.stride
    pad = -S % geo.stride + (m - 1) * geo.stride
    kf = jnp.pad(k.astype(F32), [(0, 0)] * (k.ndim - 3)
                 + [(0, pad), (0, 0), (0, 0)])
    lead = kf.shape[:-3]
    sums = kf.reshape(lead + (-1, geo.stride) + kf.shape[-2:]).sum(-3)
    J = S // geo.stride
    out = sum(jax.lax.slice_in_dim(sums, i, i + J, axis=sums.ndim - 3)
              for i in range(m))
    return (out / geo.kernel).astype(k.dtype)


def xla_block_scores(q, kbar, pos, *, geo: BlockGeometry, scale: float):
    """Block scores of rows ``q [R, nkv, g, d]`` at positions ``pos [R]``
    over pooled keys ``kbar [R | 1, J, nkv, d]`` (``J`` a whole number of
    blocks' pooled keys) -> ``[R, nkv, J // per_block]`` float32: ``max P``
    over the pooled keys that overlap each block (module docstring), 0 where
    a block has no visible pooled key."""
    J = kbar.shape[1]
    if kbar.shape[0] == 1:            # the rows share one sequence's keys
        logits = jnp.einsum("rngd,jnd->rngj", q, kbar[0],
                            preferred_element_type=F32) * scale
    else:
        logits = jnp.einsum("rngd,rjnd->rngj", q, kbar,
                            preferred_element_type=F32) * scale
    seen = (geo.stride * jnp.arange(J, dtype=jnp.int32) + geo.kernel - 1
            <= pos[:, None])[:, None, None, :]
    logits = jnp.where(seen, logits, -jnp.inf)
    top = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(seen, jnp.exp(logits - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    P = jnp.sum(e / jnp.where(total > 0, total, 1.0), axis=2)   # [R, nkv, J]
    r, x = geo.per_block, geo.extra
    nb = J // r
    Q = jnp.pad(P, ((0, 0), (0, 0), (x, r)))     # Q[j + x] = P[j]
    # block b's pooled keys: Q[r b .. r b + r + x - 1]
    best = jnp.max(Q[..., :r * nb].reshape(P.shape[:2] + (nb, r)), axis=-1)
    for i in range(x):
        best = jnp.maximum(best, Q[..., r + i::r][..., :nb])
    return best


def block_scores(q, kbar, pos, *, geo: BlockGeometry, scale: float,
                 impl: Optional[str] = None):
    """Registry entry of ``xla_block_scores``."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("block_scores", q, kbar, pos, geo=geo, scale=scale,
                    impl=impl)


def mark_blocks(scores, pos, geo: BlockGeometry):
    """``scores [R, nkv, NB]`` of rows at ``pos [R]`` as the choice takes
    them: ``FORCED`` on the blocks a row always keeps (the leading ``init``
    and the local run that ends at its own block), ``-inf`` on the blocks
    that begin behind the row."""
    b = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    own = (pos // geo.block)[:, None, None]
    forced = (b < geo.init) | (b > own - geo.window // geo.block)
    return jnp.where(b > own, -jnp.inf,
                     jnp.where(forced, FORCED, scores))


def selects(pos, geo: BlockGeometry):
    """Whether a row at ``pos`` selects: its context is past ``dense_len``."""
    return pos + 1 > geo.dense_len


def kept_blocks(marked, k: int):
    """``marked [..., NB]`` (``mark_blocks``) -> bool, the ``k`` best of each
    row, ties to the lower block; a row that sees fewer keeps what it
    sees."""
    from deepspeed_tpu.ops.sparse_index import _kept
    nb = marked.shape[-1]
    flat = marked.reshape(-1, nb)
    k = min(k, nb)
    vals, idx = jax.lax.top_k(flat, k)
    col = jnp.arange(nb, dtype=jnp.int32)[None, :]
    return _kept(flat, vals[:, -1:], idx[:, -1:].astype(jnp.int32),
                 col).reshape(marked.shape)


def dense_key_mask(q, k, positions, *, geo: BlockGeometry, scale: float):
    """The whole rule on one dense sequence a batch row, for the flax model
    and the tests: ``q [B, T, nkv, g, d]``, ``k [B, S, nkv, d]`` at
    ``positions [B, T]`` (keys at ``0 .. S - 1``) -> bool ``[B, nkv, T, S]``:
    the keys each row's KV head attends to."""
    B, T = positions.shape
    S = k.shape[1]
    pad = -S % geo.block
    kbar = pooled_keys(jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))), geo)
    key_pos = jnp.arange(S, dtype=jnp.int32)

    def one(qb, kb, pb):
        marked = mark_blocks(
            xla_block_scores(qb, kb[None], pb, geo=geo, scale=scale), pb, geo)
        keep = kept_blocks(marked, geo.topk)              # [T, nkv, NB]
        keep = jnp.repeat(keep, geo.block, axis=-1)[..., :S]
        keep = keep | ~selects(pb, geo)[:, None, None]
        return jnp.moveaxis(keep & (key_pos <= pb[:, None, None]), 0, 1)
    return jax.vmap(one)(q, kbar, positions)


def masked_attention(q, k, v, mask, scale: float):
    """Softmax attention under a mask a KV head: ``q [B, T, nkv, g, d]``,
    ``k``/``v [B, S, nkv, d]``, ``mask [B, nkv, T, S]`` -> ``[B, T, nkv, g,
    d]``; scores float32."""
    s = jnp.einsum("btngd,bsnd->bngts", q, k,
                   preferred_element_type=F32) * scale
    m = mask[:, :, None]
    s = jnp.where(m, s, jnp.finfo(F32).min)
    p = jnp.where(m.any(-1, keepdims=True), jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("bngts,bsnd->btngd", p.astype(v.dtype), v)


# ------------------------------------------------------- over the paged pool
# What the serving step programs (inference/v2/model.py) take.  A page holds
# whole blocks; the pooled keys lie in an array of their own beside the page
# pool, ``[pages, block_size / stride, nkv, d]``, page for page under the same
# block table: pooled key ``j`` is at ``(table[j // per_page], j % per_page)``,
# the page its span BEGINS in.  Every gather and scatter here takes ROWS of a
# two-dimensional view of a pool as it lies (a free reshape): asked for any
# other way the compiler re-lays the whole pool out around the gather, a copy
# of every layer's pages in every step (tests/test_chip_compile.py).

def completed_pooled_keys(k_pages, table_rows, pos, geo: BlockGeometry):
    """The pooled key that a row at ``pos [R]`` completes (the one whose
    last key it is), from keys already in the pages: ``k_pages [pages, nkv,
    bs, d]``, ``table_rows [R, MB]`` each row's slot's block table (the
    layer's first page added) -> (``[R, nkv, d]`` in the pages' type, its
    number ``j [R]``, whether the row completes one at all)."""
    pages, nkv, bs, d = k_pages.shape
    done = (pos >= geo.kernel - 1) & ((pos - (geo.kernel - 1)) % geo.stride
                                      == 0)
    j = jnp.maximum(pos - (geo.kernel - 1), 0) // geo.stride
    at = jnp.maximum(pos[:, None] - jnp.arange(
        geo.kernel - 1, -1, -1, dtype=jnp.int32), 0)          # [R, kernel]
    page = jnp.take_along_axis(table_rows, at // bs, axis=1)
    row = ((page[..., None] * nkv + jnp.arange(nkv, dtype=jnp.int32)) * bs
           + (at % bs)[..., None])                        # [R, kernel, nkv]
    keys = k_pages.reshape(pages * nkv * bs, d)[row]
    return (jnp.mean(keys.astype(F32), axis=1).astype(k_pages.dtype), j,
            done)


def write_pooled_keys(kp_pages, new, j, live, table_rows):
    """``new [R, nkv, d]`` into the pooled-key pool ``kp_pages [pages,
    per_page, nkv, d]`` at number ``j [R]`` of each row's slot, where
    ``live``."""
    pages, per_page = kp_pages.shape[:2]
    page = jnp.take_along_axis(table_rows, (j // per_page)[:, None],
                               axis=1)[:, 0]
    row = jnp.where(live, page * per_page + j % per_page,
                    pages * per_page)                          # dropped
    return kp_pages.reshape((pages * per_page,) + kp_pages.shape[2:]).at[
        row].set(new, mode="drop").reshape(kp_pages.shape)


def slot_pooled_keys(kp_pages, table):
    """Every slot's pooled keys gathered: ``table [S, MB]`` -> ``[S, MB *
    per_page, nkv, d]``."""
    S, MB = table.shape
    got = kp_pages[table]                       # [S, MB, per_page, nkv, d]
    return got.reshape((S, MB * got.shape[2]) + got.shape[3:])


def block_pages(pool, geo: BlockGeometry):
    """A page pool ``[pages, nkv, bs, d]`` as pages of ONE block of ONE kv
    head: ``[pages * nkv * (bs / block), 1, block, d]``, a free view (the two
    minor dims stand, the page's positions split into whole blocks)."""
    pages, nkv, bs, d = pool.shape
    return pool.reshape(pages * nkv * (bs // geo.block), 1, geo.block, d)


def kept_block_table(table, blocks, pos, live, bs: int, geo: BlockGeometry):
    """The kept blocks of one row a slot as the paged decode kernel takes a
    context: ``blocks [S, nkv, topk]`` (``topk`` blocks at or before the
    row's own, which is among them) -> (the block table ``[S * nkv, topk]``
    over ``block_pages``, ascending, so that the row's own block is the LAST
    page of a context whose every other page is whole; that context's length
    ``[S * nkv]``, 0 where the slot is not ``live``).  Each kv head of a slot
    is a sequence of its own to the kernel: its 16 query heads one group
    over 64 pages of 64 keys."""
    S, nkv, topk = blocks.shape
    per_page = bs // geo.block
    blocks = jnp.sort(blocks, axis=-1)
    page = jnp.take_along_axis(
        table, (blocks // per_page).reshape(S, -1), axis=1).reshape(
            blocks.shape)
    heads = jnp.arange(nkv, dtype=jnp.int32)[None, :, None]
    rows = (page * nkv + heads) * per_page + blocks % per_page
    length = jnp.where(live, (topk - 1) * geo.block + pos % geo.block + 1, 0)
    return (rows.reshape(S * nkv, topk),
            jnp.repeat(length.astype(jnp.int32), nkv))
