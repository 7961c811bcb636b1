"""Paged attention — Pallas TPU kernels over a block-table KV pool.

TPU-native replacement for the reference's blocked flash kernels
(inference/v2/kernels/ragged_ops/blocked_flash/ + atom_builder): each serving
slot owns a list of fixed-size KV pages; decode attends one query token per
slot over exactly that slot's pages (``paged_decode``, described here), and
the ragged prefill kernel attends the token-major rows of a mixed step, a
work list of live (slot, chunk) items over the flat batch, a block of pages a
loop step (``ragged_prefill``, described above its section, further down).

Kernel design (vs the XLA fallback, which masks over gathered pages):
- grid = (slots,): one grid step attends one slot for EVERY kv head.  A page
  of the row-major pool is contiguous over its kv heads, so ``hbm.at[page]``
  is one [nkv, bs, hd] slab (256 KB at nkv 8, bs 128, hd 128 in bf16) and one
  copy descriptor; the block table rides in scalar prefetch.
- the page pipeline: a loop iteration fetches a BLOCK of P whole pages
  (``_block_pages``: what fits 2 MB of K and V, from static shapes alone;
  4 at the serving cells' geometry, one page of one head being the loop this
  replaced).  All of a block's K and V copies are started before any is
  waited for, and the NEXT block's are started before this block's dots, so
  one to two blocks (2-4 MB) are always in flight.  When a slot's last block
  is reached the next block is the next LIVE slot's first one: the pipeline
  runs across grid steps (two SMEM words carry which buffer half and whether
  it is already under way), so only a call's first block is ever exposed.
  Each page has its own semaphores and is waited for right before its dots.
- the dots are batched over the kv heads ([nkv, g, hd] x [nkv, bs, hd]): nkv
  independent score / online-softmax / PV chains a page, fp32 accumulation,
  and the kernel normalises and writes [S, nkv, g, hd] in q's dtype itself
  (no partials, no XLA combine).
- only live pages are ever copied: a block's tail past ``kv_len`` and the
  pages before a sliding window's first key issue no copy and run no dots, so
  bytes scale with the tokens attended.  A slot with ``kv_len == 0`` costs
  one grid step that writes zeros (a mixed step hands the kernel every slot
  with most lengths zeroed).
- alibi: per-head slope × key-position bias folded into the online softmax
  (slopes [nkv, g, 1] as a VMEM input).
- on one v5e chip (PERF.md section 6, PR 32) the kernel is bound by its
  copies (copies alone take what the whole kernel takes, the dots alone
  about half of it) and reads 83-90% of the HBM roofline in both serving
  cells' decode programs: 0.088 us a (page, kv head) pair of 64 KB, where
  the one-page-one-head loop took 0.40-0.46, and nothing measurable a grid
  step.

Layouts: q [S, nkv, g, hd]; k_pages/v_pages [NB, nkv, bs, hd] (bs = tokens
per page); block_table [S, MB] int32; kv_lens [S] int32 (0 ⇒ inactive slot →
zero output).  Output [S, nkv, g, hd].

The pages are row-major in memory: the kernels DMA ``hbm.at[page]`` (decode)
and ``hbm.at[page, head]`` (prefill) slabs and, being custom calls, get their
operands in no other layout (the compiler copies an operand that some other
op keeps otherwise).  ``NB`` is
whatever the block table indexes: k_pages/v_pages (and the int8 scales) may
be the flat pool of ALL layers, [L * NB, ...], with the layer's first page
``li * NB`` added to the block table.  That is how the serving step programs
call both ops (inference/v2/model.py), so that no layer's pages are ever
sliced out of the pool; the kernels read ``bt_ref[s, p]`` and the XLA
fallbacks gather ``pages[block_table]``, neither cares how many pages lie
beyond the table's.

Latent pages (``v_pages=None``, ``v_dim``): the pool holds ONE row a token,
[NB, 1, bs, kd], that is both key and value (latent attention, absorbed: the
queries have been carried into the latent space, inference/v2/model.py).  The
value is the page's leading ``v_dim`` columns, so a page is copied once and
both dots read the same VMEM slab; q is [S, 1, g, kd] (every query head in one
group) and the output [S, 1, g, v_dim].  ``kd`` obeys the lane rule below
(576 = 512 + 64 is stored padded to 640, the pad columns zero in q and page);
``_block_pages`` counts the page's real bytes, so P follows by itself.

Unequal widths: a value head may be narrower (or wider) than a key head,
``v_pages [NB, nkv, bs, vd]`` beside ``k_pages [NB, nkv, bs, hd]`` (kv-major:
``[NB, nkv, vd, bs]`` beside ``[NB, nkv, hd, bs]``: MiMo-V2's keys are 192
wide and its values 128); the value width is the value pool's, the output is
``[.., vd]``, and nothing else changes: a page of each pool is one copy.

Sinks (``sink [heads]`` float32, or None): a learned logit a query head that
joins the softmax's denominator and carries no value (``sink_softmax``, the
dense definition the fallbacks call).  In the kernels it is the online
softmax's STARTING state: running max ``sink_h``, running sum 1, accumulator
0: one more key whose value is zero, so no page loop changes and a slot or
a row with no visible key still reads zero.

kv-major layout (``kv_major=True``): pages are stored TRANSPOSED,
[NB, nkv, hd, bs].  Mosaic requires a DMA slab's lane (last) dimension to be
128-aligned; with the standard layout that means hd % 128 == 0, which
excludes hd∈{64, 80, 96} — a large slice of the zoo (GPT-2, BLOOM-ish
configs, small llamas).  Putting the TOKEN axis on lanes instead makes the
constraint bs % 128 == 0 (a framework-controlled knob: the engine bumps
kv_block_size to 128), and the two kernel matmuls become the natural MXU
layouts: scores = q·K (contract hd = K's sublane axis) and out = P·Vᵀ
(contract bs = V's lane axis) — no transposes at all.  The engine picks
kv-major automatically whenever hd % 128 != 0 (model.py kv_major_layout).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def sink_softmax(s_log, sink=None):
    """``softmax`` over the last axis of float32 scores ``s_log`` with a sink
    logit in the denominator: ``p_j = exp(s_j - m) / (exp(sink - m) + sum_j
    exp(s_j - m))``, ``m = max(sink, max_j s_j)``.  ``sink`` broadcasts
    against ``s_log[..., :1]``; None is the plain softmax.  A row whose
    scores are all masked (the float32 minimum) reads zeros, not 1 / K."""
    if sink is None:
        return jax.nn.softmax(s_log, axis=-1)
    sink = jnp.asarray(sink, jnp.float32)
    m = jnp.maximum(jnp.max(s_log, axis=-1, keepdims=True), sink)
    e = jnp.exp(s_log - m)
    return e / (jnp.exp(sink - m) + jnp.sum(e, axis=-1, keepdims=True))


def _quant_inputs_ok(k_pages, v_pages, k_scale, v_scale, NB, nkv, bs) -> bool:
    """Shared int8-KV input contract for the decode and prefill gates: both
    pools int8 with matching per-(page, head, token) scale arrays."""
    return (v_scale is not None
            and k_pages.dtype == jnp.int8
            and v_pages.dtype == jnp.int8
            and k_scale.shape == (NB, nkv, bs)
            and v_scale.shape == (NB, nkv, bs))


def _dequant_page(k, v, ks, vs, kv_major, dtype):
    """int8 page codes × per-token fp32 scale row → compute dtype.  The token
    axis is the LANE axis of a kv-major page ([hd, bs]) and the SUBLANE axis
    otherwise ([bs, hd]) — single source of truth for both kernels."""
    if kv_major:
        k = (k.astype(jnp.float32) * ks[..., None, :]).astype(dtype)
        v = (v.astype(jnp.float32) * vs[..., None, :]).astype(dtype)
    else:
        k = (k.astype(jnp.float32) * ks[..., :, None]).astype(dtype)
        v = (v.astype(jnp.float32) * vs[..., :, None]).astype(dtype)
    return k, v


def _gather_pages(pages, block_table, kv_major):
    """Gather each slot's pages THEN normalize the layout — transposing only
    the [S, MB, …] gather result, never the whole pool.  Returns
    [S, MB*bs, nkv, hd]."""
    got = pages[block_table]               # [S, MB, nkv, bs|hd, hd|bs]
    S, MB = got.shape[:2]
    nkv = got.shape[2]
    if kv_major:                           # [S, MB, nkv, hd, bs]
        got = jnp.transpose(got, (0, 1, 4, 2, 3))
        hd = got.shape[4]
    else:                                  # [S, MB, nkv, bs, hd]
        got = jnp.swapaxes(got, 2, 3)
        hd = got.shape[4]
    return got.reshape(S, -1, nkv, hd)


def _latent_value(k_seq, v_pages, block_table, kv_major, v_dim):
    """The gathered values [S, K, nkv, vd]: of their own pool, or (latent
    pages, ``v_pages`` None) the leading ``v_dim`` columns of the keys."""
    if v_pages is None:
        return k_seq[..., :v_dim]
    return _gather_pages(v_pages, block_table, kv_major)


def _gather_scales(scale_pages, block_table):
    """Gather per-(page, head, token) scales [NB, nkv, bs] for each slot →
    [S, MB*bs, nkv] (token-major, matching _gather_pages row order)."""
    got = scale_pages[block_table]         # [S, MB, nkv, bs]
    S = got.shape[0]
    got = jnp.swapaxes(got, 2, 3)          # [S, MB, bs, nkv]
    return got.reshape(S, -1, got.shape[-1])


def _dequant_seq(seq, scales, out_dtype):
    """seq [S, K, nkv, hd] int8 codes × scales [S, K, nkv] → out_dtype."""
    return (seq.astype(jnp.float32) * scales[..., None]).astype(out_dtype)


def xla_paged_attention(q, k_pages, v_pages, block_table, kv_lens, *,
                        scale: Optional[float] = None, alibi_slopes=None,
                        window=None, interpret=None, mesh=None,
                        kv_major=False, k_scale=None, v_scale=None,
                        v_dim=None, sink=None):
    """Ground-truth XLA path: gather this slot's pages, masked softmax.

    ``mesh`` is accepted for signature parity with the Pallas path; the XLA
    body is einsum/gather code the SPMD partitioner shards on its own.
    ``k_scale``/``v_scale`` [NB, nkv, bs]: the pages are int8 codes —
    dequantize after the gather (only the slot's own pages are touched)."""
    S, nkv, g, hd = q.shape
    if kv_major:
        NB, _, _, bs = k_pages.shape
    else:
        NB, _, bs, _ = k_pages.shape
    MB = block_table.shape[1]
    if scale is None:
        scale = hd ** -0.5
    k_seq = _gather_pages(k_pages, block_table, kv_major)   # [S, MB*bs, nkv, hd]
    v_seq = _latent_value(k_seq, v_pages, block_table, kv_major, v_dim)
    if k_scale is not None:
        k_seq = _dequant_seq(k_seq, _gather_scales(k_scale, block_table),
                             q.dtype)
        v_seq = _dequant_seq(v_seq, _gather_scales(v_scale, block_table),
                             q.dtype)
    kvpos = jnp.arange(MB * bs)
    mask = kvpos[None, :] < kv_lens[:, None]                  # [S, K]
    if window is not None:
        # decode query position is kv_len-1; keep the last `window` keys
        mask = mask & (kvpos[None, :] > kv_lens[:, None] - 1 - window)
    s_log = jnp.einsum("sngd,sknd->sngk", q, k_seq,
                       preferred_element_type=jnp.float32) * scale
    if alibi_slopes is not None:
        # key-position bias per GLOBAL head h = kv_group·g + g_idx
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(nkv, g)
        s_log = s_log + sl[None, :, :, None] * kvpos[None, None, None, :]
    s_log = jnp.where(mask[:, None, None, :], s_log,
                      jnp.finfo(jnp.float32).min)
    probs = sink_softmax(s_log, None if sink is None
                         else jnp.reshape(sink, (1, nkv, g, 1)))
    probs = jnp.where(mask[:, None, None, :].any(-1, keepdims=True),
                      probs, 0.0)
    return jnp.einsum("sngk,sknd->sngd", probs.astype(q.dtype), v_seq)


# One block's K and V pages (all kv heads); the block after it is in flight
# beside it, so twice this is what the kernel keeps moving.
_BLOCK_BYTES = 2 << 20
_MAX_BLOCK_PAGES = 8


def _block_pages(pools) -> int:
    """P, the pages a loop iteration of the decode kernel fetches: the whole
    pages (every kv head of every pool the kernel copies: K and V, the scale
    rows of int8 pages; a latent page's one row kind) that fit
    ``_BLOCK_BYTES``, at least one and at most ``_MAX_BLOCK_PAGES`` (each page
    of a block is an unrolled copy).  Static shapes only: a model, a shard of
    its heads, a page dtype or a latent page changes P, nothing else does."""
    page = sum(int(np.prod(pool.shape[1:])) * jnp.dtype(pool.dtype).itemsize
               for pool in pools)
    return int(max(1, min(_MAX_BLOCK_PAGES, _BLOCK_BYTES // page)))


def _decode_kernel(*refs, S, P, bs, scale, window, has_alibi, kv_major,
                   quant, v_dim=None, vd=None, has_sink=False):
    """One grid step = one slot, every kv head (see the module docstring).

    ``state`` (SMEM) carries the page pipeline from one slot to the next:
    ``state[0]`` is the buffer half the next block to be attended lands in,
    ``state[1]`` whether its copies are already under way (the previous live
    slot started them before its own last block's dots).

    ``quant``: pages are int8 codes and two more HBM inputs carry the
    per-(page, head, token) fp32 scales; a page's scale rows ([nkv, bs]) are
    copied beside it and the page is dequantized in VMEM right before the
    dots.  The HBM traffic decode is bound by is the int8 payload.

    ``v_dim``: latent pages, one pool: the page is the key and its leading
    ``v_dim`` columns the value.  ``vd``: the value head's width (the
    latent's, or the value pool's own).  ``has_sink``: one more input, ``[nkv, g, 1]``
    float32 logits, the softmax's starting state (module docstring)."""
    it = iter(refs)
    bt_ref, len_ref, q_ref = next(it), next(it), next(it)
    slopes_ref = next(it) if has_alibi else None
    sink_ref = next(it) if has_sink else None
    hbms = [next(it) for _ in range(1 if v_dim else 4 if quant else 2)]
    o_ref = next(it)
    bufs = [next(it) for _ in hbms]
    sem, state = next(it), next(it)
    s = pl.program_id(0)

    def span(t):
        """(context, first key, first page, pages) of slot ``t``: only
        pages in [first page, pages) are ever copied."""
        length = len_ref[t]
        lo = (jnp.int32(0) if window is None
              else jnp.maximum(length - window, 0))
        return length, lo, lo // bs, (length + bs - 1) // bs

    def copies(t, p, half, i):
        page = bt_ref[t, p]
        return [pltpu.make_async_copy(hbm.at[page], buf.at[half, i],
                                      sem.at[w, half, i])
                for w, (hbm, buf) in enumerate(zip(hbms, bufs))]

    def start_block(t, p0, n_pages, half):
        for i in range(P):
            @pl.when(p0 + i < n_pages)
            def _start():
                for c in copies(t, p0 + i, half, i):
                    c.start()

    @pl.when(s == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    length, lo, first, n_pages = span(s)

    @pl.when(length == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _live():
        nblk = (n_pages - first + P - 1) // P
        nxt = jax.lax.while_loop(
            lambda t: (t < S) & (len_ref[jnp.minimum(t, S - 1)] == 0),
            lambda t: t + 1, s + 1)
        has_next = nxt < S
        nxt = jnp.minimum(nxt, S - 1)
        _, _, nxt_first, nxt_pages = span(nxt)
        half0 = state[0]

        @pl.when(state[1] == 0)
        def _first_of_the_call():
            start_block(s, first, n_pages, half0)

        q = q_ref[0]                                   # [nkv, g, hd]
        nkv, g, hd = q.shape
        slopes = slopes_ref[...] if has_alibi else None    # [nkv, g, 1]
        batch = ((0,), (0,))
        k_dims = (((2,), (1,)), batch) if kv_major else (((2,), (2,)), batch)
        v_dims = (((2,), (2,)), batch) if kv_major else (((2,), (1,)), batch)

        def block(j, carry):
            half = (half0 + j) % 2
            p0 = first + j * P
            last = j + 1 == nblk
            # all of the next block's copies before this block's dots: this
            # slot's next pages, or the next live slot's first ones
            t2 = jnp.where(last, nxt, s)
            p2 = jnp.where(last, nxt_first, p0 + P)
            n2 = jnp.where(last, nxt_pages, n_pages)

            @pl.when(jnp.logical_not(last) | has_next)
            def _prefetch():
                start_block(t2, p2, n2, 1 - half)

            def page(i, carry):
                m, l, acc = carry
                p = p0 + i
                for c in copies(s, p, half, i):
                    c.wait()
                # [nkv, bs, hd] or [nkv, hd, bs]; int8: and scales [nkv, bs]
                if v_dim:
                    k = bufs[0][half, i]
                    v, scales = k[..., :v_dim], ()
                else:
                    k, v, *scales = (buf[half, i] for buf in bufs)
                if quant:
                    k, v = _dequant_page(k, v, *scales, kv_major, q.dtype)
                scores = jax.lax.dot_general(
                    q, k, k_dims,
                    preferred_element_type=jnp.float32) * scale
                kvpos = p * bs + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 2)
                if has_alibi:
                    scores = scores + slopes * kvpos.astype(jnp.float32)
                valid = kvpos < length
                if window is not None:
                    valid = valid & (kvpos >= lo)
                scores = jnp.where(valid, scores, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(scores, axis=2, keepdims=True))
                pr = jnp.exp(scores - m_new)
                pr = jnp.where(m_new > _NEG_INF / 2, pr, 0.0)
                alpha = jnp.exp(m - m_new)
                l = alpha * l + jnp.sum(pr, axis=2, keepdims=True)
                pv = jax.lax.dot_general(pr.astype(v.dtype), v, v_dims,
                                         preferred_element_type=jnp.float32)
                return m_new, l, acc * alpha + pv

            return jax.lax.fori_loop(0, jnp.minimum(P, n_pages - p0), page,
                                     carry)

        if has_sink:        # one more key, seen already, whose value is 0
            m0 = sink_ref[...]
            l0 = jnp.ones((nkv, g, 1), jnp.float32)
        else:
            m0 = jnp.full((nkv, g, 1), _NEG_INF, jnp.float32)
            l0 = jnp.zeros((nkv, g, 1), jnp.float32)
        acc0 = jnp.zeros((nkv, g, vd or hd), jnp.float32)
        _, l, acc = jax.lax.fori_loop(0, nblk, block, (m0, l0, acc0))
        state[0] = (half0 + nblk) % 2
        state[1] = has_next.astype(jnp.int32)
        o_ref[0] = (acc / l).astype(o_ref.dtype)


def pallas_paged_attention(q, k_pages, v_pages, block_table, kv_lens, *,
                           alibi_slopes=None, window=None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           mesh=None, kv_major=False,
                           k_scale=None, v_scale=None, v_dim=None, sink=None):
    """Mesh-aware entry: with a ``tp`` axis the kv-head dim is sharded, and the
    kernel runs per-shard under shard_map (attention is independent per kv
    head, so TP needs no collective here — the reference shards its blocked
    flash the same way, model_implementations/sharding/attn.py).  A shard's
    heads of a page are still one contiguous slab of its local pool."""
    if (mesh is not None and mesh.shape.get("tp", 1) > 1 and v_pages is not None
            and q.shape[1] % mesh.shape["tp"] == 0):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        inner = functools.partial(_pallas_paged_attention_local,
                                  scale=scale, window=window,
                                  interpret=interpret, kv_major=kv_major)
        kv_spec = P(None, "tp", None, None)
        in_specs = [kv_spec, kv_spec, kv_spec, P(None, None), P(None)]
        args = [q, k_pages, v_pages, block_table, kv_lens]
        n_scales = 0
        if k_scale is not None:        # [NB, nkv, bs]: kv-head axis shards
            args += [k_scale, v_scale]
            in_specs += [P(None, "tp", None)] * 2
            n_scales = 2
        if alibi_slopes is not None:
            # slopes [nkv, g] shard with the kv-head axis
            args.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(
                q.shape[1], q.shape[2]))
            in_specs.append(P("tp", None))

        def wrapped(q_, k_, v_, bt_, lens_, *rest):
            sc = rest[:n_scales]
            sl = rest[n_scales:]
            return inner(q_, k_, v_, bt_, lens_,
                         k_scale=sc[0] if sc else None,
                         v_scale=sc[1] if sc else None,
                         alibi_slopes=sl[0] if sl else None)
        return shard_map(
            wrapped, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=kv_spec, check_vma=False,
        )(*args)
    return _pallas_paged_attention_local(q, k_pages, v_pages, block_table,
                                         kv_lens, alibi_slopes=alibi_slopes,
                                         window=window, scale=scale,
                                         interpret=interpret,
                                         kv_major=kv_major,
                                         k_scale=k_scale, v_scale=v_scale,
                                         v_dim=v_dim, sink=sink)


def _pallas_paged_attention_local(q, k_pages, v_pages, block_table, kv_lens, *,
                                  alibi_slopes=None, window=None,
                                  scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  kv_major=False, k_scale=None, v_scale=None,
                                  v_dim=None, sink=None):
    S, nkv, g, hd = q.shape
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if alibi_slopes is not None:
        alibi_slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(
            nkv, g, 1)
    if sink is not None:
        sink = jnp.asarray(sink, jnp.float32).reshape(nkv, g, 1)
    return _paged_decode_call(
        q, k_pages, v_pages, block_table.astype(jnp.int32),
        kv_lens.astype(jnp.int32), alibi_slopes, k_scale, v_scale, sink,
        window=int(window) if window is not None else None,
        scale=float(scale), interpret=bool(interpret), kv_major=kv_major,
        v_dim=None if v_pages is not None else int(v_dim))


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret",
                                             "kv_major", "v_dim"))
def _paged_decode_call(q, k_pages, v_pages, block_table, kv_lens,
                       alibi_slopes, k_scale, v_scale, sink=None, *, window,
                       scale, interpret, kv_major, v_dim=None):
    """Grid (S,): the kernel normalises and writes [S, nkv, g, hd] in q's
    dtype itself.  A jit of its own: a step program calls it once a layer
    with the same shapes (the layer is a value, its first page in the
    table), so the kernel is traced once a process and lowered once a
    program, not once a layer; every program's lowering is paid in
    ``setup_s`` before jax can look its compile cache up."""
    S, nkv, g, hd = q.shape
    bs = k_pages.shape[3] if kv_major else k_pages.shape[2]
    quant = k_scale is not None
    has_alibi = alibi_slopes is not None
    has_sink = sink is not None
    pools = [k_pages] if v_pages is None else [k_pages, v_pages]
    # the value head's width: the latent's, or the value pool's own
    vd = v_dim or v_pages.shape[2 if kv_major else 3]
    if quant:
        pools += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    P = _block_pages(pools)
    kernel = functools.partial(
        _decode_kernel, S=S, P=P, bs=bs, scale=scale, window=window,
        has_alibi=has_alibi, kv_major=kv_major, quant=quant, v_dim=v_dim,
        vd=vd, has_sink=has_sink)
    whole = pl.BlockSpec((1, nkv, g, hd), lambda s, *_: (s, 0, 0, 0))
    out_block = (whole if vd == hd else
                 pl.BlockSpec((1, nkv, g, vd), lambda s, *_: (s, 0, 0, 0)))
    in_specs, inputs = [whole], [q]
    for per_head in (alibi_slopes, sink):
        if per_head is not None:
            in_specs.append(pl.BlockSpec((nkv, g, 1),
                                         lambda s, *_: (0, 0, 0)))
            inputs.append(per_head)
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)
    inputs += pools
    # both halves of the pipeline, P whole pages each
    scratch = [pltpu.VMEM((2, P) + pool.shape[1:], pool.dtype)
               for pool in pools]
    held = sum(int(np.prod(buf.shape)) * buf.dtype.itemsize
               for buf in scratch)
    scratch += [pltpu.SemaphoreType.DMA((len(pools), 2, P)),
                pltpu.SMEM((2,), jnp.int32)]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=in_specs,
            out_specs=out_block,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((S, nkv, g, vd), q.dtype),
        # sequential: a slot hands its successor a block already in flight
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=held + (16 << 20)),
        interpret=interpret,
        name="paged_decode",
    )(block_table, kv_lens, *inputs)


def _dma_layout_ok(hd: int, bs: int, kv_major: bool,
                   quant: bool = False) -> bool:
    """Mosaic constraint on the per-page DMA slab: its LANE (last) dim must
    be 128-aligned and its sublane dim 8-aligned (padded lane dims make the
    slice non-contiguous and the compile is rejected — found on real v5e).
    int8 pages tile (32, 128), so the sublane requirement tightens to 32;
    the [bs] f32 scale slab additionally needs bs % 128 == 0."""
    sub = 32 if quant else 8
    if kv_major:
        return bs % 128 == 0 and hd % sub == 0
    return (hd % 128 == 0 and bs % sub == 0
            and (not quant or bs % 128 == 0))


def _latent_ok(v_pages, v_dim, hd, kv_major, quant, alibi_slopes) -> bool:
    """Latent pages (no value pool): the value is the page's leading
    ``v_dim`` columns, a whole number of lane tiles; row-major bf16/f32
    pages without alibi are what the kernels' latent form reads."""
    if v_pages is not None:
        return v_dim is None
    return (v_dim is not None and 0 < int(v_dim) <= hd
            and int(v_dim) % 128 == 0
            and not (kv_major or quant or alibi_slopes is not None))


def _values_ok(v_pages, k_pages, sink, nkv, g, bs, kv_major, quant,
               mesh) -> bool:
    """A value pool of its own width, and a sink: the value pool is the key
    pool's shape but for its head width, which obeys the same slab rule; a
    sink is one logit a query head.  Either is built for unquantised pages
    on one chip (a ``tp`` mesh shards neither, and latent pages carry no
    sink)."""
    own_width = v_pages is not None and v_pages.shape != k_pages.shape
    if own_width:
        axis = 2 if kv_major else 3
        vd = v_pages.shape[axis]
        if (k_pages.shape[:axis] + (vd,) + k_pages.shape[axis + 1:]
                != v_pages.shape
                or not _dma_layout_ok(vd, bs, kv_major, quant=quant)):
            return False
    if sink is None and not own_width:
        return True
    if sink is not None and (v_pages is None or np.size(sink) != nkv * g):
        return False
    return not quant and not (mesh is not None
                              and mesh.shape.get("tp", 1) > 1)


def supported(q, k_pages, v_pages, block_table, kv_lens, *, scale=None,
              alibi_slopes=None, window=None, interpret=None, mesh=None,
              kv_major=False, k_scale=None, v_scale=None, v_dim=None,
              sink=None):
    if q.ndim != 4 or k_pages.ndim != 4:
        return False
    S, nkv, g, hd = q.shape
    if kv_major:
        NB, nkv2, hd2, bs = k_pages.shape
    else:
        NB, nkv2, bs, hd2 = k_pages.shape
    quant = k_scale is not None
    if quant and not _quant_inputs_ok(k_pages, v_pages, k_scale, v_scale,
                                      NB, nkv2, bs):
        return False
    if alibi_slopes is not None and np.size(alibi_slopes) != nkv * g:
        return False
    if window is not None and int(window) <= 0:
        return False
    return (nkv == nkv2 and hd == hd2
            and _latent_ok(v_pages, v_dim, hd, kv_major, quant, alibi_slopes)
            and _dma_layout_ok(hd, bs, kv_major, quant=quant)
            and _values_ok(v_pages, k_pages, sink, nkv, g, bs, kv_major,
                           quant, mesh)
            and block_table.ndim == 2 and block_table.shape[0] == S)


def paged_attention(q, k_pages, v_pages, block_table, kv_lens, *,
                    scale: Optional[float] = None,
                    alibi_slopes=None, window=None,
                    impl: Optional[str] = None,
                    interpret: Optional[bool] = None,
                    mesh=None, kv_major=False, k_scale=None, v_scale=None,
                    v_dim: Optional[int] = None, sink=None):
    """Registry entry (ops/__init__ registers this like causal_attention).
    ``v_pages=None`` with ``v_dim``: latent pages; ``sink [heads]``: a sink
    logit a query head (module docstring)."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("paged_attention", q, k_pages, v_pages, block_table,
                    kv_lens, scale=scale, alibi_slopes=alibi_slopes,
                    window=window, impl=impl, interpret=interpret, mesh=mesh,
                    kv_major=kv_major, k_scale=k_scale, v_scale=v_scale,
                    v_dim=v_dim, sink=sink)


# ===================================================================
# Ragged prefill (VERDICT r2 item 4 — reference blocked_flash + atom_builder)
# ===================================================================
#
# A mixed prefill/decode step's queries arrive as they leave the projections,
# token-major [N, nkv, g, hd]: slot s owns the ``q_counts[s]`` rows from
# ``row_starts[s]`` on, one contiguous span of the flat batch holding the
# CONTIGUOUS positions [q_starts[s], q_starts[s] + q_counts[s]); its KV,
# the rows just appended included, lives in ``kv_lens[s]`` tokens across the
# slot's block-table pages.  The output is token-major too, [N, nkv, g, vd].
# The XLA fallback gathers every slot's rows dense and its full page span and
# runs one masked-dense attention (cost O(S · Q · MBmax·bs)).
#
# The Pallas kernel walks a WORK LIST: an item is ``cq`` rows (a chunk; 128 at
# the serving sizes, 32 in the latent form) of one slot, ``ceil(count / cq)``
# items a slot, built in the program from ``q_counts`` and scalar-prefetched.
# The grid is (items_max, kv heads) with the static bound ``items_max = N // cq
# + S``; an item past the live count is a bare grid step.  q and the output
# stay in HBM: a live item copies ITS ``cq`` rows of its kv head in and its
# result out, and nothing else moves.  A slot's last chunk overhangs its
# count: the rows past it are the next slot's of the flat batch (or the
# batch's pad), read but masked, and on the way out the item first reads what
# those rows hold and writes that back with its own.  So rows of slots the
# kernel was told hold nothing (count 0: empty slots, and the one-row slots a
# mixed step hands the paged decode kernel above, whose tile is that row)
# come back as they were, whatever order the slots lie in.
#
# Over its rows an item walks ONLY the pages the chunk can causally see (from
# its first row's window start to its last live row's page), a BLOCK of P
# pages a loop step (``_prefill_block_pages``: P from static shapes, 8 at
# heads of 128 over pages of 128, 1 where a page alone fills the budget):
# - all of a block's K and V copies (P pages of this kv head) are started
#   before any is waited for, and the NEXT block's before this block's dots
#   (two buffer halves of P pages);
# - one score tile ``[cq * g, P * bs]``, one running max, one sum and one
#   rescale of the accumulator a block; the three live in VMEM scratch, the
#   max and the sum in every lane of their rows;
# - every block takes the mask, two compares against a per-row bound
#   ``(lo, hi]`` and one select: a second body without it for the blocks no
#   edge crosses was built and measured to gain nothing (the mask hides behind
#   the dots), so there is one body.
# THE MASKED FORM (``sel_mask``, a static flag like ``has_alibi``: a call
# without it lowers to the program it always was).  A layer that SELECTS its
# keys (``ops/sparse_index.py``) hands the kernel, beside the pages, which
# positions each row keeps: bit ``n % 32`` of word ``[n // 32, c]`` says row
# ``n`` of the flat batch keeps position ``c`` of its sequence
# (``threshold_mask``), one answer for all heads of the row.  Rows are packed
# into words because a copy may take only whole tiles of an array's two minor
# dims and an item's ``cq`` rows begin anywhere: as ``[groups, 1, C]`` the
# group is a leading dim, a block's slab ``[its rows' groups, 1, P * bs]``
# is whole lanes, and the array is an eighth of a byte a pair.  The slab is
# copied beside the block's pages, in flight behind the dots as they are, and
# a score whose bit is clear takes the masked value in the one body: a select
# between the groups an item's rows straddle, one AND and one compare more.
# FLOPs and bandwidth scale with the live chunks; a live chunk pays for all
# its ``cq`` rows over every key of every block it walks, so a context's last
# block costs its P pages however few of them are live.  On one v5e chip
# (PERF.md section 6, PR 37) a (page, kv head) step takes 0.49 us at heads of
# 128 and 8 pages a block where the one-page loop before it took 1.33, and
# 1.07 at P = 1: the copies hide behind the dots and the masks behind both;
# what a step costs is the score tile's passes and a fixed cost a block, and
# the wider the tile the better its dots and its softmax overlap.


def xla_ragged_prefill(q, k_pages, v_pages, block_table, kv_lens, q_starts,
                       q_counts, row_starts, *, max_q: Optional[int] = None,
                       scale: Optional[float] = None,
                       alibi_slopes=None, window=None, interpret=None,
                       mesh=None, kv_major=False, k_scale=None, v_scale=None,
                       v_dim=None, sel_mask=None, sink=None):
    """Ground-truth gather + masked-dense path (the round-2 prefill body):
    each slot's rows gathered dense [S, Q, ...] (``Q = max_q``, the most rows
    a slot can hold; every row of the batch if not said), attended, and
    scattered back to their flat rows; rows no slot owns come back zero.
    ``k_scale``/``v_scale``: int8-KV dequant after the gather (see
    xla_paged_attention).  ``sel_mask``: the positions each row keeps
    (section comment)."""
    N, nkv, g, hd = q.shape
    if kv_major:
        NB, _, _, bs = k_pages.shape
    else:
        NB, _, bs, _ = k_pages.shape
    MB = block_table.shape[1]
    Q = N if max_q is None else min(int(max_q), N)
    if scale is None:
        scale = hd ** -0.5
    k_seq = _gather_pages(k_pages, block_table, kv_major)
    v_seq = _latent_value(k_seq, v_pages, block_table, kv_major, v_dim)
    if k_scale is not None:
        k_seq = _dequant_seq(k_seq, _gather_scales(k_scale, block_table),
                             q.dtype)
        v_seq = _dequant_seq(v_seq, _gather_scales(v_scale, block_table),
                             q.dtype)
    kvpos = jnp.arange(MB * bs)                                # [K]
    rows = jnp.arange(Q)
    qpos = q_starts[:, None] + rows[None, :]                   # [S, Q]
    live = rows[None, :] < q_counts[:, None]                   # [S, Q]
    flat = jnp.where(live, row_starts[:, None] + rows[None, :], N)
    q = q[jnp.minimum(flat, N - 1)]                            # [S, Q, ...]
    mask = (kvpos[None, None, :] <= qpos[:, :, None]) \
        & (kvpos[None, None, :] < kv_lens[:, None, None]) \
        & live[:, :, None]                                     # [S, Q, K]
    if window is not None:
        mask = mask & (kvpos[None, None, :] > qpos[:, :, None] - window)
    kept = None
    if sel_mask is not None:
        row = jnp.minimum(flat, N - 1)
        heads = sel_mask.ndim == 3          # a selection a kv head
        kept = jnp.right_shift(
            sel_mask[:, row // 32] if heads else sel_mask[row // 32],
            (row % 32)[..., None]) & 1
        if not heads:
            mask = mask & (kept != 0)
    s_log = jnp.einsum("sqngd,sknd->snqgk", q, k_seq,
                       preferred_element_type=jnp.float32) * scale
    if alibi_slopes is not None:
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(nkv, g)
        s_log = s_log + (sl[None, :, None, :, None]
                         * kvpos[None, None, None, None, :].astype(
                             jnp.float32))
    m = mask[:, None, :, None, :]                              # [S,1,Q,1,K]
    if kept is not None and kept.ndim == 4:         # [nkv, S, Q, K]
        m = m & (jnp.moveaxis(kept, 0, 1)[:, :, :, None, :] != 0)
    s_log = jnp.where(m, s_log, jnp.finfo(jnp.float32).min)
    probs = sink_softmax(s_log, None if sink is None
                         else jnp.reshape(sink, (1, nkv, 1, g, 1)))
    probs = jnp.where(m.any(-1, keepdims=True), probs, 0.0)
    o = jnp.einsum("snqgk,sknd->sqngd", probs.astype(q.dtype), v_seq)
    return jnp.zeros((N,) + o.shape[2:], o.dtype).at[flat].set(
        o, mode="drop")


def _prefill_kernel(*refs, P, bs, cq, g, hd, scale, window, has_alibi,
                    kv_major, quant=False, v_dim=None, has_mask=False,
                    vd=None, has_sink=False, mask_groups=0):
    """One grid step = one work item (``cq`` rows of one slot) for one kv
    head; see the section comment.  The chunk buffers hold ``g`` heads of
    ``hd`` (``vd``) values in their leading rows and columns: the arrays in
    HBM are padded to whole tiles (``_tile_pad``).  ``P``: the pages of a
    block (``_prefill_block_pages``).  ``v_dim``: latent pages, one pool and
    one buffer: the page is the key and its leading ``v_dim`` columns the
    value.  ``has_mask``: the masked form, one more array in HBM and one
    more buffer (section comment); ``mask_groups``: a selection a KV HEAD,
    each head's words ``mask_groups`` groups behind the one before (0: one
    selection for all heads).  ``vd``: the value head's width (the
    latent's, or the value pool's own).  ``has_sink``: ``[nkv, g]`` float32 logits
    behind the scalars, the softmax's starting state (module docstring)."""
    it = iter(refs)
    bt_ref, len_ref, start_ref, count_ref, row_ref, item_slot_ref, \
        item_chunk_ref, n_items_ref = (next(it) for _ in range(8))
    slopes_ref = next(it) if has_alibi else None
    sink_ref = next(it) if has_sink else None
    q_hbm = next(it)
    hbms = [next(it) for _ in range(1 if v_dim else 4 if quant else 2)]
    mask_hbm = next(it) if has_mask else None
    o_hbm, q_buf, o_buf = next(it), next(it), next(it)
    bufs = [next(it) for _ in hbms]
    mask_buf = next(it) if has_mask else None
    m_ref, l_ref, acc_ref, sem, row_sem = (next(it) for _ in range(5))
    mask_sem = next(it) if has_mask else None
    item, h = pl.program_id(0), pl.program_id(1)
    R, K = cq * g, P * bs                  # the score tile of a block
    vd = vd or hd
    # (plain lax on the scalars: every ``//``, ``jnp.where`` or ``jnp.clip``
    # is a jitted helper, and every step program pays for tracing and
    # lowering each one: PERF.md section 6, PR 34)
    lax, i32 = jax.lax, jnp.int32

    if P > 1:
        # a block's buffer half holds ``P`` pages and a context's last block
        # fewer live ones: what lies behind them is an earlier block's pages
        # or these zeros, never whatever the memory held (its scores are
        # masked, but 0 x NaN of a value row is not 0)
        @pl.when((item == 0) & (h == 0))
        def _clear():
            for buf in bufs:
                buf[...] = jnp.zeros_like(buf)

    @pl.when(item < n_items_ref[0])
    def _live():
        s = item_slot_ref[item]
        length = len_ref[s]
        row0 = item_chunk_ref[item] * cq
        n_rows = lax.min(count_ref[s] - row0, i32(cq))
        flat0 = row_ref[s] + row0

        def rows_copy(hbm, buf, way, out=False):
            """The item's ``cq`` rows of kv head ``h`` between the
            token-major array in HBM and the chunk buffer."""
            ends = hbm.at[pl.ds(flat0, cq), h], buf
            return pltpu.make_async_copy(*(ends[::-1] if out else ends),
                                         row_sem.at[way])
        rows_copy(q_hbm, q_buf, 0).start()
        # pages the chunk can causally see: from its FIRST row's window
        # start, which bounds every row's from below, to its LAST live row's
        # position
        pos0 = start_ref[s] + row0                 # the first row's position
        n_pages = lax.div(pos0 + n_rows + (bs - 1), i32(bs))
        p_start = (i32(0) if window is None else
                   lax.div(lax.max(pos0 - (window - 1), i32(0)), i32(bs)))
        nblk = lax.div(n_pages - p_start + (P - 1), i32(P))

        def block_copies(p0, half, act):
            """Start (or wait for) the copies of the block's live pages: a
            loop, not ``P`` conditional copies, which every step program
            would pay for in its lowering."""
            def page(i, _):
                for w, (hbm, buf) in enumerate(zip(hbms, bufs)):
                    act(pltpu.make_async_copy(
                        hbm.at[bt_ref[s, p0 + i], h], buf.at[half, i],
                        sem.at[w, half, i]))
                return 0
            lax.fori_loop(0, lax.clamp(i32(0), n_pages - p0, i32(P)), page,
                          0)
            if has_mask:
                # the words of the groups of 32 rows the item's rows lie
                # in, over the block's keys
                act(pltpu.make_async_copy(
                    mask_hbm.at[pl.ds(group0, mask_buf.shape[1]), :,
                                pl.ds(p0 * bs, K)],
                    mask_buf.at[half], mask_sem.at[half]))

        group0 = lax.shift_right_logical(flat0, i32(5)) if has_mask else None
        first_group = group0        # of the item's rows, in any head's words
        if mask_groups:
            group0 = group0 + h * i32(mask_groups)
        block_copies(p_start, 0, lambda c: c.start())
        # a row sees the keys in (lo, hi]: its position bounds them above
        # (and the context's length, and nothing at all if the row is past
        # the item's live ones), its window below
        rown = lax.div(lax.broadcasted_iota(i32, (R, 1), 0), i32(g))
        qpos = pos0 + rown                             # [cq·g, 1]
        hi = lax.select(rown < n_rows, lax.min(qpos, length - 1),
                        lax.full((R, 1), -1, i32))
        lo = None if window is None else qpos - window
        if has_mask:
            # row r's bit, and which of the copied groups holds it
            flat = flat0 + rown
            group = lax.shift_right_logical(flat, i32(5)) - first_group
            bit = lax.shift_left(lax.full((R, 1), 1, i32),
                                 lax.bitwise_and(flat, i32(31)))
        if has_alibi:
            # SMEM scalar-prefetch slopes [nkv, g]: row r = j·g+gi needs
            # slopes[h, r % g] — tile the per-group column cq times
            sl = jnp.stack([slopes_ref[h, i] for i in range(g)]).reshape(g, 1)
            slope_rows = jnp.tile(sl, (cq, 1))         # [cq·g, 1]
        if has_sink:
            # one more key, seen already, whose value is 0: row r = j·g+gi
            # starts at its head's logit with a sum of 1
            sk = jnp.stack([sink_ref[h, i] for i in range(g)]).reshape(g, 1)
            m_ref[...] = jnp.broadcast_to(jnp.tile(sk, (cq, 1)), m_ref.shape)
            l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
        else:
            m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        rows_copy(q_hbm, q_buf, 0).wait()
        # a last chunk's rows past ``n_rows`` are the next slot's (or the
        # batch's pad): ``hi`` masks their scores
        q = q_buf[:, :g, :hd].reshape(R, hd)       # [cq·g, hd] row r=(j·g+gi)
        k_dims = ((1,), (0,)) if kv_major else ((1,), (1,))
        v_dims = ((1,), (1,)) if kv_major else ((1,), (0,))

        def lanes(x, n):
            """A row's running max or sum, held in every lane of its row
            (``[cq·g, 128]``), beside ``n`` columns."""
            w = x.shape[1]
            if n <= w:
                return x[:, :n]
            return pltpu.repeat(x, n // w, axis=1) if n % w == 0 else \
                jnp.broadcast_to(x[:, :1], (R, n))

        def block(j, _):
            """One softmax update over the block's ``P * bs`` keys."""
            half = lax.rem(j, i32(2))
            p0 = p_start + j * P

            # all of the next block's copies before this block's dots
            @pl.when(j + 1 < nblk)
            def _prefetch():
                block_copies(p0 + P, 1 - half, lambda c: c.start())

            block_copies(p0, half, lambda c: c.wait())
            if v_dim:
                k = bufs[0][half]
                v, scales = k[..., :v_dim], ()
            else:
                k, v, *scales = (buf[half] for buf in bufs)
            if quant:
                k, v = _dequant_page(k, v, *scales, kv_major, q.dtype)
            if kv_major:               # [P, hd, bs] -> [hd, P·bs]
                k, v = (jnp.concatenate(list(x), axis=1) for x in (k, v))
            else:                      # [P, bs, hd] -> [P·bs, hd]
                k, v = k.reshape(K, hd), v.reshape(K, vd)
            scores = lax.dot_general(
                q, k, (k_dims, ((), ())),
                preferred_element_type=jnp.float32) * scale    # [cq·g, K]
            kvpos = p0 * bs + lax.broadcasted_iota(i32, (R, K), 1)
            if has_alibi:
                scores = scores + slope_rows * kvpos.astype(jnp.float32)
            valid = kvpos <= hi
            if window is not None:
                valid = valid & (kvpos > lo)
            if has_mask:
                words = jnp.broadcast_to(mask_buf[half, 0], (R, K))
                for i in range(1, mask_buf.shape[1]):
                    words = jnp.where(
                        group == i, jnp.broadcast_to(mask_buf[half, i],
                                                     (R, K)), words)
                valid = valid & (lax.bitwise_and(words, bit) != 0)
            scores = jnp.where(valid, scores, _NEG_INF)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            # a row with no valid key in this block AND none so far: m_new
            # is still -inf and exp(scores - m_new) would alias to 1: take
            # its scores from 0, which leaves exp(-inf) = 0 (dead rows,
            # early rows of a later block under a window)
            m_off = jnp.where(m_new > _NEG_INF / 2, m_new, 0.0)
            pr = jnp.exp(scores - lanes(m_off, K))
            alpha = jnp.exp(m - m_new)
            m_ref[...] = m_new
            l_ref[...] = alpha * l_ref[...] + jnp.sum(pr, axis=1,
                                                      keepdims=True)
            pv = lax.dot_general(pr.astype(v.dtype), v, (v_dims, ((), ())),
                                 preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * lanes(alpha, vd) + pv
            return 0

        lax.fori_loop(0, nblk, block, 0)
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                # dead rows -> zeros
        # the write is ``cq`` rows too, so a last chunk first reads what the
        # rows past its own hold and writes that back: they are another
        # slot's (its result already there, or not yet) or nobody's, and
        # come back as they were.  Grid steps run one after the other and
        # each waits for its write, so no other item is under way.
        @pl.when(n_rows < cq)
        def _theirs():
            rows_copy(o_hbm, o_buf, 1).start()
            rows_copy(o_hbm, o_buf, 1).wait()
        mine = lax.broadcasted_iota(i32, (cq, g, vd), 0) < n_rows
        o_buf[:, :g, :vd] = jnp.where(
            mine, (acc_ref[...] / l).reshape(cq, g, vd).astype(o_buf.dtype),
            o_buf[:, :g, :vd])
        rows_copy(o_hbm, o_buf, 1, out=True).start()
        rows_copy(o_hbm, o_buf, 1, out=True).wait()


def pallas_ragged_prefill(q, k_pages, v_pages, block_table, kv_lens, q_starts,
                          q_counts, row_starts, *,
                          max_q: Optional[int] = None,
                          scale: Optional[float] = None,
                          alibi_slopes=None, window=None,
                          interpret: Optional[bool] = None, mesh=None,
                          kv_major=False, k_scale=None, v_scale=None,
                          v_dim=None, sel_mask=None, sink=None):
    """Rows of slots with ``q_counts`` 0, and rows no slot owns, come back
    as the fresh output buffer held them (nothing): the caller does not read
    them (the mixed step selects the paged decode kernel's result for them,
    or zero)."""
    if (mesh is not None and mesh.shape.get("tp", 1) > 1 and v_pages is not None
            and q.shape[1] % mesh.shape["tp"] == 0 and sel_mask is None):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        inner = functools.partial(_pallas_ragged_prefill_local, max_q=max_q,
                                  scale=scale, window=window,
                                  interpret=interpret, kv_major=kv_major)
        kv_spec = P(None, "tp", None, None)
        in_specs = [kv_spec, kv_spec, kv_spec, P(None, None), P(None),
                    P(None), P(None), P(None)]
        args = [q, k_pages, v_pages, block_table, kv_lens, q_starts, q_counts,
                row_starts]
        n_scales = 0
        if k_scale is not None:        # [NB, nkv, bs]: kv-head axis shards
            args += [k_scale, v_scale]
            in_specs += [P(None, "tp", None)] * 2
            n_scales = 2
        if alibi_slopes is not None:
            args.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(
                q.shape[1], q.shape[2]))
            in_specs.append(P("tp", None))

        def wrapped(q_, k_, v_, bt_, lens_, st_, ct_, rs_, *rest):
            sc = rest[:n_scales]
            sl = rest[n_scales:]
            return inner(q_, k_, v_, bt_, lens_, st_, ct_, rs_,
                         k_scale=sc[0] if sc else None,
                         v_scale=sc[1] if sc else None,
                         alibi_slopes=sl[0] if sl else None)
        return shard_map(
            wrapped, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=kv_spec, check_vma=False,
        )(*args)
    return _pallas_ragged_prefill_local(
        q, k_pages, v_pages, block_table, kv_lens, q_starts, q_counts,
        row_starts, max_q=max_q, scale=scale, alibi_slopes=alibi_slopes,
        window=window, interpret=interpret, kv_major=kv_major,
        k_scale=k_scale, v_scale=v_scale, v_dim=v_dim, sel_mask=sel_mask,
        sink=sink)


# the prefill kernel's float32 accumulator [cq * g, value width] stays under
# this: 128 rows of any GQA group at head width 128 do (a group of 8 is
# 512 KB), 16 query heads on one 512-wide latent take a chunk of 32 rows
_ACC_BYTES = 1 << 20


def _prefill_chunk(Q: int, g: int = 1, vd: int = 128) -> Optional[int]:
    """Query rows a work item of the prefill kernel attends: the largest
    power of two up to 128 that divides ``Q`` (the most rows a slot holds)
    and keeps the accumulator of its ``cq * g`` rows of ``vd`` values within
    ``_ACC_BYTES``."""
    for cq in (128, 64, 32, 16, 8, 4, 2, 1):
        if cq <= Q and Q % cq == 0 and (cq * g * vd * 4 <= _ACC_BYTES
                                        or cq == 1):
            return cq
    return None


def prefill_grid_items(N: int, S: int, Q: int, cq: int) -> int:
    """The static bound on a call's work items: a flat batch of ``N`` rows
    over ``S`` slots of at most ``Q`` rows, ``cq`` rows an item (``cq``
    divides ``Q``: ``_prefill_chunk``)."""
    return min(N // cq + S, S * (Q // cq))


# The float32 score tile of a block beside the item's accumulator.  Fitted on
# the chip at heads of 128 (PERF.md section 6, PR 37: 8 pages a block read 7%
# faster than 4 there, and 4% slower than 4 in the latent form).
_TILE_BYTES = 4 << 20


def _prefill_block_pages(pools, rows: int, bs: int, vd: int) -> int:
    """P of the prefill kernel, from static shapes alone: ONE kv head's pages
    of every pool within ``_BLOCK_BYTES`` (a grid step copies one head's), and
    the float32 scores of the item's ``rows`` (query rows x group) over the
    block's keys beside their accumulator within ``_TILE_BYTES``; at least one
    and at most ``_MAX_BLOCK_PAGES``."""
    page = sum(int(np.prod(pool.shape[2:])) * jnp.dtype(pool.dtype).itemsize
               for pool in pools)
    tile = (_TILE_BYTES - rows * vd * 4) // (rows * bs * 4)
    return int(max(1, min(_MAX_BLOCK_PAGES, _BLOCK_BYTES // page, tile)))


def _tile_pad(g: int, width: int, dtype):
    """(heads, width) of a token-major array [N, nkv, g, width] padded to
    whole tiles of its two minor dims, which is how the array lies in HBM
    whatever its shape says and the only slabs a copy may take of it: the
    width to whole lanes, the heads to the compiler's sublane tile for a dim
    of ``g`` (a power of two from the dtype's packing up to 8 rows)."""
    tile = 4 // jnp.dtype(dtype).itemsize or 1
    while tile < min(g, 8):
        tile *= 2
    return -(-g // tile) * tile, -(-width // 128) * 128


def _pallas_ragged_prefill_local(q, k_pages, v_pages, block_table, kv_lens,
                                 q_starts, q_counts, row_starts, *,
                                 max_q: Optional[int] = None,
                                 scale: Optional[float] = None,
                                 alibi_slopes=None, window=None,
                                 interpret: Optional[bool] = None,
                                 kv_major=False, k_scale=None, v_scale=None,
                                 v_dim=None, sel_mask=None, sink=None):
    N, nkv, g, hd = q.shape
    S, MB = block_table.shape
    bs = k_pages.shape[3] if kv_major else k_pages.shape[2]
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    latent = v_pages is None
    vd = int(v_dim) if latent else v_pages.shape[2 if kv_major else 3]
    Q = N if max_q is None else min(int(max_q), N)
    cq = _prefill_chunk(Q, g, vd)
    q_counts = q_counts.astype(jnp.int32)
    has_alibi = alibi_slopes is not None
    has_mask = sel_mask is not None
    has_sink = sink is not None
    quant = k_scale is not None

    # the work list: slot after slot, each slot's chunks in order.  Item i
    # is of the slot whose chunks end past i, and its chunk is i less the
    # chunks of the slots before.  (Plain lax: this is traced for every step
    # program, and set-up pays for every jitted helper it calls.)
    lax = jax.lax
    items_max = prefill_grid_items(N, S, Q, cq)
    chunks = lax.shift_right_logical(q_counts + (cq - 1),
                                     jnp.int32(cq.bit_length() - 1))
    ends = lax.cumsum(chunks)
    idx = lax.iota(jnp.int32, items_max)
    before = lax.ge(lax.broadcast_in_dim(idx, (items_max, S), (0,)),
                    lax.broadcast_in_dim(ends, (items_max, S), (1,)))
    item_slot = lax.min(
        lax.reduce(lax.convert_element_type(before, jnp.int32), jnp.int32(0),
                   lax.add, (1,)), jnp.int32(S - 1))
    item_chunk = idx - lax.reduce(
        lax.select(before, lax.broadcast_in_dim(chunks, (items_max, S), (1,)),
                   lax.full((items_max, S), 0, jnp.int32)),
        jnp.int32(0), lax.add, (1,))

    pools = [k_pages] if latent else [k_pages, v_pages]
    if quant:
        pools += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    P = _prefill_block_pages(pools, cq * g, bs, vd)
    # (an item's ``cq`` rows lie in at most ``span`` groups of 32; a
    # selection a kv head, ``[nkv, groups, C]``: each head's words behind
    # the one before's)
    span = (cq + 30) // 32 + 1
    groups = (N - 1) // 32 + span
    mask_heads = has_mask and sel_mask.ndim == 3
    kernel = functools.partial(
        _prefill_kernel, P=P, bs=bs, cq=cq, g=g, hd=hd, scale=float(scale),
        window=int(window) if window is not None else None,
        has_alibi=has_alibi, kv_major=kv_major, quant=quant,
        v_dim=vd if latent else None, has_mask=has_mask, vd=vd,
        has_sink=has_sink, **({"mask_groups": groups} if mask_heads else {}))
    prefetch = [block_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
                q_starts.astype(jnp.int32), q_counts,
                row_starts.astype(jnp.int32), item_slot, item_chunk,
                lax.slice(ends, (S - 1,), (S,))]
    if has_alibi:
        prefetch.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(
            nkv, g))
    if has_sink:
        prefetch.append(jnp.asarray(sink, jnp.float32).reshape(nkv, g))
    (gp, hp), (_, vp) = _tile_pad(g, hd, q.dtype), _tile_pad(g, vd, q.dtype)
    # ... and ``cq`` rows more, for the last chunk of the batch's last slot
    q = lax.pad(q, jnp.zeros((), q.dtype),
                ((0, cq, 0), (0, 0, 0), (0, gp - g, 0), (0, hp - hd, 0)))
    inputs = [q] + pools
    if has_mask:
        # an item's ``cq`` rows lie in at most ``span`` groups of 32 and a
        # context's last block reaches ``P - 1`` pages past the table: so
        # many groups and columns more, zeros, for the copies to stay inside
        heads = ((0, 0, 0),) if mask_heads else ()
        inputs.append(lax.pad(
            sel_mask, jnp.int32(0),
            heads + ((0, groups - sel_mask.shape[-2], 0),
                     (0, (P - 1) * bs, 0))
        ).reshape(-1, 1, (MB + P - 1) * bs))
    # the chunk's rows in and out, both halves of the page pipeline (P pages
    # of one kv head each), and the softmax state of the item's rows
    scratch = [pltpu.VMEM((cq, gp, hp), q.dtype),
               pltpu.VMEM((cq, gp, vp), q.dtype)]
    scratch += [pltpu.VMEM((2, P) + pool.shape[2:], pool.dtype)
                for pool in pools]
    if has_mask:
        scratch.append(pltpu.VMEM((2, span, 1, P * bs), jnp.int32))
    # (the running max and sum fill their rows' lanes: a column one lane
    # wide costs a block a masked store and a broadcast for every 8 rows,
    # 1.4 us at 768 rows, more than a page's dots: PERF.md section 6, PR 37)
    scratch += [pltpu.VMEM((cq * g, 128), jnp.float32),
                pltpu.VMEM((cq * g, 128), jnp.float32),
                pltpu.VMEM((cq * g, vd), jnp.float32)]
    held = sum(int(np.prod(buf.shape[:-1])) * (-(-buf.shape[-1] // 128) * 128)
               * buf.dtype.itemsize for buf in scratch)
    scratch += [pltpu.SemaphoreType.DMA((len(pools), 2, P)),
                pltpu.SemaphoreType.DMA((2,))]
    if has_mask:
        scratch.append(pltpu.SemaphoreType.DMA((2,)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(items_max, nkv),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(inputs),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((N + cq, nkv, gp, vp), q.dtype),
        # (the scratch, and room for a few score tiles of a block)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=held + 8 * cq * g * P * bs * 4 + (8 << 20)),
        interpret=interpret,
        name="ragged_prefill",
    )(*prefetch, *inputs)
    return lax.slice(out, (0, 0, 0, 0), (N, nkv, g, vd))


def ragged_prefill_supported(q, k_pages, v_pages, block_table, kv_lens,
                             q_starts, q_counts, row_starts, *, max_q=None,
                             scale=None, alibi_slopes=None, window=None,
                             interpret=None, mesh=None, kv_major=False,
                             k_scale=None, v_scale=None, v_dim=None,
                             sel_mask=None, sink=None):
    if q.ndim != 4 or k_pages.ndim != 4:
        return False
    N, nkv, g, hd = q.shape
    if kv_major:
        NB, nkv2, hd2, bs = k_pages.shape
    else:
        NB, nkv2, bs, hd2 = k_pages.shape
    quant = k_scale is not None
    if quant and not _quant_inputs_ok(k_pages, v_pages, k_scale, v_scale,
                                      NB, nkv2, bs):
        return False
    if alibi_slopes is not None and np.size(alibi_slopes) != nkv * g:
        return False
    if window is not None and int(window) <= 0:
        return False
    return (nkv == nkv2 and hd == hd2
            and _latent_ok(v_pages, v_dim, hd, kv_major, quant, alibi_slopes)
            and _dma_layout_ok(hd, bs, kv_major, quant=quant)
            and _values_ok(v_pages, k_pages, sink, nkv, g, bs, kv_major,
                           quant, mesh)
            # (a block's slab of the mask is whole lanes)
            and (sel_mask is None or bs % 128 == 0)
            and block_table.ndim == 2
            and row_starts.shape == (block_table.shape[0],))


def ragged_prefill_attention(q, k_pages, v_pages, block_table, kv_lens,
                             q_starts, q_counts, row_starts, *,
                             max_q: Optional[int] = None,
                             scale: Optional[float] = None,
                             alibi_slopes=None, window=None,
                             impl: Optional[str] = None,
                             interpret: Optional[bool] = None, mesh=None,
                             kv_major=False, k_scale=None, v_scale=None,
                             v_dim: Optional[int] = None, sel_mask=None,
                             sink=None):
    """Registry entry for the ragged prefill kernel: token-major ``q``
    [N, nkv, g, hd] -> [N, nkv, g, vd]; slot ``s`` owns rows ``row_starts[s]
    + [0, q_counts[s])`` at positions ``q_starts[s] + [0, q_counts[s])``,
    at most ``max_q`` of them.  ``v_pages=None`` with ``v_dim``: latent pages
    (module docstring).  ``sel_mask``: ``[ceil(N / 32), MB * bs]`` int32,
    the positions of its sequence each row keeps beside what is causal
    (``ops.threshold_mask``; the section comment has the layout), or ``[nkv,
    ceil(N / 32), MB * bs]``: a selection a KV head (``ops/block_select``).
    ``sink [heads]``: a sink logit a query head (module docstring)."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("ragged_prefill_attention", q, k_pages, v_pages,
                    block_table, kv_lens, q_starts, q_counts, row_starts,
                    max_q=max_q, scale=scale,
                    alibi_slopes=alibi_slopes, window=window, impl=impl,
                    interpret=interpret, mesh=mesh, kv_major=kv_major,
                    k_scale=k_scale, v_scale=v_scale,
                    v_dim=v_dim, sel_mask=sel_mask, sink=sink)
