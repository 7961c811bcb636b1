"""Paged-attention decode — Pallas TPU kernel over a block-table KV pool.

TPU-native replacement for the reference's blocked flash decode kernels
(inference/v2/kernels/ragged_ops/blocked_flash/ + atom_builder): each serving
slot owns a list of fixed-size KV pages; decode attends one query token per
slot over exactly that slot's pages.

Kernel design (vs the XLA fallback, which masks over gathered pages):
- grid = (slots, kv_heads, kv_splits) — flash-decoding style.  Each step
  runs an in-kernel double-buffered HBM→VMEM DMA loop over ITS SHARE of the
  slot's live pages (block table via scalar prefetch), with online-softmax
  m/l/acc scratch, and emits unnormalized partials that a tiny XLA epilogue
  merges (logsumexp-weighted).  One split (the default — Pallas TPU grids
  run sequentially per core, so splits don't parallelize under current
  dispatch) degenerates to the single-pass kernel; the split knob exists
  for explicit experimentation on dispatch modes where the axis can run
  concurrently.  Bandwidth always scales with tokens
  actually attended (only live pages are ever read — the property the
  reference kernel gets from its atom decomposition), and a sliding window
  additionally starts the loop past wholly-out-of-window pages.
- GQA native: q arrives [S, nkv, group, hd]; one grid step attends the whole
  group for one kv head (scores [group, bs] on the MXU).
- alibi: per-head slope × key-position bias folded into the online softmax.

Layouts: q [S, nkv, g, hd]; k_pages/v_pages [NB, nkv, bs, hd] (bs = tokens
per page); block_table [S, MB] int32; kv_lens [S] int32 (0 ⇒ inactive slot →
zero output).  Output [S, nkv, g, hd].

The pages are row-major in memory: the kernels DMA ``hbm.at[page, head]``
slabs and, being custom calls, get their operands in no other layout (the
compiler copies an operand that some other op keeps otherwise).  ``NB`` is
whatever the block table indexes: k_pages/v_pages (and the int8 scales) may
be the flat pool of ALL layers, [L * NB, ...], with the layer's first page
``li * NB`` added to the block table.  That is how the serving step programs
call both ops (inference/v2/model.py), so that no layer's pages are ever
sliced out of the pool; the kernels read ``bt_ref[s, p]`` and the XLA
fallbacks gather ``pages[block_table]``, neither cares how many pages lie
beyond the table's.

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
        k = (k.astype(jnp.float32) * ks[None, :]).astype(dtype)
        v = (v.astype(jnp.float32) * vs[None, :]).astype(dtype)
    else:
        k = (k.astype(jnp.float32) * ks[:, None]).astype(dtype)
        v = (v.astype(jnp.float32) * vs[:, None]).astype(dtype)
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
                        kv_major=False, k_scale=None, v_scale=None):
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
    v_seq = _gather_pages(v_pages, block_table, kv_major)
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
    probs = jax.nn.softmax(s_log, axis=-1)
    probs = jnp.where(mask[:, None, None, :].any(-1, keepdims=True),
                      probs, 0.0)
    return jnp.einsum("sngk,sknd->sngd", probs.astype(q.dtype), v_seq)


def _split_kernel(*refs, bs, scale, window, has_alibi, n_splits, kv_major,
                  quant=False):
    """Flash-decoding-SHAPED kernel (one grid step = one KV split of one
    (slot, kv-head)): the page loop covers only this split's share of the
    slot's live pages and emits UNNORMALIZED partials (acc, m, l) that a
    tiny XLA epilogue merges with the standard logsumexp-weighted combine.
    n_splits=1 (the default) IS the single-pass decode kernel; more splits
    only help where the grid axis can actually run concurrently — see the
    module docstring.

    Alibi slopes ride in SMEM scalar prefetch ([nkv, g] f32): a (1, g)
    VMEM BlockSpec is rejected by Mosaic when nkv > 1 (sublane block of 1
    against an nkv-sized axis), and per-head scalars are SMEM-natured
    anyway.

    ``quant``: pages are int8 codes and two extra HBM inputs carry the
    per-(page, head, token) fp32 scales — the page loop DMAs the scale rows
    alongside the pages (double-buffered the same way) and dequantizes in
    VMEM right before the dots.  The HBM traffic that decode is bound by is
    the int8 payload: half the bf16 bytes."""
    if quant:
        if has_alibi:
            bt_ref, len_ref, slopes_ref, q_ref, k_hbm, v_hbm, ks_hbm, \
                vs_hbm, o_ref, m_ref, l_ref, k_buf, v_buf, ks_buf, vs_buf, \
                sem = refs
        else:
            bt_ref, len_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, \
                o_ref, m_ref, l_ref, k_buf, v_buf, ks_buf, vs_buf, sem = refs
            slopes_ref = None
    elif has_alibi:
        bt_ref, len_ref, slopes_ref, q_ref, k_hbm, v_hbm, \
            o_ref, m_ref, l_ref, k_buf, v_buf, sem = refs
    else:
        bt_ref, len_ref, q_ref, k_hbm, v_hbm, \
            o_ref, m_ref, l_ref, k_buf, v_buf, sem = refs
        slopes_ref = None
    if not quant:
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    s, h, sp = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    length = len_ref[s]
    n_pages = (length + bs - 1) // bs
    g, hd = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0]                                # [g, hd]
    if window is None:
        lo_page = jnp.int32(0)
        lo = jnp.int32(0)
    else:
        lo = jnp.maximum(length - window, 0)
        lo_page = lo // bs
    live_pages = jnp.maximum(n_pages - lo_page, 0)
    per = (live_pages + n_splits - 1) // n_splits
    p_start = lo_page + sp * per
    p_end = jnp.minimum(p_start + per, n_pages)

    def dma(hbm, buf, slot, p, way):
        return pltpu.make_async_copy(
            hbm.at[bt_ref[s, p], h], buf.at[slot], sem.at[way * 2 + slot])

    def start_page(slot, p):
        dma(k_hbm, k_buf, slot, p, 0).start()
        dma(v_hbm, v_buf, slot, p, 1).start()
        if quant:
            dma(ks_hbm, ks_buf, slot, p, 2).start()
            dma(vs_hbm, vs_buf, slot, p, 3).start()

    @pl.when(p_end > p_start)
    def _warmup():
        start_page(jax.lax.rem(p_start, 2), p_start)

    def body(p, carry):
        m, l, acc = carry
        slot = jax.lax.rem(p, 2)
        nxt = jax.lax.rem(p + 1, 2)

        @pl.when(p + 1 < p_end)
        def _prefetch():
            start_page(nxt, p + 1)

        dma(k_hbm, k_buf, slot, p, 0).wait()
        dma(v_hbm, v_buf, slot, p, 1).wait()
        k = k_buf[slot]                # [bs, hd] or [hd, bs] (kv-major)
        v = v_buf[slot]
        if quant:
            dma(ks_hbm, ks_buf, slot, p, 2).wait()
            dma(vs_hbm, vs_buf, slot, p, 3).wait()
            k, v = _dequant_page(k, v, ks_buf[slot], vs_buf[slot],
                                 kv_major, q.dtype)
        k_dims = ((1,), (0,)) if kv_major else ((1,), (1,))
        scores = jax.lax.dot_general(
            q, k, (k_dims, ((), ())),
            preferred_element_type=jnp.float32) * scale
        kvpos = p * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        if has_alibi:
            sl = jnp.stack([slopes_ref[h, i] for i in range(g)])
            scores = scores + sl[:, None] * kvpos.astype(jnp.float32)
        valid = kvpos < length
        if window is not None:
            valid = valid & (kvpos >= lo)
        scores = jnp.where(valid, scores, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        pr = jnp.exp(scores - m_new)
        pr = jnp.where(m_new > _NEG_INF / 2, pr, 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(pr, axis=1, keepdims=True)
        v_dims = ((1,), (1,)) if kv_major else ((1,), (0,))
        pv = jax.lax.dot_general(pr.astype(v.dtype), v,
                                 (v_dims, ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l, acc * alpha + pv

    m0 = jnp.full((g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    acc0 = jnp.zeros((g, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(p_start, p_end, body, (m0, l0, acc0))
    o_ref[0, 0, 0] = acc                           # fp32 partial
    m_ref[0, 0, 0] = m[:, 0]
    l_ref[0, 0, 0] = l[:, 0]


def pallas_paged_attention(q, k_pages, v_pages, block_table, kv_lens, *,
                           alibi_slopes=None, window=None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           num_kv_splits: Optional[int] = None,
                           mesh=None, kv_major=False,
                           k_scale=None, v_scale=None):
    """Mesh-aware entry: with a ``tp`` axis the kv-head dim is sharded, and the
    kernel runs per-shard under shard_map (attention is independent per kv
    head, so TP needs no collective here — the reference shards its blocked
    flash the same way, model_implementations/sharding/attn.py)."""
    if (mesh is not None and mesh.shape.get("tp", 1) > 1
            and q.shape[1] % mesh.shape["tp"] == 0):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        inner = functools.partial(_pallas_paged_attention_local,
                                  scale=scale, window=window,
                                  interpret=interpret,
                                  num_kv_splits=num_kv_splits,
                                  kv_major=kv_major)
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
                                         num_kv_splits=num_kv_splits,
                                         kv_major=kv_major,
                                         k_scale=k_scale, v_scale=v_scale)


def _pallas_paged_attention_local(q, k_pages, v_pages, block_table, kv_lens, *,
                                  alibi_slopes=None, window=None,
                                  scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  num_kv_splits: Optional[int] = None,
                                  kv_major=False, k_scale=None, v_scale=None):
    S, nkv, g, hd = q.shape
    if kv_major:
        NB, _, _, bs = k_pages.shape
    else:
        NB, _, bs, _ = k_pages.shape
    MB = block_table.shape[1]
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_table = block_table.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    if num_kv_splits is None:
        # DEFAULT 1: Pallas TPU executes grid dimensions sequentially on a
        # core (and this DMA-loop kernel must not be megacore-partitioned),
        # so extra splits do not parallelize on current single-core
        # dispatch — they only pay partial-writeback + combine.  The knob
        # exists for explicit experimentation (e.g. future megacore-safe
        # variants or very small slot×head grids); measure before enabling.
        num_kv_splits = 1
    return _pallas_paged_attention_split(
        q, k_pages, v_pages, block_table, kv_lens,
        alibi_slopes=alibi_slopes, window=window, scale=float(scale),
        interpret=interpret, num_kv_splits=int(num_kv_splits),
        kv_major=kv_major, k_scale=k_scale, v_scale=v_scale)


def _pallas_paged_attention_split(q, k_pages, v_pages, block_table, kv_lens,
                                  *, alibi_slopes, window, scale, interpret,
                                  num_kv_splits: int, kv_major: bool,
                                  k_scale=None, v_scale=None):
    """Grid (S, nkv, splits) of unnormalized partials + logsumexp-weighted
    XLA combine (flash-decoding shape).  Inputs arrive NORMALIZED (int32
    tables, float scale) from _pallas_paged_attention_local — the only
    caller."""
    S, nkv, g, hd = q.shape
    bs = k_pages.shape[3] if kv_major else k_pages.shape[2]
    NS = num_kv_splits
    quant = k_scale is not None
    kernel = functools.partial(
        _split_kernel, bs=bs, scale=float(scale),
        window=int(window) if window is not None else None,
        has_alibi=alibi_slopes is not None, n_splits=NS, kv_major=kv_major,
        quant=quant)
    n_prefetch = 2
    prefetch = [block_table, kv_lens]
    if alibi_slopes is not None:
        n_prefetch = 3
        prefetch.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(
            nkv, g))
    in_specs = [
        pl.BlockSpec((1, 1, g, hd), lambda s, h, sp, *_: (s, h, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [q, k_pages, v_pages]
    buf_shape = (2, hd, bs) if kv_major else (2, bs, hd)
    scratch = [
        pltpu.VMEM(buf_shape, k_pages.dtype),
        pltpu.VMEM(buf_shape, v_pages.dtype),
    ]
    if quant:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        inputs += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
        scratch += [pltpu.VMEM((2, bs), jnp.float32),
                    pltpu.VMEM((2, bs), jnp.float32)]
    scratch.append(pltpu.SemaphoreType.DMA((8 if quant else 4,)))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(S, nkv, NS),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, 1, g, hd),
                             lambda s, h, sp, *_: (s, h, sp, 0, 0)),
                pl.BlockSpec((1, 1, 1, g),
                             lambda s, h, sp, *_: (s, h, sp, 0)),
                pl.BlockSpec((1, 1, 1, g),
                             lambda s, h, sp, *_: (s, h, sp, 0)),
            ],
            scratch_shapes=scratch,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, nkv, NS, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((S, nkv, NS, g), jnp.float32),
            jax.ShapeDtypeStruct((S, nkv, NS, g), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(*prefetch, *inputs)
    # combine: o = Σ exp(m_s − m*) acc_s / Σ exp(m_s − m*) l_s
    m_star = jnp.max(m, axis=2, keepdims=True)              # [S, nkv, 1, g]
    w = jnp.exp(m - m_star)                                 # [S, nkv, NS, g]
    num = jnp.sum(acc * w[..., None], axis=2)               # [S, nkv, g, hd]
    den = jnp.sum(l * w, axis=2)                            # [S, nkv, g]
    den = jnp.where(den == 0.0, 1.0, den)                   # inactive slots
    return (num / den[..., None]).astype(q.dtype)


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


def supported(q, k_pages, v_pages, block_table, kv_lens, *, scale=None,
              alibi_slopes=None, window=None, interpret=None, mesh=None,
              kv_major=False, k_scale=None, v_scale=None):
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
            and _dma_layout_ok(hd, bs, kv_major, quant=quant)
            and block_table.ndim == 2 and block_table.shape[0] == S)


def paged_attention(q, k_pages, v_pages, block_table, kv_lens, *,
                    scale: Optional[float] = None,
                    alibi_slopes=None, window=None,
                    impl: Optional[str] = None,
                    interpret: Optional[bool] = None,
                    mesh=None, kv_major=False, k_scale=None, v_scale=None):
    """Registry entry (ops/__init__ registers this like causal_attention)."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("paged_attention", q, k_pages, v_pages, block_table,
                    kv_lens, scale=scale, alibi_slopes=alibi_slopes,
                    window=window, impl=impl, interpret=interpret, mesh=mesh,
                    kv_major=kv_major, k_scale=k_scale, v_scale=v_scale)


# ===================================================================
# Ragged prefill (VERDICT r2 item 4 — reference blocked_flash + atom_builder)
# ===================================================================
#
# Mixed prefill/decode batches arrive as a dense-per-slot query layout
# [S, Q, nkv, g, hd] where slot s owns ``q_counts[s]`` live rows holding the
# CONTIGUOUS positions [q_starts[s], q_starts[s] + q_counts[s]); its KV —
# including the rows just appended — lives in ``kv_lens[s]`` tokens across
# the slot's block-table pages.  The XLA fallback gathers every slot's full
# page span and runs one masked-dense attention (cost O(S · Q · MBmax·bs));
# the Pallas kernel instead grids over (slot, kv head, q-chunk) and runs the
# decode kernel's double-buffered HBM→VMEM DMA loop over ONLY the pages the
# chunk can causally see — dead (slot, chunk) pairs are skipped outright, so
# FLOPs and bandwidth scale with the live CHUNKS of ``cq`` rows (128 at the
# serving sizes), not S × longest: a live chunk pays for all its ``cq`` rows
# over every page it sees, however few of them are live.  So the mixed step
# (inference/v2/model.py ``ragged_forward``) hands a slot that holds ONE row
# to the paged decode kernel above, whose tile is that row, and passes it
# here with a count of 0.


def xla_ragged_prefill(q, k_pages, v_pages, block_table, kv_lens, q_starts,
                       q_counts, *, scale: Optional[float] = None,
                       alibi_slopes=None, window=None, interpret=None,
                       mesh=None, kv_major=False, k_scale=None, v_scale=None):
    """Ground-truth gather + masked-dense path (the round-2 prefill body).
    ``k_scale``/``v_scale``: int8-KV dequant after the gather (see
    xla_paged_attention)."""
    S, Q, nkv, g, hd = q.shape
    if kv_major:
        NB, _, _, bs = k_pages.shape
    else:
        NB, _, bs, _ = k_pages.shape
    MB = block_table.shape[1]
    if scale is None:
        scale = hd ** -0.5
    k_seq = _gather_pages(k_pages, block_table, kv_major)
    v_seq = _gather_pages(v_pages, block_table, kv_major)
    if k_scale is not None:
        k_seq = _dequant_seq(k_seq, _gather_scales(k_scale, block_table),
                             q.dtype)
        v_seq = _dequant_seq(v_seq, _gather_scales(v_scale, block_table),
                             q.dtype)
    kvpos = jnp.arange(MB * bs)                                # [K]
    rows = jnp.arange(Q)
    qpos = q_starts[:, None] + rows[None, :]                   # [S, Q]
    live = rows[None, :] < q_counts[:, None]                   # [S, Q]
    mask = (kvpos[None, None, :] <= qpos[:, :, None]) \
        & (kvpos[None, None, :] < kv_lens[:, None, None]) \
        & live[:, :, None]                                     # [S, Q, K]
    if window is not None:
        mask = mask & (kvpos[None, None, :] > qpos[:, :, None] - window)
    s_log = jnp.einsum("sqngd,sknd->snqgk", q, k_seq,
                       preferred_element_type=jnp.float32) * scale
    if alibi_slopes is not None:
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(nkv, g)
        s_log = s_log + (sl[None, :, None, :, None]
                         * kvpos[None, None, None, None, :].astype(
                             jnp.float32))
    m = mask[:, None, :, None, :]                              # [S,1,Q,1,K]
    s_log = jnp.where(m, s_log, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(s_log, axis=-1)
    probs = jnp.where(m.any(-1, keepdims=True), probs, 0.0)
    return jnp.einsum("snqgk,sknd->sqngd", probs.astype(q.dtype), v_seq)


def _prefill_kernel(*refs, bs, cq, g, scale, window, has_alibi, kv_major,
                    quant=False):
    if quant:
        if has_alibi:
            bt_ref, len_ref, start_ref, count_ref, slopes_ref, \
                q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, \
                k_buf, v_buf, ks_buf, vs_buf, sem = refs
        else:
            bt_ref, len_ref, start_ref, count_ref, \
                q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, \
                k_buf, v_buf, ks_buf, vs_buf, sem = refs
            slopes_ref = None
    elif has_alibi:
        bt_ref, len_ref, start_ref, count_ref, slopes_ref, \
            q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem = refs
    else:
        bt_ref, len_ref, start_ref, count_ref, \
            q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem = refs
        slopes_ref = None
    if not quant:
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    s, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    count = count_ref[s]
    start = start_ref[s]
    length = len_ref[s]
    hd = q_ref.shape[4]
    row0 = c * cq
    live = row0 < count
    # pages the chunk can causally see: up to its LAST live row's position
    last_pos = start + jnp.minimum(count, row0 + cq) - 1
    n_pages = jnp.where(live, (last_pos + bs) // bs, 0)
    if window is None:
        p_start = jnp.int32(0)
    else:
        # the chunk's FIRST row's window start bounds every row's from below
        p_start = jnp.maximum(start + row0 - window + 1, 0) // bs

    def dma(hbm, buf, slot, p, way):
        return pltpu.make_async_copy(
            hbm.at[bt_ref[s, p], h], buf.at[slot], sem.at[way * 2 + slot])

    def start_page(slot, p):
        dma(k_hbm, k_buf, slot, p, 0).start()
        dma(v_hbm, v_buf, slot, p, 1).start()
        if quant:
            dma(ks_hbm, ks_buf, slot, p, 2).start()
            dma(vs_hbm, vs_buf, slot, p, 3).start()

    @pl.when(n_pages > p_start)
    def _warmup():
        start_page(jax.lax.rem(p_start, 2), p_start)

    q = q_ref[0, :, 0].reshape(cq * g, hd)         # [cq·g, hd] row r=(j·g+gi)
    rown = jax.lax.broadcasted_iota(jnp.int32, (cq * g, bs), 0) // g
    qpos = start + row0 + rown                     # [cq·g, bs]
    row_live = row0 + rown < count
    if has_alibi:
        # SMEM scalar-prefetch slopes [nkv, g]: row r = j·g+gi needs
        # slopes[h, r % g] — tile the per-group column cq times
        sl = jnp.stack([slopes_ref[h, i] for i in range(g)]).reshape(g, 1)
        slope_rows = jnp.tile(sl, (cq, 1))         # [cq·g, 1]

    def body(p, carry):
        m, l, acc = carry
        slot = jax.lax.rem(p, 2)
        nxt = jax.lax.rem(p + 1, 2)

        @pl.when(p + 1 < n_pages)
        def _prefetch():
            start_page(nxt, p + 1)

        dma(k_hbm, k_buf, slot, p, 0).wait()
        dma(v_hbm, v_buf, slot, p, 1).wait()
        k = k_buf[slot]                # [bs, hd] or [hd, bs] (kv-major)
        v = v_buf[slot]
        if quant:
            dma(ks_hbm, ks_buf, slot, p, 2).wait()
            dma(vs_hbm, vs_buf, slot, p, 3).wait()
            k, v = _dequant_page(k, v, ks_buf[slot], vs_buf[slot],
                                 kv_major, q.dtype)
        k_dims = ((1,), (0,)) if kv_major else ((1,), (1,))
        scores = jax.lax.dot_general(
            q, k, (k_dims, ((), ())),
            preferred_element_type=jnp.float32) * scale       # [cq·g, bs]
        kvpos = p * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        if has_alibi:
            scores = scores + slope_rows * kvpos.astype(jnp.float32)
        valid = (kvpos <= qpos) & (kvpos < length) & row_live
        if window is not None:
            valid = valid & (kvpos > qpos - window)
        scores = jnp.where(valid, scores, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        pr = jnp.exp(scores - m_new)
        # a row with no valid key in this page AND none so far: m_new is
        # still -inf and exp aliases to 1 — zero it (dead rows, early rows
        # of a later page under a window)
        pr = jnp.where(m_new > _NEG_INF / 2, pr, 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(pr, axis=1, keepdims=True)
        v_dims = ((1,), (1,)) if kv_major else ((1,), (0,))
        pv = jax.lax.dot_general(pr.astype(v.dtype), v,
                                 (v_dims, ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l, acc * alpha + pv

    m0 = jnp.full((cq * g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((cq * g, 1), jnp.float32)
    acc0 = jnp.zeros((cq * g, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(p_start, n_pages, body, (m0, l0, acc0))
    l = jnp.where(l == 0.0, 1.0, l)                # dead rows -> zeros
    o_ref[0, :, 0] = (acc / l).reshape(cq, g, hd).astype(o_ref.dtype)


def pallas_ragged_prefill(q, k_pages, v_pages, block_table, kv_lens, q_starts,
                          q_counts, *, scale: Optional[float] = None,
                          alibi_slopes=None, window=None,
                          interpret: Optional[bool] = None, mesh=None,
                          kv_major=False, k_scale=None, v_scale=None):
    if (mesh is not None and mesh.shape.get("tp", 1) > 1
            and q.shape[2] % mesh.shape["tp"] == 0):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        inner = functools.partial(_pallas_ragged_prefill_local, scale=scale,
                                  window=window, interpret=interpret,
                                  kv_major=kv_major)
        q_spec = P(None, None, "tp", None, None)
        kv_spec = P(None, "tp", None, None)
        in_specs = [q_spec, kv_spec, kv_spec, P(None, None), P(None),
                    P(None), P(None)]
        args = [q, k_pages, v_pages, block_table, kv_lens, q_starts, q_counts]
        n_scales = 0
        if k_scale is not None:        # [NB, nkv, bs]: kv-head axis shards
            args += [k_scale, v_scale]
            in_specs += [P(None, "tp", None)] * 2
            n_scales = 2
        if alibi_slopes is not None:
            args.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(
                q.shape[2], q.shape[3]))
            in_specs.append(P("tp", None))

        def wrapped(q_, k_, v_, bt_, lens_, st_, ct_, *rest):
            sc = rest[:n_scales]
            sl = rest[n_scales:]
            return inner(q_, k_, v_, bt_, lens_, st_, ct_,
                         k_scale=sc[0] if sc else None,
                         v_scale=sc[1] if sc else None,
                         alibi_slopes=sl[0] if sl else None)
        return shard_map(
            wrapped, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=q_spec, check_vma=False,
        )(*args)
    return _pallas_ragged_prefill_local(
        q, k_pages, v_pages, block_table, kv_lens, q_starts, q_counts,
        scale=scale, alibi_slopes=alibi_slopes, window=window,
        interpret=interpret, kv_major=kv_major,
        k_scale=k_scale, v_scale=v_scale)


def _prefill_chunk(Q: int) -> Optional[int]:
    for cq in (128, 64, 32, 16, 8, 4, 2, 1):
        if cq <= Q and Q % cq == 0:
            return cq
    return None


def _pallas_ragged_prefill_local(q, k_pages, v_pages, block_table, kv_lens,
                                 q_starts, q_counts, *,
                                 scale: Optional[float] = None,
                                 alibi_slopes=None, window=None,
                                 interpret: Optional[bool] = None,
                                 kv_major=False, k_scale=None, v_scale=None):
    S, Q, nkv, g, hd = q.shape
    bs = k_pages.shape[3] if kv_major else k_pages.shape[2]
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cq = _prefill_chunk(Q)
    block_table = block_table.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    q_starts = q_starts.astype(jnp.int32)
    q_counts = q_counts.astype(jnp.int32)
    has_alibi = alibi_slopes is not None
    quant = k_scale is not None

    grid = (S, nkv, Q // cq)
    kernel = functools.partial(
        _prefill_kernel, bs=bs, cq=cq, g=g, scale=float(scale),
        window=int(window) if window is not None else None,
        has_alibi=has_alibi, kv_major=kv_major, quant=quant)
    n_prefetch = 4
    prefetch = [block_table, kv_lens, q_starts, q_counts]
    if has_alibi:
        n_prefetch = 5
        prefetch.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(
            nkv, g))
    in_specs = [
        pl.BlockSpec((1, cq, 1, g, hd),
                     lambda s, h, c, *_: (s, c, h, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [q, k_pages, v_pages]
    buf_shape = (2, hd, bs) if kv_major else (2, bs, hd)
    scratch = [
        pltpu.VMEM(buf_shape, k_pages.dtype),
        pltpu.VMEM(buf_shape, v_pages.dtype),
    ]
    if quant:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        inputs += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
        scratch += [pltpu.VMEM((2, bs), jnp.float32),
                    pltpu.VMEM((2, bs), jnp.float32)]
    scratch.append(pltpu.SemaphoreType.DMA((8 if quant else 4,)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, cq, 1, g, hd),
                                   lambda s, h, c, *_: (s, c, h, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((S, Q, nkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="ragged_prefill",
    )(*prefetch, *inputs)
    return out


def ragged_prefill_supported(q, k_pages, v_pages, block_table, kv_lens,
                             q_starts, q_counts, *, scale=None,
                             alibi_slopes=None, window=None, interpret=None,
                             mesh=None, kv_major=False,
                             k_scale=None, v_scale=None):
    if q.ndim != 5 or k_pages.ndim != 4:
        return False
    S, Q, nkv, g, hd = q.shape
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
            and _dma_layout_ok(hd, bs, kv_major, quant=quant)
            and _prefill_chunk(Q) is not None
            and block_table.ndim == 2 and block_table.shape[0] == S)


def ragged_prefill_attention(q, k_pages, v_pages, block_table, kv_lens,
                             q_starts, q_counts, *,
                             scale: Optional[float] = None,
                             alibi_slopes=None, window=None,
                             impl: Optional[str] = None,
                             interpret: Optional[bool] = None, mesh=None,
                             kv_major=False, k_scale=None, v_scale=None):
    """Registry entry for the ragged prefill kernel."""
    from deepspeed_tpu.ops.registry import dispatch
    return dispatch("ragged_prefill_attention", q, k_pages, v_pages,
                    block_table, kv_lens, q_starts, q_counts, scale=scale,
                    alibi_slopes=alibi_slopes, window=window, impl=impl,
                    interpret=interpret, mesh=mesh, kv_major=kv_major,
                    k_scale=k_scale, v_scale=v_scale)
