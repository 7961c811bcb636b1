"""Flash attention — Pallas TPU kernel with XLA fallback.

TPU-native replacement for the reference's fused attention kernels
(training: csrc/transformer/*_kernels.cu strided-batch-gemm + softmax path;
inference v1: csrc/transformer/inference/csrc/softmax.cu; the blocked flash in
inference/v2/kernels/ragged_ops/blocked_flash is the ragged cousin, see
inference/v2).  Online-softmax tiling keeps the [T, T] score matrix out of HBM:
(bq, bk) tiles stream through the MXU with running max/denominator rescaling,
forward saves only the logsumexp row stats for the backward pass.

The loop over blocks is the KERNEL's, not the grid's (PR 52): a grid step
holds a span of a head's query rows and the head's keys and values (forward),
or a kv head's keys and values and a q head's rows (backward), and walks with
``lax.fori_loop`` only the blocks that are live for it: from the window's
first block to the block the diagonal ends in.  A dead block costs no grid
step, no copy and no work; only a block that the diagonal or the window's
edge crosses builds a mask.  The backward looks at a tile's scores once: dV,
dK and dQ come out of one pass (five matmuls and one exponential a live
pair), dQ summed in float32 VMEM scratch and written once, while a head's
query-side rows fit the VMEM budget (``flash_plan``); beyond it a dQ kernel
and a dK/dV kernel stream keys and queries in groups.  Scores are held
transposed ([keys, queries]), so the row statistics are rows.

Variants handled IN-KERNEL:
- alibi: per-head slope × key-position logit bias (bloom/falcon-rw;
  reference v1 kernels includes/alibi.h) — slopes ride SMEM, the bias folds
  into the online softmax and the backward, masked tile or not.
- sliding window (mistral/gpt-neo local attention): the loop starts at the
  window's first block, so FLOPs scale with T·window instead of T²/2.
  Fully-masked rows (a window that starts past a block) are guarded so
  exp(s − m) cannot alias to 1.
- ``causal=False`` (BERT, diffusion, the ring's live sub-blocks): the
  unmasked body over all blocks.

Layout convention: public API is [B, T, N, D] (batch, seq, heads, head_dim) to
match the model code; kernels run on [B, N, T, D].
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import record

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30  # finite "minus infinity": avoids inf-inf NaNs in rescaling

# per-seq-len (bq, bk) overrides of the in-kernel blocks: the baked-in
# `_block_pair` table came from ONE chip's sweep (v5e, PR 52), so it is
# overridable without a code change:
# ``configure_flash_blocks({4096: (512, 1024)})`` or env
# ``DSTPU_FLASH_BLOCKS="4096:512x1024,8192:512x1024"``.
# scripts/sweep_flash_blocks.py measures candidates on the current hardware
# and prints the winning env line.
_BLOCK_OVERRIDES = None   # None = not yet resolved from env; {} = none


def _parse_block_spec(spec: str):
    """'4096:512x1024,8192:512' → {4096: (512, 1024), 8192: (512, 512)}."""
    out = {}
    for part in spec.replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        try:
            t_s, blocks = part.split(":")
            bq_s, _, bk_s = blocks.partition("x")
            bq = int(bq_s)
            bk = int(bk_s) if bk_s else bq
            out[int(t_s)] = (bq, bk)
        except ValueError as e:
            raise ValueError(
                f"bad flash block spec {part!r} (want 'T:BQxBK' or 'T:B'): "
                f"{e}") from e
    return out


def _validate_blocks(overrides) -> dict:
    mapping = {}
    for t, pair in dict(overrides).items():
        bq, bk = int(pair[0]), int(pair[1])
        if int(t) < 8 or bq < 8 or bk < 8:
            raise ValueError(
                f"flash block override T={t}: ({bq}, {bk}) — seq len and "
                f"blocks must be >= 8")
        mapping[int(t)] = (bq, bk)
    return mapping


def configure_flash_blocks(overrides=None):
    """Install (bq, bk) overrides keyed by sequence length; ``None`` resets
    to the ``DSTPU_FLASH_BLOCKS`` env (or the built-in table when unset).
    Divisibility is validated at use time (T is only known then); shape
    sanity is validated here — on BOTH paths, so a typo'd env spec raises
    a clear ValueError instead of a ZeroDivisionError inside kernel
    tracing.  Returns the active mapping."""
    global _BLOCK_OVERRIDES
    if overrides is None:
        env = os.environ.get("DSTPU_FLASH_BLOCKS", "")
        overrides = _parse_block_spec(env) if env else {}
    _BLOCK_OVERRIDES = _validate_blocks(overrides)
    return dict(_BLOCK_OVERRIDES)


def flash_block_overrides():
    """The active override table (env resolved lazily on first use)."""
    global _BLOCK_OVERRIDES
    if _BLOCK_OVERRIDES is None:
        configure_flash_blocks(None)
    return _BLOCK_OVERRIDES


def _block_sizes(t: int, prefer: int = DEFAULT_BLOCK_Q):
    for b in (prefer, 512, 256, 128, 64, 32, 16, 8):
        if b <= t and t % b == 0:
            return b
    return None


def _block_pair(t: int, d: int = 64, window=None):
    """(bq, bk): the blocks of the loop INSIDE the kernels, bq query rows by
    bk key rows a tile.  Set by the v5e sweep of PR 52 (fwd + bwd at the two
    train cells' shapes, B8·H16·D64 at T=1024 and B2·H32/8·D128 at T=4096, and
    B4·H12 at D 64, 128, 256 and T 1024-8192; ``PERF.md`` section 6 has the
    table, ``chiprun_out/pr52/sweep*.jsonl`` the runs): always square, the
    largest power of two that divides T up to

    - 1,024 at T >= 2048 with heads up to 128 and no window (Mistral's shard
      7.52 ms against 8.23 at 512; D64 5.31 against 5.71 at T=4096): the
      diagonal's tiles are walked in strips of ``_STRIP`` rows, so a larger
      tile computes no more of the square, and fewer, larger tiles wait out
      fewer matmul latencies;
    - 512 otherwise (T=1024: 1.50 ms against 1.55 at 1,024; heads of 256 as
      fast at 512, where the tiles' VMEM is known to fit; a window's edge
      tiles are not walked in strips, so their waste grows with the tile).

    Small blocks lose (256: 2.06 ms, 128: 4.4 at T=1024: every tile waits
    out its own chain of five matmuls), and so do rectangular ones.

    An entry in the override table (``configure_flash_blocks`` /
    ``DSTPU_FLASH_BLOCKS``) wins over the table: it is an explicit
    hardware-tuned choice (scripts/sweep_flash_blocks.py); only
    T-divisibility is still enforced (a non-dividing block is a wrong grid,
    not a tuning choice)."""
    ov = flash_block_overrides()
    if t in ov:
        bq, bk = ov[t]
        if t % bq or t % bk:
            raise ValueError(
                f"flash block override for T={t}: ({bq}, {bk}) must divide "
                f"the sequence length")
        return bq, bk
    wide = t >= 2048 and d <= 128 and window is None
    bq = _block_sizes(t, 1024 if wide else DEFAULT_BLOCK_Q)
    return bq, bq


def supported(q, k, v, *, causal=True, scale=None, window=None,
              alibi_slopes=None, **_):
    """Shape predicate for the pallas path (registry.OpSpec.supported)."""
    if q.ndim != 4 or q.shape != v.shape[:2] + q.shape[2:]:
        return False
    t, d = q.shape[1], q.shape[3]
    if k.shape[1] != t:  # cross/ragged attention -> fallback
        return False
    if q.shape[2] % k.shape[2] != 0:  # GQA group must divide
        return False
    if window is not None and (not causal or int(window) <= 0):
        return False
    if alibi_slopes is not None and (not causal
                                     or np.size(alibi_slopes) != q.shape[2]):
        return False
    return _block_sizes(t) is not None and d % 8 == 0


# ------------------------------------------------------------ live blocks
#
# The loop over blocks is the kernel's, not the grid's: a grid step holds a
# span of query rows (or of key rows, in the backward) and walks, with
# ``lax.fori_loop``, only the blocks of the other side that are live for it.
# Bounds are Python ints where the grid has one step along the sequence and
# traced scalars otherwise; the helpers below take both.

def _static(*xs):
    return all(isinstance(x, int) for x in xs)


def _lesser(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _greater(a, b):
    return max(a, b) if _static(a, b) else jnp.maximum(a, b)


def _div(a, b):
    """floor(a / b) of a position that is not negative."""
    return a // b if _static(a) else jax.lax.div(a, jnp.int32(b))


def _rows(i, n):
    """Rows [i*n, (i+1)*n) of a resident block."""
    if _static(i):
        return pl.ds(i * n, n)
    return pl.ds(pl.multiple_of(i * n, n), n)


def _parts(plan, strips, iq, ik):
    """What a tile (query block ``iq``, key block ``ik`` of the resident
    rows) is walked in, as ``(query rows, key rows, columns of the block's
    row statistics, first query row in the block)`` each: the whole tile, or
    ``strips`` strips of query rows, each against the keys up to its own
    diagonal's end."""
    if strips == 1:
        return [(_rows(iq, plan.bq), _rows(ik, plan.bk), slice(None), 0)]
    n = plan.strip

    def rows(at, size):
        return pl.ds(at if _static(at) else pl.multiple_of(at, n), size)

    return [(rows(iq * plan.bq + r * n, n), rows(ik * plan.bk, (r + 1) * n),
             slice(r * n, (r + 1) * n), r * n) for r in range(strips)]


def _when(cond, fn):
    """``pl.when`` that also takes what is known at trace time."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _live_keys(q0, bq, bk, nkb, causal, window):
    """Key blocks that query rows [q0, q0 + bq) see, as ``(lo, plain_lo,
    plain_hi, hi)``: blocks lo..hi are live, and those in plain_lo..plain_hi
    hold no masked pair (below the diagonal, inside the window)."""
    lo = plain_lo = 0
    hi = plain_hi = nkb
    if causal:
        hi = _div(q0 + bq - 1, bk) + 1
        plain_hi = _div(q0 + 1, bk)
    if window is not None:
        lo = _div(_greater(q0 - window + 1, 0), bk)
        plain_lo = _div(_greater(q0 + bq - window, 0) + bk - 1, bk)
    return lo, plain_lo, plain_hi, hi


def _live_queries(k0, bq, bk, nqb, causal, window):
    """The same for the query blocks that see key rows [k0, k0 + bk): the
    diagonal crosses the first of them, the window's edge the last."""
    lo = plain_lo = 0
    hi = plain_hi = nqb
    if causal:
        lo = _div(k0, bq)
        plain_lo = _div(k0 + bk + bq - 2, bq)
    if window is not None:
        hi = _lesser(_div(k0 + bk + window - 2, bq) + 1, nqb)
        plain_hi = _div(k0 + window, bq)
    return lo, plain_lo, plain_hi, hi


def _for_live(live, first, count, body, carry, edge_lo, edge_hi):
    """Run ``body(block, carry, masked)`` over the live blocks that are
    resident (``first .. first + count``): the masked body only where an edge
    of the mask crosses (``edge_lo`` / ``edge_hi``: whether one can, at the
    low and the high end), the plain body, with no iota, compare or select,
    over the blocks between."""
    lo, plain_lo, plain_hi, hi = live
    lo = _greater(lo, first)
    hi = _lesser(hi, first + count)
    plain_lo = _lesser(_greater(plain_lo, lo), hi)
    plain_hi = _lesser(_greater(plain_hi, plain_lo), hi)
    masked = functools.partial(body, masked=True)
    if edge_lo:
        carry = _loop(lo, plain_lo, masked, carry)
    carry = _loop(plain_lo, plain_hi, functools.partial(body, masked=False),
                  carry)
    if edge_hi:
        carry = _loop(plain_hi, hi, masked, carry)
    return carry


def _loop(lo, hi, body, carry):
    """``fori_loop``, but a trip known at trace time to be the only one (or
    none) is traced in line: a short sequence's walk is straight-line code."""
    if _static(lo, hi) and hi - lo <= 1:
        return body(lo, carry) if hi > lo else carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _each(n, fn):
    """``fn(i)`` for the blocks a grid step holds: unrolled while that keeps
    the program small (and the inner bounds static), a loop beyond."""
    if n <= 4:
        for i in range(n):
            fn(i)
    else:
        jax.lax.fori_loop(0, n, lambda i, c: (fn(i), c)[1], 0)


def _scores(a, b, scale):
    """Scaled logits [rows of a, rows of b], float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale


def _bias_mask(s, q0, k0, q_axis, causal, window, slope, masked):
    """Alibi's key-position term, and the mask where ``masked``.  ``s`` is
    [queries, keys] (``q_axis`` 0) or its transpose (1); ``q0`` and ``k0``
    are the positions of its corner."""
    k_axis = 1 - q_axis
    if slope is not None:
        shape = [1, 1]
        shape[k_axis] = s.shape[k_axis]
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, tuple(shape), k_axis)
        s = s + slope * kpos.astype(jnp.float32)
    if masked:
        # query position less key position, one compare a mask edge
        rel = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
               - jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis))
        off = k0 - q0
        valid = rel >= off if causal else None
        if window is not None:
            inside = rel < off + window
            valid = inside if valid is None else valid & inside
        s = jnp.where(valid, s, _NEG_INF)
    return s


# Rows of a strip: a tile the diagonal crosses corner to corner (bq == bk,
# no window) is walked as strips of query rows, each against the keys up to
# its own diagonal's end, so the dead corner of the tile is not computed.
# The strips are independent chains in one basic block, which the scheduler
# interleaves; blocks this small as the loop's own would each wait out their
# matmuls' latency (the chip sweep, PERF.md section 6, PR 52).
_STRIP = 256


def _strips(plan, causal, window, least):
    """How many strips a diagonal tile is walked in (1: whole).  The
    backward takes two or more, the forward four or more: its strips each
    carry their own statistics through VMEM, which two do not pay for."""
    if (causal and window is None and plan.bq == plan.bk
            and plan.bq % plan.strip == 0):
        n = plan.bq // plan.strip
        return n if n >= least else 1
    return 1


def _span(t, block, cap):
    """Rows a grid step holds: the most whole blocks under ``cap`` rows that
    divide the sequence (a step's own overhead is as long as a small tile's
    work, so a short sequence is one step)."""
    n = t // block
    for per in range(max(1, cap // block), 0, -1):
        if n % per == 0:
            return per * block
    return block


# Rows x head_dim up to which one head's keys and values (forward) and its
# query-side rows q, dO and the float32 dQ (backward) stay resident in VMEM:
# the one-pass backward then holds 32 * T * d bytes of blocks, buffers and
# accumulators (32 MiB here) under ``_VMEM_LIMIT``.  Beyond, both stream.
_RESIDENT_ROWS_X_DIM = 1 << 20
_VMEM_LIMIT = 64 * 1024 * 1024      # of a v5e core's 128 MiB
_SPAN_ROWS = 1024                   # rows of a grid step, short of residency
_STREAM_ROWS = 2048                 # rows of a streamed group


class _Plan(NamedTuple):
    """What the kernels run at a shape, chosen from ``(T, d, window)`` and
    this module's constants and nothing else: a static argument of the
    jitted calls below, so that a later layer of the same plan is not traced
    again."""
    bq: int
    bk: int
    resident: bool      # a head's rows fit: one-pass backward, k/v resident
    strip: int
    span_rows: int
    stream_rows: int


def _plan(t, d, window):
    bq, bk = _block_pair(t, d, window)
    return _Plan(bq, bk, t * d <= _RESIDENT_ROWS_X_DIM, _STRIP, _SPAN_ROWS,
                 _STREAM_ROWS)


def flash_plan(t, d=64, window=None):
    """``(bq, bk, backward form)`` the kernels run at these shapes, from the
    shapes alone: the in-kernel blocks, and "one-pass" (dq, dk and dv out of
    one look at the scores, a head's query rows resident) or "streamed" (a dq
    kernel and a dk/dv kernel, keys and queries arriving in groups)."""
    plan = _plan(t, d, window)
    return plan.bq, plan.bk, "one-pass" if plan.resident else "streamed"


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------- forward

def _fwd_tile(q, k, v, m, l, acc, q0, k0, scale, causal, window, slope,
              masked):
    """One online-softmax step on transposed scores [keys, queries]: the
    running maximum and denominator are rows ([1, queries]: a vreg or two,
    where a column of statistics takes a vreg every eight), reduced over
    sublanes, and the accumulator is [d, queries]."""
    st = _bias_mask(_scores(k, q, scale), q0, k0, 1, causal, window, slope,
                    masked)
    m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
    pt = jnp.exp(st - m_new)                     # [keys, queries] fp32
    if masked and window is not None:
        # a query whose window starts past this block: m_new is still -inf
        # and exp(s - m_new) would alias masked entries to 1
        pt = jnp.where(m_new > _NEG_INF / 2, pt, 0.0)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(pt, axis=0, keepdims=True)
    pv = jax.lax.dot_general(v, pt.astype(v.dtype), (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return m_new, l, acc * alpha + pv


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, plan, scale, causal, window,
                has_alibi, nkb, nspan, ngrp, strips):
    # grid (batch, head, query span, key group): the span's query blocks each
    # walk the live key blocks of the resident group (all of them, where keys
    # and values are resident).  A block's statistics are the loop's carry;
    # they live in VMEM scratch instead where they outlive a grid step (key
    # groups) or are taken a strip at a time.  The output block is
    # transposed back once, at the end.
    slopes_ref = rest[0] if has_alibi else None
    o_ref, lse_ref, *scr = rest[1:] if has_alibi else rest
    bq, bk = plan.bq, plan.bk
    nsub, gkb, d = q_ref.shape[2] // bq, k_ref.shape[2] // bk, q_ref.shape[3]
    span = pl.program_id(2) if nspan > 1 else 0
    grp = pl.program_id(3) if ngrp > 1 else 0
    slope = slopes_ref[pl.program_id(1)] if has_alibi else None
    tile = functools.partial(_fwd_tile, scale=scale, causal=causal,
                             window=window, slope=slope)
    in_scratch = bool(scr)

    def block(i):
        q0 = (span * nsub + i) * bq
        row = pl.ds(i, 1)

        def state(cols=slice(None)):
            m_scr, l_scr, acc_scr = scr
            return (m_scr.at[row, cols], l_scr.at[row, cols],
                    acc_scr.at[i, :, cols])

        def attend(jk, carry, masked):
            jl = jk - grp * gkb
            if not in_scratch:
                return tile(q_ref[0, 0, _rows(i, bq), :],
                            k_ref[0, 0, _rows(jl, bk), :],
                            v_ref[0, 0, _rows(jl, bk), :], *carry, q0,
                            jk * bk, masked=masked)
            for qrows, krows, cols, first in _parts(
                    plan, strips if masked else 1, i, jl):
                refs = state(cols)
                new = tile(q_ref[0, 0, qrows, :], k_ref[0, 0, krows, :],
                           v_ref[0, 0, krows, :], *(x[...] for x in refs),
                           q0 + first, jk * bk, masked=masked)
                for ref, x in zip(refs, new):
                    ref[...] = x
            return carry

        def finish(m, l, acc):
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, 0, _rows(i, bq), :] = jnp.transpose(
                acc / l).astype(o_ref.dtype)
            lse_ref[0, 0, 0, row, :] = m + jnp.log(l)

        fresh = (jnp.full((1, bq), _NEG_INF, jnp.float32),
                 jnp.zeros((1, bq), jnp.float32),
                 jnp.zeros((d, bq), jnp.float32))

        def init():
            for ref, x in zip(state(), fresh):
                ref[...] = x

        if in_scratch:
            _when(grp == 0, init)
        carry = _for_live(_live_keys(q0, bq, bk, nkb, causal, window),
                          grp * gkb, gkb, attend, 0 if in_scratch else fresh,
                          window is not None, causal)
        if in_scratch:
            _when(grp == ngrp - 1,
                  lambda: finish(*(ref[...] for ref in state())))
        else:
            finish(*carry)

    _each(nsub, block)


def _last_live_group(q_end, tk):
    """Index-map clamp: a group past the diagonal is never fetched (the block
    index does not change, so the copy is skipped with the work)."""
    return (q_end - 1) // tk


def _fwd(q, k, v, slopes, causal, scale, window, has_alibi, interpret):
    return _fwd_call(q, k, v, slopes, _plan(q.shape[2], q.shape[3], window),
                     causal, scale, window, has_alibi, interpret)


# inline: the calls below trace once a (plan, shape) and lower where they
# are called, under the caller's scope, which names the kernel in a trace
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9), inline=True)
def _fwd_call(q, k, v, slopes, plan, causal, scale, window, has_alibi,
              interpret):
    b, n, t, d = q.shape
    group = n // k.shape[1]   # GQA: kv head = q head // group (no expansion)
    bq, bk = plan.bq, plan.bk
    if plan.resident:
        tq, tk = _span(t, bq, plan.span_rows), t
    else:
        tq, tk = bq, _span(t, bk, plan.stream_rows)
    nspan, ngrp, nsub = t // tq, t // tk, tq // bq
    strips = _strips(plan, causal, window, least=4)
    kernel = functools.partial(
        _fwd_kernel, plan=plan, scale=scale, causal=causal, window=window,
        has_alibi=has_alibi, nkb=t // bk, nspan=nspan, ngrp=ngrp,
        strips=strips)

    def kv_map(b_, h, s, g):
        if causal:      # dead groups: hold the last live one, copy nothing
            g = jnp.minimum(g, _last_live_group((s + 1) * tq, tk))
        return b_, h // group, g, 0

    q_spec = pl.BlockSpec((1, 1, tq, d), lambda b_, h, s, g: (b_, h, s, 0))
    kv_spec = pl.BlockSpec((1, 1, tk, d), kv_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [q, k, v]
    if has_alibi:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(slopes)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, n, nspan, ngrp),
        in_specs=in_specs,
        out_specs=[
            q_spec,
            # row stats ride [B, N, span, block, bq]: a block's statistics
            # are one row, found by index and never by a slice along lanes
            pl.BlockSpec((1, 1, 1, nsub, bq),
                         lambda b_, h, s, g: (b_, h, s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, n, nspan, nsub, bq), jnp.float32),
        ],
        # the statistics' home where they outlive a grid step (key groups)
        # or are taken a strip at a time; else they are the loop's carry
        scratch_shapes=[
            pltpu.VMEM((nsub, bq), jnp.float32),
            pltpu.VMEM((nsub, bq), jnp.float32),
            pltpu.VMEM((nsub, d, bq), jnp.float32),
        ] if ngrp > 1 or strips > 1 else [],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(*inputs)
    return o, lse.reshape(b, n, 1, t)


# ---------------------------------------------------------------- backward

def _dq_transposed(d):
    """Whether dQ is summed transposed, [d, queries] = K^T dS^T: the tile
    dS^T is the product's stationary operand as it stands, where dS K would
    transpose it first, a tile.  Heads up to 64 only (G's shape 1.64 -> 1.56
    ms; at 128 the d rows that stream past each stationary tile no longer
    cover its load, Z's shape 8.14 -> 8.27; my chip runs, PR 52)."""
    return d <= 64


def _bwd_tile(q, do, lse, delta, k, v, dk_ref, dv_ref, dq_ref, q0, k0, scale,
              causal, window, slope, masked):
    """One look at a tile's scores, transposed ([keys, queries]: the row
    statistics broadcast as rows), summed into dK, dV and, one pass, dQ, all
    less the scale, which dK and dQ take once, in float32.  Five matmuls and
    one exponential."""
    st = _bias_mask(_scores(k, q, scale), q0, k0, 1, causal, window, slope,
                    masked)
    pt = jnp.exp(st - lse)                                   # [keys, queries]
    if masked and window is not None:
        # fully-masked row: lse is -inf and exp(-inf + inf) is 1
        pt = jnp.where(lse > _NEG_INF / 2, pt, 0.0)
    dv_ref[...] += jax.lax.dot_general(
        pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dst = (pt * (dpt - delta)).astype(q.dtype)
    dk_ref[...] += jax.lax.dot_general(
        dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    if dq_ref is None:
        return
    if _dq_transposed(q.shape[1]):
        dq_ref[...] += jax.lax.dot_general(
            k, dst, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        dq_ref[...] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                plan, scale, causal, window, has_alibi, nqb, group, nspan,
                ngrp, with_dq):
    # grid (batch, kv head, key span, q head of the group x query group):
    # each key block of the span walks the live query blocks of the resident
    # group.  dK and dV sum over the whole GQA group in VMEM; ``with_dq`` (one
    # span, one group: a head's query rows resident) makes it one pass, dQ
    # summed in float32 and written once.
    slopes_ref = rest[0] if has_alibi else None
    rest = rest[1:] if has_alibi else rest
    if with_dq:
        dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, dq_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    bq, bk = plan.bq, plan.bk
    gqb, gkb = q_ref.shape[2] // bq, k_ref.shape[2] // bk
    span = pl.program_id(2) if nspan > 1 else 0
    j = pl.program_id(3) if group * ngrp > 1 else 0
    grp = j % ngrp if ngrp > 1 else 0
    slope = (slopes_ref[pl.program_id(1) * group + j // ngrp]
             if has_alibi else None)
    tile = functools.partial(_bwd_tile, scale=scale, causal=causal,
                             window=window, slope=slope)
    strips = _strips(plan, causal, window, least=2)
    dqt = _dq_transposed(q_ref.shape[3])

    def zero():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    _when(j == 0, zero)
    if with_dq:
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def block(jl):
        k0 = (span * gkb + jl) * bk

        def attend(iq, carry, masked):
            il = iq - grp * gqb
            row = pl.ds(il, 1)
            for qrows, krows, cols, first in _parts(
                    plan, strips if masked else 1, il, jl):
                dq = None
                if with_dq:
                    dq = (dq_scr.at[il, :, cols] if dqt
                          else dq_scr.at[qrows, :])
                tile(q_ref[0, 0, qrows, :], do_ref[0, 0, qrows, :],
                     lse_ref[0, 0, 0, row, cols],
                     delta_ref[0, 0, 0, row, cols], k_ref[0, 0, krows, :],
                     v_ref[0, 0, krows, :], dk_scr.at[krows, :],
                     dv_scr.at[krows, :], dq,
                     iq * bq + first, k0, masked=masked)
            return carry

        _for_live(_live_queries(k0, bq, bk, nqb, causal, window), grp * gqb,
                  gqb, attend, 0, causal, window is not None)

    _each(gkb, block)

    def write():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

    _when(j == group * ngrp - 1, write)
    if with_dq and dqt:
        def out(i):
            dq_ref[0, 0, _rows(i, bq), :] = (
                jnp.transpose(dq_scr[i]) * scale).astype(dq_ref.dtype)
        _each(gqb, out)
    elif with_dq:
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               plan, scale, causal, window, has_alibi, nkb, ngrp):
    # the streamed form's dQ: grid (batch, head, query block, key group), the
    # forward's walk over the live key blocks of the resident group
    if has_alibi:
        slopes_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
    bq, bk = plan.bq, plan.bk
    gkb = k_ref.shape[2] // bk
    iq, grp = pl.program_id(2), pl.program_id(3)
    slope = slopes_ref[pl.program_id(1)] if has_alibi else None
    q, do = q_ref[0, 0], do_ref[0, 0]
    lse = lse_ref[0, 0, 0, 0][:, None]                       # [bq, 1]
    delta = delta_ref[0, 0, 0, 0][:, None]

    @pl.when(grp == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def attend(jk, dq, masked):
        krows = _rows(jk - grp * gkb, bk)
        k, v = k_ref[0, 0, krows, :], v_ref[0, 0, krows, :]
        s = _bias_mask(_scores(q, k, scale), iq * bq, jk * bk, 0, causal,
                       window, slope, masked)
        p = jnp.exp(s - lse)
        if masked and window is not None:
            p = jnp.where(lse > _NEG_INF / 2, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq_scr[...] = _for_live(
        _live_keys(iq * bq, bq, bk, nkb, causal, window), grp * gkb, gkb,
        attend, dq_scr[...], window is not None, causal)

    @pl.when(grp == ngrp - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, slopes, causal, scale, window, has_alibi,
              interpret):
    return _bwd_call(q, k, v, o, lse, do, slopes,
                     _plan(q.shape[2], q.shape[3], window), causal, scale,
                     window, has_alibi, interpret)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 12), inline=True)
def _bwd_call(q, k, v, o, lse, do, slopes, plan, causal, scale, window,
              has_alibi, interpret):
    b, n, t, d = q.shape
    nkv = k.shape[1]
    group = n // nkv
    bq, bk = plan.bq, plan.bk
    one_pass = plan.resident
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                   # [b, n, 1, t]
    static = dict(plan=plan, scale=scale, causal=causal, window=window,
                  has_alibi=has_alibi)
    alibi_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)] if has_alibi else []
    alibi = [slopes] if has_alibi else []

    # dk, dv (and, one pass, dq): kv-major, the q heads of a GQA group and
    # the query groups fused innermost so dk/dv accumulate in VMEM scratch
    tk = t if one_pass else _span(t, bk, plan.span_rows)
    tq = t if one_pass else _span(t, bq, plan.stream_rows)
    nspan, ngrp, gqb = t // tk, t // tq, tq // bq

    def q_map(b_, h, s, j):
        g = j % ngrp
        if causal:      # query groups wholly above the span's diagonal
            g = jnp.maximum(g, (s * tk) // tq)
        return b_, h * group + j // ngrp, g, 0

    q_spec = pl.BlockSpec((1, 1, tq, d), q_map)
    kv_spec = pl.BlockSpec((1, 1, tk, d), lambda b_, h, s, j: (b_, h, s, 0))
    row_spec = pl.BlockSpec((1, 1, 1, gqb, bq),
                            lambda *i: q_map(*i) + (0,))
    rows = (lse.reshape(b, n, ngrp, gqb, bq),
            delta.reshape(b, n, ngrp, gqb, bq))
    kv_out = [kv_spec, kv_spec]
    kv_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype)]
    kv_scr = [pltpu.VMEM((tk, d), jnp.float32),
              pltpu.VMEM((tk, d), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, nqb=t // bq, group=group, nspan=nspan,
                          ngrp=ngrp, with_dq=one_pass, **static),
        grid=(b, nkv, nspan, group * ngrp),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
        + alibi_spec,
        out_specs=kv_out + ([q_spec] if one_pass else []),
        out_shape=kv_shape + ([jax.ShapeDtypeStruct(q.shape, q.dtype)]
                              if one_pass else []),
        scratch_shapes=kv_scr + ([pltpu.VMEM(
            (gqb, d, bq) if _dq_transposed(d) else (t, d), jnp.float32)]
            if one_pass else []),
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(q, k, v, do, *rows, *alibi)
    if one_pass:
        dk, dv, dq = outs
        return dq, dk, dv
    dk, dv = outs

    tk = _span(t, bk, plan.stream_rows)
    ngrp = t // tk

    def kv_map(b_, h, iq, g):
        if causal:
            g = jnp.minimum(g, _last_live_group((iq + 1) * bq, tk))
        return b_, h // group, g, 0

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, g: (b_, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, tk, d), kv_map)
    row_spec = pl.BlockSpec((1, 1, 1, 1, bq),
                            lambda b_, h, iq, g: (b_, h, iq, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nkb=t // bk, ngrp=ngrp, **static),
        grid=(b, n, t // bq, ngrp),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
        + alibi_spec,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(q, k, v, do, lse.reshape(b, n, t // bq, 1, bq),
      delta.reshape(b, n, t // bq, 1, bq), *alibi)
    return dq, dk, dv


# ------------------------------------------------------- custom_vjp plumbing

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, slopes, causal, scale, window, has_alibi, interpret):
    o, _ = _fwd(q, k, v, slopes, causal, scale, window, has_alibi, interpret)
    return o


def _flash_fwd(q, k, v, slopes, causal, scale, window, has_alibi, interpret):
    o, lse = _fwd(q, k, v, slopes, causal, scale, window, has_alibi,
                  interpret)
    return o, (q, k, v, slopes, o, lse)


def _flash_bwd(causal, scale, window, has_alibi, interpret, res, do):
    q, k, v, slopes, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, o, lse, do, slopes, causal, scale,
                           window, has_alibi, interpret)
    return dq, dk, dv, jnp.zeros_like(slopes)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    alibi_slopes=None,
                    interpret: Optional[bool] = None):
    """Flash attention over [B, T, N, D] inputs (returns same layout).

    GQA (fewer kv heads) is consumed natively: the kernels index the kv head as
    ``q_head // group`` so K/V are never expanded in HBM (the reference
    blocked_flash consumes grouped KV the same way), and dk/dv accumulate the
    whole group inside the kv-major backward kernel.

    ``window``: sliding-window causal attention (key within the last
    ``window`` positions) with dead tiles skipped — FLOPs scale with
    T·window.  ``alibi_slopes`` [N]: per-head key-position bias.
    """
    if not supported(q, k, v, causal=causal, window=window,
                     alibi_slopes=alibi_slopes):
        raise ValueError(
            "flash_attention: unsupported shapes "
            f"q={q.shape} k={k.shape} v={v.shape} window={window}; requires "
            "[B, T, N, D] with equal q/kv seq len, kv heads dividing q heads, "
            "seq len divisible by a power-of-two block (>=8), head_dim % 8 "
            "== 0, and window/alibi only with causal=True "
            "(ops.causal_attention dispatches to the XLA path for these)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # the form that runs is static, so its counter is a note: once a trace
    record("flash_attention", "pallas", "blocks %dx%d, backward %s"
           % flash_plan(q.shape[1], q.shape[3], window))
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    has_alibi = alibi_slopes is not None
    slopes = (jnp.asarray(alibi_slopes, jnp.float32).reshape(q.shape[2])
              if has_alibi else jnp.zeros((q.shape[2],), jnp.float32))
    o = _flash(qt, kt, vt, slopes, causal, float(scale),
               int(window) if window is not None else None, has_alibi,
               bool(interpret))
    return jnp.transpose(o, (0, 2, 1, 3))
