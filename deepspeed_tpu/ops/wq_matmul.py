"""Quantized-weight matmuls — int8/int4 weights streamed through VMEM,
dequantized per tile.

Reference parity: the FP6-LLM W6A16 quantized GEMM
(``inference/v2/modules/implementations/linear/quantized_linear.py:205`` +
``inference/v2/kernels/core_ops/cuda_linear/``) — the weight matrix stays
quantized THROUGH the matmul; full-precision weight values exist only in
on-chip memory, one tile at a time.

TPU shape of the idea: decode is weight-bandwidth-bound, so the win is HBM
traffic — the kernel reads int8 codes (1 byte/param) + per-group fp32
scales (≈3% overhead at group 128) instead of bf16 (2 bytes/param),
halving the weight stream; the W4A16 variant reads nibble-PACKED codes
(½ byte/param), quartering it.  Each grid step loads a [g, bn] int8 tile
(W4: a [g/2, bn] byte tile holding nibble pairs) and its [1, bn] scale
row, dequantizes in VMEM registers, and feeds the MXU:

    y[M, N] = x[M, K] @ (codes[K, N] · scales[K/g, N])

The K-tile size equals the quantization group ``g`` so the scale is a
single broadcastable row per tile — no in-kernel gather/reshape.

N does NOT need to tile: the grid rounds the column dim up and Mosaic
masks the trailing partial block (same idea as the M-pad), so real vocabs
like GPT-2's 50257 run the kernel (round-4 verdict: the silent fallback
meant the flagship bench's unembed never engaged).  K must tile exactly —
it is contracted, and garbage in an out-of-bounds K block would pollute
every output.

Tensor-parallel reach (``wq_matmul_tp``): GSPMD cannot partition the
Mosaic custom call, so a tp-sharded store is run through a manual
``shard_map`` over the tp axis — each shard calls the kernel on its slice
(the reference's per-rank quantized GEMM under AutoTP,
``module_inject/auto_tp.py:273``), with a psum closing row-parallel
(contraction-sharded) layouts.

``wq_matmul`` falls back to dequantize-then-matmul (XLA) for layouts the
kernel doesn't cover (the store's dim-0 must be the contraction dim,
g % 32 == 0 — W4: g % 64; K tile-aligned).  Serving-only: no VJP is
defined (the store is inference-time state).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.quantization import (dequantize_weight,
                                            dequantize_weight4,
                                            is_quantized_weight,
                                            is_quantized_weight4,
                                            unpack_nibbles_f32)


def _on_tpu(interpret: Optional[bool]) -> bool:
    """True when the kernel will hit the real Mosaic lowering (which
    enforces (8, 128)-aligned-or-full block tiles) rather than interpret
    mode (which accepts anything — the round-4 kernels were interpret-clean
    and still failed on first chip contact)."""
    if interpret is not None:
        return not interpret
    return jax.default_backend() == "tpu"


def _pick(total, prefer):
    for b in (prefer, 512, 256, 128, 64, 32, 16, 8):
        if b <= total and total % b == 0:
            return b
    return None


def _lane_ok(block, dim) -> bool:
    """Mosaic lane rule for a block's LAST dim: divisible by 128 or equal to
    the full array dim."""
    return block % 128 == 0 or block == dim


def _sublane(dtype) -> int:
    """Min sublane multiple for a dtype's native tile: fp32 (8, 128),
    bf16/f16 (16, 128), int8/fp8 (32, 128).  M pads to this so the x/out
    block's second-minor dim is always tile-legal (a block equal to the
    full dim is also legal, which the padded M satisfies when m == bm)."""
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def _pick_n(total, prefer=512):
    """Column-dim block size: a 128-aligned exact divisor when one exists
    (Mosaic's lane rule — the last block dim must be %128 or the full dim),
    else the full dim when small, else the preferred tile rounded to 128
    with an out-of-bounds trailing block (Mosaic masks the partial write;
    the N dim is never contracted, so the padding lanes' garbage stays in
    columns the caller's out_shape doesn't include)."""
    for b in (prefer, 512, 384, 256, 128):
        if b <= total and total % b == 0 and b % 128 == 0:
            return b
    if total <= prefer:
        return total                    # block == full dim: always legal
    return -(-prefer // 128) * 128


_warned_shapes = set()

# trace-time counters: how many pallas-kernel calls were STAGED per variant
# (tests assert the kernel path engaged instead of the silent dequant
# fallback — the same reasoning as the warn-once below, made checkable)
trace_counts = {"w8": 0, "w8t": 0, "w4": 0}


def _record_refused(variant: str) -> None:
    """Count a layout the eligibility gate refused (dequant fallback)."""
    from deepspeed_tpu.ops.registry import record
    record(variant, "xla", "layout refused")


def _tile_legal(block, array_shape) -> bool:
    """Mosaic's block-shape rule (jax pallas/mosaic/lowering.py
    ``_check_block_mappings``): for rank >= 2, the block's last dim must be
    % 128 or equal the array's, and its second-minor must be % 8 or equal
    the array's."""
    if len(block) < 2:
        return block[0] == array_shape[0] or block[0] % 128 == 0
    b0, a0 = block[-1], array_shape[-1]
    b1, a1 = block[-2], array_shape[-2]
    return (b0 == a0 or b0 % 128 == 0) and (b1 == a1 or b1 % 8 == 0)


def _preflight(variant: str, blocks, interpret: bool) -> bool:
    """True when every (block, array_shape) pair the kernel is about to
    stage satisfies Mosaic's tiling rule (interpret mode accepts anything).
    The eligibility gates above should make this unreachable — but the
    round-5 on-chip sweep recorded a serving leg dying inside an unguarded
    block-shape raise, so the rule is re-checked against the EXACT blocks
    before ``pallas_call`` and an illegal combination takes the dequant
    fallback (warned once, counted every time in the registry's dispatch
    log) instead of erroring out of the caller's step."""
    from deepspeed_tpu.ops.registry import record
    for block, ashape in blocks:
        # a None block (no usable tile divisor) falls back on ANY backend;
        # interpret mode otherwise accepts every block shape
        if block is None or (not interpret
                             and not _tile_legal(block, ashape)):
            key = ("preflight", variant) + tuple(
                tuple(b) if b else b for b, _ in blocks)
            if key not in _warned_shapes:
                _warned_shapes.add(key)
                from deepspeed_tpu.utils.logging import logger
                logger.warning(
                    "%s: staged block shapes %s are not Mosaic-legal "
                    "(last two block dims must be %%(8, 128) or equal the "
                    "array dims); falling back to dequantize-then-matmul",
                    variant, [b for b, _ in blocks])
            record(variant, "xla", "preflight: block shapes not tile-legal")
            return False
    return True


def kernel_supported(x, store, interpret: Optional[bool] = None) -> bool:
    """True when the Pallas path can run (M and N are NOT constrained —
    both pad to the tile).  Unsupported 2-D stores warn ONCE per shape: a
    silent fallback would let an operator benchmark 'the W8A16 kernel'
    while measuring the dequant path.

    On the real Mosaic lowering the activation tile is [bm, g], whose lane
    dim is the GROUP — so g must be %128 (or the whole K): found on first
    chip contact, round 5."""
    if not is_quantized_weight(store):
        return False
    v, s = store["v"], store["s"]
    if v.ndim != 2 or x.ndim != 2 or x.shape[1] != v.shape[0]:
        return False
    if s.shape[1:] != v.shape[1:]:
        return False                   # kernel assumes dim-0 grouping
    k, n = v.shape
    g = k // s.shape[0]
    ok = k % g == 0 and g % 32 == 0 and g >= 32
    why = "group % 32 == 0"
    if ok and _on_tpu(interpret) and not _lane_ok(g, k):
        ok, why = False, "group % 128 == 0 on TPU (x tile lane dim)"
    if not ok and (k, n, g) not in _warned_shapes:
        _warned_shapes.add((k, n, g))
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            "wq_matmul: store [%d, %d] (group %d) cannot tile for the "
            "W8A16 kernel (needs %s); falling back to "
            "dequantize-then-matmul — the int8 HBM-traffic saving does "
            "NOT engage for this weight", k, n, g, why)
    return ok


def kernel4_supported(x, store, interpret: Optional[bool] = None) -> bool:
    """W4A16 eligibility: nibble-packed ``quantize_weight4`` store, dim-0
    contraction, g % 64 == 0 (the kernel reads [g/2, bn] byte tiles, so
    the packed sublane dim must stay int8-tileable).  On the real Mosaic
    lowering the de-interleaved activation tile is [bm, g/2] — its lane
    dim g/2 must be %128 (or the whole K/2), i.e. g % 256 == 0."""
    if not is_quantized_weight4(store):
        return False
    p, s = store["v4"], store["s"]
    if p.ndim != 2 or x.ndim != 2 or x.shape[1] != 2 * p.shape[0]:
        return False
    if s.shape[1:] != p.shape[1:]:
        return False
    k = 2 * p.shape[0]
    g = k // s.shape[0]
    ok = k % g == 0 and g % 64 == 0
    why = "group % 64 == 0"
    if ok and _on_tpu(interpret) and not _lane_ok(g // 2, k // 2):
        ok, why = False, ("group % 256 == 0 on TPU (de-interleaved x tile "
                          "lane dim is group/2)")
    if not ok and (k, p.shape[1], g, "w4") not in _warned_shapes:
        _warned_shapes.add((k, p.shape[1], g, "w4"))
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            "wq_matmul4: packed store [%d, %d] (group %d) cannot tile for "
            "the W4A16 kernel (needs %s); falling back to "
            "dequantize-then-matmul", k, p.shape[1], g, why)
    return ok


def _kernel(x_ref, w_ref, s_ref, o_ref, acc, *, nk, contract):
    """Shared body for both W8 orientations: dequantize one weight tile
    (codes · broadcast scale row) and accumulate the dot.  ``contract`` is
    the weight-side contraction dim: 0 for ``x @ W`` ([g, bn] tiles), 1 for
    ``x @ Wᵀ`` ([g, bk] tiles).  The scale arrives as a [1, 1, bn] block of
    the 3-D [K/g, 1, N] view (a flat [1, bn] block would have sublane dim 1
    — illegal under Mosaic's (8, 128) tiling unless the array is one row);
    ``s_ref[0]`` recovers the broadcastable row."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    # dequantize with an f32 product, cast ONCE into the ACTIVATION dtype,
    # and let the MXU accumulate in f32: bf16 activations then ride the
    # MXU's native bf16 multipliers (an all-f32 dot here measured the whole
    # kernel BELOW the bf16 baseline on chip — fp32 matmul throughput is a
    # fraction of bf16's), and the f32-product-then-cast exactly matches
    # ``dequantize_weight``'s rounding, so the kernel agrees with the
    # fallback path element-for-element.
    x = x_ref[...]
    w = (w_ref[...].astype(jnp.float32)
         * s_ref[0].astype(jnp.float32)).astype(x.dtype)
    acc[...] += jax.lax.dot_general(
        x, w, (((1,), (contract,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _kernel4(xe_ref, xo_ref, p_ref, s_ref, o_ref, acc, *, nk):
    """W4A16 body: one [g/2, bn] byte tile unpacks to the group's EVEN rows
    (low nibbles) and ODD rows (high nibbles) — ``pack_nibbles`` folds
    adjacent dim-0 pairs — which contract against the pre-de-interleaved
    activation halves xe = x[:, 0::2], xo = x[:, 1::2].  Both halves share
    the tile's single scale row (even and odd rows belong to the same
    group), so dequant stays one broadcast multiply per nibble."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    lo, hi = unpack_nibbles_f32(p_ref[...])   # shift-free: Mosaic has no
    s = s_ref[0].astype(jnp.float32)    # int8 vector shifts ([1,1,bn]→row)
    # dequant in f32 (exact nibble × scale), then cast to the activation
    # dtype so bf16 rides the MXU's native multipliers (same finding as
    # ``_kernel``: all-f32 dots ran the kernel below the bf16 baseline)
    xdt = xe_ref.dtype
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    acc[...] += dot(xe_ref[...], (lo * s).astype(xdt))
    acc[...] += dot(xo_ref[...], (hi * s).astype(xdt))

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def kernel_t_supported(x, store, interpret: Optional[bool] = None) -> bool:
    """Transposed variant (``x @ storeᵀ``, tied-embedding unembed): store is
    [V, H] grouped along dim 0 (the embed gather's required layout), so the
    scale varies along the CONTRACTION dim within each g-row output tile —
    still a single broadcastable row per tile.  The output tile width is
    structurally pinned to g, so g must be lane-aligned (128).  H is
    contracted and must tile exactly (vocab-padded stores make V % g == 0
    by construction)."""
    if not is_quantized_weight(store):
        return False
    v, s = store["v"], store["s"]
    if v.ndim != 2 or x.ndim != 2 or x.shape[1] != v.shape[1]:
        return False
    if s.shape[1:] != v.shape[1:]:
        return False                   # dim-0 grouping only
    vocab, h = v.shape
    g = vocab // s.shape[0]
    bk = _pick(h, 512)
    ok = (vocab % g == 0 and g % 128 == 0 and bk is not None)
    why = "group % 128 == 0, plus an H divisor <= 512"
    if ok and _on_tpu(interpret) and not _lane_ok(bk, h):
        ok, why = False, "an H block divisor that is % 128 on TPU"
    if not ok and (vocab, h, g, "t") not in _warned_shapes:
        _warned_shapes.add((vocab, h, g, "t"))
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            "wq_matmul_t: tied store [%d, %d] (group %d) cannot tile for "
            "the transposed W8A16 kernel (the output tile width IS the "
            "group, so it needs %s); falling back to "
            "dequantize-then-matmul", vocab, h, g, why)
    return ok


def wq_matmul_t(x, store, *, interpret: Optional[bool] = None):
    """``x [M, H] @ dequant(store [V, H]).T`` → [M, V] with the table kept
    int8 in HBM — the tied-embedding unembed.  One output tile per
    scale-group row keeps the dequant a single broadcast multiply.  Vocabs
    that don't group-tile are padded at STORE CREATION (engine packer), not
    here — padding the table per call would re-stream the whole weight."""
    if not kernel_t_supported(x, store, interpret):
        _record_refused("wq_matmul_t")
        return x @ dequantize_weight(store, x.dtype).T
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    v, s = store["v"], store["s"]
    vocab, h = v.shape
    m0 = x.shape[0]
    pad = (-m0) % _sublane(x.dtype)
    m = m0 + pad
    g = vocab // s.shape[0]
    bm = _pick(m, 256)
    bk = _pick(h, 512)
    if bm is None or bk is None or not _preflight("wq_matmul_t", [
            ((bm, bk), (m, h)), ((g, bk), (vocab, h)),
            ((1, 1, bk), (vocab // g, 1, h)), ((bm, g), (m, vocab))],
            interpret):
        return x @ dequantize_weight(store, x.dtype).T
    trace_counts["w8t"] += 1
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nk = h // bk
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, contract=1),
        grid=(m // bm, vocab // g, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda im, jv, ik: (im, ik)),
            pl.BlockSpec((g, bk), lambda im, jv, ik: (jv, ik)),
            pl.BlockSpec((1, 1, bk), lambda im, jv, ik: (jv, 0, ik)),
        ],
        out_specs=pl.BlockSpec((bm, g), lambda im, jv, ik: (im, jv)),
        out_shape=jax.ShapeDtypeStruct((m, vocab), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, g), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, v, s[:, None, :])
    return out[:m0] if pad else out


def wq_matmul(x, store, *, interpret: Optional[bool] = None):
    """``x [M, K] @ dequant(store [K, N])`` with the weight kept int8 in HBM.

    store: ``ops/quantization.quantize_weight`` dict (dim-0 = contraction
    dim).  Returns [M, N] in ``x.dtype``.  Falls back to the XLA
    dequantize-then-matmul for unsupported layouts.
    """
    if not kernel_supported(x, store, interpret):
        _record_refused("wq_matmul")
        return x @ dequantize_weight(store, x.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    v, s = store["v"], store["s"]
    k, n = v.shape
    m0 = x.shape[0]
    pad = (-m0) % _sublane(x.dtype)     # decode token counts tile to rows
    m = m0 + pad
    g = k // s.shape[0]
    bm = _pick(m, 256)
    bn = _pick_n(n, 512)
    if not _preflight("wq_matmul", [
            (None if bm is None else (bm, g), (m, k)),
            ((g, bn), (k, n)), ((1, 1, bn), (k // g, 1, n)),
            (None if bm is None else (bm, bn), (m, n))], interpret):
        return x @ dequantize_weight(store, x.dtype)
    trace_counts["w8"] += 1
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nk = k // g
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, contract=0),
        grid=(m // bm, -(-n // bn), nk),
        in_specs=[
            pl.BlockSpec((bm, g), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((g, bn), lambda im, jn, ik: (ik, jn)),
            pl.BlockSpec((1, 1, bn), lambda im, jn, ik: (ik, 0, jn)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, v, s[:, None, :])
    return out[:m0] if pad else out


def wq_matmul4(x, store, *, interpret: Optional[bool] = None):
    """``x [M, K] @ dequant4(store)`` with the weight kept nibble-PACKED in
    HBM — ¼ the bf16 weight stream (reference FP6-LLM sub-8-bit GEMM,
    ``cuda_linear.py``: the weight is unpacked on-chip, never in HBM).

    store: ``ops/quantization.quantize_weight4`` dict
    ({"v4": int8 [K/2, N] nibble pairs, "s": f32 [K/g, N]}).  The
    activation is de-interleaved ONCE outside the kernel (xe = even K
    columns, xo = odd) so each byte tile's two nibble planes contract
    against clean contiguous tiles — no in-kernel row interleave."""
    if not kernel4_supported(x, store, interpret):
        _record_refused("wq_matmul4")
        return x @ dequantize_weight4(store, x.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    p, s = store["v4"], store["s"]
    kh, n = p.shape                     # kh = K/2
    k = 2 * kh
    m0 = x.shape[0]
    pad = (-m0) % _sublane(x.dtype)
    m = m0 + pad
    g = k // s.shape[0]
    gh = g // 2
    bm = _pick(m, 256)
    bn = _pick_n(n, 512)
    if not _preflight("wq_matmul4", [
            (None if bm is None else (bm, gh), (m, kh)),
            ((gh, bn), (kh, n)), ((1, 1, bn), (k // g, 1, n)),
            (None if bm is None else (bm, bn), (m, n))], interpret):
        return x @ dequantize_weight4(store, x.dtype)
    trace_counts["w4"] += 1
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    xe = x[:, 0::2]                     # [M, K/2] — O(M·K) shuffle, free
    xo = x[:, 1::2]                     # next to the GEMM it feeds
    nk = k // g
    out = pl.pallas_call(
        functools.partial(_kernel4, nk=nk),
        grid=(m // bm, -(-n // bn), nk),
        in_specs=[
            pl.BlockSpec((bm, gh), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((bm, gh), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((gh, bn), lambda im, jn, ik: (ik, jn)),
            pl.BlockSpec((1, 1, bn), lambda im, jn, ik: (ik, 0, jn)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xe, xo, p, s[:, None, :])
    return out[:m0] if pad else out


def wq_any(x, store, *, interpret: Optional[bool] = None):
    """Dispatch a 2-D quantized store to its kernel (int8 → wq_matmul,
    nibble-packed → wq_matmul4)."""
    if is_quantized_weight4(store):
        return wq_matmul4(x, store, interpret=interpret)
    return wq_matmul(x, store, interpret=interpret)


# ------------------------------------------------------------ 2-D store views
def store_as_2d(store):
    """A free (row-major reshape) 2-D view of a 3-D quantized store whose
    flattened layout keeps uniform dim-0 grouping, or None.

    Two cases cover the attention projections (round-4 verdict item 3):
    - grouped along dim 0 (qkv [H, heads, hd]): flatten the TRAILING dims
      into N — rows keep their group.
    - grouped along dim 1 of 3 (attn-out [heads, hd, H], group g | hd):
      flatten the LEADING two dims into K.  Flat row r = head·hd + d maps
      to scale row r // g = head·(hd/g) + d//g exactly because g divides
      hd — grouping stays uniform.
    Packed (v4) stores only support the dim-0-grouped case (nibble pairs
    fold dim 0).
    """
    if is_quantized_weight(store):
        v, s = store["v"], store["s"]
        if v.ndim != 3:
            return None
        if s.shape[1:] == v.shape[1:]:          # grouped dim 0
            return {"v": v.reshape(v.shape[0], -1),
                    "s": s.reshape(s.shape[0], -1)}
        if (s.shape[0] == v.shape[0] and s.shape[2:] == v.shape[2:]
                and v.shape[1] % s.shape[1] == 0):   # grouped dim 1
            return {"v": v.reshape(-1, v.shape[2]),
                    "s": s.reshape(-1, s.shape[2])}
        return None
    if is_quantized_weight4(store):
        p, s = store["v4"], store["s"]
        if p.ndim != 3 or s.shape[1:] != p.shape[1:]:
            return None
        return {"v4": p.reshape(p.shape[0], -1),
                "s": s.reshape(s.shape[0], -1)}
    return None


# ------------------------------------------------------------- TP shard_map
def wq_matmul_tp(x, store, mesh, mode: str, axis: str = "tp", *,
                 interpret: Optional[bool] = None):
    """Run a quantized-weight matmul with the store SHARDED over ``axis``,
    keeping the Pallas kernel engaged per shard (GSPMD cannot partition the
    Mosaic custom call, so the round-3 design bypassed the kernel for tp>1
    — exactly the bandwidth-hungriest configs; reference AutoTP runs its
    quantized GEMM per rank, ``module_inject/auto_tp.py:273``).

    ``mode``:
    - "col": store [K, N] sharded on N (qkv / MLP-in / untied lm_head).
      x is replicated; output comes back N-sharded.
    - "row": store [K, N] sharded on K (attn-out / MLP-out).  x arrives
      K-sharded, each shard computes a partial product, a psum closes it.
    - "tcol": transposed tied-unembed store [V, H] sharded on V; output
      comes back V-sharded.
    x: [M, K] (2-D; callers flatten leading dims).  Inside each shard the
    usual eligibility checks run on LOCAL shapes, so an unsupported slice
    falls back to dequant-matmul per shard — still correctly partitioned.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if mesh is None or mesh.shape.get(axis, 1) == 1:
        if mode == "tcol":
            return wq_matmul_t(x, store, interpret=interpret)
        return wq_any(x, store, interpret=interpret)

    packed = is_quantized_weight4(store)
    key = "v4" if packed else "v"
    size = mesh.shape[axis]
    v, s = store[key], store["s"]
    d = 1 if mode == "col" else 0
    if (v.shape[d] % size or s.shape[d] % size
            or (mode == "row" and x.shape[1] % size)):
        # shard boundary would split a group / nibble pair — stay on the
        # GSPMD dequant path, which partitions any layout correctly
        w = (dequantize_weight4(store, x.dtype) if packed
             else dequantize_weight(store, x.dtype))
        return x @ (w.T if mode == "tcol" else w)
    if mode == "col":
        wspec = {key: P(None, axis), "s": P(None, axis)}
        xspec, ospec = P(), P(None, axis)
    elif mode == "row":
        wspec = {key: P(axis, None), "s": P(axis, None)}
        xspec, ospec = P(None, axis), P()
    elif mode == "tcol":
        if packed:
            # no packed transposed kernel exists — keep the documented
            # graceful-fallback contract (dequant partitions fine)
            return x @ dequantize_weight4(store, x.dtype).T
        wspec = {key: P(axis, None), "s": P(axis, None)}
        xspec, ospec = P(), P(None, axis)
    else:
        raise ValueError(f"mode must be col|row|tcol, got {mode!r}")

    def local(xs, vs, ss):
        st = {key: vs, "s": ss}
        if mode == "tcol":
            return wq_matmul_t(xs, st, interpret=interpret)
        y = wq_any(xs, st, interpret=interpret)
        if mode == "row":
            y = jax.lax.psum(y, axis)
        return y

    return shard_map(
        local, mesh=mesh, in_specs=(xspec, wspec[key], wspec["s"]),
        out_specs=ospec, check_vma=False)(x, store[key], store["s"])
