"""Block quantization ops — int8/int4 symmetric, per-block scales.

TPU-native analog of the reference quantizer kernels
(csrc/quantization/quantize.cu, fake_quantizer.cu; python surface
deepspeed/ops/quantizer + inference/quantization).  Semantics match the
reference's symmetric blocked quantizer: a tensor is viewed as flat blocks of
``block_size`` values; each block stores int values in [-(2^(bits-1)-1),
2^(bits-1)-1] plus one fp scale.  On TPU this is a handful of elementwise ops
+ a reduce per block — XLA fuses it into surrounding code; there is no kernel
to write, the value is the WIRE/STORAGE format (quantized collectives, ZeRO++
weight gathers, ZeRO-Inference weight storage).

int4 packs two values per int8 byte (reference quantize_int4.cu) so the wire
moves 4 bits/value.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class QuantizedBlocks(NamedTuple):
    """values: int8 [N/bs, bs] (int4: packed [N/bs, bs/2]); scales fp32
    [N/bs, 1]; meta carries the original shape/dtype/bits for dequant."""

    values: jax.Array
    scales: jax.Array
    shape: Tuple[int, ...]
    dtype: object
    bits: int
    block_size: int


def _pad_to_blocks(flat, block_size):
    n = flat.shape[0]
    pad = (-n) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, n


def pack_nibbles(q):
    """Fold int8 values (range [-7, 7]) pairwise along dim 0 into bytes:
    low nibble = even index, high = odd.  Shared by the blockwise wire
    format and the packed weight store."""
    lo = q[0::2] & 0x0F
    hi = (q[1::2] & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_nibbles(p):
    """Inverse of ``pack_nibbles``: (lo, hi) sign-extended int8 halves."""
    lo = (p << 4).astype(jnp.int8) >> 4
    hi = p >> 4                                      # arithmetic shift
    return lo, hi


def unpack_nibbles_f32(p):
    """Shift-free ``unpack_nibbles`` returning float32 — the in-kernel
    variant: Mosaic cannot legalize shifts on int8 vectors
    (``arith.shli : vector<..xi8>``, found on first chip contact round 5),
    so the byte is widened to f32 (exact for [-128, 127]) and the nibbles
    split with floor/multiply VPU arithmetic (all quantities are small
    integers, exact in f32)."""
    b = p.astype(jnp.float32)
    ub = jnp.where(b < 0, b + 256.0, b)              # unsigned byte view
    hi4 = jnp.floor(ub * 0.0625)                     # ub // 16
    lo4 = ub - hi4 * 16.0
    lo = lo4 - jnp.where(lo4 >= 8.0, 16.0, 0.0)      # sign-extend 4-bit
    hi = hi4 - jnp.where(hi4 >= 8.0, 16.0, 0.0)
    return lo, hi


def quantize_blockwise(x, *, bits: int = 8,
                       block_size: int = 256) -> QuantizedBlocks:
    """Symmetric per-block quantization (reference quantize.cu semantics:
    scale = max|x| / qmax per block, stochastic-free round-to-nearest)."""
    if bits not in (2, 4, 8):
        raise ValueError(f"bits must be 2, 4, or 8, got {bits}")
    orig_shape, orig_dtype = x.shape, x.dtype
    flat, _ = _pad_to_blocks(x.reshape(-1).astype(jnp.float32), block_size)
    blocks = flat.reshape(-1, block_size)
    qmax = float(2 ** (bits - 1) - 1)
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scales = absmax / qmax
    inv = jnp.where(scales > 0, 1.0 / jnp.maximum(scales, 1e-30), 0.0)
    q = jnp.clip(jnp.round(blocks * inv), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        # pack pairs along the block dim: transpose in/out of the shared
        # dim-0 packer
        q = pack_nibbles(q.T).T
    return QuantizedBlocks(values=q, scales=scales, shape=orig_shape,
                           dtype=orig_dtype, bits=bits, block_size=block_size)


def dequantize_blockwise(qb: QuantizedBlocks) -> jax.Array:
    q = qb.values
    if qb.bits == 4:
        lo, hi = unpack_nibbles(q)
        q = jnp.stack([lo, hi], axis=-1).reshape(q.shape[0], -1)
    x = q.astype(jnp.float32) * qb.scales
    n = 1
    for d in qb.shape:
        n *= d
    return x.reshape(-1)[:n].reshape(qb.shape).astype(qb.dtype)


def quantize_dequantize(x, *, bits: int = 8, block_size: int = 256):
    """Fake-quant (reference fake_quantizer.cu): the QDQ roundtrip used for
    error injection / compression emulation inside fp math."""
    return dequantize_blockwise(quantize_blockwise(x, bits=bits,
                                                   block_size=block_size))


# ---------------------------------------------------------------- collectives
def quantized_all_gather(x, mesh, axis: str, *, bits: int = 8,
                         block_size: int = 256, gather_dim: int = 0):
    """All-gather ``x`` (sharded on ``gather_dim`` over mesh axis) moving int
    values + fp scales on the wire instead of full-precision values — the
    ZeRO++ qwZ quantized weight all-gather
    (reference runtime/zero/stage3.py:1497 all_gather_coalesced with
    quantization=..., csrc/quantization/ kernels).

    Returns the gathered, dequantized array (replicated over ``axis``).
    Compression: bits/16 of the bf16 wire volume (+ scales overhead).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    size = mesh.shape[axis]
    if size == 1:
        return x
    if x.shape[gather_dim] % size:
        raise ValueError(f"dim {gather_dim} ({x.shape[gather_dim]}) not "
                         f"divisible by mesh axis {axis}={size}")

    in_spec = [None] * x.ndim
    in_spec[gather_dim] = axis

    def local(xs):
        return qag_local(xs, axis, size, gather_dim,
                         bits=bits, block_size=block_size)

    return shard_map(local, mesh=mesh, in_specs=P(*in_spec),
                     out_specs=P(), check_vma=False)(x)


def _wire_block(n: int, block_size: int) -> int:
    """Effective wire block for an ``n``-element slice: the configured size,
    halved (min 8) while the slice wouldn't even half-fill it.  Blockwise
    padding is pure wire waste — a 4-element bias slice padded to a 256
    block ships 64x its data; real models carry many such small leaves
    (biases, norms) next to the big matrices."""
    b = block_size
    while b > 8 and n <= b // 2:
        b //= 2
    return b


def _log_qwire(kind: str, bits: int, payload_bytes: int, axis: str,
               size: int, ring_factor) -> None:
    """Trace-time wire accounting for the quantized collective bodies: the
    telemetry byte counters see the int codes + fp32 scales at their WIRE
    width, tagged with the format (``all_gather_q8``, ``all_to_all_q4``) —
    comm/collectives.log_wire.  ``ring_factor(payload, n)`` maps payload to
    per-participant ring bytes per the convention in collectives.py."""
    from deepspeed_tpu.comm.collectives import log_wire
    log_wire(f"{kind}_q{bits}", ring_factor(payload_bytes, size), axis)


def _qb_bytes(qb: QuantizedBlocks) -> int:
    return (int(qb.values.size) * qb.values.dtype.itemsize
            + int(qb.scales.size) * qb.scales.dtype.itemsize)


def q_gather_rows(flat, axis: str, size: int, *, bits: int = 8,
                  block_size: int = 256):
    """Quantized stacked all-gather of one flat buffer, inside
    ``shard_map`` over ``axis``: ``[B] -> [size, B]``.  Int codes + fp32
    block scales on the wire, per-member dequant back to ``flat.dtype``.
    THE quantized-gather wire core — ``qag_local`` and the composable
    pipeline's ``_qwire_exchange`` forward (runtime/zero.py) both run
    through here, so the wire format and its byte accounting live once."""
    qb = quantize_blockwise(flat, bits=bits,
                            block_size=_wire_block(flat.size, block_size))
    _log_qwire("all_gather", bits, _qb_bytes(qb), axis, size,
               lambda b, n: b * (n - 1))
    vg = jax.lax.all_gather(qb.values, axis)             # int8 on the wire
    sg = jax.lax.all_gather(qb.scales, axis)
    return jnp.stack([
        dequantize_blockwise(qb._replace(values=vg[i], scales=sg[i]))
        for i in range(size)])


def q_reduce_rows(rows, axis: str, size: int, *, bits: int = 8,
                  block_size: int = 256):
    """Quantized reduce-scatter of pre-split rows, inside ``shard_map``
    over ``axis``: ``rows[j]`` is this device's contribution to member j;
    returns the sum over devices of their row for THIS member (``[size,
    B] -> [B]``, ``rows.dtype``).  Each row quantizes independently
    (blocks never straddle member boundaries), one all-to-all moves the
    codes + scales.  THE quantized-reduce wire core — ``qrs_local`` and
    ``_qwire_exchange``'s backward both run through here."""
    bs = _wire_block(rows.shape[1], block_size)
    qbs = [quantize_blockwise(rows[i], bits=bits, block_size=bs)
           for i in range(size)]
    _log_qwire("all_to_all", bits, sum(_qb_bytes(q) for q in qbs), axis,
               size, lambda b, n: b * (n - 1) // n)
    v = jax.lax.all_to_all(jnp.stack([q.values for q in qbs]),
                           axis, 0, 0, tiled=False)
    s = jax.lax.all_to_all(jnp.stack([q.scales for q in qbs]),
                           axis, 0, 0, tiled=False)
    total = jnp.zeros(rows.shape[1:], jnp.float32)
    for i in range(size):
        qi = qbs[0]._replace(values=v[i], scales=s[i])
        total = total + dequantize_blockwise(qi).astype(jnp.float32)
    return total.astype(rows.dtype)


def q_all_to_all(x, axis: str, size: int, split_axis: int, concat_axis: int,
                 *, bits: int = 8, block_size: int = 256):
    """Quantized all-to-all, inside ``shard_map`` over ``axis``: the exact
    data movement of ``lax.all_to_all(x, axis, split_axis, concat_axis,
    tiled=True)`` with int codes + fp32 block scales on the wire instead of
    full-width values.  Each destination's slice quantizes INDEPENDENTLY
    (blocks never straddle destinations, same invariant as
    ``q_reduce_rows``); one stacked a2a pair moves codes + scales; each
    received slice dequants back to ``x.dtype`` and concats along
    ``concat_axis``.  THE quantized-a2a wire core — the MoE expert
    dispatch/combine exchanges (moe/comm.py) run through here, so the wire
    format and its ``all_to_all_q{bits}`` byte accounting live once."""
    parts = jnp.split(x, size, axis=split_axis)
    bs = _wire_block(parts[0].size, block_size)
    qbs = [quantize_blockwise(p, bits=bits, block_size=bs) for p in parts]
    _log_qwire("all_to_all", bits, sum(_qb_bytes(q) for q in qbs), axis,
               size, lambda b, n: b * (n - 1) // n)
    v = jax.lax.all_to_all(jnp.stack([q.values for q in qbs]),
                           axis, 0, 0, tiled=False)
    s = jax.lax.all_to_all(jnp.stack([q.scales for q in qbs]),
                           axis, 0, 0, tiled=False)
    return jnp.concatenate([
        dequantize_blockwise(qbs[0]._replace(values=v[i], scales=s[i]))
        for i in range(size)], axis=concat_axis).astype(x.dtype)


def qag_local(xs, axis: str, size: int, gather_dim: int = 0, *,
              bits: int = 8, block_size: int = 256):
    """Per-device body of a quantized all-gather (inside ``shard_map`` over
    ``axis``): int values + fp32 block scales on the wire, per-member dequant,
    concat along ``gather_dim``.  Shared by ``quantized_all_gather`` and
    ``qpsum_local``."""
    rows = q_gather_rows(xs.reshape(-1), axis, size, bits=bits,
                         block_size=block_size)
    return jnp.concatenate([rows[i].reshape(xs.shape) for i in range(size)],
                           axis=gather_dim)


def qrs_local(xs, axis: str, size: int, scatter_dim: int = 0, *,
              bits: int = 8, block_size: int = 256):
    """Per-device body of a quantized reduce-scatter, for use INSIDE an
    existing ``shard_map`` over ``axis`` (the engine's qgZ grad path calls
    this directly; ``quantized_psum_scatter`` wraps it for standalone use).

    ``xs`` is this device's full-shape partial contribution.  Quantize each
    target shard's slice INDEPENDENTLY (blocks never straddle shard
    boundaries), all_to_all so member i receives every member's contribution
    for slice i, dequant + sum.  Wire format: int values + fp32 block scales
    — bits/32 of the fp32 reduce volume (+ scales overhead).
    Returns this device's reduced slice (shape[scatter_dim] / size).
    """
    parts = jnp.split(xs, size, axis=scatter_dim)
    rows = jnp.stack([p.reshape(-1) for p in parts])
    total = q_reduce_rows(rows, axis, size, bits=bits,
                          block_size=block_size)
    return total.reshape(parts[0].shape)


def qpsum_local(xs, axis: str, size: int, scatter_dim: int = 0, *,
                bits: int = 8, block_size: int = 256):
    """Quantized all-reduce body (inside ``shard_map`` over ``axis``):
    quantized reduce-scatter then a quantized all-gather of the reduced
    slices, so BOTH wire phases move ints — total ≈ (1 + 1/size)·bits/32 of
    one fp32 ring allreduce.  Used for qgZ leaves whose layout stays
    replicated.  Returns the full reduced array on every device."""
    total = qrs_local(xs, axis, size, scatter_dim,
                      bits=bits, block_size=block_size)
    return qag_local(total, axis, size, scatter_dim,
                     bits=bits, block_size=block_size).astype(xs.dtype)


def quantized_psum_scatter(x, mesh, axis: str, *, bits: int = 8,
                           block_size: int = 256, scatter_dim: int = 0):
    """Reduce-scatter with int-quantized wire format + fp32 scale exchange —
    the qgZ quantized gradient reduce direction (reference
    runtime/zero/stage3.py quantized_reduce_scatter path,
    csrc/quantization/swizzled_quantize.cu).  all-to-all of quantized shard
    contributions, local dequant + sum.

    x is replicated per-shard-group input (leading dim divisible by axis
    size); returns this shard's reduced slice.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    size = mesh.shape[axis]
    if size == 1:
        return x
    if x.shape[scatter_dim] % size:
        raise ValueError(f"dim {scatter_dim} ({x.shape[scatter_dim]}) not "
                         f"divisible by mesh axis {axis}={size}")

    out_spec = [None] * x.ndim
    out_spec[scatter_dim] = axis

    def local(xs):
        return qrs_local(xs, axis, size, scatter_dim,
                         bits=bits, block_size=block_size)

    return shard_map(local, mesh=mesh, in_specs=P(),
                     out_specs=P(*out_spec), check_vma=False)(x)


def quantized_weight_gather(x, mesh, axis: str, gather_dim: int, *,
                            bits: int = 8, block_size: int = 256):
    """Differentiable ZeRO++ qwZ gather: forward moves int values on the wire
    (quantized_all_gather); backward constrains the cotangent back to the
    sharded layout so XLA emits the ordinary grad reduce-scatter — weight
    quantization never biases gradients (reference: qwZ quantizes the fwd/bwd
    weight all-gather only, runtime/zero/stage3.py:1497)."""
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * x.ndim
    spec[gather_dim] = axis
    shard_sharding = NamedSharding(mesh, P(*spec))
    dtype = x.dtype

    @_jax.custom_vjp
    def gather(v):
        return quantized_all_gather(v, mesh, axis, bits=bits,
                                    block_size=block_size,
                                    gather_dim=gather_dim)

    def fwd(v):
        return gather(v), None

    def bwd(_, ct):
        return (_jax.lax.with_sharding_constraint(
            ct.astype(dtype), shard_sharding),)

    gather.defvjp(fwd, bwd)
    return gather(x)


def weight_group_size(shape, group: int, min_group: int = 16) -> int:
    """Effective dim-0 group for ``quantize_weight``: the largest power-of-2
    divisor of shape[0] that is ≤ ``group``; 0 (= don't quantize) if even
    ``min_group`` doesn't divide."""
    if not shape:
        return 0
    g = 1
    while g * 2 <= group and shape[0] % (g * 2) == 0:
        g *= 2
    return g if g >= min_group else 0


def quantize_weight(w, *, bits: int = 8, group: int = 128, dim: int = 0):
    """Shape-preserving group-wise symmetric weight quantization — the
    serving weight-storage format (reference
    inference/v2/modules/implementations/linear/quantized_linear.py:205 FP6
    W6A16 and inference/quantization/layers.py:114 matmul-time dequant; here
    int8 codes + per-(group-along-``dim`` × channel) fp32 scales).

    w → {"v": int8 same shape, "s": f32 with shape[dim] → shape[dim]/g}.
    Keeping the LEAF SHAPE (unlike the flat ``quantize_blockwise`` wire
    format) means the store shards exactly like the weight it replaces — the
    quant × tensor-parallel composition falls out — and consumers dequantize
    at their use site, so the full-precision tree never exists at rest.
    ``dim`` defaults to 0 (the usual contraction dim); MoE expert stacks
    [E, in, out] group along dim=1.
    """
    w = jnp.asarray(w)
    g = weight_group_size((w.shape[dim],), group)
    if g == 0:
        raise ValueError(f"dim {dim} of {w.shape} has no usable group "
                         f"≤ {group}")
    qmax = float(2 ** (bits - 1) - 1)
    wm = jnp.moveaxis(w, dim, 0)
    d0 = wm.shape[0]
    wf = wm.astype(jnp.float32).reshape((d0 // g, g) + wm.shape[1:])
    absmax = jnp.max(jnp.abs(wf), axis=1)                  # [d0/g, *rest]
    s = absmax / qmax
    inv = jnp.where(s > 0, 1.0 / jnp.maximum(s, 1e-30), 0.0)
    q = jnp.clip(jnp.round(wf * inv[:, None]), -qmax, qmax)
    return {"v": jnp.moveaxis(q.reshape(wm.shape).astype(jnp.int8), 0, dim),
            "s": jnp.moveaxis(s, 0, dim)}


def _store_dim(d) -> int:
    """The grouped dim of a store: where codes and scales disagree."""
    v, s = d["v"], d["s"]
    for i, (a, b) in enumerate(zip(v.shape, s.shape)):
        if a != b:
            return i
    return 0


def dequantize_weight(d, dtype=jnp.bfloat16):
    """Inverse of ``quantize_weight`` (jit-safe; the per-consumer call)."""
    v, s = d["v"], d["s"]
    dim = _store_dim(d)
    vm = jnp.moveaxis(v, dim, 0)
    sm = jnp.moveaxis(s, dim, 0)
    g = vm.shape[0] // sm.shape[0]
    wf = vm.astype(jnp.float32).reshape((sm.shape[0], g) + vm.shape[1:])
    return jnp.moveaxis((wf * sm[:, None]).reshape(vm.shape), 0,
                        dim).astype(dtype)


def is_quantized_weight(leaf) -> bool:
    return (isinstance(leaf, dict) and set(leaf) == {"v", "s"}
            and getattr(leaf["v"], "dtype", None) == jnp.int8)


def quantize_weight4(w, *, group: int = 128):
    """int4 NIBBLE-PACKED weight store: ¼ the bf16 bytes (vs the
    shape-preserving int8 store's ½) — the ZeRO-Inference single-chip
    HBM-fit format (reference inference/quantization int4 path,
    csrc/quantization/quantize_int4.cu).

    Packing folds dim-0 PAIRS into one byte (low nibble = even row, high =
    odd row), so codes are [d0/2, *rest] — NOT the weight's shape.  That
    breaks the shard-like-the-weight property, so this format is for
    UNSHARDED (single-shard / mesh-free) serving only; sharded or
    kernel-eligible paths use ``quantize_weight``.
    Returns {"v4": int8 [d0/2, *rest], "s": f32 [d0/g, *rest]}."""
    w = jnp.asarray(w)
    if w.shape[0] % 2:
        raise ValueError(f"dim 0 of {w.shape} is odd — nibble packing "
                         f"folds row pairs")
    q = quantize_weight(w, bits=4, group=group)      # shared scale math
    return {"v4": pack_nibbles(q["v"]), "s": q["s"]}


def is_quantized_weight4(leaf) -> bool:
    return (isinstance(leaf, dict) and set(leaf) == {"v4", "s"}
            and getattr(leaf["v4"], "dtype", None) == jnp.int8)


def quantized_codes(leaf):
    """The codes array of a quantized store leaf (int8 ``v`` or packed
    ``v4``), or None when ``leaf`` is not a store — the one place consumers
    ask "is this quantized, and what shape is it"."""
    if is_quantized_weight(leaf):
        return leaf["v"]
    if is_quantized_weight4(leaf):
        return leaf["v4"]
    return None


def dequantize_weight4(d, dtype=jnp.bfloat16):
    """Inverse of ``quantize_weight4`` (jit-safe; the per-consumer call)."""
    p, s = d["v4"], d["s"]
    lo, hi = unpack_nibbles(p)
    d0 = 2 * p.shape[0]
    q = jnp.stack([lo, hi], axis=1).reshape((d0,) + p.shape[1:])
    return dequantize_weight({"v": q, "s": s}, dtype)


def store_shardings(store, shardings, mesh):
    """NamedSharding tree for a ``quantize_weight`` param store: codes take
    the replaced weight's sharding verbatim (shape-preserving format); scales
    take it too unless the grouped-dim group count doesn't divide over the
    sharded axis, in which case the small scale tensor just replicates.
    This is what makes quant × tensor-parallel compose (round-3 verdict item
    4: the old flat store dropped ``in_shardings`` and rejected tp>1).

    Nibble-packed (v4) leaves shard like the weight too — "pack after
    shard": byte row r holds global rows 2r/2r+1, so a dim-0 shard of the
    packed codes IS the packed shard of the weight as long as the shard
    boundary never splits a row pair or a scale group (checked per dim;
    fall back to replicating the leaf when it would)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def axis_size(ax):
        axes = (ax,) if isinstance(ax, str) else ax
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n

    def f(p, sh):
        if is_quantized_weight4(p):
            spec = list(sh.spec)
            spec += [None] * (p["v4"].ndim - len(spec))
            s_spec = list(spec)
            for d, ax in enumerate(spec):
                if ax is None:
                    continue
                n = axis_size(ax)
                if p["v4"].shape[d] % n:
                    spec[d] = None          # would split a nibble pair
                if p["s"].shape[d] % n:
                    s_spec[d] = None        # would split a scale group
            return {"v4": NamedSharding(mesh, P(*spec)),
                    "s": NamedSharding(mesh, P(*s_spec))}
        if not is_quantized_weight(p):
            return sh
        spec = list(sh.spec)
        spec += [None] * (p["v"].ndim - len(spec))
        s_spec = list(spec)
        d = _store_dim(p)
        ax = s_spec[d]
        if ax is not None and p["s"].shape[d] % axis_size(ax):
            s_spec[d] = None
        # vocab-padded stores: codes may be longer than the weight was —
        # re-check the padded dim still divides
        for dd, a in enumerate(spec):
            if a is not None and p["v"].shape[dd] % axis_size(a):
                spec[dd] = None
        return {"v": NamedSharding(mesh, P(*spec)),
                "s": NamedSharding(mesh, P(*s_spec))}
    return jax.tree_util.tree_map(
        f, store, shardings,
        is_leaf=lambda x: is_quantized_weight(x) or is_quantized_weight4(x))


def make_param_store(params, *, bits: int = 8, block_size: int = 128,
                     pack4: bool = False):
    """Pack a param tree into int-quantized storage + a jit-safe materializer
    — ZeRO-Inference weight storage (reference inference/quantization/
    __init__.py _init_group_wise_weight_quantization: weights live in HBM at
    ``bits``/16 of their bf16 size; each consumer dequantizes on the fly and
    XLA frees the transient fp buffer after use).

    Returns (stored, materialize): ``stored`` is a pytree holding
    {"v": int8, "s": fp32} (shape-preserving ``quantize_weight`` format, so
    the store inherits the weight's sharding) for quantized leaves and the
    raw leaf otherwise; ``materialize(stored)`` rebuilds the original tree
    inside jit.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    stored, metas = [], []
    for leaf in leaves:
        leaf = jnp.asarray(leaf)
        if (jnp.issubdtype(leaf.dtype, jnp.floating)
                and leaf.ndim >= 2        # matmul weights only: quantizing
                # 1-D norm scales/biases costs accuracy for negligible bytes
                # (matches the v2 pack() policy and the reference's
                # linear-weights-only restriction)
                and leaf.size >= block_size
                and weight_group_size(leaf.shape, block_size)):
            if pack4 and leaf.shape[0] % 2 == 0:
                stored.append(quantize_weight4(leaf, group=block_size))
            else:
                stored.append(quantize_weight(leaf, bits=bits,
                                              group=block_size))
            metas.append(leaf.dtype)
        else:
            stored.append(leaf)
            metas.append(None)

    def materialize(stored_tree):
        leaves_in = jax.tree_util.tree_leaves(
            stored_tree,
            is_leaf=lambda x: (is_quantized_weight(x)
                               or is_quantized_weight4(x)))
        out = []
        for item, meta in zip(leaves_in, metas):
            if meta is None:
                out.append(item)
            elif is_quantized_weight4(item):
                out.append(dequantize_weight4(item, meta))
            else:
                out.append(dequantize_weight(item, meta))
        return jax.tree_util.tree_unflatten(treedef, out)

    # the store keeps the PARAM TREE structure (quantized leaves become
    # {"v", "s"} subtrees) so sharding trees map over it directly
    return jax.tree_util.tree_unflatten(treedef, stored), materialize


# ------------------------------------------------------------- fp8 (FP6-LLM)
_FP8_MAX = {"e4m3": 448.0, "e5m2": 57344.0}


def quantize_fp8(x, *, fmt: str = "e4m3",
                 block_size: int = 256) -> QuantizedBlocks:
    """Blockwise-scaled fp8 quantization — the FP-quantizer analog
    (reference csrc/fp_quantizer/fp_quantize.cu: FP6/FP8/FP12 bit-packed
    formats for weight storage).  On TPU the natural targets are the NATIVE
    XLA fp8 dtypes (float8_e4m3fn / float8_e5m2); each block carries one fp32
    scale so the fp8 dynamic range is centered on the block's magnitude.

    values dtype is jnp.float8_*; fp8 blocks dequantize with
    ``dequantize_fp8`` (the int path keeps ``dequantize_blockwise``)."""
    if fmt not in _FP8_MAX:
        raise ValueError(f"fmt must be one of {sorted(_FP8_MAX)}, got {fmt!r}")
    dt = jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2
    orig_shape, orig_dtype = x.shape, x.dtype
    flat, _ = _pad_to_blocks(x.reshape(-1).astype(jnp.float32), block_size)
    blocks = flat.reshape(-1, block_size)
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scales = absmax / _FP8_MAX[fmt]
    inv = jnp.where(scales > 0, 1.0 / jnp.maximum(scales, 1e-30), 0.0)
    q = (blocks * inv).astype(dt)
    return QuantizedBlocks(values=q, scales=scales, shape=orig_shape,
                           dtype=orig_dtype, bits=8, block_size=block_size)


def dequantize_fp8(qb: QuantizedBlocks) -> jax.Array:
    # fp8 values cast-to-fp32 ARE their numeric values, so the generic
    # astype-multiply-trim path applies unchanged (bits=8 ⇒ no nibble unpack)
    return dequantize_blockwise(qb)


def quantize_dequantize_fp8(x, *, fmt: str = "e4m3", block_size: int = 256):
    return dequantize_fp8(quantize_fp8(x, fmt=fmt, block_size=block_size))
