"""Ring collective-matmul fusions — overlap TP collectives with the matmuls
that produce/consume them.

The TP hot path has two collective shapes (reference: Megatron-style
row/column-parallel linears, module_inject/auto_tp.py; here the matmuls in
linear.py / models/gpt.py):

- **all-gather → matmul**: activations sharded on a sequence/row dim must be
  gathered before a matmul consumes every row.
- **matmul → reduce-scatter / all-reduce**: a contraction-dim-sharded matmul
  produces partial sums that must be reduced (row-parallel linear).

XLA emits each as one blocking collective at the matmul boundary.  The
decomposition here (Wang et al. "Overlap Communication with Dependent
Computation via Decomposition", ASPLOS'23; T3 arXiv:2401.16677; the same
``ppermute`` ring ``sequence/ring.py`` uses for KV rotation) splits the
matmul into ``axis``-many chunk matmuls and replaces the collective with
neighbor ``ppermute`` hops issued BETWEEN them — each hop's wire time
overlaps the previous chunk's MXU time, and the scheduler needs no
heroics: the dependence structure itself is overlapped.

Selection rides the op registry (ops/registry.py) like every other op:
``xla`` is the unfused reference (the numeric ground truth — one collective
at the boundary, what GSPMD would do), and the fast path carries the ring
decomposition.  The fast slot is registered under the registry's ``pallas``
key for dispatch parity (TPU-gated auto selection, ``impl=`` forcing,
``DSTPU_DISABLE_PALLAS``) — it is a shard_map/ppermute program, not a
Pallas kernel, but the dispatch semantics are identical and the ring only
wins where ppermute rides ICI.

All entries are numerics-exact vs their unfused reference: the gather
fusion is pure data movement (bitwise); the reduce fusions sum the same
per-device partials in ring order (tolerance-exact — summation order may
differ from XLA's reduction tree).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.comm.comm import comms_logger
from deepspeed_tpu.telemetry.registry import record_collective
from jax import shard_map


def _batch_spec(b: int, mesh: Mesh, batch_axes: Tuple[str, ...]):
    """Batch-dim spec entry: the (dp, fsdp) product when it divides B, else
    replicated (serving-sized batches must not force a batch reshard)."""
    axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if not axes or b % size:
        return None
    return axes if len(axes) > 1 else axes[0]


def _check(cond: bool, msg: str):
    if not cond:
        raise ValueError(msg)


def _log_ring(kind: str, nbytes: int, axis: str):
    comms_logger.record(kind, nbytes, axis)
    # per-link split rides along, same as comm/collectives._log: ring
    # ppermute hops crossing a host boundary book as dcn, the rest ici
    from deepspeed_tpu.comm.collectives import axis_dcn_fraction
    record_collective(kind, nbytes, axis,
                      dcn_fraction=axis_dcn_fraction(axis))


# --------------------------------------------------------- all-gather → matmul

def _ag_matmul_xla(x, w, mesh, axis, batch_axes):
    """Unfused reference: one all-gather of x's sequence dim, then the full
    matmul — the boundary collective GSPMD inserts."""
    bspec = _batch_spec(x.shape[0], mesh, batch_axes)

    def body(xl, wl):
        xg = lax.all_gather(xl, axis, axis=1, tiled=True)
        return xg @ wl

    return shard_map(body, mesh=mesh,
                     in_specs=(P(bspec, axis, None), P(None, None)),
                     out_specs=P(bspec, None, None), check_vma=False)(x, w)


def _ag_matmul_ring(x, w, mesh, axis, batch_axes):
    """Fused ring: at step s each device matmuls the x block it currently
    holds (owner ``(me − s) mod n``) into that owner's output rows, then
    rotates the block one neighbor on.  n−1 hops total, each overlapping
    the previous block's matmul.  Bitwise-equal to the reference: every
    block meets the same weights, only the schedule changes."""
    n = mesh.shape[axis]
    bspec = _batch_spec(x.shape[0], mesh, batch_axes)
    perm = [(i, (i + 1) % n) for i in range(n)]
    _log_ring("ag_matmul_ring_ppermute",
              x.size * x.dtype.itemsize // n * (n - 1), axis)

    def body(xl, wl):
        me = lax.axis_index(axis)
        tl = xl.shape[1]
        out = jnp.zeros((xl.shape[0], tl * n, wl.shape[1]),
                        jnp.promote_types(xl.dtype, wl.dtype))
        cur = xl
        for s in range(n):
            src = (me - s) % n
            out = lax.dynamic_update_slice_in_dim(out, cur @ wl, src * tl,
                                                  axis=1)
            if s < n - 1:
                cur = lax.ppermute(cur, axis, perm)
        return out

    return shard_map(body, mesh=mesh,
                     in_specs=(P(bspec, axis, None), P(None, None)),
                     out_specs=P(bspec, None, None), check_vma=False)(x, w)


def all_gather_matmul(x, w, mesh: Mesh, *, axis: str = "tp",
                      batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
                      impl: Optional[str] = None):
    """``all_gather(x over seq) @ w`` with the gather fused into the matmul.

    x: [B, T, K] with T sharded over ``axis``; w: [K, N] replicated over
    ``axis``.  Returns [B, T, N] replicated over ``axis``.  Registry op
    ``all_gather_matmul``.
    """
    from deepspeed_tpu.ops.registry import dispatch
    _check(x.ndim == 3 and w.ndim == 2 and x.shape[2] == w.shape[0],
           f"all_gather_matmul expects x [B, T, K] and w [K, N], got "
           f"{x.shape} @ {w.shape}")
    _check(x.shape[1] % mesh.shape[axis] == 0,
           f"all_gather_matmul: seq dim {x.shape[1]} not divisible by "
           f"{axis}={mesh.shape[axis]}")
    return dispatch("all_gather_matmul", x, w, mesh, axis, batch_axes,
                    impl=impl)


# --------------------------------------------------- matmul → reduce-scatter

def _matmul_rs_xla(x, w, mesh, axis, batch_axes):
    """Unfused reference: full partial product, then one psum_scatter over
    the sequence dim."""
    bspec = _batch_spec(x.shape[0], mesh, batch_axes)

    def body(xl, wl):
        part = (xl @ wl).astype(jnp.float32)
        return lax.psum_scatter(part, axis, scatter_dimension=1, tiled=True)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(bspec, None, axis), P(axis, None)),
                     out_specs=P(bspec, axis, None), check_vma=False)(x, w)


def _matmul_rs_ring(x, w, mesh, axis, batch_axes):
    """Fused ring: a one-chunk accumulator travels the ring; at step s each
    device adds its partial product for the chunk that accumulator will
    deliver (owner schedule ``(me − s − 1) mod n``).  After n steps device
    ``me`` holds the fully-reduced chunk ``me`` — psum_scatter decomposed
    into n−1 hops interleaved with n chunk matmuls."""
    n = mesh.shape[axis]
    bspec = _batch_spec(x.shape[0], mesh, batch_axes)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunk_bytes = x.shape[0] * (x.shape[1] // n) * w.shape[1] * 4
    _log_ring("matmul_rs_ring_ppermute", chunk_bytes * (n - 1), axis)

    def body(xl, wl):
        me = lax.axis_index(axis)
        c = xl.shape[1] // n
        acc = jnp.zeros((xl.shape[0], c, wl.shape[1]), jnp.float32)
        for s in range(n):
            if s:
                acc = lax.ppermute(acc, axis, perm)
            idx = (me - s - 1) % n
            xc = lax.dynamic_slice_in_dim(xl, idx * c, c, axis=1)
            acc = acc + (xc @ wl).astype(jnp.float32)
        return acc

    return shard_map(body, mesh=mesh,
                     in_specs=(P(bspec, None, axis), P(axis, None)),
                     out_specs=P(bspec, axis, None), check_vma=False)(x, w)


def matmul_reduce_scatter(x, w, mesh: Mesh, *, axis: str = "tp",
                          batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
                          impl: Optional[str] = None):
    """``psum_scatter(x @ w over seq)`` with the reduce fused into the
    matmul (the row-parallel linear's scatter half).

    x: [B, T, K] with K (the contraction) sharded over ``axis``; w: [K, N]
    sharded on K.  Returns [B, T, N] with T sharded over ``axis``, fp32
    accumulation.  Requires T % axis == 0.  Registry op
    ``matmul_reduce_scatter``.
    """
    from deepspeed_tpu.ops.registry import dispatch
    _check(x.ndim == 3 and w.ndim == 2 and x.shape[2] == w.shape[0],
           f"matmul_reduce_scatter expects x [B, T, K] and w [K, N], got "
           f"{x.shape} @ {w.shape}")
    n = mesh.shape[axis]
    _check(x.shape[1] % n == 0,
           f"matmul_reduce_scatter: seq dim {x.shape[1]} not divisible by "
           f"{axis}={n}")
    _check(x.shape[2] % n == 0,
           f"matmul_reduce_scatter: contraction dim {x.shape[2]} not "
           f"divisible by {axis}={n}")
    return dispatch("matmul_reduce_scatter", x, w, mesh, axis, batch_axes,
                    impl=impl)


# ------------------------------------------------- row-parallel (all-reduce)

def _row_parallel_xla(x, w, mesh, axis, batch_axes, out_dtype):
    """Unfused reference: partial product + one blocking psum — the
    boundary all-reduce GSPMD inserts after a row-parallel matmul."""
    bspec = _batch_spec(x.shape[0], mesh, batch_axes)

    def body(xl, wl):
        part = (xl @ wl).astype(jnp.float32)
        return lax.psum(part, axis).astype(out_dtype)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(bspec, None, axis), P(axis, None)),
                     out_specs=P(bspec, None, None), check_vma=False)(x, w)


def _row_parallel_ring(x, w, mesh, axis, batch_axes, out_dtype):
    """Fused ring: the all-reduce decomposed as ring matmul-reduce-scatter
    (chunk matmuls interleaved with n−1 accumulator hops) followed by a
    ring all-gather of the reduced chunks (n−1 more hops) — 2·(n−1)
    neighbor hops total, the bandwidth-optimal all-reduce schedule, with
    every hop overlappable against a chunk matmul."""
    n = mesh.shape[axis]
    bspec = _batch_spec(x.shape[0], mesh, batch_axes)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunk_elems = x.shape[0] * (x.shape[1] // n) * w.shape[1]
    _log_ring("row_parallel_ring_ppermute",
              chunk_elems * 4 * (n - 1)                       # RS leg, fp32
              + chunk_elems * jnp.dtype(out_dtype).itemsize * (n - 1),  # AG
              axis)

    def body(xl, wl):
        me = lax.axis_index(axis)
        c = xl.shape[1] // n
        acc = jnp.zeros((xl.shape[0], c, wl.shape[1]), jnp.float32)
        for s in range(n):
            if s:
                acc = lax.ppermute(acc, axis, perm)
            idx = (me - s - 1) % n
            xc = lax.dynamic_slice_in_dim(xl, idx * c, c, axis=1)
            acc = acc + (xc @ wl).astype(jnp.float32)
        # acc = fully-reduced chunk ``me``; ring-gather chunks back to full
        acc = acc.astype(out_dtype)
        out = jnp.zeros((xl.shape[0], c * n, wl.shape[1]), out_dtype)
        cur = acc
        for s in range(n):
            idx = (me - s) % n
            out = lax.dynamic_update_slice_in_dim(out, cur, idx * c, axis=1)
            if s < n - 1:
                cur = lax.ppermute(cur, axis, perm)
        return out

    return shard_map(body, mesh=mesh,
                     in_specs=(P(bspec, None, axis), P(axis, None)),
                     out_specs=P(bspec, None, None), check_vma=False)(x, w)


def row_parallel_matmul(x, w, mesh: Mesh, *, axis: str = "tp",
                        batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
                        out_dtype=None, impl: Optional[str] = None):
    """Row-parallel linear ``psum(x @ w)`` with the all-reduce decomposed
    into ring reduce-scatter + ring all-gather chunk schedules.

    x: [B, T, K] with K sharded over ``axis``; w: [K, N] sharded on K.
    Returns the full [B, T, N] (replicated over ``axis``), accumulated in
    fp32 and cast to ``out_dtype`` (default: x's dtype).  Requires
    T % axis == 0 and K % axis == 0.  Registry op ``row_parallel_matmul`` —
    the entry the TP matmuls in models/gpt.py and linear.py route through
    under ``overlap.collective_matmul``.
    """
    from deepspeed_tpu.ops.registry import dispatch
    _check(x.ndim == 3 and w.ndim == 2 and x.shape[2] == w.shape[0],
           f"row_parallel_matmul expects x [B, T, K] and w [K, N], got "
           f"{x.shape} @ {w.shape}")
    n = mesh.shape[axis]
    _check(x.shape[1] % n == 0,
           f"row_parallel_matmul: seq dim {x.shape[1]} not divisible by "
           f"{axis}={n} (the ring chunks the sequence)")
    _check(x.shape[2] % n == 0,
           f"row_parallel_matmul: contraction dim {x.shape[2]} not "
           f"divisible by {axis}={n}")
    out_dtype = out_dtype if out_dtype is not None else x.dtype
    return dispatch("row_parallel_matmul", x, w, mesh, axis, batch_axes,
                    out_dtype, impl=impl)


def _register():
    from deepspeed_tpu.ops.registry import register_op
    register_op("all_gather_matmul", xla=_ag_matmul_xla,
                pallas=_ag_matmul_ring)
    register_op("matmul_reduce_scatter", xla=_matmul_rs_xla,
                pallas=_matmul_rs_ring)
    register_op("row_parallel_matmul", xla=_row_parallel_xla,
                pallas=_row_parallel_ring)


_register()
