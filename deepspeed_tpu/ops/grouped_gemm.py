"""Grouped GEMM: ``rows [A, K]`` sorted by group, ``weights [G, K, N]``,
``group_sizes [G]`` -> ``[A, N]``, row ``r`` of group ``g`` times
``weights[g]`` (reference analog: the cutlass grouped GEMM of inference/v2's
MoE, MegaBlocks' block-sparse product).  With ``gate [G, K, N]`` it is the
gated first half of an expert FFN in one pass over the rows:
``silu(rows @ gate[g]) * (rows @ weights[g])``, float32 until the one
rounding to the rows' dtype.  ``sum(group_sizes)`` may be less than ``A``:
the rows behind the last group belong to no expert, are not multiplied, and
callers must not read them (moe/layer.py masks them: ``lax.ragged_dot``
writes 0 there, the kernel writes NOTHING, so they hold whatever the buffer
held, NaN included).

Two implementations, chosen by ops/registry.py:

- ``xla``: ``lax.ragged_dot``, one call a product.  It has a transpose, so
  it is what everything differentiated takes (the flax ``MoE`` module and
  the ep route ask for it by name), what a CPU runs, and what a shape the
  kernel declines falls back to.  On a v5e its cost follows the ROWS of the
  buffer, not the bytes of the weights: one call streams Moonlight's 369 MB
  of one matrix kind in 1.21 ms at 288 rows and in 2.28 ms at 6,144.
- ``pallas`` (serving's expert layers on a TPU, forward only): a grid step
  for every (group with rows, row tile it overlaps) pair and no other, from
  scalar-prefetched metadata computed from ``group_sizes`` on the device.  A
  group no row chose streams nothing, the rows behind the last group are
  never visited, and a step multiplies one row tile by a column panel
  ``[K, tn]`` of ITS group's matrix, as wide as VMEM holds twice (the next
  step's panel, usually the next expert's, is in flight behind the
  product).  Rows of a tile that belong to a neighbouring group are masked
  at the store, as megablox does.  No copy of the weights in another
  layout: the gated form takes ``weights`` and ``gate`` as two operands
  under one block index.  The row tile ``tm`` comes from the static shape
  (``_row_tile``): the mean rows a group, between the dtype's sublane
  packing and 128.

What the chip said (``scripts/step0_grouped_gemm.py`` on one v5e, PR 45,
``chiprun_out/pr45/step0.md``): one expert layer's three products in
milliseconds, and GB/s of the weights of the groups that have rows (819 is
the chip's).  ``tm`` as ``_row_tile`` takes it; megablox is
``jax.experimental.pallas.ops.tpu.megablox.gmm`` of jax 0.9.0, three calls,
at its best tiling of four (its default ``(128, 128, 128)`` reads 19.5-20.2
ms in the mixed steps) and where 128 divides the rows:

    cell, step        rows  live  groups   floor  ragged_dot  megablox   this kernel
    Moonlight decode   288   288  63/64    1.33   3.72 (293)  -          1.56 (700) tm 16
    Moonlight mixed  6,144 6,144  64/64    1.35   7.34 (151)  3.39 (326) 2.14 (517) tm 128
    Trinity decode      64    10  10/32    0.69   0.90 (631)  -          0.81 (703) tm 16
    Trinity mixed    4,096   493  32/32    2.21   5.87 (309)  2.95 (614) 2.54 (712) tm 128
    dots3 decode       128    11  10/32    0.58   0.84 (561)  0.82 (576) 0.67 (700) tm 16
    dots3 mixed      8,192 1,063  32/32    1.84   5.26 (287)  3.39 (446) 2.35 (642) tm 128

The other row tiles read within 3% of the rule's wherever the rule takes 64
or more (Moonlight mixed: 16 rows 3.01, 32 rows 2.29, 256 rows 2.11), so
the rule is not tuned further.  The scope's other ops, each alone: the sort
0.21-0.24 ms at every shape, the row gather 0.22-0.23, the weighted
scatter-add 0.20-0.25 in the decode steps and 0.57 / 0.57 / 3.04 in the
mixed ones (dots3's 8,192 rows of 5,120, seven eighths of them nobody's).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def xla_grouped_gemm(rows, weights, group_sizes, gate=None):
    out = jax.lax.ragged_dot(rows, weights, group_sizes)
    if gate is not None:
        out = jax.nn.silu(jax.lax.ragged_dot(rows, gate, group_sizes)) * out
    return out


# what the double-buffered weight panels of a step may hold of VMEM (a v5e
# core has 128 MiB; the compiler's own default limit is 16)
_PANEL_BYTES = 40 << 20


def _row_tile(A: int, G: int, itemsize: int) -> int:
    """Rows a grid step multiplies: the mean rows a group (every row local:
    the most a shape can hold), rounded up to a power of two, between the
    dtype's sublane packing (16 rows of bf16) and the MXU's 128.  A wider
    tile than a group's rows multiplies rows it then masks; a narrower one
    revisits the panel."""
    lo = 32 // itemsize
    tm = lo
    while tm < 128 and tm * G < A:
        tm *= 2
    return tm


def _col_tile(K: int, N: int, itemsize: int, operands: int) -> int:
    """Columns of a weight panel: the widest multiple of 128 that divides N
    (1,408 = 11 x 128 takes 128 or all of it) whose ``operands`` panels fit
    ``_PANEL_BYTES`` twice; all of N where N has no such divisor."""
    if N % 128:
        return N
    fits = [d for d in range(128, N + 1, 128) if N % d == 0
            and 2 * operands * K * d * itemsize <= _PANEL_BYTES]
    return max(fits) if fits else 128


def supported(rows, weights, group_sizes, gate=None) -> bool:
    A, K = rows.shape
    N = weights.shape[2]
    it = jnp.dtype(rows.dtype).itemsize
    return (rows.dtype == weights.dtype and it in (2, 4)
            and (gate is None or gate.dtype == rows.dtype)
            and K % 128 == 0 and N % 128 == 0 and A % (32 // it) == 0
            and 2 * (1 if gate is None else 2) * K * 128 * it <= _PANEL_BYTES)


def _steps(group_sizes, A: int, tm: int):
    """The grid's work list from the group sizes: ``(offsets [G + 1], group
    [T], row tile [T], live steps)``, ``T = A // tm + G - 1`` the most there
    can be.  Step ``s < live`` multiplies row tile ``tile[s]`` by group
    ``group[s]``; steps run by group then by tile, so a tile two groups share
    is visited by consecutive steps (its output block stays in VMEM between
    them).  A group without rows has no step."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    step_ends = jnp.cumsum(count)
    T = A // tm + G - 1
    s = jnp.arange(T, dtype=jnp.int32)
    # the group whose steps hold s: one compare of [T, G], no search loop
    group = jnp.minimum(jnp.sum(step_ends[None, :] <= s[:, None], axis=1),
                        G - 1).astype(jnp.int32)
    tile = first[group] + s - (step_ends - count)[group]
    # behind the live steps: stay on the last block (no copy, and no step
    # runs there anyway)
    tile = jnp.clip(tile, 0, A // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # never an empty grid: with no row at all the one step is the last
    # group's, whose row range is empty, so it stores nothing
    return offsets, group, tile, jnp.maximum(step_ends[-1], 1)


def _kernel(offsets, group, tile, x_ref, *refs, tm: int, gated: bool):
    o_ref = refs[-1]
    s = pl.program_id(1)
    g = group[s]
    x = x_ref[...]
    out = jnp.dot(x, refs[0][...], preferred_element_type=jnp.float32)
    if gated:
        out = jax.nn.silu(jnp.dot(
            x, refs[1][...], preferred_element_type=jnp.float32)) * out
    row = tile[s] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    # the other rows of this tile are a neighbouring group's (its step
    # writes them) or lie behind the last group (nobody does)
    o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


def pallas_grouped_gemm(rows, weights, group_sizes, gate=None, *,
                        tm=None, tn=None, interpret=None):
    """The kernel (module docstring).  ``tm`` / ``tn`` override the shape
    rules (scripts/step0_grouped_gemm.py times a few); ``interpret``
    defaults to "not on a TPU"."""
    A, K = rows.shape
    G, _, N = weights.shape
    it = jnp.dtype(rows.dtype).itemsize
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if tm is None:
        tm = _row_tile(A, G, it)
    if tn is None:
        tn = _col_tile(K, N, it, 1 if gate is None else 2)
    if N % tn:
        raise ValueError(f"column tile {tn} does not divide N = {N}")
    pad = -A % tm
    if pad:       # no shape serving builds; a kernel that is forced gets on
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = _grouped_gemm_call(rows, weights, gate, group_sizes, tm=int(tm),
                             tn=int(tn), interpret=bool(interpret))
    return out[:A] if pad else out


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _grouped_gemm_call(rows, weights, gate, group_sizes, *, tm, tn,
                       interpret):
    """Grid (N // tn, live steps): the column panels outermost, so that a
    group's panel is read once however many row tiles the group has (the
    rows, a hundredth of the weights' bytes, are read once a panel).  A jit
    of its own, like the attention kernels': an expert layer calls it with
    the same shapes as the one before, so it is traced once a process and
    lowered once a step program, not once a layer."""
    A, K = rows.shape
    G, _, N = weights.shape
    gated = gate is not None
    it = jnp.dtype(rows.dtype).itemsize
    offsets, group, tile, live = _steps(group_sizes, A, tm)
    x_spec = pl.BlockSpec((tm, K), lambda n, s, off, grp, til: (til[s], 0))
    w_spec = pl.BlockSpec((None, K, tn),
                          lambda n, s, off, grp, til: (grp[s], 0, n))
    o_spec = pl.BlockSpec((tm, tn), lambda n, s, off, grp, til: (til[s], n))
    operands = [weights, gate] if gated else [weights]
    held = 2 * (len(operands) * K * tn + tm * K + tm * tn) * it
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, live),
            in_specs=[x_spec] + [w_spec] * len(operands),
            out_specs=o_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((A, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=held + 6 * tm * tn * 4 + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * len(operands) * A * K * N,
            bytes_accessed=(len(operands) * G * K * N
                            + (N // tn) * A * K + A * N) * it,
            transcendentals=A * N if gated else 0),
        interpret=interpret,
        name="grouped_gemm_gate_up" if gated else "grouped_gemm_down",
    )(offsets, group, tile, rows, *operands)
