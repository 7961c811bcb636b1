"""Grouped GEMM: ``rows [A, K]`` sorted by group, ``weights [G, K, N]``,
``group_sizes [G]`` -> ``[A, N]``, row ``r`` of group ``g`` times
``weights[g]`` (reference analog: the cutlass grouped GEMM of inference/v2's
MoE, MegaBlocks' block-sparse product).  With ``gate [G, K, N]`` it is the
gated first half of an expert FFN in one pass over the rows:
``silu(rows @ gate[g]) * (rows @ weights[g])``, float32 until the one
rounding to the rows' dtype.  ``sum(group_sizes)`` may be less than ``A``:
the rows behind the last group belong to no expert and callers must not
use them (moe/layer.py gathers each kept assignment's product from its own
place before them and masks a dropped one's by ``where``: ``lax.ragged_dot``
writes 0 there, the kernel the products of whatever the buffer held, NaN
included, as far as its last tile reaches, and nothing behind that).

Two implementations, chosen by ops/registry.py:

- ``xla``: ``lax.ragged_dot``, one call a product.  It has a transpose, so
  it is what everything differentiated takes (the flax ``MoE`` module and
  the ep route ask for it by name), what a CPU runs, and what a shape the
  kernel declines falls back to.  On a v5e its cost follows the ROWS of the
  buffer, not the bytes of the weights: one call streams Moonlight's 369 MB
  of one matrix kind in 1.21 ms at 288 rows and in 2.28 ms at 6,144.
- ``pallas`` (serving's expert layers on a TPU, forward only): a grid step
  for every (column panel, group with rows) pair and no other, from
  scalar-prefetched metadata computed from ``group_sizes`` on the device.  A
  group no row chose streams nothing and the rows behind the last group's
  last tile are never visited.  Every operand stays in HBM and a step moves
  what it needs: ITS group's column panel ``[K, tn]``, as wide as VMEM holds
  twice, started a whole step ahead, so that it is in flight behind ALL of
  the group before it, however many row tiles that has; and its row tiles,
  ``tm`` rows from the group's own first row (rounded down to the dtype's
  sublane packing), a loop inside the step, so that a group of ``r`` rows
  multiplies ``ceil(r / tm)`` tiles and never a neighbour's.  A tile is
  written whole: what it holds behind its group's last row a later step
  overwrites, and what it holds before the group's first row it takes from
  the tile written before it (``_kernel``).  No copy of the weights in
  another layout: the gated form takes ``weights`` and ``gate`` as two
  operands copied side by side.  The row tile ``tm`` comes from the static
  shape (``_row_tile``): the mean rows a group, between the dtype's sublane
  packing and 128.

Until PR 51 a step was one (group, fixed ``tm``-row block of the buffer it
overlaps) pair under the pipeline's block specs: a group's steps shared a
block index, so the next group's panel was copied behind the LAST of them
alone, and a mean-sized group straddled two blocks and was multiplied twice
(the parent's column below).

What the chip said (``scripts/step0_grouped_gemm.py --parent`` on one v5e,
PR 51, ``chiprun_out/pr51/step0.md``; PR 45's table, uniform routing, in
``chiprun_out/pr45/step0.md``): one expert layer's three products in
milliseconds, and in brackets GB/s of the weights of the groups that have
rows (819 is the chip's) and the rows multiplied a live row; routing seeded
and skewed (an expert's popularity log-normal, sigma 0.5), ``tm`` as
``_row_tile`` takes it; megablox is
``jax.experimental.pallas.ops.tpu.megablox.gmm`` of jax 0.9.0, three calls,
at its best tiling of four (its default ``(128, 128, 128)`` reads 19.6-25.3
ms in the mixed steps) and where 128 divides the rows:

    cell, step       rows  live  groups floor ragged_dot megablox   parent (PR 45)    this kernel
    Moonlight decode  288   288  62/64   1.31 3.62 (296) -          1.52 (705; 4.17)  1.48 (726; 4.17) tm 16
    Moonlight mixed 6,144 6,144  64/64   1.35 7.36 (151) 3.42 (323) 2.15 (515; 2.31)  1.67 (664; 1.71) tm 128
    Trinity decode     64    11  10/32   0.69 0.91 (624) -          0.80 (706; 14.55) 0.80 (707; 14.55) tm 16
    Trinity mixed   4,096   526  32/32   2.21 6.10 (297) 3.19 (568) 2.64 (687; 8.76)  2.57 (704; 7.79) tm 128
    dots3 decode      128    24  15/32   0.86 1.23 (575) 1.20 (590) 1.00 (708; 10.67) 0.99 (711; 10.67) tm 16
    dots3 mixed     8,192   949  32/32   1.84 5.11 (296) 3.31 (457) 2.31 (654; 5.26)  2.24 (673; 4.32) tm 128
    LFM2 decode       256   256  59/64   1.36 2.38 (467) 1.92 (580) 1.57 (710; 4.31)  1.54 (725; 4.31) tm 16
    LFM2 mixed      8,192 8,192  64/64   1.47 4.90 (246) 4.14 (292) 2.54 (476; 1.98)  1.86 (650; 1.53) tm 128
    Xing4 decode      256   256  59/64   1.59 2.73 (475) 2.12 (614) 1.84 (708; 4.31)  1.80 (723; 4.31) tm 16
    Xing4 mixed     4,096 4,096  64/64   1.72 5.07 (278) 3.36 (419) 2.49 (566; 1.97)  2.09 (674; 1.56) tm 64

The other row tiles (the kernel, mixed steps, 16 / 32 / 64 / 128 / 256 rows):
LFM2 3.27 / 2.30 / 2.01 / 1.86 / 1.97, Moonlight 2.48 / 1.90 / 1.73 / 1.67 /
1.76, Xing4 2.94 / 2.27 / 2.09 / 2.11 / 2.31: the rule's tile is the best
wherever an expert's rows are the buffer's; in a share, whose live rows an
expert are an eighth of the static mean, 32 rows read 4-5% under the rule's
128 (Trinity 2.48 / 2.57, dots3 2.14 / 2.24): the kernel sees the sizes, the
tile is static.  An expert of LFM2's mixed step takes 29.0 us (39.6 on the
parent) where its 18.9 MB need 23.0 at 819 GB/s and 26.0 at the decode
steps' 725: what is over that is the rows' own traffic, a tenth of the
weights' bytes there (8,192 rows read, written 1,536 wide, read again,
written).  The scope's other ops, each alone: the sort 0.21-0.24 ms at every
shape, the row gather 0.22-0.25, the weighted scatter-add 0.22-0.26 in the
decode steps and 0.58 / 0.57 / 3.04 / 0.80 / 0.78 in the mixed ones (dots3's
8,192 rows of 5,120, seven eighths of them nobody's); a call alone carries
~0.2 ms of launch, which is all the first two read.

Inside a step program (PR 56, a mixed step of LFM2 traced on the parent,
``chiprun_out/pr56/ops_lfm2_parent.md``, a layer of 8,192 rows): the sort
0.009 ms, the row gather 0.046, the scatter-add 0.63 behind a mask-and-weight
pass of 0.014, and three scalar-indexed ops no table had: the gather of the
weights by ``order`` 0.079, of ``tok_rows`` 0.059, the scatter-add of the
group sizes 0.072.  Since PR 56 moe/layer.py takes the products back by ``k``
gathers of ``[S, H]`` summed in float32 (0.10 ms a layer there with the
layout copy the compiler makes of each; eight layers unrolled in one
program, ``step0.md``'s ``ms in program``, Moonlight / Trinity / dots3 / LFM2
/ Xing4: 0.13 / 0.12 / 0.63 / 0.18 / 0.14 where the scatter-add reads 0.57 /
0.57 / 3.39 / 0.80 / 0.78, each over a floor of ~0.09), positions and group
sizes come from a blockwise count (0.01), the weights are read where they
lie, and the sort stays for ``order`` (its inverse as an int32 scatter read
0.038).
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
    """Rows of a row tile: the mean rows a group (every row local: the most
    a shape can hold), rounded up to a power of two, between the dtype's
    sublane packing (16 rows of bf16) and the MXU's 128.  A wider tile than a
    group's rows multiplies rows a later step writes over; a narrower one
    passes over the panel once more a tile."""
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


@functools.partial(jax.jit, static_argnames=("tm", "sub"))
def _steps(group_sizes, tm: int, sub: int):
    """The grid's work list from the group sizes, one entry a step: ``(group
    [G], begin [G], first [G], tiles [G], live [1])``.  Step ``s < live[0]``
    is group ``group[s]``, the groups that have rows in their order (a group
    without rows has no step); its rows begin at ``begin[s]``, and it
    multiplies ``tiles[s]`` tiles of ``tm`` rows from ``first[s]``, its first
    row rounded down to ``sub``.  A jit of its own: both calls of an expert
    layer, at every width of buffer, take one trace of it."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    # behind the live steps: group 0 (no step runs there).  Never an empty
    # grid, never a step without a tile: with no row at all the one step is
    # group 0's and multiplies the buffer's first tile, which is nobody's
    group = jnp.nonzero(sizes > 0, size=G, fill_value=0)[0].astype(jnp.int32)
    begin = (ends - sizes)[group]
    first = begin // sub * sub
    tiles = jnp.maximum((ends[group] - first + tm - 1) // tm, 1)
    live = jnp.maximum(jnp.sum(sizes > 0), 1).astype(jnp.int32)
    return group, begin, first, tiles, live[None]


# row tiles of a step whose rows are fetched before the step begins
_TILES_AHEAD = 2


def _kernel(group, begin, first, tiles, live, x_hbm, *refs, A: int, tm: int,
            tn: int, sub: int, gated: bool):
    """One grid step = one (column panel, group with rows); every operand
    stays in HBM and the step moves what it needs.  The copies of a core run
    in the order they were started, so the order is the design: as step
    ``q`` begins it starts, for step ``q + 1``, the first ``_TILES_AHEAD``
    row tiles and THEN its weight panel, into the halves of ``x_buf`` and
    ``w_buf`` step ``q - 1`` has done with (the call's first step starts its
    own as well).  The panel is in flight behind all of step ``q``'s
    products; the rows land before it and are there when step ``q + 1``
    begins.  Only a larger group's later tiles are started inside its own
    step, behind the next panel, and by then its products outlast that copy.

    ``state`` (SMEM) carries the result tiles from one step to the next:
    ``state[0]`` counts the tiles so far (its parity is the half of
    ``o_buf`` the next tile is written to), ``state[1]`` is the first row of
    the last tile written, which still lies in the other half."""
    n_w = 1 + gated
    w_hbm, (o_hbm, x_buf, w_buf, o_buf, x_sem, w_sem, o_sem, state) = (
        refs[:n_w], refs[n_w:])
    n, j = pl.program_id(0), pl.program_id(1)
    steps = live[0]
    total = steps * pl.num_programs(0)
    q = n * steps + j                   # steps of the call so far
    side = q % 2

    def tile_start(first, i):
        # a tile that would run off the buffer moves up: it then multiplies
        # rows of its own group again, or rows the merge below keeps
        return pl.multiple_of(jnp.minimum(first + i * tm, A - tm), sub)

    def x_copy(t, side, slot):
        return pltpu.make_async_copy(x_hbm.at[pl.ds(t, tm), :],
                                     x_buf.at[side, slot],
                                     x_sem.at[side, slot])

    def w_copies(g, cols, side):
        return [pltpu.make_async_copy(w.at[g, :, pl.ds(cols, tn)],
                                      w_buf.at[side, k], w_sem.at[side, k])
                for k, w in enumerate(w_hbm)]

    def o_copy(t, half):
        # one panel where 128 does not divide tn, so the claim holds
        cols = pl.ds(pl.multiple_of(n * tn, 128), tn)
        return pltpu.make_async_copy(o_buf.at[half, pl.ds(0, tm), :],
                                     o_hbm.at[pl.ds(t, tm), cols],
                                     o_sem.at[0])

    @pl.when(q == 0)
    def _first_of_the_call():
        state[0] = 0
        state[1] = 0

    def start_step(k, _):
        step, side_k = q + k, (side + k) % 2

        @pl.when(step < total)
        def _a_step():
            s = step % steps
            x_copy(tile_start(first[s], 0), side_k, 0).start()
            for slot in range(1, _TILES_AHEAD):
                @pl.when(slot < tiles[s])
                def _rows():
                    x_copy(tile_start(first[s], slot), side_k, slot).start()

            cols = pl.multiple_of(step // steps * tn, 128)
            for copy in w_copies(group[s], cols, side_k):
                copy.start()
        return _

    # the step behind this one; the call's first step its own before that
    jax.lax.fori_loop(jnp.where(q == 0, 0, 1), 2, start_step, None)
    count = tiles[j]
    done = state[0]
    for copy in w_copies(0, 0, side):
        copy.wait()

    def tile(i, _):
        half = (done + i) % 2
        slot = i % _TILES_AHEAD
        t = tile_start(first[j], i)
        x_copy(0, side, slot).wait()
        x = x_buf[side, slot]
        out = jnp.dot(x, w_buf[side, 0], preferred_element_type=jnp.float32)
        if gated:
            out = jax.nn.silu(jnp.dot(
                x, w_buf[side, 1], preferred_element_type=jnp.float32)) * out
        # the tile is written whole.  Rows behind the group's last are a
        # later group's, whose step writes them afterwards, or nobody's;
        # rows before its first (under ``sub`` of them, or a tile moved up
        # from the buffer's end) were written by the steps before, and the
        # last tile written holds every one of them
        shift = pl.multiple_of(jnp.clip(t - state[1], 0, tm), sub)
        row = t + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        o_buf[half, pl.ds(0, tm), :] = jnp.where(
            row >= begin[j], out.astype(o_buf.dtype),
            o_buf[1 - half, pl.ds(shift, tm), :])

        # the products have read the slot: it takes the tile two further on
        @pl.when(i + _TILES_AHEAD < count)
        def _a_later_tiles_rows():
            x_copy(tile_start(first[j], i + _TILES_AHEAD), side, slot).start()

        # one write in flight: tiles overlap, and the later has to land last
        @pl.when(done + i > 0)
        def _the_write_before():
            o_copy(0, 0).wait()

        o_copy(t, half).start()
        state[1] = t
        return _

    jax.lax.fori_loop(0, count, tile, None)
    state[0] = done + count

    @pl.when(q + 1 == total)
    def _last_of_the_call():
        o_copy(0, 0).wait()


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
    """Grid (N // tn, groups with rows): the column panels outermost, so
    that a group's panel is read once however many row tiles the group has
    (the rows, a few hundredths of the weights' bytes, are read once a
    panel).
    A jit of its own, like the attention kernels': an expert layer calls it
    with the same shapes as the one before, so it is traced once a process
    and lowered once a step program, not once a layer."""
    A, K = rows.shape
    G, _, N = weights.shape
    gated = gate is not None
    it = jnp.dtype(rows.dtype).itemsize
    steps = _steps(group_sizes, tm, 32 // it)
    operands = [weights, gate] if gated else [weights]
    held = (2 * len(operands) * K * tn + 2 * _TILES_AHEAD * tm * K
            + 4 * tm * tn) * it
    return pl.pallas_call(
        functools.partial(_kernel, A=A, tm=tm, tn=tn, sub=32 // it,
                          gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(steps),
            grid=(N // tn, steps[-1][0]),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(operands)),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, _TILES_AHEAD, tm, K), rows.dtype),
                pltpu.VMEM((2, len(operands), K, tn), rows.dtype),
                # a tile and as much again: the merge reads the tile before
                # from any row of it on
                pltpu.VMEM((2, 2 * tm, tn), rows.dtype),
                pltpu.SemaphoreType.DMA((2, _TILES_AHEAD)),
                pltpu.SemaphoreType.DMA((2, len(operands))),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SMEM((2,), jnp.int32)],
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
    )(*steps, rows, *operands)
