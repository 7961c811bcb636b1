"""The paged KV append: a step's new k/v rows go into their pages of the flat
``[L * NB, nkv, ...]`` pools, in place.

Row ``n`` of the step's ``[N, nkv, hd]`` rows belongs to slot ``row_slot[n]``
(``S``, out of range, for a pad or an inactive slot: dropped) and holds
position ``row_pos[n]``; a slot's rows are ONE run of the step's rows, in
position order, for contiguous positions (ragged.py packs them so; the dense
``[S, G]`` verify layout and the one-row decode are the same thing).  So the
write is planned by UNIT, not by row (``append_plan``): a unit is ``granule``
consecutive tokens of a page (the page itself on kv-major pages, whose tokens
are lanes; a sublane tile or more of a standard page), a slot's run touches
at most ``J = (rows_per_slot + granule - 2) // granule + 1`` of them, and of
the ``S * J`` candidates at most ``N // granule + 2 * S`` hold a row of the
step.  Those are sorted to the front, the rest ride behind them.  A unit that
is written belongs to one slot (the prefix cache shares full pages only), so
no two candidates of a step name the same unit.

Two forms, registered as op ``paged_kv_append`` (ops/__init__.py):

``xla_paged_kv_append``: the forms the serving engine ran until PR 48, kept as
the numeric reference and for a CPU, an int8 pool with its scale pools, a
one-head (latent, index-key) pool and any shape ``supported`` declines.
Standard pages take a scatter of ``[hd]`` rows of the pool seen as ``[L * NB *
nkv * bs, hd]``, ``N * nkv`` updates of ~65-90 ns each on a v5e whatever their
size; kv-major pages read every candidate page, merge it with the new rows
under a select and scatter it back by page index (PERF.md, PR 27: both are
shaped so that the pool stays row-major, which the attention kernels demand).

``pallas_paged_kv_append``: one kernel for both layouts and for k and v
together.  The pools are aliased to the outputs, so nothing outside the
touched units is read or written; the grid walks the candidates, the unit a
candidate names comes in as a block, the tokens ``lo <= t < hi`` of it are
replaced under a mask by rows ``start + t`` of the step, and the block goes
back: a unit's bytes move once in and once out.  The rows are taken as they
are, ``[N, nkv * hd]`` (a free view): the two aligned blocks of ``granule``
rows that hold a unit's window come in by the same scalar-prefetched plan,
are rotated into place on the sublanes in float32 (Mosaic rotates 32-bit data
only; bfloat16 -> float32 -> bfloat16 is exact) and, for kv-major pages, turned
token-on-lanes a head at a time.  A candidate that holds no row repeats the
block indices of the last that does, so it costs a grid step and no DMA.
K and V rows may differ in width (keys of 192 beside values of 128): each pool
has its own blocks and its own rotation, in the one call.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

_BIG = jnp.iinfo(jnp.int32).max
# what the kernel's blocks may take of a core's 16 MiB of scoped VMEM
_VMEM_BUDGET = 10 * 2 ** 20


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["unit", "start", "lo", "hi", "rows"],
                   meta_fields=["granule"])
@dataclasses.dataclass(frozen=True)
class AppendPlan:
    """Where a step's rows go, by candidate unit (``[C]`` each; module
    docstring): ``unit`` = page * (block_size // granule) + the unit's place
    in its page, before the layer's first page is added; ``start`` = the index
    among the step's rows of the unit's token 0 (negative where the run starts
    inside the unit); tokens ``lo <= t < hi`` are written (none, ``hi <= lo``,
    for a candidate that holds no row).  ``rows``: the same write by row,
    (page, offset in it, live) ``[N]`` each, which the XLA form takes on
    standard pages (None on kv-major pages)."""
    unit: jax.Array
    start: jax.Array
    lo: jax.Array
    hi: jax.Array
    rows: tuple | None
    granule: int


def append_granule(block_size: int, kv_major: bool) -> int:
    """Tokens a unit: the page where tokens are lanes; on standard pages a
    sublane tile of bfloat16 (16 tokens, two tiles of float32), which a
    one-row slot's write moves instead of its page."""
    if kv_major or block_size % 16:
        return block_size
    return 16


def append_plan(block_table, row_slot, row_pos, block_size: int,
                rows_per_slot: int, kv_major: bool) -> AppendPlan:
    """The step's ``AppendPlan``: the same for every layer of a page group,
    so computed once a step."""
    S, MB = block_table.shape
    N = row_slot.shape[0]
    g = append_granule(block_size, kv_major)
    r = block_size // g
    rows = None
    if not kv_major:
        rows = (block_table[jnp.minimum(row_slot, S - 1),
                            row_pos // block_size],
                row_pos % block_size, row_slot < S)
    counts = jnp.zeros((S,), jnp.int32).at[row_slot].add(1, mode="drop")
    live = counts > 0

    def first(x):                       # of each slot's run; 0 if none
        return jnp.where(live, jnp.full((S,), _BIG, jnp.int32).at[
            row_slot].min(x, mode="drop"), 0)
    starts = first(row_pos)
    row0 = first(jnp.arange(N, dtype=jnp.int32))
    J = (rows_per_slot + g - 2) // g + 1
    lu = (starts // g)[:, None] + jnp.arange(J, dtype=jnp.int32)
    tok0 = lu * g - starts[:, None]           # unit token 0, as a row
    lo = jnp.clip(-tok0, 0, g).reshape(-1)
    hi = jnp.clip(counts[:, None] - tok0, 0, g).reshape(-1)
    page = jnp.take_along_axis(block_table, jnp.minimum(lu // r, MB - 1),
                               axis=1)
    unit = (page * r + lu % r).reshape(-1)
    start = (row0[:, None] + tok0).reshape(-1)
    # the candidates that hold a row, first: a run of c rows touches at most
    # (c + g - 2) // g + 1 <= c / g + 2 units
    C = min(S * J, N // g + 2 * min(S, N))
    dead = hi <= lo
    order = jnp.argsort(dead, stable=True)[:C]
    n_live = jnp.sum(~dead)
    # ... and the others behind them, as the last that does with nothing to
    # write: the kernel's block indices then stand still (no DMA)
    order = jnp.where(jnp.arange(C) < n_live, order,
                      order[jnp.maximum(n_live - 1, 0)])
    keep = jnp.arange(C) < n_live
    return AppendPlan(unit[order], start[order], jnp.where(keep, lo[order], 0),
                      jnp.where(keep, hi[order], 0), rows, g)


def xla_paged_kv_append(pools, new, plan: AppendPlan, base, *,
                        kv_major: bool):
    """``pools`` (k[, v[, k_scale, v_scale]]) with ``new`` (``[N, nkv, ...]``
    each) written at ``plan`` from page ``base`` on -> the pools."""
    if not kv_major:
        page, off, live = plan.rows
        nkv, bs = pools[0].shape[1:3]
        # (page, head, offset) as a row of the pool seen as
        # [L * NB * nkv * bs, hd]: the form XLA brings this scatter to
        # anyway, and written so it keeps its scope in the trace
        row = (((base + page)[:, None] * nkv + jnp.arange(nkv)) * bs
               + off[:, None])
        row = jnp.where(live[:, None], row, _BIG).reshape(-1)

        def put(pool, x):              # x [N, nkv, hd], or [N, nkv] scales
            rows = pool.reshape((-1,) + pool.shape[3:])
            x = x.reshape((-1,) + x.shape[2:]).astype(pool.dtype)
            return rows.at[row].set(x, mode="drop").reshape(pool.shape)
        return tuple(put(pool, x) for pool, x in zip(pools, new))

    bs = pools[0].shape[3]
    tok = jnp.arange(bs, dtype=jnp.int32)
    fresh = (tok >= plan.lo[:, None]) & (tok < plan.hi[:, None])   # [C, bs]
    dst = jnp.where(plan.hi > plan.lo, base + plan.unit, _BIG)
    src = jnp.minimum(dst, pools[0].shape[0] - 1)      # dropped: any page

    def merge(pool, x):
        """x [N, nkv, ...] step rows over the pages they land in."""
        xp = jnp.pad(x, ((bs, bs),) + ((0, 0),) * (x.ndim - 1))
        win = jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(
            xp, s, bs))(plan.start + bs)                # [C, bs, nkv, ...]
        rows = jnp.moveaxis(win, 1, -1)                 # [C, nkv, ..., bs]
        mask = fresh.reshape((-1,) + (1,) * (rows.ndim - 2) + (bs,))
        return pool.at[dst].set(
            jnp.where(mask, rows.astype(pool.dtype), pool[src]), mode="drop")
    return tuple(merge(pool, x) for pool, x in zip(pools, new))


def _append_kernel(page, tile, xa, xb, back, lo, hi, *refs, pools, nkv, hds,
                   g, kv_major):
    """One candidate: ``refs`` = for each pool its rows' two blocks ``[g, nkv
    * hd]`` and the unit's block, then the pools' output blocks (``hds``:
    each pool's head width).  Written in lax primitives, a pool's rows
    rotated as one block: a step program traces and lowers this once for
    every shape of its rows, so every equation here is paid some thirty
    times a serving start."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax import lax
    del page, tile, xa, xb
    i = pl.program_id(0)
    first, last, shift = lo[i], hi[i], back[i]

    # a candidate with nothing to write leaves the block of the one before it
    # as that left it; the first has none before it and passes its block on
    @pl.when(lax.bitwise_or(lax.gt(last, first), lax.eq(i, 0)))
    def _():
        f32 = jnp.float32

        def masks(hd):
            # window row t is row t + s of the two blocks a, b laid end to
            # end, s = g - shift (s = 0: shift = 0): a rotated back by s
            # where t + s < g, else b rotated likewise
            row = lax.broadcasted_iota(jnp.int32, (g, nkv * hd), 0)
            from_a = lax.bitwise_or(lax.lt(row, shift), lax.eq(shift, 0))
            tok = lax.broadcasted_iota(
                jnp.int32, (hd, g) if kv_major else (g, hd),
                1 if kv_major else 0)
            return from_a, lax.bitwise_and(lax.ge(tok, first),
                                           lax.lt(tok, last))
        # (once a head width: pools of one width share their masks)
        by_width = {hd: masks(hd) for hd in dict.fromkeys(hds)}
        for p, hd in enumerate(hds):
            from_a, fresh = by_width[hd]
            a, b, old = refs[3 * p:3 * p + 3]
            out = refs[3 * pools + p]
            win = lax.select(
                from_a,
                pltpu.roll(lax.convert_element_type(a[...], f32), shift, 0),
                pltpu.roll(lax.convert_element_type(b[...], f32), shift, 0))
            if not kv_major:
                win = lax.convert_element_type(win, out.dtype)
            for h in range(nkv):
                rows = lax.slice_in_dim(win, h * hd, (h + 1) * hd, axis=1)
                if kv_major:                 # tokens on lanes
                    rows = lax.convert_element_type(
                        lax.transpose(rows, (1, 0)), out.dtype)
                out[h] = lax.select(fresh, rows, old[h])


def pallas_paged_kv_append(pools, new, plan: AppendPlan, base, *,
                           kv_major: bool, interpret=None):
    """``xla_paged_kv_append`` as one kernel over the candidates (module
    docstring); pools aliased to the outputs."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    g = plan.granule
    N, nkv, _ = new[0].shape
    hds = tuple(x.shape[2] for x in new)
    bs = pools[0].shape[3 if kv_major else 2]
    r = bs // g
    blocks = -(-N // g)
    # the step's rows as they are, [N, nkv * hd] (padded to whole blocks: a
    # decode step's few rows)
    rows = [jnp.pad(x.reshape(N, -1), ((0, blocks * g - N), (0, 0)))
            for x in new]
    # a unit's window is rows start .. start + g: blocks xa and xa + 1 (a
    # block outside the rows holds none that is written: any block will do)
    xa = plan.start // g
    back = (xa * g - plan.start) % g      # the rotation that brings it there
    xb = jnp.clip(xa + 1, 0, blocks - 1)
    xa = jnp.clip(xa, 0, blocks - 1)
    unit = jnp.asarray(base, jnp.int32) * r + plan.unit
    page, tile = unit // r, unit % r       # (index maps only look values up)

    def specs(hd):               # (rows a, rows b, the unit) of one pool
        if kv_major:             # r == 1
            block = pl.BlockSpec((None, nkv, hd, g),
                                 lambda i, page, *_: (page[i], 0, 0, 0))
        else:
            block = pl.BlockSpec(
                (None, nkv, g, hd),
                lambda i, page, tile, *_: (page[i], 0, tile[i], 0))
        row_a = pl.BlockSpec((g, nkv * hd),
                             lambda i, _, __, xa, *___: (xa[i], 0))
        row_b = pl.BlockSpec((g, nkv * hd),
                             lambda i, _, __, ___, xb, *____: (xb[i], 0))
        return [row_a, row_b, block]
    n = len(pools)
    in_specs = [spec for hd in hds for spec in specs(hd)]
    operands = [a for x, pool in zip(rows, pools) for a in (x, x, pool)]
    out = pl.pallas_call(
        functools.partial(_append_kernel, pools=n, nkv=nkv, hds=hds, g=g,
                          kv_major=kv_major),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=(plan.unit.shape[0],),
            in_specs=in_specs, out_specs=in_specs[2::3]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={7 + 3 * p + 2: p for p in range(n)},
        interpret=interpret, name="paged_kv_append",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(page, tile, xa, xb, back, plan.lo, plan.hi, *operands)
    return tuple(out)


def supported(pools, new, plan: AppendPlan, base, *, kv_major: bool):
    """The kernel's shapes: k, or k and v, of more than one kv head in
    bfloat16 or float32 (an int8 pool comes with scale pools, and a one-head
    pool, latent or index keys, is one update a row to the XLA scatter), the
    lanes full and the sublanes in whole tiles, the blocks inside the
    VMEM budget."""
    pool = pools[0]
    axis = 2 if kv_major else 3          # a page's head width: each pool's own

    def but_width(shape):
        return shape[:axis] + shape[axis + 1:]
    if (len(pools) > 2 or pool.ndim != 4 or pool.shape[1] < 2
            or pool.dtype not in (jnp.bfloat16, jnp.float32)
            or new[0].ndim != 3
            or any(p.ndim != 4 or but_width(p.shape) != but_width(pool.shape)
                   or p.dtype != pool.dtype or x.shape[:2] != new[0].shape[:2]
                   or x.ndim != 3 or x.shape[2] != p.shape[axis]
                   for p, x in zip(pools, new))):
        return False
    nkv = new[0].shape[1]
    g = plan.granule
    tile = 8 * 4 // pool.dtype.itemsize
    need = 0
    for x in new:
        hd = x.shape[2]
        lanes, sublanes = (g, hd) if kv_major else (hd, g)
        if lanes % 128 or sublanes % tile or g % tile:
            return False
        # per pool: two row blocks and the unit in, the unit out, each twice
        # (the pipeline's two buffers)
        block = nkv * g * hd
        need += 2 * (2 * block * x.dtype.itemsize
                     + 2 * block * pool.dtype.itemsize)
    return need <= _VMEM_BUDGET
