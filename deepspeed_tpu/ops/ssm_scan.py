"""The three operations of a Mamba-2 (SSD) scan layer, each over many
sequences at once and each carrying what a sequence leaves behind: the
chunked scan with an initial and a final state a segment, the one-row
recurrence of a decode step, and the depthwise causal conv with its tail
(which a gated short-convolution layer, LFM2's, takes without the SiLU).

The recurrence, a head ``h`` of width ``p`` over a state ``[p, n]``
(``dt`` after its softplus, ``A < 0``, ``B``/``C`` shared by the heads of a
group):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;    y_t = S_t C_t + D x_t

``ssm_chunk_scan`` computes it chunk by chunk (Dao & Gu 2024, "Transformers
are SSMs", the SSD form): inside a chunk the lower-triangular decay matrix
``L[i, j] = exp(sum_{j < m <= i} dt_m A)`` turns the recurrence into two
matmuls, and one state ``[h, p, n]`` a sequence goes from chunk to chunk (a
``lax.scan`` over chunks, batched over sequences: static, so it
differentiates).  ``dt``, ``A``, every decay and the state are float32; a row
with ``dt = 0`` leaves the state as it is, which is how a partial last chunk
and the rows behind a segment's end are padded.

Layouts of the scan.  Dense: ``x [G, L, h, p]``, one sequence a row of
``G`` (with ``segments = (None, count [G])`` the rows behind a sequence's
count are padding: what the serving engine's mixed step hands it, its
prompt chunks gathered by ``segment_rows``).  Token-major: ``x [N, h, p]``
with ``segments = (start [G], count [G])``, segment ``g`` being rows ``start[g]
.. start[g] + count[g]`` of the ``N`` (``count`` 0: no rows, the state comes
back as it went in); rows are gathered into the dense layout (``max_len``
wide, static), scanned, and scattered back, rows of no segment reading 0.

The XLA forms are what the CPU tests run and the reference's recurrence
(``benchmark/reference/_granite_hybrid.py``) is compared with; all are
registered in ``ops/__init__.py``.  The serving engine's two operations on
its packed state pool have a Pallas kernel each, which the registry takes on
the chip where the shapes allow: ``ssm_state_update`` (one row a slot) and
``ssm_pool_chunk_scan`` (a mixed step's pass of prompt chunks: the chunked
scan above, reading and writing each chunk's slot in the pool as it lies).
``ssm_chunk_scan`` itself, which training differentiates, is XLA only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST     # everything that reads or writes a state


def _heads_of_groups(a, heads):
    """``a [..., g, n]`` of the groups -> ``[..., h, n]`` of their heads."""
    g = a.shape[-2]
    return a if g == heads else jnp.repeat(a, heads // g, axis=-2)


def _chunk(x, dt, A, B, C, D, state, swapped=False):
    """One chunk of every sequence: ``x [G, c, h, p]``, ``dt [G, c, h]``,
    ``B``/``C [G, c, g, n]``, ``state [G, h, p, n]`` -> (y, state').  The
    heads are taken group by group (``[g, r]``, ``r`` heads a group), so
    that ``C B^T`` is computed once a group and not once a head.
    ``swapped``: the state is ``[G, h, n, p]`` in and out, as the packed
    pool holds a head the lanes' width: the two products that touch it name
    their axes in that order, and no transpose of a state exists for the
    compiler to turn into a layout of the whole pool."""
    G, c, h, p = x.shape
    g = B.shape[2]
    r = h // g
    a = dt * A                                           # [G, c, h], <= 0
    cum = jnp.cumsum(a, axis=1)                          # through row i
    ci = jnp.moveaxis(cum, 1, -1)                        # [G, h, c]
    low = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(low, ci[..., :, None] - ci[..., None, :],
                              -jnp.inf))                 # [G, h, i, j]
    cb = jnp.einsum("gikn,gjkn->gkij", C, B)             # [G, g, i, j]
    w = (cb[:, :, None]
         * (decay * jnp.moveaxis(dt, 1, -1)[..., None, :]).reshape(
             G, g, r, c, c))
    xg = x.reshape(G, c, g, r, p)
    y = jnp.einsum("gkrij,gjkrp->gikrp", w, xg)
    # what the state brought into the chunk gives row i, decayed through i
    sg = state.reshape((G, g, r) + state.shape[2:])
    y = y.reshape(x.shape) + jnp.einsum(
        "gikn,gkrnp->gikrp" if swapped else "gikn,gkrpn->gikrp", C, sg,
        precision=_HI).reshape(x.shape) * jnp.exp(cum)[..., None]
    # ... and the state the chunk leaves: the old one decayed through the
    # chunk, each row's outer product decayed from that row to the end
    last = cum[:, -1:, :]
    wx = (x * (dt * jnp.exp(last - cum))[..., None]).reshape(xg.shape)
    kept = state * jnp.exp(last[:, 0])[..., None, None]
    grown = (jnp.einsum("gjkn,gjkrp->gkrnp", B, wx, precision=_HI)
             if swapped else
             jnp.einsum("gjkrp,gjkn->gkrpn", wx, B, precision=_HI))
    state = kept + grown.reshape(state.shape)
    return y + D[:, None] * x, state


def _ssd_dense(x, dt, A, B, C, D, state0, chunk, swapped=False):
    """(y [G, L, h, p] float32, state1) for ``L`` a whole number of
    chunks."""
    G, L = x.shape[:2]
    n = L // chunk
    chunk_fn = functools.partial(_chunk, swapped=True) if swapped else _chunk
    if n == 1:
        return chunk_fn(x, dt, A, B, C, D, state0)

    def split(a):                    # [G, L, ...] -> [n, G, chunk, ...]
        return jnp.moveaxis(a.reshape((G, n, chunk) + a.shape[2:]), 1, 0)

    def step(state, rows):
        y, state = chunk_fn(*rows[:2], A, *rows[2:], D, state)
        return state, y
    state1, y = jax.lax.scan(step, state0, tuple(map(split, (x, dt, B, C))))
    return jnp.moveaxis(y, 0, 1).reshape(x.shape), state1


def segment_rows(segments, max_len, N):
    """Where the dense layout's ``[G, max_len]`` cells sit among ``N``
    token-major rows: (row to read, row to write: ``N``, out of range, for a
    cell behind its segment's end, whether the cell is live)."""
    start, count = segments
    off = jnp.arange(max_len, dtype=jnp.int32)
    live = off < count[:, None]
    idx = start[:, None] + off
    return jnp.clip(idx, 0, N - 1), jnp.where(live, idx, N), live


def xla_ssm_chunk_scan(x, dt, A, B, C, D, state0, segments=None, *,
                       chunk: int, max_len=None, swapped: bool = False):
    """``(y, state1)``: the scan of every segment from its ``state0 [G, h,
    p, n]`` (module docstring for the layouts).  ``y`` is float32, shaped
    like ``x``; ``dt`` is taken as given (after the bias and the softplus).
    Dense, ``segments`` may still be ``(None, count [G])``: rows behind a
    sequence's count are padding.  ``swapped``: ``state0`` and ``state1``
    are ``[G, h, n, p]`` (``_chunk``)."""
    A, D = A.astype(F32), D.astype(F32)
    count = None if segments is None else segments[1]
    ragged = segments is not None and segments[0] is not None
    if ragged:
        N = x.shape[0]
        read, write, live = segment_rows(segments, max_len, N)
        x, dt, B, C = (a[read] for a in (x, dt, B, C))
    elif count is not None:
        live = jnp.arange(x.shape[1]) < count[:, None]
    x, dt, B, C = (a.astype(F32) for a in (x, dt, B, C))
    if count is not None:
        dt = jnp.where(live[..., None], dt, 0.0)
    L = x.shape[1]
    chunk = min(chunk, L)
    pad = -L % chunk
    if pad:                                   # dt = 0: the state stands
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    y, state1 = _ssd_dense(x, dt, A, B, C, D, state0.astype(F32), chunk,
                           **({"swapped": True} if swapped else {}))
    y = y[:, :L]
    if ragged:
        y = jnp.zeros((N,) + y.shape[2:], F32).at[write].set(y, mode="drop")
    return y, state1


# ------------------------------------------------------- the packed state pool
# The serving engine keeps the states of a scan layer's slots PACKED for the
# one-row recurrence: ``[slots, h / k, n, k * p]``, ``k`` heads side by side on
# the lanes (``k * p`` = 128 where ``p`` divides 128), the state's ``n`` on the
# sublanes.  In ``[h, p, n]`` order the recurrence's ``x_t (x) B_t`` needs one
# value a (head, p) ROW broadcast along the lanes, a relayout in every vector
# unit's pass (the XLA form of it ran at a third of the chip's bandwidth on
# the v5e, PERF.md section 6, PR 44); packed, ``dt x`` and the decay are lane
# vectors a head group, broadcast down the sublanes, ``B`` and ``C`` are
# columns, and ``y`` is a sublane reduction: every operand lies as the vector
# unit wants it.  The chunked scan's products take the packed layout as it
# is (``C [c, n] @ S [n, k p]`` is ``y`` on the lanes, ``B^T [n, c] @ wx [c,
# k p]`` the state's growth), which is what the in-pool kernel does; only
# the XLA form of ``ssm_pool_chunk_scan`` unpacks the few states it touches
# to ``[h, p, n]`` and packs them again (``pack_state``).

LANES = 128


def lane_heads(heads: int, head_dim: int) -> int:
    """``k``: how many heads share the lanes of a packed state."""
    k = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 else 1
    return k if heads % k == 0 else 1


def packed_state_shape(heads: int, head_dim: int, state: int):
    k = lane_heads(heads, head_dim)
    return heads // k, state, k * head_dim


def pack_state(st):
    """``[..., h, p, n]`` -> ``[..., h / k, n, k * p]``."""
    *lead, h, p, n = st.shape
    k = lane_heads(h, p)
    st = st.reshape(*lead, h // k, k, p, n)
    return jnp.moveaxis(st, -1, -3).reshape(*lead, h // k, n, k * p)


def unpack_state(st, head_dim: int):
    """``[..., h / k, n, k * p]`` -> ``[..., h, p, n]``."""
    *lead, hk, n, kp = st.shape
    k = kp // head_dim
    st = st.reshape(*lead, hk, n, k, head_dim)
    return jnp.moveaxis(st, -3, -1).reshape(*lead, hk * k, head_dim, n)


def _packed_rows(a, heads, head_dim):
    """``a [S, h]`` (a value a head) or ``[S, h, p]`` -> ``[S, h / k, k *
    p]``: as the packed state's lanes see it."""
    if a.ndim == 2:
        a = jnp.repeat(a[..., None], head_dim, axis=-1)
    k = lane_heads(heads, head_dim)
    return a.reshape(a.shape[0], heads // k, k * head_dim)


def _packed_cols(a, heads, head_dim):
    """``B`` or ``C [S, g, n]`` -> ``[S, h / k, n, k * p]`` (broadcast, never
    materialised: one group's column under each of its heads' lanes)."""
    S, g, n = a.shape
    k = lane_heads(heads, head_dim)
    if g == 1:
        return a[:, :, :, None]
    ah = _heads_of_groups(a, heads).reshape(S, heads // k, k, n)
    return jnp.repeat(jnp.moveaxis(ah, -1, -2), head_dim, axis=-1)


def _update_operands(x, dt, A, B, C, active, fresh):
    S, h, p = x.shape
    x, dt = x.astype(F32), dt.astype(F32)
    if active is None:
        active = jnp.ones((S,), bool)
    if fresh is None:
        fresh = jnp.zeros((S,), bool)
    return (x, _packed_rows(x * dt[..., None], h, p),
            _packed_rows(jnp.exp(dt * A.astype(F32)), h, p),
            B.astype(F32), C.astype(F32), active, fresh)


def xla_ssm_state_update(x, dt, A, B, C, D, pool, layer=0, active=None,
                         fresh=None):
    """One row a slot through layer ``layer`` (an int, or a traced scalar)
    of a packed state pool:
    ``x [S, h, p]``, ``dt [S, h]``, ``B``/``C [S, g, n]``, ``pool [layers, S,
    h / k, n, k * p]`` float32 -> (y [S, h, p] float32, pool').  A slot that
    is ``fresh`` starts from zero whatever the pool holds; one that is not
    ``active`` keeps its state."""
    S, h, p = x.shape
    x, xdt, decay, B, C, active, fresh = _update_operands(
        x, dt, A, B, C, active, fresh)
    state = jnp.where(fresh[:, None, None, None], 0.0, pool[layer])
    new = (state * decay[:, :, None, :]
           + _packed_cols(B, h, p) * xdt[:, :, None, :])
    y = jnp.sum(new * _packed_cols(C, h, p), axis=2).reshape(S, h, p)
    new = jnp.where(active[:, None, None, None], new, state)
    return y + D.astype(F32)[:, None] * x, pool.at[layer].set(new)


def _update_kernel(flags, _, xdt, decay, b, c, st, y, out, *, rows,
                   per_head=False):
    import jax.experimental.pallas as pl
    f = flags[pl.program_id(0)]
    active, fresh = (f & 1) == 1, (f & 2) == 2
    for j in range(rows):                   # one head group's [n, lanes]
        old = jnp.where(fresh, 0.0, st[j])
        # (a group a head: ``b``/``c`` are the block's heads' columns side
        # by side, ``[n, rows]``, each broadcast along its own head's lanes)
        bj, cj = (b[:, j:j + 1], c[:, j:j + 1]) if per_head else (b[0], c[0])
        new = old * decay[0, j][None, :] + bj * xdt[0, j][None, :]
        y[0, j] = jnp.sum(new * cj, axis=0)
        out[j] = jnp.where(active, new, old)


def pallas_ssm_state_update(x, dt, A, B, C, D, pool, layer=0, active=None,
                            fresh=None, *, interpret=None):
    """``xla_ssm_state_update`` as one kernel that reads and writes each
    slot's state in place, once: the grid walks (slot, block of head
    groups), the pool is aliased to the output, and a block is [8 head
    groups, n, 128 lanes] float32 (512 KB at n = 128)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, h, p = x.shape
    x, xdt, decay, B, C, active, fresh = _update_operands(
        x, dt, A, B, C, active, fresh)
    hk, n, lanes = pool.shape[2:]
    rows = _groups_a_block(hk)
    flags = active.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)
    which = jnp.asarray(layer, jnp.int32).reshape(1)   # static or traced
    row = pl.BlockSpec((1, rows, lanes), lambda s, j, *_: (s, j, 0))
    per_head = B.shape[1] > 1
    if per_head:
        # a group a head (and a head the lanes' width): a block's heads'
        # columns side by side, [S, blocks, n, rows]; the block's minor dim
        # is the array's own, so a copy takes it whole
        cols = [jnp.moveaxis(a.reshape(S, hk // rows, rows, n), -1, -2)
                for a in (B, C)]
        col = pl.BlockSpec((None, None, n, rows),
                           lambda s, j, *_: (s, j, 0, 0))
    else:
        cols = [jnp.broadcast_to(a[:, 0, :, None], (S, n, lanes))
                for a in (B, C)]
        col = pl.BlockSpec((1, n, lanes), lambda s, j, *_: (s, 0, 0))
    slab = pl.BlockSpec((None, None, rows, n, lanes),
                        lambda s, j, _, which: (which[0], s, j, 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_update_kernel, rows=rows,
                          **({"per_head": True} if per_head else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, hk // rows),
            in_specs=[row, row, col, col, slab], out_specs=[row, slab]),
        out_shape=[jax.ShapeDtypeStruct((S, hk, lanes), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1}, interpret=interpret,
        name="ssm_state_update",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(flags, which, xdt, decay, *cols, pool)
    return y.reshape(S, h, p) + D.astype(F32)[:, None] * x, pool


def state_update_supported(x, dt, A, B, C, D, pool, layer=0, active=None,
                           fresh=None):
    """The kernel's shapes: one group (``B``/``C`` are one column a slot),
    or a group a head whose width is the lanes' (lightning attention: 32
    heads of 128, a column a head); a float32 pool whose lanes are full and
    whose ``n`` fills whole sublanes."""
    groups_ok = B.shape[1] == 1 or (B.shape[1] == x.shape[1]
                                    and x.shape[2] == LANES)
    return (groups_ok and pool.dtype == jnp.float32
            and pool.shape[-1] == LANES and pool.shape[-2] % 8 == 0)


# ------------------------------------------- prompt chunks, in the pool
# A mixed step's pass: ``G`` prompt chunks of up to ``Q`` rows, each scanned
# from its slot's state in layer ``layer`` of the packed pool, the state
# written back where it lay.  Forward only and in the pool, so an op of its
# own beside ``ssm_chunk_scan`` (which the training mixers differentiate).

def _groups_a_block(hk: int) -> int:
    """Head groups a block of either kernel over the pool (512 KB of state
    at n = 128)."""
    return 8 if hk % 8 == 0 else hk


def _pool_sizes(xBC, dt, pool):
    """(h, p, g, n, k) of a pass whose conv'd rows are ``xBC [..., h p + 2 g
    n]`` (x, then B, then C) over a pool ``[layers, S, h / k, n, k p]``."""
    h = dt.shape[-1]
    hk, n, lanes = pool.shape[2:]
    k = h // hk
    p = lanes // k
    return h, p, (xBC.shape[-1] - h * p) // (2 * n), n, k


def xla_ssm_pool_chunk_scan(xBC, dt, A, D, pool, layer, slots, count, fresh,
                            live, *, chunk: int):
    """``xBC [G, Q, h p + 2 g n]`` (the rows as the conv leaves them: x, then
    B, then C), ``dt [G, Q, h]``, ``pool [layers, S, h / k, n, k * p]``
    float32 -> (y [G, Q, h p] float32, pool').  Lane ``i`` scans the first
    ``count[i]`` rows of its chunk from the state of slot ``slots[i]`` (from
    zero where ``fresh[i]``) and leaves the state behind them there; a lane
    that is not ``live`` leaves its slot as it is, and ``y`` behind a lane's
    count is the caller's to drop.  The XLA form gathers the pass's states,
    scans them (``xla_ssm_chunk_scan``; a head the lanes' width is scanned
    as the pool holds it, any other unpacked and packed again) and scatters
    them back."""
    G, Q = xBC.shape[:2]
    S = pool.shape[1]
    h, p, g, n, k = _pool_sizes(xBC, dt, pool)
    x, B, C = (a.reshape(G, Q, -1, w) for a, w in zip(
        jnp.split(xBC, (h * p, h * p + g * n), axis=-1), (p, n, n)))
    held = pool[layer, slots]
    as_held = k == 1                           # [h, n, p]: ``swapped``
    state = jnp.where(fresh[:, None, None, None], 0.0,
                      held if as_held else unpack_state(held, p))
    y, state = xla_ssm_chunk_scan(
        x, dt, A, B, C, D, state, (None, count), chunk=chunk,
        swapped=as_held)
    return y.reshape(G, Q, h * p), pool.at[
        layer, jnp.where(live, slots, S)].set(
            state if as_held else pack_state(state), mode="drop")


def _pool_chunk_kernel(slot, flags, count, _, x, b, c, cols, rows, d, held,
                       y, st, *, groups, k, per_head, lo):
    """One grid step: ``groups`` head groups of one lane's slot through one
    chunk of ``x.shape[0]`` rows.  ``cols`` (rows down the sublanes, a head a
    lane: the sum of ``dt A`` through a row, its exp, and ``dt`` times the
    decay from the row to the chunk's end) and ``rows`` (a head a sublane,
    rows along the lanes: the same sum, and ``dt``) are what XLA made of
    ``dt``.  ``st`` is the slot's block of the pool, resident from the
    lane's first chunk to its last."""
    import jax.experimental.pallas as pl
    g, ci = pl.program_id(0), pl.program_id(2)
    f = flags[g]
    live, fresh = (f & 1) == 1, (f & 2) == 2
    rows_n, lanes = x.shape[0], LANES
    n = st.shape[1]

    @pl.when(live & (ci == 0))
    def _():
        st[...] = jnp.where(fresh, 0.0, held[...])

    @pl.when(jnp.logical_not(live) & ((flags[0] & 1) == 0))
    def _():                  # no lane is live: the block goes back as it came
        st[...] = held[...]

    busy = live & (ci * rows_n < count[g])

    @pl.when(jnp.logical_not(busy))
    def _():
        y[...] = jnp.zeros(y.shape, y.dtype)

    @pl.when(busy)
    def _():
        i = jax.lax.broadcasted_iota(jnp.int32, (rows_n, rows_n), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (rows_n, rows_n), 1)
        low = i >= j
        head = jax.lax.broadcasted_iota(
            jnp.int32, (rows_n, lanes), 1) // (lanes // k)

        def mm(a, bb, dims, precision=None):
            return jax.lax.dot_general(a, bb, (dims, ((), ())),
                                       precision=precision,
                                       preferred_element_type=F32)

        def mm_state(col, full, dims):
            """``col`` (B or C) times a float32 operand at ``HIGHEST``.
            That is six bfloat16 passes over both operands' three parts;
            where ``col`` IS bfloat16 its lower parts are zero and the three
            passes over ``full``'s parts are all of them."""
            if col.dtype != jnp.bfloat16:
                return mm(col.astype(F32), full, dims, _HI)
            parts = []
            for _ in range(3):
                parts.append(full.astype(jnp.bfloat16))
                full = full - parts[-1].astype(F32)
            return sum(mm(col, part, dims) for part in reversed(parts))
        if not per_head:                  # one group: C B^T once a chunk
            bg, cg = b[...], c[...]
            cb = mm(lo(cg), lo(bg), ((1,), (1,)))
        for r in range(groups):           # static: lane slices of a block
            at = slice(r * lanes, (r + 1) * lanes)
            xr, x_mxu = x[:, at].astype(F32), lo(x[:, at])
            if per_head:                  # a group a head: its own C B^T
                bg, cg = b[:, r * n:(r + 1) * n], c[:, r * n:(r + 1) * n]
                cb = mm(lo(cg), lo(bg), ((1,), (1,)))
            y_in = e_in = w_out = through = None
            for m in range(k):            # the heads side by side on the lanes
                hh = r * k + m
                cum = cols[0, :, hh:hh + 1]
                decay = jnp.exp(jnp.where(
                    low, cum - rows[0, hh:hh + 1, :], -jnp.inf))
                w = cb * decay * rows[1, hh:hh + 1, :]
                got = (mm(lo(w), x_mxu, ((1,), (0,))),
                       cols[1, :, hh:hh + 1], cols[2, :, hh:hh + 1],
                       # through the chunk: the last row's (picked by a
                       # masked sum: a slice at sublane 7 does not broadcast)
                       jnp.exp(jnp.sum(jnp.where(i[:, :1] == rows_n - 1, cum,
                                                 0.0), axis=0, keepdims=True)))
                if m == 0:
                    y_in, e_in, w_out, through = (
                        jnp.broadcast_to(a, (a.shape[0], lanes)) for a in got)
                else:
                    y_in, e_in, w_out, through = (
                        jnp.where(head[:a.shape[0]] == m, a, was) for a, was
                        in zip(got, (y_in, e_in, w_out, through)))
            old = st[r]
            y[:, at] = (y_in + mm_state(cg, old, ((1,), (0,))) * e_in
                        + d[r][None, :] * xr)
            st[r] = old * through + mm_state(bg, xr * w_out, ((0,), (0,)))


def pallas_ssm_pool_chunk_scan(xBC, dt, A, D, pool, layer, slots, count,
                               fresh, live, *, chunk: int, interpret=None):
    """``xla_ssm_pool_chunk_scan`` as one kernel over the pool as it lies:
    the grid walks (lane, block of head groups, chunk), the chunk innermost
    and in order; a block ``[8 head groups, n, 128 lanes]`` of the lane's
    slot is read once, carried through the lane's chunks in VMEM and written
    back once, the pool aliased to the output, and the decay matrix of a
    chunk never leaves VMEM; x, B and C are blocks of ``xBC``'s columns,
    so no slice of it is made.  The products that touch a state are float32
    at ``HIGHEST`` (``mm_state``: where B and C come in bfloat16, as a
    serving model's conv leaves them, the three passes that is); ``C B^T``
    and the chunk's own ``(L o C B^T) x`` are at the default precision as in
    ``_chunk``: on the chip one bfloat16 pass, which a kernel has to ask for
    by the operands' type.  The live lanes come first, as a pass has them; a
    lane behind them takes the block the last live lane ended on and does
    nothing, so no block of its own slot is fetched or written."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pool_chunk_scan_call(
        xBC, dt, A, D, pool, jnp.asarray(layer, jnp.int32), slots, count,
        fresh, live, chunk=chunk, interpret=interpret)


# (behind a jit of its own, inlined: every step program traces the mixer
# again, and the kernel's body, unrolled over a block's heads, is what costs
# the host; the shapes of a pass are the same in all of them)
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"),
                   inline=True)
def _pool_chunk_scan_call(xBC, dt, A, D, pool, layer, slots, count, fresh,
                          live, *, chunk, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    G, Q = xBC.shape[:2]
    h, p, g, n, k = _pool_sizes(xBC, dt, pool)
    hk, lanes = pool.shape[2], pool.shape[4]
    rows_n = min(chunk, Q)
    if Q % rows_n:
        raise ValueError(f"a prompt chunk of {Q} rows is no whole number of "
                         f"scan chunks of {rows_n}")
    nc = Q // rows_n
    groups = _groups_a_block(hk)
    blocks = hk // groups
    per_head = g > 1
    if per_head and (g != h or k != 1):
        raise ValueError(f"{g} groups over {h} heads of {p}: the kernel "
                         f"takes one group, or a group a head the lanes' "
                         f"width")
    # what XLA makes of dt: [G, Q, h] arrays, a few small ops.  (The sums
    # within a chunk are a product with a triangle of ones at HIGHEST: a
    # cumsum is a reduce-window, which read 2.4 ms a mixed step with the
    # heads of a block minor and 0.35 with all 64, PERF.md section 6, PR 58.)
    dt = jnp.where(jnp.arange(Q)[None, :, None] < count[:, None, None],
                   dt.astype(F32), 0.0).reshape(G, nc, rows_n, h)
    cum = jnp.matmul(jnp.tril(jnp.ones((rows_n, rows_n), F32)),
                     dt * A.astype(F32), precision=_HI)

    def by_block(*parts):      # [G, chunks, rows, h] each -> a block's heads
        a = jnp.stack(parts).reshape(len(parts), G, Q, blocks, groups * k)
        return jnp.transpose(a, (1, 3, 0, 2, 4))
    cols = by_block(cum, jnp.exp(cum), dt * jnp.exp(cum[:, :, -1:] - cum))
    rows = jnp.swapaxes(by_block(cum, dt), -1, -2)
    d = _packed_rows(D.astype(F32)[None], h, p)[0]
    flags = live.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)

    def block(shape, index):
        return pl.BlockSpec(shape, lambda gi, bi, ci, *_: index(gi, bi, ci))
    wide = block((None, rows_n, groups * lanes),
                 lambda gi, bi, ci: (gi, ci, bi))

    def col(first):        # B's or C's columns of xBC, from column ``first``
        if per_head:       # (a block's heads' own)
            return block((None, rows_n, groups * n), lambda gi, bi, ci: (
                gi, ci, first // (groups * n) + bi))
        return block((None, rows_n, n),
                     lambda gi, bi, ci: (gi, ci, first // n))

    def slab_at(gi, bi, ci, slot, flags, count, which):
        # a dead lane: the last live lane's last block (lane 0's where none
        # is live), found on the scalar core
        alive = (flags[gi] & 1) == 1
        last = jnp.maximum(sum(flags[m] & 1 for m in range(G)) - 1, 0)
        return (which[0], slot[jnp.where(alive, gi, last)],
                jnp.where(alive, bi, blocks - 1), 0, 0)
    slab = pl.BlockSpec((None, None, groups, n, lanes), slab_at)
    lo = ((lambda a: a.astype(F32)) if interpret
          else (lambda a: a.astype(jnp.bfloat16)))
    y, pool = pl.pallas_call(
        functools.partial(_pool_chunk_kernel, groups=groups, k=k,
                          per_head=per_head, lo=lo),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(G, blocks, nc),
            in_specs=[
                wide, col(h * p), col(h * p + g * n),
                block((None, None, 3, rows_n, groups * k),
                      lambda gi, bi, ci: (gi, bi, 0, ci, 0)),
                block((None, None, 2, groups * k, rows_n),
                      lambda gi, bi, ci: (gi, bi, 0, 0, ci)),
                block((groups, lanes), lambda gi, bi, ci: (bi, 0)),
                slab],
            out_specs=[wide, slab]),
        out_shape=[jax.ShapeDtypeStruct((G, Q, h * p), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={10: 1}, interpret=interpret,
        name="ssm_pool_chunk_scan",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(slots.astype(jnp.int32), flags, count.astype(jnp.int32),
      layer.reshape(1), xBC, xBC, xBC, cols, rows, d, pool)
    return y, pool


def pool_chunk_scan_supported(xBC, dt, A, D, pool, layer, slots, count,
                              fresh, live, *, chunk: int):
    """The kernel's shapes: the recurrence kernel's (``state_update_
    supported``: one group, or a group a head the lanes' width, over a float32
    pool with full lanes) with a state whose ``n`` fills whole lanes too (a
    matmul's minor dimension, and the width of B's and C's blocks of
    ``xBC``), and a prompt chunk that is whole scan chunks of whole lanes (a
    row is a lane of the decay matrix)."""
    Q = xBC.shape[1]
    h, p, g, n, k = _pool_sizes(xBC, dt, pool)
    rows_n = min(chunk, Q)
    width = n if g == 1 else _groups_a_block(pool.shape[2]) * n
    return ((g == 1 or (g == h and k == 1)) and pool.dtype == jnp.float32
            and pool.shape[-1] == LANES and n % LANES == 0
            and xBC.shape[-1] == h * p + 2 * g * n
            and (h * p) % width == 0 and (g * n) % width == 0
            and rows_n % LANES == 0 and Q % rows_n == 0)


def xla_causal_conv1d(xBC, w, b, tail, count=None, activation="silu"):
    """Depthwise causal conv over every sequence, continued from the ``K -
    1`` rows before it, then ``activation``: "silu" (a Mamba-2 layer's conv
    over its x, B and C channels) or None (a short-conv layer's, LFM2: the
    plain weighted sum).  ``xBC [G, L, C]``, ``w [C, K]``, ``b [C]`` or
    None, ``tail [G, K - 1, C]`` -> (out like ``xBC``, tail': the last ``K -
    1`` rows of the tail and the sequence's rows together).  ``count [G]``:
    rows behind a sequence's count are padding, and its tail' ends at its
    last live row."""
    if activation not in ("silu", None):
        raise ValueError(f"causal_conv1d applies silu or nothing (None), "
                         f"got activation={activation!r}")
    K = w.shape[1]
    dtype = xBC.dtype
    L = xBC.shape[1]
    rows = jnp.concatenate([tail.astype(dtype), xBC], axis=1)
    wf = w.astype(F32)
    out = sum(rows[:, j:j + L].astype(F32) * wf[:, j] for j in range(K))
    if b is not None:
        out = out + b.astype(F32)
    if activation == "silu":
        out = jax.nn.silu(out)
    out = out.astype(dtype)
    if count is None:
        new_tail = rows[:, L:]
    else:                       # rows count .. count + K - 1 of tail + rows
        at = count[:, None] + jnp.arange(K - 1, dtype=jnp.int32)
        new_tail = jnp.take_along_axis(rows, at[..., None], axis=1)
    return out, new_tail.astype(tail.dtype)
