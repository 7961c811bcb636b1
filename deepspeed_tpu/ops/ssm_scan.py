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

These are the XLA forms, registered in ``ops/__init__.py``; they are what
the CPU tests run and the reference's recurrence
(``benchmark/reference/_granite_hybrid.py``) is compared with.
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
# unit wants it.  The chunked scan keeps ``[h, p, n]``, which its matmuls
# want, and packs the few states it touches (``pack_state``).

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
    rows = 8 if hk % 8 == 0 else hk
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
