"""ZeRO semantics: master-weight optimizer wrapper + the composable stage-3
collective pipeline.

Reference parity map (see parallel/partition.py for the sharding half):

- fp32 master weights partitioned over DP
  (stage_1_and_2.py single_partition_of_fp32_groups; stage3.py
  _create_fp32_partitions:794) → ``with_master_weights`` below: the fp32 master
  copy lives *inside the optax state*, so it inherits ZeRO state sharding
  (sharded over fsdp at stage ≥ 1) while model params stay bf16/fp16.
- grad reduce-scatter (stage_1_and_2.py:1361 reduce_ipg_grads; stage3.py:1249) →
  XLA inserts psum-scatter when grads feed sharded state.
- param all-gather (partition_parameters.py all_gather_coalesced) → XLA inserts
  all-gather per consumer at stage 3; overlap via the latency-hiding scheduler.
- coalesced/overlapped gather (partitioned_param_coordinator.py prefetching,
  all_gather_coalesced bucketing) → ``pipeline_param_gather`` below.

**The composable pipeline** (ISSUE 14 tentpole): the stage-3 param gather /
grad reduce-scatter is ONE pipeline with three orthogonal layers, each
independently on/off —

- **chunking** (``overlap.num_chunks``): byte-balanced per-layer-group flat
  collectives the latency-hiding scheduler interleaves with neighboring
  matmuls (T3, arXiv:2401.16677; The Big Send-off, arXiv:2504.18658);
- **block quantization** (``zero_quantized_weights`` /
  ``zero_quantized_gradients`` + the ``zeropp`` bits knobs): the per-chunk
  wire moves int8/int4 codes + fp32 block scales instead of full-width
  values — ZeRO++ qwZ on the forward gather, qgZ on the backward
  reduce-scatter (arXiv:2306.10209), fused INSIDE the chunk bodies rather
  than layered as an alternative gather path (T3's
  quantize-chunk-overlap-at-fine-grain blueprint);
- **hierarchy** (``zeropp.hierarchical``): per-axis wire policy — an axis
  whose ring stays inside one host (all-ICI) keeps full-width values, an
  axis crossing hosts quantizes (the hpZ/ZeRO++ hierarchical design:
  intra-host full-width over ICI, cross-host compressed over DCN).

The quantization layer lives in ``_qwire_exchange``: a per-device
``custom_vjp`` whose forward is the quantized all-gather and whose backward
is the quantized all-to-all reduce-scatter, spliced into the SAME chunk
body the exact path uses — so chunk-only mode (both bits = 0) runs the
byte-identical PR 4 program, bitwise.

``pipeline_grad_reduce`` is the data-axis half: the EQuARX-style
block-quantized allreduce/reduce-scatter (arXiv:2506.17615) the engine's
qgZ path applies to per-replica gradient stacks (stage 1/2 dp grads, and
the cross-replica reduce at stage 3).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


def _gather_group(leaves, dims, specs, mesh, axis, world, exchange=None):
    """One layer group's gather: flatten each local shard, concatenate into
    per-dtype flat buffers, all-gather each buffer ONCE over ``axis``, and
    rebuild every leaf's global layout with pure data movement (exact).

    The transpose of this program under autodiff is precisely the chunked
    grad reduce-scatter: ``all_gather(tiled)`` transposes to ``psum_scatter``
    of the flat buffer, so each layer group's gradients leave the backward
    pass as one reduce-scatter the scheduler can overlap with the next
    group's backward matmuls.

    ``exchange`` is the quantization layer's splice point (``flat [B] ->
    rows [world, B]``, see ``_qwire_exchange``): when set, FLOATING buffers
    route their wire through it — int codes + scales forward (qwZ) and/or
    a quantized all-to-all in the autodiff transpose (qgZ) — while integer
    buffers (no meaningful quantization grid) and the ``exchange=None``
    default keep this exact full-width program, bitwise."""
    from deepspeed_tpu.comm import collectives
    from deepspeed_tpu.parallel.partition import spec_without_axis
    from jax import shard_map

    in_specs = tuple(s.spec for s in specs)
    out_specs = tuple(spec_without_axis(s.spec, axis) for s in specs)

    def body(*locs):
        # bucket by dtype: one flat buffer (= one collective) per dtype
        buckets = {}
        for i, x in enumerate(locs):
            buckets.setdefault(x.dtype, []).append(i)
        gathered = [None] * len(locs)
        for dtype, idxs in buckets.items():
            flat = (jnp.concatenate([locs[i].reshape(-1) for i in idxs])
                    if len(idxs) > 1 else locs[idxs[0]].reshape(-1))
            if exchange is not None and jnp.issubdtype(dtype, jnp.floating):
                g = exchange(flat)                      # [world, B]
            else:
                g = collectives.all_gather(flat, axis, gather_dim=0,
                                           tiled=True, chunked=True)
                g = g.reshape(world, flat.shape[0])
            off = 0
            for i in idxs:
                x, d = locs[i], dims[i]
                blk = jax.lax.slice_in_dim(g, off, off + x.size, axis=1)
                blk = blk.reshape((world,) + x.shape)   # [world, *local]
                blk = jnp.moveaxis(blk, 0, d)           # device axis → d
                shape = list(x.shape)
                shape[d] = shape[d] * world
                gathered[i] = blk.reshape(shape)
                off += x.size
        return tuple(gathered)

    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)(*leaves)


class WirePlan(NamedTuple):
    """Resolved wire policy for one collective pipeline — the three layers
    as plain data (engine builds it once from the ``overlap``/``zeropp``
    config blocks).

    ``weight_bits``/``grad_bits`` = 0 means full-width on that direction
    (the exact PR 4 program); 4/8 selects the blockwise int wire format
    (ops/quantization.py).  ``hierarchical`` makes quantization per-axis
    conditional on host crossing (see ``resolve_wire_bits``)."""

    num_chunks: int = 1
    weight_bits: int = 0     # fwd all-gather wire (ZeRO++ qwZ)
    grad_bits: int = 0       # bwd reduce-scatter wire (ZeRO++ qgZ)
    block_size: int = 256
    hierarchical: bool = False


def resolve_wire_bits(plan: WirePlan, mesh, axis):
    """The hierarchy layer: (weight_bits, grad_bits) effective on ``axis``.

    Non-hierarchical plans quantize wherever the bits knobs say.  A
    hierarchical plan keeps full-width values on any axis whose ring never
    leaves a host (all-ICI — bandwidth is cheap there, and skipping the
    quant round-trip keeps intra-host numerics exact) and quantizes only
    axes that cross hosts (DCN wire is the scarce resource) — the
    ZeRO++/hpZ hierarchical design as a per-axis wire policy."""
    if not (plan.weight_bits or plan.grad_bits):
        return 0, 0
    if plan.hierarchical:
        from deepspeed_tpu.comm.collectives import axis_dcn_fraction
        if axis_dcn_fraction(axis, mesh=mesh) == 0.0:
            return 0, 0
    return plan.weight_bits, plan.grad_bits


def _qwire_exchange(axis, world, w_bits, g_bits, block_size):
    """Per-device wire primitive for one flat chunk buffer, for use INSIDE
    a full-manual ``shard_map`` body: ``flat [B] -> rows [world, B]``.

    Forward: quantized all-gather when ``w_bits`` (int codes + fp32 block
    scales on the wire — qwZ), else the plain stacked all-gather.
    Backward (custom_vjp, so it splices into the chunk body's autodiff
    transpose exactly where ``lax.all_gather``'s built-in psum-scatter
    transpose would run): quantized all-to-all reduce-scatter when
    ``g_bits`` (qgZ wire), else the exact ``psum_scatter``.  The cotangent
    arriving here is this device's [world, B] partial contribution — row j
    is what this device owes member j — so member j's reduced row is the
    sum over devices of their row j: exactly one (quantized) all-to-all +
    local sum.
    """
    from deepspeed_tpu.comm.collectives import log_wire
    from deepspeed_tpu.ops.quantization import q_gather_rows, q_reduce_rows
    from jax import lax

    @jax.custom_vjp
    def exchange(flat):
        if w_bits:
            return q_gather_rows(flat, axis, world, bits=w_bits,
                                 block_size=block_size).astype(flat.dtype)
        # full-width forward inside a grads-quantized group: same chunk-
        # train tag the exact path carries
        log_wire("all_gather_chunked", flat.size * flat.dtype.itemsize
                 * (world - 1), axis)
        return lax.all_gather(flat, axis)

    def fwd(flat):
        return exchange(flat), None

    def bwd(_, ct_rows):
        if g_bits:
            return (q_reduce_rows(ct_rows, axis, world, bits=g_bits,
                                  block_size=block_size),)
        log_wire("reduce_scatter_chunked",
                 ct_rows.size * ct_rows.dtype.itemsize
                 * (world - 1) // world, axis)
        return (lax.psum_scatter(ct_rows, axis, scatter_dimension=0,
                                 tiled=False),)

    exchange.defvjp(fwd, bwd)
    return exchange


def pipeline_param_gather(params, shardings, mesh, plan: WirePlan,
                          axis: str = "fsdp"):
    """The composable stage-3 gather: every ``axis``-sharded leaf gathered
    explicitly in ``plan.num_chunks`` byte-balanced per-layer-group flat
    collectives, with the wire format per ``resolve_wire_bits`` (chunking ×
    quantization × hierarchy on ONE path — the conflict-gated either/or of
    the previous design is gone).

    Chunk-only plans (both bits resolved to 0) run the untouched
    ``_gather_group`` program — bitwise-identical forward, identical
    autodiff transpose — so enabling quantization is the ONLY thing that
    changes numerics.  Leaves not sharded over ``axis`` alone pass through
    untouched, as before."""
    from deepspeed_tpu.parallel.partition import layer_groups, sharded_dim
    world = mesh.shape[axis]
    if world <= 1 or plan.num_chunks < 1:
        return params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    specs = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    dims = [sharded_dim(sh.spec, axis) for sh in specs]
    gather_idx = [i for i, (leaf, d) in enumerate(zip(leaves, dims))
                  if d >= 0 and leaf.size > 0]
    if not gather_idx:
        return params
    w_bits, g_bits = resolve_wire_bits(plan, mesh, axis)
    exchange = (_qwire_exchange(axis, world, w_bits, g_bits,
                                plan.block_size)
                if (w_bits or g_bits) else None)
    groups = layer_groups([leaves[i].size * leaves[i].dtype.itemsize
                           for i in gather_idx], plan.num_chunks)
    out = list(leaves)
    for grp in groups:
        idxs = [gather_idx[j] for j in grp]
        gathered = _gather_group([leaves[i] for i in idxs],
                                 [dims[i] for i in idxs],
                                 [specs[i] for i in idxs],
                                 mesh, axis, world, exchange=exchange)
        for i, g in zip(idxs, gathered):
            out[i] = g
    return jax.tree_util.tree_unflatten(treedef, out)


def pipeline_grad_reduce(stacked, target_shardings, mesh, axis,
                         plan: WirePlan, mean: bool = True):
    """Data-axis half of the pipeline: reduce a tree of PER-REPLICA
    gradient stacks (leading dim = ``mesh.shape[axis]``, one slot per data
    replica, laid out ``P(axis, ...)``) down to the reduced gradients in
    ``target_shardings``.

    Per leaf, inside ONE full-manual ``shard_map``:

    - a leaf whose target sharding has a dim over ``axis`` takes the
      quantized reduce-scatter straight into that layout (qgZ,
      ops/quantization.qrs_local);
    - a blockable replicated leaf takes the EQuARX-style block-quantized
      allreduce (arXiv:2506.17615): quantized reduce-scatter + quantized
      all-gather, ints on the wire both phases (qpsum_local);
    - tiny/scalar leaves take a plain full-width psum (negligible bytes).

    ``resolve_wire_bits``'s grad side applies, so a hierarchical plan keeps
    an all-ICI data axis full-width.  ``mean=True`` divides by the axis
    size (per-replica losses are replica means)."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops.quantization import qpsum_local, qrs_local
    from deepspeed_tpu.parallel.partition import spec_without_axis
    from jax import shard_map
    from deepspeed_tpu.comm import collectives

    world = mesh.shape[axis]
    if world <= 1:
        return jax.tree_util.tree_map(lambda g: g[0], stacked)
    _, g_bits = resolve_wire_bits(plan, mesh, axis)

    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    tspecs = [s.spec for s in jax.tree_util.tree_leaves(
        target_shardings, is_leaf=lambda x: hasattr(x, "spec"))]

    def scatter_dim(spec):
        for d, ax in enumerate(spec):
            if ax == axis or (isinstance(ax, tuple) and axis in ax):
                return d
        return -1

    dims = [scatter_dim(sp) for sp in tspecs]
    in_specs = tuple(P(axis, *spec_without_axis(sp, axis)) for sp in tspecs)
    out_specs = tuple(P(*sp) for sp in tspecs)

    def body(*ls):
        out = []
        for l, d in zip(ls, dims):
            g = l[0]                       # this replica's contribution
            if (g_bits and jnp.issubdtype(g.dtype, jnp.floating)
                    and d >= 0 and g.shape[d] % world == 0):
                r = qrs_local(g, axis, world, d, bits=g_bits,
                              block_size=plan.block_size)
            elif (g_bits and jnp.issubdtype(g.dtype, jnp.floating)
                    and g.ndim >= 1 and g.shape[0] % world == 0
                    and g.size >= 64):
                r = qpsum_local(g, axis, world, 0, bits=g_bits,
                                block_size=plan.block_size)
            elif d >= 0 and g.shape[d] % world == 0:
                r = collectives.reduce_scatter(g, axis, scatter_dim=d)
            else:
                r = collectives.all_reduce(g, axis)
            out.append(r / world if mean else r)
        return tuple(out)

    reduced = shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)(*leaves)
    return jax.tree_util.tree_unflatten(treedef, list(reduced))


def chunked_param_gather(params, shardings, mesh, num_chunks,
                         axis: str = "fsdp"):
    """Gather every ``axis``-sharded leaf of ``params`` explicitly, in
    ``num_chunks`` byte-balanced per-layer-group flat collectives, instead
    of leaving XLA to insert one implicit all-gather per consumer.

    Leaves not sharded over ``axis`` alone (replicated, tp-only, or
    co-sharded tuple specs) pass through untouched and keep the
    partitioner's implicit handling.  Gathered leaves come back in their
    post-gather layout (``axis`` dropped from the spec, other axes kept).
    Forward is bitwise-exact vs the implicit gather (pure data movement);
    the backward pass runs the transposed program — ``num_chunks``
    per-layer-group flat reduce-scatters (tolerance-exact vs the implicit
    reduce: summation order may differ).

    PR 4's entry point, kept as the chunk-only plan of the composable
    pipeline (same code path — the bitwise guarantee is asserted against
    this equivalence in tests/test_comm_pipeline.py).
    """
    return pipeline_param_gather(params, shardings, mesh,
                                 WirePlan(num_chunks=num_chunks), axis)


class MasterWeightsState(NamedTuple):
    master: optax.Params  # fp32 copy, mirrors param tree → gets ZeRO state sharding
    inner: optax.OptState


def with_master_weights(inner: optax.GradientTransformation,
                        ) -> optax.GradientTransformation:
    """Wrap an optimizer to keep an fp32 master copy of low-precision params.

    The returned update expects fp32 grads (cast upstream) and low-precision
    ``params``; it computes the inner update against the fp32 master and emits a
    delta that moves the low-precision params to ``cast(new_master)``.

    Equivalent role: BF16_Optimizer (runtime/bf16_optimizer.py:34) and the fp32
    flat partitions of ZeRO 1/2/3.
    """

    def init(params):
        master = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        return MasterWeightsState(master=master, inner=inner.init(master))

    def update(grads, state, params=None, **kw):
        f32_grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32)
            if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
        updates, new_inner = inner.update(f32_grads, state.inner, state.master, **kw)
        new_master = optax.apply_updates(state.master, updates)
        if params is None:
            raise ValueError("with_master_weights requires params")
        deltas = jax.tree_util.tree_map(
            lambda m, p: (m.astype(p.dtype) - p).astype(p.dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else jnp.zeros_like(p),
            new_master, params)
        return deltas, MasterWeightsState(master=new_master, inner=new_inner)

    return optax.GradientTransformation(init, update)
