"""Grouped GEMM: ``rows [A, K]`` sorted by group, ``weights [G, K, N]``,
``group_sizes [G]`` -> ``[A, N]``, row ``r`` of group ``g`` times
``weights[g]`` (reference analog: the cutlass grouped GEMM of inference/v2's
MoE, MegaBlocks' block-sparse product).  ``sum(group_sizes)`` may be less
than ``A``: the rows behind the last group belong to no expert, are not
multiplied, and callers must not read them (moe/layer.py masks them).

The one implementation is ``lax.ragged_dot``, which the TPU compiler lowers
natively; it is registered so that the dispatch log says what an MoE step
ran, and so that a Pallas kernel, if a trace ever shows the XLA lowering
reading the weights of groups no row chose, has a place to go."""

import jax


def xla_grouped_gemm(rows, weights, group_sizes):
    return jax.lax.ragged_dot(rows, weights, group_sizes)
