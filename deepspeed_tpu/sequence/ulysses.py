"""Ulysses sequence parallelism.

Reference parity: ``deepspeed/sequence/layer.py`` (113 LoC) —
``DistributedAttention`` wraps a local attention op: the sequence-sharded
activations are all-to-all'd to head-sharded layout before attention and back
after (``single_all_to_all`` :15, ``_SeqAllToAll`` :44).

TPU-native: the same two all-to-alls expressed inside ``shard_map`` over the
``sp`` mesh axis — ``lax.all_to_all`` rides ICI, which is exactly the topology
Ulysses was designed around (all-to-all is cheap on ICI; see BASELINE.md Ulysses
row).  Works under jit, composes with fsdp/tp axes of the same mesh, and the
backward pass is the mirrored all-to-all pair, generated by autodiff.

Layouts (per shard_map block, sp = sequence-parallel size):
    in : q,k,v [B, T/sp, H, D]    (sequence sharded)
    mid: q,k,v [B, T, H/sp, D]    (heads sharded)   ← local attention here
    out:   o   [B, T/sp, H, D]
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from deepspeed_tpu.comm.comm import comms_logger


def _seq_to_heads(x, scatter_idx=2, gather_idx=1):
    """Scatter the head dim, gather the sequence dim (reference
    single_all_to_all; default layout [B, T/sp, H, D] → [B, T, H/sp, D])."""
    comms_logger.record("ulysses_all_to_all", x.size * x.dtype.itemsize, "sp")
    return lax.all_to_all(x, "sp", split_axis=scatter_idx,
                          concat_axis=gather_idx, tiled=True)


def _heads_to_seq(x, scatter_idx=2, gather_idx=1):
    """Inverse swap (default [B, T, H/sp, D] → [B, T/sp, H, D])."""
    comms_logger.record("ulysses_all_to_all", x.size * x.dtype.itemsize, "sp")
    return lax.all_to_all(x, "sp", split_axis=gather_idx,
                          concat_axis=scatter_idx, tiled=True)


def ulysses_attention(attn_fn: Callable, mesh: Mesh, q, k, v, *args,
                      batch_axes=("dp", "fsdp"), scatter_idx: int = 2,
                      gather_idx: int = 1):
    """Run ``attn_fn(q, k, v, *args)`` with Ulysses head/sequence swap.

    q/k/v are rank-4 global arrays whose ``gather_idx`` (sequence) dim is (or
    will be) sharded over ``sp`` and whose ``scatter_idx`` (head) dim is
    local.  Layout-general like the reference ``DistributedAttention``
    (sequence/layer.py:15 single_all_to_all takes both indices): the default
    (2, 1) is [B, T, H, D]; (2, 0) serves seq-first [T, B, H, D] models.
    attn_fn must be shardable per-head (true for softmax attention) and
    receives tensors in the SAME layout with seq gathered / heads sharded.
    """
    if scatter_idx == gather_idx or q.ndim != 4 \
            or not (0 <= scatter_idx < 3 and 0 <= gather_idx < 3):
        raise ValueError(
            f"scatter_idx/gather_idx must be distinct dims in [0, 3) of a "
            f"rank-4 tensor (head dim stays off the trailing feature dim); "
            f"got ({scatter_idx}, {gather_idx})")
    if mesh.shape["sp"] == 1:
        return attn_fn(q, k, v, *args)

    spec_l = [None] * 4
    spec_l[gather_idx] = "sp"
    bdim = next(d for d in (0, 1, 2)
                if d not in (scatter_idx, gather_idx))
    spec_l[bdim] = batch_axes
    spec = P(*spec_l)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def inner(q, k, v):
        q = _seq_to_heads(q, scatter_idx, gather_idx)
        k = _seq_to_heads(k, scatter_idx, gather_idx)
        v = _seq_to_heads(v, scatter_idx, gather_idx)
        o = attn_fn(q, k, v, *args)
        return _heads_to_seq(o, scatter_idx, gather_idx)

    return inner(q, k, v)


class DistributedAttention:
    """Callable wrapper mirroring the reference class
    (sequence/layer.py:60 DistributedAttention(local_attn, sp_group,
    scatter_idx, gather_idx)) — layout-general since round 4."""

    def __init__(self, local_attention: Callable, mesh: Mesh,
                 scatter_idx: int = 2, gather_idx: int = 1):
        self.local_attn = local_attention
        self.mesh = mesh
        self.scatter_idx = scatter_idx
        self.gather_idx = gather_idx

    def __call__(self, query, key, value, *args):
        return ulysses_attention(self.local_attn, self.mesh, query, key,
                                 value, *args,
                                 scatter_idx=self.scatter_idx,
                                 gather_idx=self.gather_idx)
