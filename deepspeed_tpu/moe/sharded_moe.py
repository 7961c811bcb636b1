"""Top-k gating for MoE.

Reference parity: ``deepspeed/moe/sharded_moe.py`` — ``TopKGate`` (:372),
``top1gating`` (:181), ``top2gating`` (:288): softmax router with capacity
limits, optional jitter noise, load-balancing aux loss, GShard-style einsum
dispatch/combine tensors.

The einsum-dispatch formulation is *already* the TPU-native paradigm (it comes
from GShard, which targeted TPU): everything is dense one-hot algebra that XLA
maps onto the MXU — no scatter/gather kernels needed.

Shapes: S tokens (per dispatch group), E experts, C capacity.
Returns (aux_loss, combine [S,E,C] float, dispatch [S,E,C] bool).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int, k: int = 1) -> int:
    """reference sharded_moe.py:_capacity — tokens-per-expert budget."""
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor * k))
    return max(cap, min_capacity)


def _one_hot(idx, n, dtype=jnp.float32):
    return jax.nn.one_hot(idx, n, dtype=dtype)


def topk_gating(logits: jax.Array, k: int, capacity_factor: float = 1.0,
                min_capacity: int = 4, rng: Optional[jax.Array] = None,
                noise_std: float = 0.0,
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Generic top-k gating (k=1 ≡ reference top1gating, k=2 ≡ top2gating).

    Load-balancing aux loss = E * Σ_e mean(gate_e) * mean(assigned_e)
    (reference sharded_moe.py:249) computed on the top-1 assignment.

    k == 1 routes through ``_top1_gating_indexed`` — same outputs bitwise
    (test-pinned) without materializing the intermediate fp32 one-hot
    ``[S, E]``/``[S, E, C]`` algebra, the layer's biggest HBM term at
    large S·E·C.
    """
    if k == 1:
        return _top1_gating_indexed(logits, capacity_factor, min_capacity,
                                    rng, noise_std)
    return _topk_gating_dense(logits, k, capacity_factor, min_capacity,
                              rng, noise_std)


def _top1_gating_indexed(logits, capacity_factor=1.0, min_capacity=4,
                         rng=None, noise_std=0.0):
    """Index-based top-1 gating: argmax index + scatter instead of the dense
    one-hot cumsum algebra.  Bitwise-equal to ``_topk_gating_dense`` at
    k == 1: picking ``gates[s, idx]`` equals summing ``gates * one_hot``
    (adding exact zeros), integer ranks equal the fp32 cumsum-of-one-hot
    positions (counts < 2^24), and the dropped-token scatter adds +0.0 —
    bitwise-neutral on the zero-initialized combine tensor."""
    S, E = logits.shape
    C = _capacity(S, E, capacity_factor, min_capacity, 1)
    if rng is not None and noise_std > 0.0:
        logits = logits + jax.random.normal(rng, logits.shape,
                                            logits.dtype) * noise_std
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [S, E]
    idx = jnp.argmax(gates, axis=-1)                             # [S]
    gval = jnp.take_along_axis(gates, idx[:, None], axis=-1)[:, 0]

    counts = jnp.bincount(idx, length=E)                         # [E]
    me = jnp.mean(gates, axis=0)
    ce = counts.astype(jnp.float32) / S
    aux_loss = jnp.sum(me * ce) * E

    gval = gval / jnp.clip(gval, 1e-9, None)

    # rank within the expert queue: stable sort by expert, offset by the
    # expert's segment start (== the dense path's cumsum-of-one-hot)
    order = jnp.argsort(idx)
    start = (jnp.cumsum(counts) - counts).astype(jnp.int32)      # [E]
    pos = jnp.zeros((S,), jnp.int32).at[order].set(
        jnp.arange(S, dtype=jnp.int32) - start[idx[order]])
    keep = pos < C
    combine = jnp.zeros((S, E, C), jnp.float32).at[
        jnp.arange(S), idx, jnp.minimum(pos, C - 1)].add(gval * keep)
    dispatch = combine > 0.0
    return aux_loss, combine, dispatch


def _topk_gating_dense(logits: jax.Array, k: int, capacity_factor: float = 1.0,
                       min_capacity: int = 4, rng: Optional[jax.Array] = None,
                       noise_std: float = 0.0,
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The dense GShard one-hot algebra, any k — the k == 1 reference for
    the indexed fast path's bitwise pin."""
    S, E = logits.shape
    C = _capacity(S, E, capacity_factor, min_capacity, k)
    if rng is not None and noise_std > 0.0:
        # reference: 'Jitter'/'RSample' noisy gate policy (sharded_moe.py:426)
        logits = logits + jax.random.normal(rng, logits.shape,
                                            logits.dtype) * noise_std
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [S, E]

    remaining = gates
    masks, gate_vals = [], []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)            # [S]
        mask = _one_hot(idx, E)                         # [S, E]
        masks.append(mask)
        gate_vals.append(jnp.sum(gates * mask, axis=-1))  # [S]
        remaining = remaining * (1.0 - mask)

    # aux loss on the primary assignment
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(masks[0], axis=0)
    aux_loss = jnp.sum(me * ce) * E

    # normalize the k gate values (reference top2gating denominator)
    denom = jnp.clip(sum(gate_vals), 1e-9, None)
    gate_vals = [g / denom for g in gate_vals]

    # positions within each expert queue, later choices stacked after earlier
    combine = jnp.zeros((S, E, C), jnp.float32)
    prior_counts = jnp.zeros((E,), jnp.float32)
    for mask, gval in zip(masks, gate_vals):
        loc = jnp.cumsum(mask, axis=0) - mask + prior_counts[None, :]  # [S, E]
        pos = jnp.sum(loc * mask, axis=-1).astype(jnp.int32)           # [S]
        keep = pos < C
        gval = gval * keep
        sc = _one_hot(pos, C)                                          # [S, C]
        combine = combine + (gval[:, None] * mask)[..., None] * sc[:, None, :]
        prior_counts = prior_counts + jnp.sum(mask, axis=0)

    dispatch = combine > 0.0
    return aux_loss, combine, dispatch


def top1_gating(logits, capacity_factor=1.0, min_capacity=4, rng=None,
                noise_std=0.0):
    """reference sharded_moe.py:181 top1gating."""
    return topk_gating(logits, 1, capacity_factor, min_capacity, rng, noise_std)


def top2_gating(logits, capacity_factor=1.0, min_capacity=4, rng=None,
                noise_std=0.0):
    """reference sharded_moe.py:288 top2gating."""
    return topk_gating(logits, 2, capacity_factor, min_capacity, rng, noise_std)


def dropless_topk(logits: jax.Array, k: int,
                  rng: Optional[jax.Array] = None, noise_std: float = 0.0,
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless top-k routing: (aux_loss, expert_idx [S,k], weights [S,k]).

    The capacity-free side of the gating algebra (reference sharded_moe.py
    uses fixed capacity; MegaBlocks-style dropless needs only the assignment
    and normalized weights — the grouped GEMM handles raggedness).  Expert
    choice and weight normalization match ``topk_gating`` exactly, so at
    large capacity the two paths agree numerically."""
    S, E = logits.shape
    if rng is not None and noise_std > 0.0:
        logits = logits + jax.random.normal(rng, logits.shape,
                                            logits.dtype) * noise_std
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    remaining = gates
    idxs, gate_vals, masks = [], [], []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        mask = _one_hot(idx, E)
        idxs.append(idx)
        masks.append(mask)
        gate_vals.append(jnp.sum(gates * mask, axis=-1))
        remaining = remaining * (1.0 - mask)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(masks[0], axis=0)
    aux_loss = jnp.sum(me * ce) * E
    denom = jnp.clip(sum(gate_vals), 1e-9, None)
    weights = jnp.stack([g / denom for g in gate_vals], axis=1)
    return aux_loss, jnp.stack(idxs, axis=1).astype(jnp.int32), weights


def sigmoid_topk(logits: jax.Array, k: int, bias: Optional[jax.Array] = None,
                 route_norm: bool = True, route_scale: float = 1.0,
                 eps: float = 1e-20) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid router (afmoe / Trinity; DeepSeek-V3's form): (expert_idx
    [S, k] int32, weights [S, k] float32).  Scores are ``sigmoid(logits)``
    in float32, one per expert and independent of the others; ``bias``
    (the published ``expert_bias``, kept level by the load balancer) is
    added for the SELECTION of the top k only, and the weights are the
    chosen experts' own scores, renormalised to sum to one (``route_norm``:
    over their sum + ``eps``, afmoe's 1e-20, LFM2's 1e-6) and scaled by
    ``route_scale``."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    sel = s if bias is None else s + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(sel, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), w * route_scale
