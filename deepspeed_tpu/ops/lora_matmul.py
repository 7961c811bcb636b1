"""Batched-gather LoRA matmul — N adapters in ONE ragged dispatch.

Punica (arXiv:2310.18547) shape of the idea: a multi-tenant batch carries a
per-row adapter id, and the LoRA delta

    y[i] += (x[i] @ A[id_i]) @ B[id_i] · s[id_i]

is computed for ALL rows in one segmented (SGMV-style) matmul instead of
splitting the batch per tenant — which is what keeps N ≫ 1 adapters at
near-single-adapter throughput.  The adapter pages live PACKED in device
tables ``a_pages [S, H, r]`` / ``b_pages [S, r, O]`` (S = pool slots, one
slot per resident adapter; slot 0 is the base-model identity — the
AdapterPool keeps its pages zero, so id-0 rows pay a zero delta, not a
branch).

Two implementations behind the op registry, the ``wq_matmul`` convention:

- **xla** (reference + numeric ground truth): per-row gather of the A/B
  pages feeding two batched einsums.  Row-independent by construction —
  the per-request-loop exactness tests lean on this.
- **pallas** (fast slot): grid ``(M/bm, S)`` — each token block visits
  every adapter slot once, computes the dense rank-r delta for the whole
  block, and masks it onto the rows whose id matches the slot.  Dense
  over slots (BGMV-style) rather than sorted-segment SGMV: the ragged
  engine's row order is schedule-determined and a sort would reorder the
  batch the caller packed; the wasted flops are ``(S-1)/S`` of an
  O(M·H·r) term with r ≪ H, noise next to the base projections.  All
  staged blocks equal their array dims except the row tile, so the
  Mosaic (8, 128) preflight (re-checked against the EXACT blocks, the
  ``wq_matmul`` pattern) passes for any lane-aligned H/O and falls back
  warn-once to the XLA gather otherwise.

Serving-only: no VJP is defined (adapter pages are inference-time state;
training a LoRA happens upstream of the pool).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.wq_matmul import (_pick, _preflight, _record_refused,
                                         _sublane, _warned_shapes)

# trace-time counter: how many pallas-kernel calls were STAGED (tests assert
# the kernel path engaged instead of the silent gather fallback)
trace_counts = {"lora": 0}


def _shapes_ok(x, a_pages, b_pages, adapter_ids, scales) -> bool:
    if x.ndim != 2 or a_pages.ndim != 3 or b_pages.ndim != 3:
        return False
    s, h, r = a_pages.shape
    if b_pages.shape[:2] != (s, r) or x.shape[1] != h:
        return False
    if adapter_ids.ndim != 1 or adapter_ids.shape[0] != x.shape[0]:
        return False
    return scales.ndim == 1 and scales.shape[0] == s


def xla_lora_matmul(x, a_pages, b_pages, adapter_ids, scales, *,
                    interpret: Optional[bool] = None):
    """Gather reference: ``y[i] = (x[i] @ A[id_i]) @ B[id_i] · s[id_i]``.

    x [M, H], a_pages [S, H, r], b_pages [S, r, O], adapter_ids [M] int,
    scales [S] → [M, O] in ``x.dtype``.  Rank products accumulate in f32
    and cast back through the activation dtype between the two dots —
    the same rounding the Pallas kernel applies, so the two impls agree
    to accumulation order."""
    del interpret
    ids = adapter_ids.astype(jnp.int32)
    a = jnp.take(a_pages, ids, axis=0)               # [M, H, r]
    b = jnp.take(b_pages, ids, axis=0)               # [M, r, O]
    u = jnp.einsum("mh,mhr->mr", x, a,
                   preferred_element_type=jnp.float32)
    y = jnp.einsum("mr,mro->mo", u.astype(x.dtype), b,
                   preferred_element_type=jnp.float32)
    y = y * jnp.take(scales, ids).astype(jnp.float32)[:, None]
    return y.astype(x.dtype)


def lora_supported(x, a_pages, b_pages, adapter_ids, scales, *,
                   interpret: Optional[bool] = None) -> bool:
    """Kernel eligibility.  Every staged block equals its array dim except
    the padded row tile, so the only structural demands are 2-D/3-D
    layouts and a usable row divisor; unsupported layouts warn ONCE per
    shape (the ``wq_matmul`` rule: a silent fallback would let an
    operator benchmark 'the batched-gather kernel' while measuring the
    XLA gather)."""
    del interpret
    if not _shapes_ok(x, a_pages, b_pages, adapter_ids, scales):
        key = ("lora", tuple(x.shape), tuple(a_pages.shape),
               tuple(b_pages.shape))
        if key not in _warned_shapes:
            _warned_shapes.add(key)
            from deepspeed_tpu.utils.logging import logger
            logger.warning(
                "lora_matmul: layout x%s / A%s / B%s does not fit the "
                "batched-gather kernel (x [M,H], A [S,H,r], B [S,r,O], "
                "ids [M], scales [S]); falling back to the XLA gather",
                tuple(x.shape), tuple(a_pages.shape), tuple(b_pages.shape))
        return False
    return True


def _kernel(ids_ref, x_ref, a_ref, b_ref, s_ref, o_ref, acc, *, ns):
    """One (row-block, adapter-slot) grid step: dense delta for the block
    through slot ``js``'s pages, masked onto the matching rows.  f32
    accumulator across the slot dim (arbitrary semantics); the rank
    product casts back through the activation dtype between the two dots
    so bf16 activations ride the MXU's native multipliers (the
    ``wq_matmul`` finding: all-f32 dots ran BELOW the bf16 baseline)."""
    js = pl.program_id(1)

    @pl.when(js == 0)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    x = x_ref[...]
    u = jax.lax.dot_general(x, a_ref[0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    d = jax.lax.dot_general(u.astype(x.dtype), b_ref[0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    hit = (ids_ref[...] == js).astype(jnp.float32)   # [bm, 1] row mask
    acc[...] += d * (hit * s_ref[0, 0, 0].astype(jnp.float32))

    @pl.when(js == ns - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def pallas_lora_matmul(x, a_pages, b_pages, adapter_ids, scales, *,
                       interpret: Optional[bool] = None):
    """Batched-gather LoRA delta with the adapter tables resident in HBM —
    one kernel for the whole mixed-adapter batch."""
    if not lora_supported(x, a_pages, b_pages, adapter_ids, scales):
        _record_refused("lora_matmul")
        return xla_lora_matmul(x, a_pages, b_pages, adapter_ids, scales)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    s, h, r = a_pages.shape
    o = b_pages.shape[2]
    m0 = x.shape[0]
    pad = (-m0) % _sublane(x.dtype)     # decode token counts tile to rows
    m = m0 + pad
    bm = _pick(m, 256)
    if not _preflight("lora_matmul", [
            (None if bm is None else (bm, h), (m, h)),
            (None if bm is None else (bm, 1), (m, 1)),
            ((1, h, r), (s, h, r)), ((1, r, o), (s, r, o)),
            ((1, 1, 1), (s, 1, 1)),
            (None if bm is None else (bm, o), (m, o))], interpret):
        return xla_lora_matmul(x, a_pages, b_pages, adapter_ids, scales)
    trace_counts["lora"] += 1
    ids = adapter_ids.astype(jnp.int32)[:, None]     # [M, 1] sublane-tiled
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_kernel, ns=s),
        grid=(m // bm, s),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda im, js: (im, 0)),
            pl.BlockSpec((bm, h), lambda im, js: (im, 0)),
            pl.BlockSpec((1, h, r), lambda im, js: (js, 0, 0)),
            pl.BlockSpec((1, r, o), lambda im, js: (js, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda im, js: (js, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, o), lambda im, js: (im, 0)),
        out_shape=jax.ShapeDtypeStruct((m, o), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, o), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, x, a_pages, b_pages, scales[:, None, None])
    return out[:m0] if pad else out
