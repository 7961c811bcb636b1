"""deepspeed_tpu.ops — kernel layer (reference: deepspeed/ops + csrc/ + op_builder/).

Every op has an XLA reference implementation and, where it pays, a Pallas TPU
kernel; selection goes through the registry (ops/registry.py, the op_builder
analog).  Public surface:

- ``causal_attention(q, k, v, ...)``      fused flash attention w/ fallback
- ``flash_attention(...)``                direct Pallas kernel entry
- ``lm_cross_entropy(...)``               chunked unembed + softmax CE
- ``paged_kv_append(...)``                a step's k/v rows into their pages
- ``op_report()``                         ds_report-style compatibility matrix
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax.numpy as jnp

from deepspeed_tpu.ops import registry
from deepspeed_tpu.ops.cross_entropy import lm_cross_entropy, masked_nll_sum
from deepspeed_tpu.ops.flash_attention import (configure_flash_blocks,
                                               flash_attention)
from deepspeed_tpu.ops.norms import layer_norm, rms_norm
from deepspeed_tpu.ops.registry import dispatch, list_ops, op_report, register_op


def _attention_xla(q, k, v, *, causal=True, scale=None, dropout_fn=None,
                   mask=None, bias=None, window=None, alibi_slopes=None,
                   interpret=None, mesh=None):
    """Plain attention on [B, T, N, D] — numeric ground truth for the kernel.

    The ONE XLA softmax-attention body in the codebase: causal tril masking, or
    an explicit [B, Tq, S] boolean mask (the KV-cache / padded-prefill path;
    all-False rows produce zeros, not NaN, so left-pad garbage never reaches
    later layers' V inputs).  ``bias`` [B|1, N, Tq|1, S] is added to the fp32
    logits pre-softmax (alibi; reference bloom/falcon-rw baddbmm bias).
    ``window``/``alibi_slopes`` are the FIRST-CLASS forms of the same
    semantics over canonical (arange) positions — the forms the Pallas kernel
    consumes in-kernel.
    """
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    import jax
    t, s = q.shape[1], k.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("btnd,bsnd->bnts", q, k).astype(jnp.float32) * scale
    if alibi_slopes is not None:
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(q.shape[2])
        logits = logits + (sl[None, :, None, None]
                           * jnp.arange(s, dtype=jnp.float32))
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    if window is not None:
        rel = jnp.arange(t)[:, None] - jnp.arange(s)[None, :]
        wtri = (rel >= 0) & (rel < window)
        logits = jnp.where(wtri[None, None], logits, neg)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(jnp.any(wtri[None, None], axis=-1, keepdims=True),
                          probs, 0.0)
    elif mask is not None:
        m = mask[:, None]                                # [B, 1, Tq, S]
        logits = jnp.where(m, logits, neg)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(jnp.any(m, axis=-1, keepdims=True), probs, 0.0)
    else:
        if causal:
            tri = jnp.tril(jnp.ones((t, s), dtype=bool))
            logits = jnp.where(tri[None, None], logits, neg)
        probs = jax.nn.softmax(logits, axis=-1)
    probs = probs.astype(q.dtype)
    if dropout_fn is not None:
        probs = dropout_fn(probs)
    return jnp.einsum("bnts,bsnd->btnd", probs, v)


def _attention_pallas(q, k, v, *, causal=True, scale=None, dropout_fn=None,
                      mask=None, bias=None, window=None, alibi_slopes=None,
                      interpret=None, mesh=None):
    if dropout_fn is not None:
        raise ValueError(
            "the pallas flash-attention kernel has no probs-dropout; use "
            "impl='xla', dropout=0, or output dropout (Ulysses-branch style)")
    if mask is not None:
        raise ValueError("the pallas flash-attention kernel takes no explicit "
                         "mask; use impl='xla' for the KV-cache/padded path "
                         "(sliding windows go through window=, not mask=)")
    if bias is not None:
        raise ValueError("the pallas flash-attention kernel takes no free-"
                         "form logit bias; alibi goes through alibi_slopes=, "
                         "other biases through impl='xla'")
    kernel = functools.partial(flash_attention, causal=causal, scale=scale,
                               window=window, interpret=interpret)
    if mesh is None:
        return kernel(q, k, v, alibi_slopes=alibi_slopes)
    return _flash_over_mesh(kernel, mesh, q, k, v, alibi_slopes)


def _flash_over_mesh(kernel, mesh, q, k, v, alibi_slopes):
    """Run the flash kernel per shard under ``shard_map``: a Mosaic kernel
    cannot be partitioned automatically, so inside a jit over a multi-device
    mesh a bare ``pallas_call`` is refused at lowering.  Attention is
    independent per (batch row, head): the batch dim rides the data axes
    (dp, fsdp — how the engine shards every batch) and the head dim rides tp
    (how the column-parallel qkv projections leave it); other axes
    replicate.  Axes already manual in the enclosing region (qgZ's
    manual-over-dp gradients, Ulysses) are left to that region."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.mesh import manual_axes_now
    manual = manual_axes_now()
    auto = [a for a in mesh.axis_names
            if mesh.shape[a] > 1 and a not in manual]
    if not auto:
        return kernel(q, k, v, alibi_slopes=alibi_slopes)
    data = tuple(a for a in ("dp", "fsdp") if a in auto)
    if data and q.shape[0] % math.prod(mesh.shape[a] for a in data):
        data = ()          # e.g. the init trace's single example row
    tp = mesh.shape.get("tp", 1)
    heads = ("tp" if "tp" in auto and q.shape[2] % tp == 0
             and k.shape[2] % tp == 0 else None)
    spec = P(data or None, None, heads, None)
    args, in_specs = [q, k, v], [spec, spec, spec]
    if alibi_slopes is not None:
        args.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(
            q.shape[2]))
        in_specs.append(P(heads))

    def local(q_, k_, v_, *slopes):
        return kernel(q_, k_, v_,
                      alibi_slopes=slopes[0] if slopes else None)
    return shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=spec, check_vma=False,
                     axis_names=frozenset(mesh.axis_names) - manual)(*args)


def _attention_supported(q, k, v, *, causal=True, scale=None, dropout_fn=None,
                         mask=None, bias=None, window=None, alibi_slopes=None,
                         interpret=None, mesh=None):
    from deepspeed_tpu.ops.flash_attention import supported as flash_supported
    return (dropout_fn is None and mask is None and bias is None
            and flash_supported(q, k, v, causal=causal, window=window,
                                alibi_slopes=alibi_slopes))


register_op("causal_attention", xla=_attention_xla, pallas=_attention_pallas,
            supported=_attention_supported)

from deepspeed_tpu.ops import paged_attention as _paged  # noqa: E402
from deepspeed_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention, ragged_prefill_attention, sink_softmax)

register_op("paged_attention", xla=_paged.xla_paged_attention,
            pallas=_paged.pallas_paged_attention, supported=_paged.supported)
register_op("ragged_prefill_attention", xla=_paged.xla_ragged_prefill,
            pallas=_paged.pallas_ragged_prefill,
            supported=_paged.ragged_prefill_supported)

from deepspeed_tpu.ops import sparse_index as _index  # noqa: E402
from deepspeed_tpu.ops.sparse_index import (  # noqa: E402
    index_scores, index_select, selected_attention, selection_mask,
    threshold_mask)

register_op("index_scores", xla=_index.xla_index_scores,
            pallas=_index.pallas_index_scores,
            supported=_index.index_scores_supported)
register_op("index_select", xla=_index.xla_index_select)
register_op("selected_attention", xla=_index.xla_selected_attention)
register_op("selection_mask", xla=_index.xla_selection_mask)
register_op("threshold_mask", xla=_index.xla_threshold_mask,
            pallas=_index.pallas_threshold_mask,
            supported=_index.threshold_mask_supported)

from deepspeed_tpu.ops import block_select as _blocks  # noqa: E402
from deepspeed_tpu.ops.block_select import block_scores  # noqa: E402

register_op("block_scores", xla=_blocks.xla_block_scores)

from deepspeed_tpu.ops import grouped_gemm as _grouped  # noqa: E402

register_op("grouped_gemm", xla=_grouped.xla_grouped_gemm,
            pallas=_grouped.pallas_grouped_gemm, supported=_grouped.supported)


def grouped_gemm(rows, weights, group_sizes, gate=None, *,
                 impl: Optional[str] = None):
    """``rows [A, K]`` (sorted by group) x ``weights [G, K, N]`` by
    ``group_sizes [G]`` -> [A, N]; with ``gate [G, K, N]`` the gated first
    half of an expert FFN, ``silu(rows @ gate[g]) * (rows @ weights[g])``
    (ops/grouped_gemm.py): the MoE expert products, through the registry so
    the dispatch log names what ran.  The kernel is forward only and leaves
    the rows behind the last group unwritten: what is differentiated asks
    for ``impl="xla"`` (``lax.ragged_dot``)."""
    return dispatch("grouped_gemm", rows, weights, group_sizes, gate,
                    impl=impl)


from deepspeed_tpu.ops import ssm_scan as _ssm  # noqa: E402

register_op("ssm_chunk_scan", xla=_ssm.xla_ssm_chunk_scan)
register_op("ssm_state_update", xla=_ssm.xla_ssm_state_update,
            pallas=_ssm.pallas_ssm_state_update,
            supported=_ssm.state_update_supported)
register_op("ssm_pool_chunk_scan", xla=_ssm.xla_ssm_pool_chunk_scan,
            pallas=_ssm.pallas_ssm_pool_chunk_scan,
            supported=_ssm.pool_chunk_scan_supported)
# (SiLU after the conv by default, a Mamba-2 layer's; activation=None: none,
# a short-conv layer's)
register_op("causal_conv1d", xla=_ssm.xla_causal_conv1d)


def ssm_chunk_scan(x, dt, A, B, C, D, state0, segments=None, *, chunk: int,
                   max_len=None, swapped: bool = False,
                   impl: Optional[str] = None):
    """A scan layer's chunked (SSD) scan of every segment from its own
    initial state -> (y float32, final states) (ops/ssm_scan.py).
    ``swapped``: the states are ``[G, h, n, p]``, as the packed pool holds
    a head the lanes' width."""
    return dispatch("ssm_chunk_scan", x, dt, A, B, C, D, state0, segments,
                    chunk=chunk, max_len=max_len, impl=impl,
                    **({"swapped": True} if swapped else {}))


def ssm_state_update(x, dt, A, B, C, D, pool, layer=0, active=None,
                     fresh=None, *, impl: Optional[str] = None):
    """A scan layer's recurrence for one row a slot, in place in layer
    ``layer`` of a packed state pool -> (y float32, pool')
    (ops/ssm_scan.py)."""
    return dispatch("ssm_state_update", x, dt, A, B, C, D, pool, layer,
                    active, fresh, impl=impl)


def ssm_pool_chunk_scan(xBC, dt, A, D, pool, layer, slots, count, fresh, live,
                        *, chunk: int, impl: Optional[str] = None):
    """A mixed step's pass of prompt chunks (the conv's rows ``[x | B | C]``)
    through a scan layer, each from its slot's state in layer ``layer`` of a
    packed state pool and the state written back in place -> (y float32,
    pool') (ops/ssm_scan.py).  Forward only: what is differentiated takes
    ``ssm_chunk_scan``."""
    return dispatch("ssm_pool_chunk_scan", xBC, dt, A, D, pool, layer, slots,
                    count, fresh, live, chunk=chunk, impl=impl)


def causal_conv1d(xBC, w, b, tail, count=None, *, activation="silu",
                  impl: Optional[str] = None):
    """A depthwise causal conv with a carried tail, then ``activation``:
    "silu" (a scan layer's) or None (a short-conv layer's) -> (out, tail')
    (ops/ssm_scan.py)."""
    return dispatch("causal_conv1d", xBC, w, b, tail, count, activation,
                    impl=impl)


from deepspeed_tpu.ops import kv_append as _append  # noqa: E402

register_op("paged_kv_append", xla=_append.xla_paged_kv_append,
            pallas=_append.pallas_paged_kv_append,
            supported=_append.supported)


def paged_kv_append(pools, new, plan, base, *, kv_major: bool,
                    impl: Optional[str] = None):
    """A step's new rows ``new`` (``[N, nkv, ...]`` each) into the flat
    ``[L * NB, nkv, ...]`` ``pools`` at ``plan`` (``kv_append.append_plan``)
    from page ``base`` on, in place -> the pools (ops/kv_append.py)."""
    return dispatch("paged_kv_append", pools, new, plan, base,
                    kv_major=kv_major, impl=impl)


from deepspeed_tpu.ops.evoformer import evoformer_attention  # noqa: E402

register_op("evoformer_attention", xla=evoformer_attention)

from deepspeed_tpu.ops import sparse_attention as _sparse  # noqa: E402

register_op("sparse_attention", xla=_sparse._sparse_xla,
            pallas=_sparse._sparse_pallas,
            supported=_sparse.block_sparse_supported)

# ring collective-matmul fusions (registers all_gather_matmul /
# matmul_reduce_scatter / row_parallel_matmul on import)
from deepspeed_tpu.ops import collective_matmul  # noqa: E402
from deepspeed_tpu.ops.collective_matmul import (  # noqa: E402
    all_gather_matmul, matmul_reduce_scatter, row_parallel_matmul)

from deepspeed_tpu.ops import lora_matmul as _lora  # noqa: E402

register_op("lora_matmul", xla=_lora.xla_lora_matmul,
            pallas=_lora.pallas_lora_matmul, supported=_lora.lora_supported)


def lora_matmul(x, a_pages, b_pages, adapter_ids, scales, *,
                impl: Optional[str] = None):
    """Batched-gather LoRA delta: ``y[i] = (x[i] @ A[id_i]) @ B[id_i] ·
    s[id_i]`` over packed per-slot adapter tables (ops/lora_matmul.py)."""
    return dispatch("lora_matmul", x, a_pages, b_pages, adapter_ids, scales,
                    impl=impl)


def causal_attention(q, k, v, *, causal: bool = True,
                     scale: Optional[float] = None,
                     dropout_fn: Optional[Callable] = None,
                     mask=None, bias=None, window: Optional[int] = None,
                     alibi_slopes=None,
                     impl: Optional[str] = None, mesh=None):
    """Dispatching attention entry used by the model layer.

    ``window``/``alibi_slopes`` assume canonical positions (query t at
    position t) — the training fast path; models with gathered/shifted
    positions (random-LTD, KV-cache) express the same semantics through
    ``mask``/``bias`` and ride the XLA body.  ``mesh``: the mesh the caller's
    jit is partitioned over, which the Pallas path needs to run per shard
    (``_flash_over_mesh``); the XLA body partitions itself."""
    return dispatch("causal_attention", q, k, v, causal=causal, scale=scale,
                    dropout_fn=dropout_fn, mask=mask, bias=bias,
                    window=window, alibi_slopes=alibi_slopes, impl=impl,
                    mesh=mesh)


__all__ = ["causal_attention", "flash_attention", "configure_flash_blocks",
           "paged_attention", "lora_matmul",
           "ragged_prefill_attention", "sink_softmax",
           "evoformer_attention",
           "index_scores", "index_select", "selected_attention",
           "selection_mask", "threshold_mask",
           "block_scores",
           "all_gather_matmul", "matmul_reduce_scatter",
           "row_parallel_matmul", "collective_matmul",
           "lm_cross_entropy", "masked_nll_sum", "rms_norm", "layer_norm",
           "op_report", "register_op", "dispatch", "list_ops", "registry",
           "grouped_gemm", "ssm_chunk_scan", "ssm_state_update",
           "ssm_pool_chunk_scan",
           "causal_conv1d", "paged_kv_append"]
