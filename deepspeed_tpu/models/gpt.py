"""Decoder-only transformer LM (GPT-2 / Llama family) — the flagship model.

This plays the role of the reference's model zoo entries (GPT-2/Llama policies in
module_inject/containers/{gpt2,llama}.py and inference/v2/model_implementations/
llama_v2) but as a TPU-first flax module:

- every parameter carries logical sharding axes via ``nn.with_partitioning``
  (mapped to mesh axes by parallel/partition.py — TP/FSDP/SP fall out of the
  annotations instead of graph surgery)
- pre-norm blocks, optional RoPE + RMSNorm (llama style) or learned positions +
  LayerNorm (gpt2 style), gated (SwiGLU) or GELU MLP
- causal attention via a single fused einsum path XLA maps onto the MXU;
  flash-attention Pallas kernel is swapped in by ops/ when enabled
- ``remat`` applies jax.checkpoint per block (reference:
  runtime/activation_checkpointing/checkpointing.py)

call contract: ``model.apply(params, batch, rngs={"dropout": k}) -> scalar loss``
where batch = {"input_ids": [B, T] int32, optional "labels": [B, T],
optional "loss_mask": [B, T]}.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = object


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    num_layers: int = 12
    num_heads: int = 12
    head_dim: int = 64
    hidden_size: int = 768
    mlp_ratio: int = 4
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: Dtype = jnp.float32          # compute dtype (engine casts params)
    param_dtype: Dtype = jnp.float32
    use_rope: bool = False              # llama-style when True
    use_rmsnorm: bool = False
    gated_mlp: bool = False             # SwiGLU
    num_kv_heads: Optional[int] = None  # GQA; defaults to num_heads
    remat: bool = False
    tie_embeddings: bool = True
    # MoE (reference deepspeed.moe; Mixtral-style when num_experts > 0)
    num_experts: int = 0
    moe_k: int = 1
    moe_every: int = 2                  # MoE replaces MLP every Nth block
    moe_aux_coef: float = 0.01
    moe_capacity_factor: float = 1.25
    moe_dropless: bool = False          # ragged grouped-GEMM routing
    #                                     (ep>1: padded-bucket a2a, no drops)
    # ep a2a fast path (moe/comm.py; pushed from the ds_config `moe` block):
    # wire width of dispatch/combine a2as (0=full, 8/4=blockwise int codes),
    # quantization block, all-ICI full-width policy, and the chunk count
    # interleaving expert GEMMs with in-flight a2a chunks
    moe_wire_bits: int = 0
    moe_wire_block: int = 256
    moe_hierarchical: bool = False
    moe_num_chunks: int = 1
    # parallelism (mesh passed separately to the GPT module attribute)
    sequence_parallel: bool = False     # attention over the sp axis
    sp_impl: str = "ulysses"            # "ulysses" (a2a head swap) | "ring"
    # ring layout: "drop_in" permutes in/out of zig-zag placement inside every
    # attention call (~4 tensor volumes of sp wire per call, contiguous
    # activations everywhere); "native" permutes token ids + positions +
    # labels ONCE per step at the loss wrapper and keeps activations in
    # zig-zag layout through the whole stack — the ring hops become the only
    # sp-axis traffic (sequence/ring.py layout= docstring)
    sp_ring_layout: str = "drop_in"     # "drop_in" | "native"
    # ring inner attend: "einsum" materializes [c, c] logits per sub-attend;
    # "flash" runs the Pallas flash kernel with logsumexp merging and a
    # ring-level custom_vjp — O(inputs) attention memory for long context
    # (sequence/ring.py inner= docstring; needs T/(2·sp) >= 8, d % 8 == 0)
    sp_ring_inner: str = "einsum"       # "einsum" | "flash"
    # kernel selection (reference: replace_with_kernel_inject / DS_BUILD flags);
    # None = registry auto (pallas on TPU, XLA elsewhere).  Training
    # attention and the v2 engine's paged decode / ragged prefill read it.
    attn_impl: Optional[str] = None
    # route the TP row-parallel matmuls (MLP down-projection, attention
    # output projection) through the explicit ppermute-ring
    # collective-matmul fusions (ops/collective_matmul.py) so the TP
    # all-reduce overlaps the chunk matmuls; set by the engine from
    # ``overlap.collective_matmul``.  Inert at tp=1; loud error on unwired
    # combinations (sequence parallelism, non-dividing shapes).
    tp_collective_matmul: bool = False
    # chunked unembed+CE (ops/cross_entropy.py); 0 = one-shot logits
    loss_chunk: int = 0
    # HF-architecture knobs (checkpoint/hf.py maps real configs onto these):
    # explicit FFN width (llama intermediate_size is not a hidden multiple),
    # rope base (llama3 5e5, qwen2 1e6), norm eps, and bias placement
    # (qwen2: qkv only; gpt2: everywhere)
    mlp_dim_override: Optional[int] = None
    rope_theta: float = 10000.0
    # rope scaling (llama-3.1+ long-context checkpoints; HF rope_scaling):
    # ("llama3", factor, low_freq_factor, high_freq_factor, original_max) or
    # ("linear", factor); None = unscaled
    rope_scaling: Optional[tuple] = None
    norm_eps: Optional[float] = None    # None = ops/norms.py defaults
    qkv_bias: bool = False
    attn_out_bias: bool = False
    mlp_bias: bool = False
    # architecture variants for the wider HF zoo (reference zoo:
    # module_inject/containers/opt.py, inference/v2/model_implementations/
    # {phi,falcon}):
    activation: str = "gelu"            # non-gated MLP: gelu|gelu_exact|relu
    parallel_block: bool = False        # x + attn(n(x)) + mlp(n(x)) (falcon/phi)
    parallel_norms: int = 1             # 1 = shared input norm; 2 = ln_attn+ln_mlp
    rope_pct: float = 1.0               # partial rotary (phi partial_rotary_factor)
    unembed_bias: bool = False          # lm_head bias (phi)
    use_alibi: bool = False             # alibi attention bias, no positional
    #                                     table (bloom/falcon-rw)
    gate_act: str = "silu"              # gated-MLP gate: silu (SwiGLU) or
    #                                     gelu (gemma GeGLU)
    embed_scale: Optional[float] = None  # gemma: x·√H after the embedding
    #                                      gather (unembed stays unscaled)
    sliding_window: Optional[int] = None  # each token sees the last W keys
    #                                       (mistral; gpt-neo local layers)
    local_attn_layers: tuple = ()       # layers the window applies to; empty
    #                                     + sliding_window set = all layers
    attn_scale: Optional[float] = None  # logit scale; None = 1/sqrt(head_dim)
    #                                     (gpt-neo uses 1.0)
    alibi_prescale: bool = False        # falcon-rw: (scores+alibi)·scale with
    #                                     bf16-rounded slopes; bloom adds the
    #                                     bias AFTER scaling
    embed_norm: bool = False            # LayerNorm right after the embedding
    #                                     (bloom word_embeddings_layernorm)
    # random-LTD (data_pipeline/random_ltd.py): layers that run on a kept
    # token subset when the batch carries "random_ltd_idx"
    random_ltd_layer_ids: tuple = ()
    # activation fake-quant bits (compression/pruning.py quant_act —
    # reference basic_layer.py QuantAct); None/0 = off
    act_quant_bits: Optional[int] = None
    # afmoe family (Trinity; checkpoint/hf.py maps model_type "afmoe"):
    # the router, the expert layer's share, and the attention variants
    moe_router: str = "softmax"         # "sigmoid": scores are sigmoid(fp32
    #                                     logits), dropless route only
    moe_route_norm: bool = True         # the chosen k renormalised to sum 1
    moe_route_scale: float = 1.0        # ... and then scaled by this
    moe_route_eps: float = 1e-20        # ... over (their sum + this)
    moe_router_bias: bool = False       # `expert_bias` [E]: added to the
    #                                     scores for the SELECTION only
    moe_shared_dim: int = 0             # shared expert's width (0 = none):
    #                                     every token takes it
    moe_dense_layers: Optional[int] = None  # leading dense layers, every
    #                                     later one is MoE (None: moe_every)
    moe_expert_dim: Optional[int] = None  # expert width where it differs
    #                                     from the dense mlp_dim
    # the share of an expert-parallel deployment held here: the router is
    # num_experts wide, the weights hold experts [expert_offset,
    # expert_offset + experts_held) and the layer computes their part of
    # the result (None = all of them)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    attn_gate: bool = False             # o * sigmoid(Wg n1(x)) before Wo
    qk_norm: bool = False               # RMSNorm on each query and key head
    rope_layers: str = "all"            # "window": RoPE on layers with a
    #                                     sliding window only (NoPE global)
    sandwich_norm: bool = False         # norms after attention and FFN too:
    #                                     x + n2(attn(n1 x)); h + n4(f(n3 h))
    # latent attention (MLA; checkpoint/hf.py maps model_type "deepseek_v3"):
    # keys and values are expanded from one normed latent a token,
    # ``kv_lora_rank`` wide, beside one rotated key part ``qk_rope_head_dim``
    # wide that all heads share.  ``head_dim`` is then a query/key head's
    # whole width (its trailing qk_rope_head_dim columns are rotated) and
    # ``v_head_dim`` a value head's.  0 = ordinary heads
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: Optional[int] = None
    # ... with a query latent (DeepSeek-V2/V3 ``q_lora_rank``): ``wq_a``, an
    # RMSNorm, ``wq_b`` in place of ``wq``.  0 = none
    q_lora_rank: int = 0
    # both latents scaled after their norms by sqrt(hidden / rank) (the
    # LongCat-Flash ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` convention)
    mla_lora_rescale: bool = False
    attn_gate_headwise: bool = False    # latent attention's gate: ONE
    #                                     sigmoid scalar a head, before Wo
    # a learned selection of keys (DeepSeek-V3.2's "lightning indexer") on
    # the layers WITHOUT a window: ``index_n_heads`` index queries of
    # ``index_head_dim`` from the query latent score one index key a token,
    # and attention reads the ``index_topk`` best keys only
    # (ops/sparse_index.py).  0 = attention reads every key
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    # attention geometry of the layers WITH a window where it differs from
    # the other layers': ((field, value), ...) over num_heads, head_dim,
    # v_head_dim, kv_lora_rank, q_lora_rank, qk_rope_head_dim, rope_theta,
    # attn_scale.  ``for_layer(i)`` is the view a layer's attention takes
    window_attn: tuple = ()
    # layers that are no attention (checkpoint/hf.py maps model_types
    # "granitemoehybrid" and "lfm2_moe"): ``layer_types[i]`` is "attention",
    # "mamba" or "conv".  "mamba": a Mamba-2 scan layer (``Mamba2Mixer``:
    # ``ssm_heads`` heads of ``ssm_head_dim`` over a state ``ssm_state`` wide,
    # ``B``/``C`` shared by the heads of each of ``ssm_groups`` groups, a
    # depthwise causal conv ``ssm_conv`` taps long over x, B and C, computed
    # ``ssm_chunk`` rows at a time).  "conv": a gated short convolution
    # (``ShortConvMixer``: a depthwise causal conv ``conv_taps`` long over
    # the hidden width, without bias or activation, between two gates).
    # Empty: every layer is attention.  ``layer_kind(i)`` is the one place
    # that reads it; ``is_state_layer(i)`` (either mixer: a fixed-size state
    # a sequence and no KV pages), ``is_scan_layer(i)`` (Mamba-2) and
    # ``is_conv_layer(i)`` are the questions readers ask
    layer_types: tuple = ()
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_conv_bias: bool = True
    conv_taps: int = 3
    # Granite's multipliers beside embed_scale and attn_scale: each branch
    # enters the residual stream times this, and the logits are divided
    residual_scale: Optional[float] = None
    logits_divisor: Optional[float] = None

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """What a token's cache row needs: the latent and the key part."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_page_dim(self) -> int:
        """... and what a page row stores: that, padded with zeros to whole
        lane tiles of 128 (ops/paged_attention.py ``_dma_layout_ok``)."""
        return -(-self.latent_dim // 128) * 128

    def for_layer(self, i: int) -> "GPTConfig":
        """The configuration as layer ``i``'s ATTENTION sees it: the window
        layers' own geometry (``window_attn``) applied, and the indexer on
        the layers without a window only.  The same object where the layers
        are all alike."""
        if self.is_state_layer(i):
            what = {"mamba": "scan", "conv": "short-convolution"}[
                self.layer_kind(i)]
            raise ValueError(
                f"layer {i} is a {what} layer ({self.layer_types[i]}): it "
                f"has no attention geometry; ask is_state_layer(i) first")
        if not self.window_attn and not self.index_topk:
            return self
        return _layer_view(self, self.window_for_layer(i) is not None)

    def layer_kind(self, i: int) -> str:
        """What mixes layer ``i``'s sequence: "attention", "mamba" (a
        Mamba-2 scan) or "conv" (a gated short convolution)."""
        if not self.layer_types:
            return "attention"
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, the "
                f"model has {self.num_layers}")
        kind = self.layer_types[i]
        if kind not in ("attention", "mamba", "conv"):
            raise ValueError(f"layer_types[{i}] must be "
                             f"attention|mamba|conv, got {kind!r}")
        return kind

    def is_scan_layer(self, i: int) -> bool:
        """Whether layer ``i`` mixes its sequence by a state-space scan
        (Mamba-2): a float32 recurrent state and a conv tail a sequence."""
        return self.layer_kind(i) == "mamba"

    def is_conv_layer(self, i: int) -> bool:
        """Whether layer ``i`` is a gated short convolution: a conv tail a
        sequence and nothing else."""
        return self.layer_kind(i) == "conv"

    def is_state_layer(self, i: int) -> bool:
        """Whether layer ``i`` is no attention: it writes no KV pages and
        keeps one fixed-size state a sequence (a scan or a conv layer)."""
        return self.layer_kind(i) != "attention"

    @property
    def scan_layers(self) -> tuple:
        """The Mamba-2 scan layers' indices, in order."""
        return tuple(i for i in range(self.num_layers)
                     if self.is_scan_layer(i))

    @property
    def conv_layers(self) -> tuple:
        """The short-convolution layers' indices, in order."""
        return tuple(i for i in range(self.num_layers)
                     if self.is_conv_layer(i))

    @property
    def state_layers(self) -> tuple:
        """The layers that keep a state a sequence (scan or conv), in
        order: a layer's place here is its place in the state pool."""
        return tuple(i for i in range(self.num_layers)
                     if self.is_state_layer(i))

    @property
    def attention_layers(self) -> tuple:
        """The layers that own KV pages, in order: all of them unless
        ``layer_types`` says otherwise."""
        return tuple(i for i in range(self.num_layers)
                     if not self.is_state_layer(i))

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """The conv's channels: x, then B and C of every group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def is_moe_layer(self, i: int) -> bool:
        """Whether layer ``i`` holds experts: after ``moe_dense_layers``
        leading dense layers where that is set, else every ``moe_every``-th
        (reference examples put MoE on every other layer)."""
        if self.num_experts <= 0:
            return False
        if self.moe_dense_layers is not None:
            return i >= self.moe_dense_layers
        return i % self.moe_every == self.moe_every - 1

    def rope_for_layer(self, i: int) -> bool:
        """RoPE on layer ``i``: all layers, or (``rope_layers="window"``)
        only those ``window_for_layer`` gives a window."""
        if not self.use_rope:
            return False
        if self.rope_layers == "all":
            return True
        if self.rope_layers == "none":     # use_rope, and no layer rotates:
            return False                   # no positions at all (NoPE)
        if self.rope_layers != "window":
            raise ValueError(f"rope_layers must be all|window|none, got "
                             f"{self.rope_layers!r}")
        return self.window_for_layer(i) is not None

    @property
    def local_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def expert_dim(self) -> int:
        return self.moe_expert_dim or self.mlp_dim

    def window_for_layer(self, i: int):
        """Per-layer sliding window — THE gating rule shared by the training
        model, ragged prefill, and paged decode paths."""
        if self.sliding_window and (not self.local_attn_layers
                                    or i in self.local_attn_layers):
            return self.sliding_window
        return None

    @property
    def mlp_dim(self) -> int:
        return self.mlp_dim_override or self.hidden_size * self.mlp_ratio

    @classmethod
    def gpt2_small(cls, **kw):
        return cls(num_layers=12, num_heads=12, head_dim=64, hidden_size=768, **kw)

    @classmethod
    def llama(cls, num_layers=8, hidden=512, heads=8, **kw):
        return cls(num_layers=num_layers, hidden_size=hidden, num_heads=heads,
                   head_dim=hidden // heads, use_rope=True, use_rmsnorm=True,
                   gated_mlp=True, tie_embeddings=False, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 128)
        return cls(num_layers=2, num_heads=4, head_dim=8, hidden_size=32,
                   mlp_ratio=2, **kw)


@functools.lru_cache(maxsize=None)
def _layer_view(cfg: GPTConfig, windowed: bool) -> GPTConfig:
    if not windowed:
        return dataclasses.replace(cfg, window_attn=())
    allowed = {"num_heads", "head_dim", "v_head_dim", "kv_lora_rank",
               "q_lora_rank", "qk_rope_head_dim", "rope_theta", "attn_scale"}
    extra = dict(cfg.window_attn)
    if set(extra) - allowed:
        raise ValueError(f"window_attn may set {sorted(allowed)}, got "
                         f"{sorted(set(extra) - allowed)}")
    return dataclasses.replace(cfg, window_attn=(), index_topk=0, **extra)


def _gather_table(table, mesh, vocab_axis="tp"):
    """Constrain a [rows, embed] lookup table's embed dim to replicated right
    before a gather.

    Under ZeRO-3 the table is fsdp-sharded on the embed dim; a direct gather
    would produce embed-sharded activations that SPMD can only reshard to the
    batch-sharded layout by replicate-then-repartition ("Involuntary full
    rematerialization").  Un-sharding just the embed dim makes XLA emit one
    clean all-gather (ZeRO-3's gather-then-use).  The vocab dim KEEPS its tp
    sharding (Megatron-style vocab-parallel embedding: masked local gather +
    activation all-reduce), so tp>1 serving never materializes the full table."""
    if mesh is None:
        return table
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import auto_axes_spec
    spec0 = None
    if (vocab_axis and mesh.shape.get(vocab_axis, 1) > 1
            and table.shape[0] % mesh.shape[vocab_axis] == 0):
        spec0 = vocab_axis
    return jax.lax.with_sharding_constraint(
        table, NamedSharding(mesh, auto_axes_spec(P(spec0, None))))


def _pin_activations(x, mesh, seq_parallel: bool):
    """Constrain [B, T, ...] activations to (dp/fsdp-batch, sp-seq) sharding.

    Applied right after the embedding gather: without it XLA's SPMD partitioner
    may resolve the gather of an fsdp-sharded table by replicating the result
    and repartitioning ("Involuntary full rematerialization") — a full
    allgather of the activations on exactly the fsdp/sp meshes this framework
    targets.  Axes that don't divide the dim are skipped (e.g. T=1 decode)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import manual_axes_now
    # axes already applied by an enclosing manual shard_map (qgZ grad
    # region) drop out: in-body shapes are LOCAL over them, and naming
    # them in a constraint is illegal — size and pin over the rest
    manual = manual_axes_now()
    baxes = tuple(a for a in ("dp", "fsdp")
                  if mesh.shape.get(a, 1) > 1 and a not in manual)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    spec = [None] * x.ndim
    if baxes and x.shape[0] % bsize == 0:
        spec[0] = baxes if len(baxes) > 1 else baxes[0]
    sp = mesh.shape.get("sp", 1)
    if (seq_parallel and sp > 1 and "sp" not in manual and x.ndim > 1
            and x.shape[1] % sp == 0):
        spec[1] = "sp"
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _collective_matmul_active(cfg, mesh, t: int, k: int,
                              use_cache: bool = False) -> bool:
    """Gate for routing a row-parallel matmul through the ring
    collective-matmul fusion (ops/collective_matmul.py).  False when there
    is nothing to fuse (flag off, no mesh, tp=1, or a decode/cache call
    whose T=1 has no sequence to chunk); RAISES on combinations the fusion
    is not wired for — an opt-in perf flag must not silently degrade."""
    if not cfg.tp_collective_matmul or mesh is None or use_cache:
        return False
    tp = mesh.shape.get("tp", 1)
    if tp <= 1:
        return False
    if cfg.sequence_parallel:
        raise ValueError(
            "tp_collective_matmul + sequence parallelism is not wired (the "
            "sp attention paths own the sequence dim the ring would chunk)")
    if t % tp or k % tp:
        raise ValueError(
            f"tp_collective_matmul: seq len {t} and contraction dim {k} "
            f"must both divide tp={tp} (the ring chunks the sequence and "
            f"shards the contraction)")
    return True


def _kernel_init():
    return nn.initializers.normal(stddev=0.02)


def _part(init, names):
    return nn.with_partitioning(init, names)


def alibi_slopes(n_heads: int, head_dim: int = 0, prescale: bool = False):
    """Per-head alibi slopes (HF build_alibi_tensor formula: geometric
    sequence from the closest power of two, odd-power infill for non-pow2
    head counts).  Reference: bloom/falcon-rw attention bias.

    ``prescale`` applies the falcon-rw convention in ONE place for all three
    attention paths: slopes bf16-rounded (HF casts them before the product)
    and folded into the 1/√head_dim scale, because falcon computes
    ``(scores + alibi)·scale`` while bloom adds the bias post-scale."""
    import math
    cp2 = 2 ** math.floor(math.log2(n_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(cp2) - 3)))
    slopes = [base ** i for i in range(1, cp2 + 1)]
    if cp2 != n_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * cp2) - 3)))
        slopes += [extra_base ** i
                   for i in range(1, 2 * (n_heads - cp2), 2)]
    import numpy as np
    s = np.asarray(slopes, np.float32)
    if prescale:
        import ml_dtypes
        s = s.astype(ml_dtypes.bfloat16).astype(np.float32) * (
            head_dim ** -0.5)
    return s


def rotary_dim(head_dim: int, rope_pct: float) -> int:
    """Rotated prefix width for partial rotary (phi partial_rotary_factor),
    rounded down to even so the half-split convention holds."""
    rot = head_dim if rope_pct >= 1.0 else int(head_dim * rope_pct)
    return rot - (rot % 2)


def _scale_rope_freq(freq, scaling):
    """Frequency transform for long-context rope scaling (HF
    modeling_rope_utils):
    - ("linear", factor): inv_freq / factor (position interpolation)
    - ("llama3", factor, low_freq_factor, high_freq_factor, original_max):
      the llama-3.1 piecewise scheme — low frequencies divide by factor,
      high frequencies pass through, the medium band interpolates smoothly
      (matches _compute_llama3_parameters bit-for-bit in fp32)."""
    import math as _math
    kind = scaling[0]
    if kind == "linear":
        return freq / float(scaling[1])
    if kind == "llama3":
        _, factor, lo_f, hi_f, orig = scaling
        factor, lo_f, hi_f, orig = (float(factor), float(lo_f),
                                    float(hi_f), float(orig))
        wavelen = 2.0 * _math.pi / freq
        low_wl = orig / lo_f
        high_wl = orig / hi_f
        scaled = jnp.where(wavelen > low_wl, freq / factor, freq)
        smooth = (orig / wavelen - lo_f) / (hi_f - lo_f)
        smoothed = (1.0 - smooth) * scaled / factor + smooth * scaled
        is_medium = (wavelen >= high_wl) & (wavelen <= low_wl)
        return jnp.where(is_medium, smoothed, scaled)
    raise ValueError(f"unknown rope scaling kind {kind!r}")


def rope(q, k, positions, head_dim, base=10000.0, rope_pct=1.0,
         scaling=None, seq_lens=None):
    """Rotary position embedding (reference CUDA kernel:
    csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu — on TPU a few
    elementwise ops XLA fuses into the attention matmuls).  rope_pct < 1
    rotates only the first ``rotary_dim`` channels (phi-style partial rotary);
    the remainder passes through.  ``scaling`` = GPTConfig.rope_scaling.

    longrope (phi-3 long-context; ("longrope", attention_factor,
    short_factors, long_factors, original_max)): the short/long per-channel
    factor table is selected IN-GRAPH from each SEQUENCE's current length vs
    the pretrained context (HF selects per forward the same way), and
    cos/sin scale by the attention factor.  ``seq_lens``: per-element
    sequence lengths shaped like ``positions`` (ragged serving passes each
    token's slot kv length so co-batched sequences select independently);
    default = per-ROW max position + 1."""
    att_factor = None
    rot = rotary_dim(head_dim, rope_pct)
    half = rot // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is not None and scaling[0] == "longrope":
        _, att_factor, short_f, long_f, orig = scaling
        if seq_lens is None:
            # per-row: a padded/multi-row batch must not let one long row
            # flip the others' factor table
            seq_lens = jnp.max(positions, axis=-1, keepdims=True) + 1
        is_long = (seq_lens > orig)[..., None]           # [..., 1]
        ext = jnp.where(is_long,
                        jnp.asarray(long_f, jnp.float32),
                        jnp.asarray(short_f, jnp.float32))
        angles = (positions[..., None].astype(jnp.float32)
                  * (freq / ext))                        # [B,T,half]
    else:
        if scaling is not None:
            freq = _scale_rope_freq(freq, tuple(scaling))
        angles = positions[..., None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if att_factor is not None:
        sin = sin * jnp.float32(att_factor)
        cos = cos * jnp.float32(att_factor)

    def rotfn(x):
        x1, x2 = x[..., :half], x[..., half:rot]
        s = sin[:, :, None, :].astype(x.dtype)
        c = cos[:, :, None, :].astype(x.dtype)
        parts = [x1 * c - x2 * s, x2 * c + x1 * s]
        if rot < head_dim:
            parts.append(x[..., rot:])
        return jnp.concatenate(parts, axis=-1)

    return rotfn(q), rotfn(k)


def mlp_activation(name: str):
    """Non-gated MLP activation by HF ``activation_function``/``hidden_act``
    name: gpt2/phi use tanh-approx gelu ("gelu_new"), falcon exact-erf gelu,
    OPT relu (reference containers set these per policy)."""
    try:
        return {"gelu": nn.gelu,
                "gelu_exact": lambda x: nn.gelu(x, approximate=False),
                "relu": nn.relu,
                "silu": nn.silu,
                # clip text encoder: x·sigmoid(1.702x)
                "quick_gelu": lambda x: x * jax.nn.sigmoid(1.702 * x)}[name]
    except KeyError:
        raise ValueError(f"unknown MLP activation {name!r}; expected "
                         "gelu|gelu_exact|relu|silu|quick_gelu") from None


class Norm(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu.ops import layer_norm, rms_norm
        from deepspeed_tpu.ops.norms import LN_EPS, RMS_EPS
        c = self.cfg
        scale = self.param("scale", _part(nn.initializers.ones, ("embed",)),
                           (c.hidden_size,), c.param_dtype)
        if c.use_rmsnorm:
            return rms_norm(x, scale, eps=c.norm_eps or RMS_EPS)
        bias = self.param("bias", _part(nn.initializers.zeros, ("embed",)),
                          (c.hidden_size,), c.param_dtype)
        return layer_norm(x, scale, bias, eps=c.norm_eps or LN_EPS)


def head_norm(x, scale, cfg):
    """RMSNorm over the head dim of ``x [..., heads, d]`` (``qk_norm``)."""
    from deepspeed_tpu.ops import rms_norm
    from deepspeed_tpu.ops.norms import RMS_EPS
    return rms_norm(x, scale, eps=cfg.norm_eps or RMS_EPS)


def attend_with_mask(q, k, v, mask, bias=None, scale=None):
    """Attention with an explicit boolean mask [B, Tq, S] — the KV-cache /
    padded-prefill path (reference: masked softmax in
    csrc/transformer/inference/csrc/softmax.cu).  Delegates to the ops layer."""
    from deepspeed_tpu import ops
    return ops.causal_attention(q, k, v, causal=False, mask=mask, bias=bias,
                                scale=scale)


def causal_attend(q, k, v, probs_dropout=None):
    """Plain causal softmax attention on [B, T, N, D] (the "local attention" in
    reference sequence/layer.py terms) — the XLA reference body lives in the ops
    registry; this thin alias keeps the Ulysses local-attention signature."""
    from deepspeed_tpu import ops
    return ops.causal_attention(q, k, v, dropout_fn=probs_dropout, impl="xla")


class Attention(nn.Module):
    cfg: GPTConfig
    mesh: Optional[object] = None

    @nn.compact
    def __call__(self, x, positions, deterministic: bool,
                 use_cache: bool = False, kv_mask=None, start_index=0,
                 kv_positions=None, window=None, fused_ok: bool = False,
                 use_rope: Optional[bool] = None):
        c = self.cfg
        B, T, H = x.shape
        nh, nkv, hd = c.num_heads, c.kv_heads, c.head_dim
        if use_rope is None:
            use_rope = c.use_rope
        if c.act_quant_bits:
            from deepspeed_tpu.compression.pruning import quant_act
            x = quant_act(x, c.act_quant_bits)

        wq = self.param("wq", _part(_kernel_init(), ("embed", "heads", "kv")),
                        (H, nh, hd), c.param_dtype)
        wk = self.param("wk", _part(_kernel_init(), ("embed", "heads", "kv")),
                        (H, nkv, hd), c.param_dtype)
        wv = self.param("wv", _part(_kernel_init(), ("embed", "heads", "kv")),
                        (H, nkv, hd), c.param_dtype)
        wo = self.param("wo", _part(_kernel_init(), ("heads", "kv", "embed")),
                        (nh, hd, H), c.param_dtype)
        bo = (self.param("bo", _part(nn.initializers.zeros, ("embed",)),
                         (H,), c.param_dtype)
              if c.attn_out_bias else None)

        cm_fused = _collective_matmul_active(c, self.mesh, T, nh * hd,
                                             use_cache=use_cache)

        if c.attn_gate:
            wgate = self.param("wgate", _part(_kernel_init(),
                                              ("embed", "heads", "kv")),
                               (H, nh, hd), c.param_dtype)
            gate = jax.nn.sigmoid(jnp.einsum("bth,hnd->btnd", x,
                                             wgate.astype(x.dtype)))

        def out_proj(o):
            if c.attn_gate:
                o = o * gate
            if cm_fused:
                # row-parallel over tp-sharded heads: the output all-reduce
                # decomposed into ring chunk matmuls + neighbor hops
                # (ops/collective_matmul.py row_parallel_matmul)
                from deepspeed_tpu.ops import collective_matmul as cm_ops
                Bo, To = o.shape[0], o.shape[1]
                y = cm_ops.row_parallel_matmul(
                    o.reshape(Bo, To, nh * hd),
                    wo.astype(x.dtype).reshape(nh * hd, H), self.mesh)
            else:
                y = jnp.einsum("btnd,ndh->bth", o, wo.astype(x.dtype))
            return y if bo is None else y + bo.astype(x.dtype)

        q = jnp.einsum("bth,hnd->btnd", x, wq.astype(x.dtype))
        k = jnp.einsum("bth,hnd->btnd", x, wk.astype(x.dtype))
        v = jnp.einsum("bth,hnd->btnd", x, wv.astype(x.dtype))
        if c.qkv_bias:
            q = q + self.param("bq", _part(nn.initializers.zeros,
                                           ("heads", "kv")),
                               (nh, hd), c.param_dtype).astype(x.dtype)
            k = k + self.param("bk", _part(nn.initializers.zeros,
                                           ("heads", "kv")),
                               (nkv, hd), c.param_dtype).astype(x.dtype)
            v = v + self.param("bv", _part(nn.initializers.zeros,
                                           ("heads", "kv")),
                               (nkv, hd), c.param_dtype).astype(x.dtype)

        if c.qk_norm:
            q, k = head_norm(q, self.param(
                "q_norm", _part(nn.initializers.ones, ("kv",)), (hd,),
                c.param_dtype), c), head_norm(k, self.param(
                    "k_norm", _part(nn.initializers.ones, ("kv",)), (hd,),
                    c.param_dtype), c)
        if use_rope:
            q, k = rope(q, k, positions, hd, base=c.rope_theta,
                        rope_pct=c.rope_pct, scaling=c.rope_scaling)

        def alibi_bias(key_pos):
            """[.., S] key positions → [.., nh, 1, S] logit bias.  Key-
            position-only form: softmax is invariant to the per-row
            -slope·qpos constant, so slope·kpos ≡ slope·(kpos−qpos)
            (reference bloom build_alibi_tensor)."""
            if not c.use_alibi:
                return None
            s = jnp.asarray(alibi_slopes(nh, hd, c.alibi_prescale))
            return (s[:, None, None]
                    * key_pos[..., None, None, :].astype(jnp.float32))

        if use_cache:
            # static KV cache in a flax "cache" collection (reference:
            # inference_context.h KV workspace; flax decode-cache idiom).
            S = c.max_seq_len
            ck = self.variable("cache", "cached_key",
                               jnp.zeros, (B, S, nkv, hd), x.dtype)
            cv = self.variable("cache", "cached_value",
                               jnp.zeros, (B, S, nkv, hd), x.dtype)
            start = jnp.asarray(start_index, jnp.int32)
            ck.value = jax.lax.dynamic_update_slice(ck.value, k,
                                                    (0, start, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v,
                                                    (0, start, 0, 0))
            # causal over LOGICAL positions: with left-padded prompts the cache
            # slot index differs from the token's position, so the engine passes
            # per-slot kv_positions; default (no padding) slot == position.
            if kv_positions is None:
                kp2 = jnp.arange(S)[None, :]                 # [1, S]
            else:
                kp2 = kv_positions                           # [B, S]
            kvpos = kp2[:, None, :]                          # [B|1, 1, S]
            mask = kvpos <= positions[:, :, None]            # causal, absolute
            if window is not None:
                # sliding window over LOGICAL positions (mistral/gpt-neo
                # local attention): key within the last `window` positions
                mask = mask & (kvpos > positions[:, :, None] - window)
            if kv_mask is not None:
                mask = mask & kv_mask[:, None, :].astype(bool)
            out = attend_with_mask(q, ck.value, cv.value, mask,
                                   bias=alibi_bias(kp2), scale=c.attn_scale)
            return out_proj(out)

        sp_active = (c.sequence_parallel and self.mesh is not None
                     and self.mesh.shape["sp"] > 1)
        if c.use_alibi and sp_active:
            raise ValueError("alibi + sequence parallelism is not wired "
                             "(the a2a/ring paths carry no logit bias)")
        if window is not None and sp_active:
            raise ValueError("sliding-window attention + sequence "
                             "parallelism is not wired")
        if c.attn_scale is not None and sp_active:
            raise ValueError("custom attn_scale + sequence parallelism is "
                             "not wired (the a2a/ring paths use the default "
                             "1/sqrt(head_dim) scale)")
        if sp_active:
            # sequence parallelism: Ulysses (seq→head all-to-all swap around
            # local attention) or ring (KV blocks rotate over neighbor links;
            # no head-divisibility constraint — sequence/ring.py).  Dropout
            # falls on the attention *output* here (rng plumbing inside
            # shard_map isn't worth it); local path keeps standard
            # prob-dropout.
            from deepspeed_tpu import ops
            if c.sp_impl == "ring":
                from deepspeed_tpu.sequence import ring_attention
                out = ring_attention(
                    self.mesh, q, k, v,
                    layout=("zigzag" if c.sp_ring_layout == "native"
                            else "contiguous"),
                    inner=c.sp_ring_inner)
            elif c.sp_impl != "ulysses":
                raise ValueError(f"unknown sp_impl {c.sp_impl!r}; expected "
                                 f"'ulysses' or 'ring'")
            else:
                from deepspeed_tpu.sequence import ulysses_attention
                local_attn = lambda q_, k_, v_: ops.causal_attention(  # noqa: E731,E501
                    q_, k_, v_, impl=c.attn_impl)
                out = ulysses_attention(local_attn, self.mesh, q, k, v)
            if c.dropout > 0 and not deterministic:
                out = nn.Dropout(rate=c.dropout)(out, deterministic=False)
        else:
            from deepspeed_tpu import ops
            pdrop = None
            if c.dropout > 0 and not deterministic:
                pdrop = lambda p: nn.Dropout(rate=c.dropout)(  # noqa: E731
                    p, deterministic=False)
            if fused_ok and (window is not None or c.use_alibi):
                # canonical positions (query t at position t): window/alibi go
                # in FIRST-CLASS so the Pallas kernel handles them in-kernel
                # (VERDICT r2 item 3 — no more masked-dense fallback for
                # bloom/falcon-rw/mistral/qwen2/gpt-neo training)
                slopes = (jnp.asarray(alibi_slopes(nh, hd, c.alibi_prescale))
                          if c.use_alibi else None)
                out = ops.causal_attention(q, k, v, causal=True,
                                           window=window,
                                           alibi_slopes=slopes,
                                           dropout_fn=pdrop,
                                           scale=c.attn_scale,
                                           impl=c.attn_impl, mesh=self.mesh)
            elif window is not None:
                # causal ∧ within-window, over absolute positions
                rel = positions[:, :, None] - positions[:, None, :]
                wmask = (rel >= 0) & (rel < window)
                out = ops.causal_attention(q, k, v, causal=False, mask=wmask,
                                           dropout_fn=pdrop,
                                           bias=alibi_bias(positions),
                                           scale=c.attn_scale,
                                           impl=c.attn_impl, mesh=self.mesh)
            else:
                out = ops.causal_attention(q, k, v, dropout_fn=pdrop,
                                           bias=alibi_bias(positions),
                                           scale=c.attn_scale,
                                           impl=c.attn_impl, mesh=self.mesh)
        return out_proj(out)


def mla_split(c: GPTConfig):
    """(nope, rope, value) widths of a latent-attention head."""
    return (c.head_dim - c.qk_rope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim or c.head_dim - c.qk_rope_head_dim)


def _lora_rescale(x, c: GPTConfig, rank: int):
    """A latent after its norm: times sqrt(hidden / rank) under
    ``mla_lora_rescale``."""
    if not c.mla_lora_rescale:
        return x
    return x * jnp.asarray((c.hidden_size / rank) ** 0.5, x.dtype)


def mla_latent(wkv_a, kv_norm, h, positions, c: GPTConfig):
    """A token's cache row from the normed layer input ``h [..., H]``:
    ``(c_kv [..., kv_lora_rank]`` after its RMSNorm, ``k_pe [..., rope]``
    rotated): the one definition the module's forward and the paged cache's
    write (inference/v2/model.py) share."""
    from deepspeed_tpu.ops import rms_norm
    from deepspeed_tpu.ops.norms import RMS_EPS
    ckv = h @ wkv_a.astype(h.dtype)
    c_kv = _lora_rescale(rms_norm(ckv[..., :c.kv_lora_rank], kv_norm,
                                  eps=c.norm_eps or RMS_EPS), c,
                         c.kv_lora_rank)
    k_pe = ckv[..., c.kv_lora_rank:]
    return c_kv, _rope_rows(k_pe[..., None, :], positions,
                            c.qk_rope_head_dim, c)[..., 0, :]


def _rope_rows(x, positions, width: int, c: GPTConfig, rotated=None):
    """``x [..., n, width]`` at ``positions [...]`` with the leading
    ``rotated`` (all) of its ``width`` columns rotated by halves at the
    layer's base."""
    lead = x.shape[:-2]
    x4 = x.reshape(((-1,) if len(lead) > 1 else (1,)) + (lead[-1],)
                   + x.shape[-2:])
    x4, _ = rope(x4, x4, positions.reshape(x4.shape[:2]), width,
                 base=c.rope_theta, scaling=c.rope_scaling,
                 rope_pct=1.0 if rotated is None else rotated / width)
    return x4.reshape(x.shape)


def mla_query_latent(wq_a, q_norm, h, c: GPTConfig):
    """The query latent ``cq [..., q_lora_rank]`` of ``h [..., H]``: normed
    (and rescaled), what ``wq_b`` and the indexer's queries read."""
    from deepspeed_tpu.ops import rms_norm
    from deepspeed_tpu.ops.norms import RMS_EPS
    return _lora_rescale(
        rms_norm(h @ wq_a.astype(h.dtype), q_norm, eps=c.norm_eps or RMS_EPS),
        c, c.q_lora_rank)


def mla_query(wq, h, positions, c: GPTConfig):
    """``(q_nope [..., nh, nope], q_pe [..., nh, rope]`` rotated) from ``h``
    through ``wq [H, nh, d]``, or from the query latent through ``wq_b``."""
    nope, rot, _ = mla_split(c)
    q = jnp.einsum("...h,hnd->...nd", h, wq.astype(h.dtype))
    return q[..., :nope], _rope_rows(q[..., nope:], positions, rot, c)


def index_query(wq_idx, ww_idx, cq, h, positions, c: GPTConfig):
    """The indexer's side of a query row: ``(qI [..., nI, dI]``, its leading
    rope columns rotated, ``w [..., nI]`` float32 with both scales in)."""
    q = jnp.einsum("...r,rnd->...nd", cq, wq_idx.astype(cq.dtype))
    q = _rope_rows(q, positions, c.index_head_dim, c,
                   rotated=c.qk_rope_head_dim)
    w = (h @ ww_idx.astype(h.dtype)).astype(jnp.float32)
    return q, w * (c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)


def index_key(wk_idx, norm_scale, norm_bias, h, positions, c: GPTConfig):
    """A token's index key ``[..., dI]``: LayerNorm of its projection, the
    leading rope columns rotated: what the index-key pool stores."""
    from deepspeed_tpu.ops import layer_norm
    k = layer_norm(h @ wk_idx.astype(h.dtype), norm_scale, norm_bias,
                   eps=1e-6)
    return _rope_rows(k[..., None, :], positions, c.index_head_dim, c,
                      rotated=c.qk_rope_head_dim)[..., 0, :]


def index_scores_dense(q, w, k):
    """``I[b, t, s] = sum_j w[b, t, j] relu(q[b, t, j] . k[b, s])``, float32:
    the uncached form (the serving op is ops/sparse_index.py)."""
    x = jnp.einsum("btjd,bsd->btjs", q, k,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("btjs,btj->bts", jax.nn.relu(x), w)


def topk_mask(scores, seen, k: int):
    """Of each row of ``scores [..., T, S]`` the ``k`` largest among the
    keys it may see (``seen``, bool), ties to the lower position, as a
    mask."""
    k = min(int(k), scores.shape[-1])
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    hit = jnp.zeros(scores.shape, bool)
    hit = jnp.put_along_axis(hit, idx, True, axis=-1, inplace=False)
    return hit & seen


class MLAttention(nn.Module):
    """Latent attention (MLA), the uncached forward as published: keys and
    values expanded from the latent, one rotated key part for all heads;
    with a query latent (``q_lora_rank``), a window, a headwise gate and a
    learned selection of keys (``index_topk``, as a mask over dense scores)
    where the configuration has them.  The serving engine reads the same
    parameters in absorbed form over latent page pools
    (inference/v2/model.py)."""

    cfg: GPTConfig
    mesh: Optional[object] = None

    @nn.compact
    def __call__(self, x, positions, deterministic: bool,
                 use_cache: bool = False, kv_mask=None, start_index=0,
                 kv_positions=None, window=None, fused_ok: bool = False,
                 use_rope: Optional[bool] = None):
        c = self.cfg
        if use_cache or c.use_alibi or c.qk_norm or c.attn_gate \
                or c.qkv_bias or c.sequence_parallel:
            raise NotImplementedError(
                "latent attention (kv_lora_rank) is built for the uncached "
                "forward and the v2 engine's latent page pools: no flax KV "
                "cache, alibi, qk_norm, elementwise gate (attn_gate_headwise "
                "is its gate), bias or sequence parallelism beside it")
        if c.index_topk and not c.q_lora_rank:
            raise NotImplementedError(
                "the indexer's queries come from the query latent: "
                "index_topk needs q_lora_rank")
        B, T, H = x.shape
        nh = c.num_heads
        nope, rot, vd = mla_split(c)
        kinit = _kernel_init()
        ones = nn.initializers.ones
        if c.q_lora_rank:
            wq_a = self.param("wq_a", _part(kinit, ("embed", None)),
                              (H, c.q_lora_rank), c.param_dtype)
            q_norm = self.param("q_norm", _part(ones, (None,)),
                                (c.q_lora_rank,), c.param_dtype)
            wq = self.param("wq_b", _part(kinit, (None, "heads", "kv")),
                            (c.q_lora_rank, nh, c.head_dim), c.param_dtype)
            cq = mla_query_latent(wq_a, q_norm, x, c)
        else:
            wq = self.param("wq", _part(kinit, ("embed", "heads", "kv")),
                            (H, nh, c.head_dim), c.param_dtype)
            cq = x
        wkv_a = self.param("wkv_a", _part(kinit, ("embed", None)),
                           (H, c.latent_dim), c.param_dtype)
        kv_norm = self.param("kv_norm", _part(ones, (None,)),
                             (c.kv_lora_rank,), c.param_dtype)
        wkv_b = self.param("wkv_b", _part(kinit, (None, "heads", "kv")),
                           (c.kv_lora_rank, nh, nope + vd), c.param_dtype)
        wo = self.param("wo", _part(kinit, ("heads", "kv", "embed")),
                        (nh, vd, H), c.param_dtype)
        q_nope, q_pe = mla_query(wq, cq, positions, c)
        c_kv, k_pe = mla_latent(wkv_a, kv_norm, x, positions, c)
        kv = jnp.einsum("btr,rnd->btnd", c_kv, wkv_b.astype(x.dtype))
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, :, None, :], (B, T, nh, rot))], -1)
        q = jnp.concatenate([q_nope, q_pe], -1)
        mask = None
        if window is not None or c.index_topk:
            rel = positions[:, :, None] - positions[:, None, :]
            mask = rel >= 0
            if window is not None:
                mask = mask & (rel < window)
        if c.index_topk:
            nI, dI = c.index_n_heads, c.index_head_dim
            wq_idx = self.param("wq_idx", _part(kinit, (None, None, None)),
                                (c.q_lora_rank, nI, dI), c.param_dtype)
            wk_idx = self.param("wk_idx", _part(kinit, ("embed", None)),
                                (H, dI), c.param_dtype)
            ww_idx = self.param("ww_idx", _part(kinit, ("embed", None)),
                                (H, nI), c.param_dtype)
            kn_s = self.param("k_idx_norm_scale", _part(ones, (None,)),
                              (dI,), c.param_dtype)
            kn_b = self.param("k_idx_norm_bias",
                              _part(nn.initializers.zeros, (None,)), (dI,),
                              c.param_dtype)
            qi, wi = index_query(wq_idx, ww_idx, cq, x, positions, c)
            ki = index_key(wk_idx, kn_s, kn_b, x, positions, c)
            mask = topk_mask(index_scores_dense(qi, wi, ki), mask,
                             c.index_topk)
        from deepspeed_tpu import ops
        # the flash kernel takes one width for keys and values: XLA here
        out = ops.causal_attention(q, k, kv[..., nope:], causal=mask is None,
                                   mask=mask, scale=c.attn_scale, impl="xla")
        if c.attn_gate_headwise:
            wgate = self.param("wgate", _part(kinit, ("embed", "heads")),
                               (H, nh), c.param_dtype)
            out = out * jax.nn.sigmoid(x @ wgate.astype(x.dtype))[..., None]
        return jnp.einsum("btnd,ndh->bth", out, wo.astype(x.dtype))


def _dt_bias_init(key, shape, dtype):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    [0.001, 0.1] (the Mamba-2 reference initialisation)."""
    import math
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype):
    """``A_log`` with ``A = exp(A_log)`` uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def _conv_init(taps: int):
    """Initialiser of a depthwise conv's weight ``[channels, taps]`` and
    bias: uniform in +-1 / sqrt(taps) (the Mamba-2 reference leaves its
    ``Conv1d`` at the framework's default, whose fan-in is the taps).  At
    the matrices' 0.02 the conv would shrink x, B and C twentyfold and the
    state would carry nothing of the output."""
    bound = taps ** -0.5

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    return init


def ssm_split(c: GPTConfig):
    """Column offsets of ``w_in``'s output ``[z | xBC | dt]``."""
    return c.ssm_inner, c.ssm_inner + c.ssm_conv_dim


def ssm_gate_norm(y, z, scale, eps):
    """A scan layer's gated norm: the gate FIRST, then RMSNorm over the
    whole inner width (one group), float32 inside."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g * scale.astype(jnp.float32)


class Mamba2Mixer(nn.Module):
    """A Mamba-2 scan layer's mixer on whole sequences ``x [B, T, H]``, by
    the chunked (SSD) form (ops/ssm_scan.py), from a zero state:

        [z | xBC | dt] = W_in x;   xBC = silu(conv(xBC));  [x | B | C] = xBC
        dt = softplus(dt + dt_bias);   A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
        out = W_out norm(y * silu(z))

    The serving engine computes the same from these parameters with a
    carried state and conv tail (inference/v2/model.py)."""
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu import ops
        from deepspeed_tpu.ops.norms import RMS_EPS
        c = self.cfg
        H, inner, cd = c.hidden_size, c.ssm_inner, c.ssm_conv_dim
        h, p, g, n = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
        w_in = self.param("w_in", _part(_kernel_init(), ("embed", "mlp")),
                          (H, inner + cd + h), c.param_dtype)
        conv_w = self.param("conv_w",
                            _part(_conv_init(c.ssm_conv), ("mlp", None)),
                            (cd, c.ssm_conv), c.param_dtype)
        conv_b = (self.param("conv_b",
                             _part(_conv_init(c.ssm_conv), ("mlp",)),
                             (cd,), c.param_dtype)
                  if c.ssm_conv_bias else None)
        dt_bias = self.param("dt_bias", _part(_dt_bias_init, (None,)), (h,),
                             c.param_dtype)
        a_log = self.param("A_log", _part(_a_log_init, (None,)), (h,),
                           c.param_dtype)
        d = self.param("D", _part(nn.initializers.ones, (None,)), (h,),
                       c.param_dtype)
        norm = self.param("norm", _part(nn.initializers.ones, ("mlp",)),
                          (inner,), c.param_dtype)
        w_out = self.param("w_out", _part(_kernel_init(), ("mlp", "embed")),
                           (inner, H), c.param_dtype)
        Bt, T = x.shape[:2]
        zxd = x @ w_in.astype(x.dtype)
        a, b = ssm_split(c)
        z, xbc, dt = zxd[..., :a], zxd[..., a:b], zxd[..., b:]
        xbc, _ = ops.causal_conv1d(
            xbc, conv_w, conv_b,
            jnp.zeros((Bt, c.ssm_conv - 1, cd), xbc.dtype))
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        y, _ = ops.ssm_chunk_scan(
            xbc[..., :inner].reshape(Bt, T, h, p), dt,
            -jnp.exp(a_log.astype(jnp.float32)),
            xbc[..., inner:inner + g * n].reshape(Bt, T, g, n),
            xbc[..., inner + g * n:].reshape(Bt, T, g, n), d,
            jnp.zeros((Bt, h, p, n), jnp.float32), chunk=c.ssm_chunk)
        y = ssm_gate_norm(y.reshape(Bt, T, inner), z, norm,
                          c.norm_eps or RMS_EPS)
        return y.astype(x.dtype) @ w_out.astype(x.dtype)


def short_conv_gates(bcx):
    """A short-conv layer's projected rows ``[..., 3 H]`` = ``[B | C | X]``
    -> (the conv's input ``B * X``, the output gate ``C``)."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return b * x, c


class ShortConvMixer(nn.Module):
    """A gated short convolution (LFM2's ``conv`` layers) on whole sequences
    ``x [B, T, H]``, from a zero tail:

        [B | C | X] = W_in x;   u = B * X
        v_t = sum_j w[:, j] u_{t - K + 1 + j}      (depthwise, K = conv_taps,
                                                    no bias, no activation)
        out = W_out (C * v)

    The serving engine computes the same from these parameters with a
    carried tail, the last ``K - 1`` rows of ``u`` (inference/v2/model.py)."""
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu import ops
        c = self.cfg
        H, K = c.hidden_size, c.conv_taps
        w_in = self.param("w_in", _part(_kernel_init(), ("embed", "mlp")),
                          (H, 3 * H), c.param_dtype)
        conv_w = self.param("conv_w", _part(_conv_init(K), ("mlp", None)),
                            (H, K), c.param_dtype)
        w_out = self.param("w_out", _part(_kernel_init(), ("mlp", "embed")),
                           (H, H), c.param_dtype)
        u, gate = short_conv_gates(x @ w_in.astype(x.dtype))
        v, _ = ops.causal_conv1d(
            u, conv_w, None, jnp.zeros((x.shape[0], K - 1, H), u.dtype),
            activation=None)
        return (gate * v) @ w_out.astype(x.dtype)


class MLP(nn.Module):
    cfg: GPTConfig
    mesh: Optional[object] = None

    @nn.compact
    def __call__(self, x, deterministic: bool, use_cache: bool = False):
        c = self.cfg
        if c.act_quant_bits:
            from deepspeed_tpu.compression.pruning import quant_act
            x = quant_act(x, c.act_quant_bits)
        H, M = c.hidden_size, c.mlp_dim
        wi = self.param("wi", _part(_kernel_init(), ("embed", "mlp")),
                        (H, M), c.param_dtype)
        wo = self.param("wo", _part(_kernel_init(), ("mlp", "embed")),
                        (M, H), c.param_dtype)
        h = x @ wi.astype(x.dtype)
        if c.mlp_bias:
            h = h + self.param("bi", _part(nn.initializers.zeros, ("mlp",)),
                               (M,), c.param_dtype).astype(x.dtype)
        if c.gated_mlp:
            wg = self.param("wg", _part(_kernel_init(), ("embed", "mlp")),
                            (H, M), c.param_dtype)
            h = mlp_activation(c.gate_act)(x @ wg.astype(x.dtype)) * h
        else:
            h = mlp_activation(c.activation)(h)
        if c.dropout > 0 and not deterministic:
            h = nn.Dropout(rate=c.dropout)(h, deterministic=False)
        if _collective_matmul_active(c, self.mesh, x.shape[1], M,
                                     use_cache=use_cache):
            # row-parallel down-projection: the tp all-reduce decomposed
            # into a ring of chunk matmuls + neighbor hops
            from deepspeed_tpu.ops import collective_matmul as cm_ops
            y = cm_ops.row_parallel_matmul(h, wo.astype(x.dtype), self.mesh)
        else:
            y = h @ wo.astype(x.dtype)
        if c.mlp_bias:
            y = y + self.param("bo", _part(nn.initializers.zeros, ("embed",)),
                               (H,), c.param_dtype).astype(x.dtype)
        return y


class Block(nn.Module):
    cfg: GPTConfig
    is_moe: bool = False
    mesh: Optional[object] = None
    attn_cfg: Optional[GPTConfig] = None   # this layer's attention view
    #                                        (GPTConfig.for_layer); None: cfg
    mixer: str = "attention"               # GPTConfig.layer_kind: "mamba"
    #                                        (Mamba2Mixer) | "conv"
    #                                        (ShortConvMixer) mix instead

    @nn.compact
    def __call__(self, x, positions, deterministic: bool,
                 use_cache: bool = False, kv_mask=None, start_index=0,
                 kv_positions=None, pld_keep=None, window=None,
                 fused_ok: bool = False, use_rope: Optional[bool] = None):
        c = self.cfg

        def pld_mask():
            # progressive layer drop (runtime/progressive_layer_drop.py):
            # one Bernoulli per sublayer per step, shared across the batch;
            # None = gate inactive (eval / cache / disabled)
            if pld_keep is None or deterministic or use_cache:
                return None
            return jax.random.bernoulli(self.make_rng("dropout"), pld_keep)

        def pld_gate(delta):
            m = pld_mask()
            if m is None:
                return delta
            # inverted scaling (PLD paper Alg. 1): kept branches divide by p
            # so train-time expectation matches the full-depth eval forward
            return delta * (m.astype(delta.dtype)
                            / jnp.asarray(pld_keep, delta.dtype))

        if c.parallel_block:
            # falcon/phi-style parallel residual: attention and MLP both read
            # the SAME residual input (one shared input norm, or falcon-40b's
            # ln_attn + ln_mlp pair) and their outputs sum into one residual
            # add (reference inference/v2/model_implementations/falcon,
            # module_inject/containers/ — parallel_attn semantics).
            if (self.is_moe or c.sandwich_norm or c.mla
                    or self.mixer != "attention"
                    or c.residual_scale is not None):
                raise ValueError("parallel_block + MoE / sandwich_norm / "
                                 "latent attention / scan or conv layers / "
                                 "residual_scale is not a supported "
                                 "architecture combination")
            h_attn = Norm(c)(x)                       # Norm_0
            h_mlp = Norm(c)(x) if c.parallel_norms == 2 else h_attn  # Norm_1
            a = Attention(c, mesh=self.mesh)(h_attn, positions, deterministic,
                                             use_cache, kv_mask, start_index,
                                             kv_positions, window=window,
                                             fused_ok=fused_ok,
                                             use_rope=use_rope)
            return (x + pld_gate(a)
                    + pld_gate(MLP(c, mesh=self.mesh)(h_mlp, deterministic,
                                                      use_cache=use_cache)),
                    jnp.float32(0.0))
        if self.mixer != "attention":
            if use_cache:
                raise NotImplementedError(
                    "a scan or conv layer through the dense KV-cache path: "
                    "its state lives in the v2 engine's pool (inference/v2); "
                    "the v1 cache holds keys and values only")
            mix = Mamba2Mixer if self.mixer == "mamba" else ShortConvMixer
            a = mix(c)(Norm(c)(x))
        else:
            attn = (MLAttention(self.attn_cfg or c, mesh=self.mesh,
                                name="Attention_0") if c.mla
                    else Attention(c, mesh=self.mesh))
            a = attn(Norm(c)(x), positions, deterministic, use_cache,
                     kv_mask, start_index, kv_positions, window=window,
                     fused_ok=fused_ok, use_rope=use_rope)
        if c.sandwich_norm:
            a = Norm(c, name="post_attn_norm")(a)
        rs = c.residual_scale
        if rs is not None:
            a = a * jnp.asarray(rs, a.dtype)
        x = x + pld_gate(a)
        if self.is_moe:
            from deepspeed_tpu.moe import MoE
            rng = (self.make_rng("dropout")
                   if self.has_rng("dropout") else None)
            moe_out, aux = MoE(hidden_size=c.hidden_size,
                               num_experts=c.num_experts, k=c.moe_k,
                               capacity_factor=c.moe_capacity_factor,
                               mlp_ratio=c.mlp_ratio, mlp_dim=c.expert_dim,
                               router=c.moe_router,
                               route_norm=c.moe_route_norm,
                               route_scale=c.moe_route_scale,
                               route_eps=c.moe_route_eps,
                               router_bias=c.moe_router_bias,
                               shared_dim=c.moe_shared_dim,
                               experts_held=c.experts_held,
                               expert_offset=c.expert_offset,
                               mesh=self.mesh,
                               param_dtype=c.param_dtype,
                               dropless=c.moe_dropless,
                               gated=c.gated_mlp,
                               wire_bits=c.moe_wire_bits,
                               wire_block=c.moe_wire_block,
                               hierarchical=c.moe_hierarchical,
                               num_chunks=c.moe_num_chunks,
                               name="moe")(Norm(c)(x), rng, deterministic)
            m = pld_mask()
            if m is not None:     # one keep gates BOTH the output and the
                scale = m.astype(moe_out.dtype) / jnp.asarray(
                    pld_keep, moe_out.dtype)
                moe_out = moe_out * scale
                aux = aux * scale.astype(aux.dtype)  # dropped ffn: no LB loss
            if c.sandwich_norm:
                moe_out = Norm(c, name="post_ffn_norm")(moe_out)
            if rs is not None:
                moe_out = moe_out * jnp.asarray(rs, moe_out.dtype)
            x = x + moe_out
        else:
            aux = jnp.float32(0.0)
            f = MLP(c, mesh=self.mesh)(Norm(c)(x), deterministic,
                                       use_cache=use_cache)
            if c.sandwich_norm:
                f = Norm(c, name="post_ffn_norm")(f)
            if rs is not None:
                f = f * jnp.asarray(rs, f.dtype)
            x = x + pld_gate(f)
        return x, aux


class GPTBackbone(nn.Module):
    """Token ids → final hidden states (used by both the LM loss wrapper and,
    later, the inference engine)."""

    cfg: GPTConfig
    mesh: Optional[object] = None

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 positions=None, use_cache: bool = False, kv_mask=None,
                 start_index=0, kv_positions=None, ltd_idx=None,
                 pld_theta=None):
        """positions: [B, T] absolute positions (default arange — the training
        path); the inference engine passes per-row positions for left-padded
        prompts and incremental decode.  kv_mask: [B, max_seq_len] validity of
        cache slots.  start_index: scalar cache write offset.  ltd_idx:
        [n_ltd_layers, B, keep] sorted random-LTD keep indices (data_pipeline/
        random_ltd.py) — layers in cfg.random_ltd_layer_ids run on the kept
        subset only, dropped tokens skip them (reference data_routing/
        basic_layer.py)."""
        c = self.cfg
        B, T = input_ids.shape
        emb = self.param("wte", _part(_kernel_init(), ("vocab", "embed")),
                         (c.vocab_size, c.hidden_size), c.param_dtype)
        x = _gather_table(emb.astype(c.dtype), self.mesh)[input_ids]
        if c.embed_scale:    # gemma √H normalizer (unembed stays unscaled)
            x = x * jnp.asarray(c.embed_scale, c.dtype)
        x = _pin_activations(x, self.mesh, c.sequence_parallel)
        if c.embed_norm:     # bloom word_embeddings_layernorm
            x = Norm(c, name="embed_norm")(x)
        canonical_pos = positions is None   # query t sits at position t: the
        # training fast path where window/alibi can fuse into the flash kernel
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        if not c.use_rope and not c.use_alibi:
            pos_emb = self.param("wpe", _part(_kernel_init(), (None, "embed")),
                                 (c.max_seq_len, c.hidden_size), c.param_dtype)
            x = x + _gather_table(pos_emb.astype(c.dtype), self.mesh,
                                  vocab_axis=None)[positions]
            x = _pin_activations(x, self.mesh, c.sequence_parallel)
        if c.dropout > 0 and not deterministic:
            x = nn.Dropout(rate=c.dropout)(x, deterministic=False)

        block_cls = Block
        if c.remat and not use_cache:
            # static: deterministic, use_cache, window, fused_ok (the last two
            # select the fused attention path at trace time)
            block_cls = nn.remat(Block, static_argnums=(3, 4, 9, 10, 11),
                                 policy=jax.checkpoint_policies.nothing_saveable)
        ltd_layers = tuple(c.random_ltd_layer_ids or ())
        aux_total = jnp.float32(0.0)
        for i in range(c.num_layers):
            kind = c.layer_kind(i)
            block = block_cls(c, c.is_moe_layer(i), self.mesh,
                              c.for_layer(i) if kind == "attention" else None,
                              kind, name=f"block_{i}")
            keep = None
            if pld_theta is not None:
                from deepspeed_tpu.runtime.progressive_layer_drop import \
                    layer_keep_prob
                keep = layer_keep_prob(i, c.num_layers, pld_theta)
            win = c.window_for_layer(i)
            if (ltd_idx is not None and i in ltd_layers and not use_cache):
                from deepspeed_tpu.data_pipeline.random_ltd import \
                    apply_random_ltd
                idx = ltd_idx[ltd_layers.index(i)]
                x, aux = apply_random_ltd(
                    # args positional: remat's static_argnums (9=window,
                    # 10=fused_ok) must be within the positional arg list;
                    # gathered positions are non-canonical → fused_ok False
                    lambda xk, pk: block(xk, pk, deterministic, False,
                                         None, 0, None, keep, win, False,
                                         c.rope_for_layer(i)),
                    x, positions, idx)
            else:
                x, aux = block(x, positions, deterministic,
                               use_cache, kv_mask, start_index, kv_positions,
                               keep, win, canonical_pos and not use_cache,
                               c.rope_for_layer(i))
            aux_total = aux_total + aux
        x = Norm(c, name="final_norm")(x)
        return x, emb, aux_total


def shift_labels(batch, input_ids):
    """(labels, mask) for next-token LM, honoring explicit labels/loss_mask and
    the -100-style ignore convention (labels < 0)."""
    labels = batch.get("labels")
    if labels is None:  # next-token LM
        labels = jnp.pad(input_ids[:, 1:], ((0, 0), (0, 1)))
        mask = jnp.ones_like(labels, dtype=jnp.float32).at[:, -1].set(0.0)
    else:
        mask = batch.get("loss_mask", jnp.ones_like(labels, dtype=jnp.float32))
        mask = mask.astype(jnp.float32) * (labels >= 0)
        labels = jnp.maximum(labels, 0)
    return labels, mask


class GPT(nn.Module):
    """LM-loss wrapper satisfying the engine's model contract.

    ``cfg.loss_chunk > 0`` computes the unembed+CE in rematerialized chunks
    (ops/cross_entropy.py) so the fp32 [B, T, V] logits never hit HBM; 0 keeps
    the one-shot logits path.
    """

    cfg: GPTConfig
    mesh: Optional[object] = None

    # subclass hook: chunk size actually used (0 = one-shot)
    def _loss_chunk(self) -> int:
        return self.cfg.loss_chunk

    @nn.compact
    def __call__(self, batch, deterministic: bool = False):
        c = self.cfg
        input_ids = batch["input_ids"]
        ltd = batch.get("random_ltd_idx")       # [B, n_ltd, keep] host layout
        if ltd is not None:
            ltd = jnp.moveaxis(jnp.asarray(ltd), 1, 0)   # → [n_ltd, B, keep]
        positions = labels = mask = None
        if c.sp_ring_layout not in ("drop_in", "native"):
            raise ValueError(f"sp_ring_layout must be drop_in|native, got "
                             f"{c.sp_ring_layout!r}")
        sp = (self.mesh.shape["sp"]
              if c.sequence_parallel and self.mesh is not None else 1)
        if c.sequence_parallel and c.sp_ring_layout == "native" and sp > 1:
            # layout-native zig-zag ring (sequence/ring.py layout=): shift
            # labels in contiguous order, then permute ids + labels + mask +
            # positions ONCE — token ids are ~H·dtype_bytes/4 cheaper to
            # reshuffle than activations, every position-wise op is layout-
            # blind, the masked-mean LM loss is permutation-invariant, and
            # the ring hops become the only per-layer sp traffic
            if c.sp_impl != "ring":
                raise ValueError("sp_ring_layout='native' requires "
                                 "sp_impl='ring' (ulysses is layout-free)")
            if ltd is not None:
                raise ValueError("random-LTD + sp_ring_layout='native' is "
                                 "not wired (the gathered subsequence breaks "
                                 "the zig-zag placement)")
            from deepspeed_tpu.sequence import zigzag_order
            idx, _ = zigzag_order(input_ids.shape[1], sp)  # raises on T%2sp
            labels, mask = shift_labels(batch, input_ids)
            input_ids = jnp.take(input_ids, idx, axis=1)
            labels = jnp.take(labels, idx, axis=1)
            mask = jnp.take(mask, idx, axis=1)
            positions = jnp.broadcast_to(idx, input_ids.shape)
        x, emb, moe_aux = GPTBackbone(c, self.mesh,
                                      name="backbone")(input_ids,
                                                       deterministic,
                                                       positions=positions,
                                                       ltd_idx=ltd,
                                                       pld_theta=batch.get(
                                                           "pld_theta"))
        with jax.named_scope("loss"):        # unembed + cross-entropy
            if c.tie_embeddings:
                unembed = emb.astype(x.dtype).T                # [H, V]
            else:
                unembed = self.param("lm_head",
                                     _part(_kernel_init(), ("embed", "vocab")),
                                     (c.hidden_size, c.vocab_size),
                                     c.param_dtype).astype(x.dtype)
            if labels is None:
                labels, mask = shift_labels(batch, input_ids)
            if c.logits_divisor:
                x = x / jnp.asarray(c.logits_divisor, x.dtype)
            lm_bias = (self.param("lm_head_bias",
                                  _part(nn.initializers.zeros, ("vocab",)),
                                  (c.vocab_size,), c.param_dtype)
                       if c.unembed_bias else None)
            from deepspeed_tpu.ops import lm_cross_entropy
            loss = lm_cross_entropy(x, unembed, labels, mask,
                                    chunk_size=self._loss_chunk() or None,
                                    bias=lm_bias)
        if c.num_experts > 0:
            loss = loss + c.moe_aux_coef * moe_aux
        return loss


class GPTLogits(nn.Module):
    """Token ids → logits, with optional KV cache — the inference-engine view of
    the same parameter tree as ``GPT`` (backbone + tied/untied unembed), so a
    training checkpoint loads directly (reference: the injected inference module
    reusing the HF layer weights, module_inject/replace_module.py:183)."""

    cfg: GPTConfig
    mesh: Optional[object] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_mask=None,
                 use_cache: bool = False, start_index=0, kv_positions=None,
                 deterministic: bool = True):
        c = self.cfg
        if (c.sequence_parallel and c.sp_ring_layout == "native"
                and self.mesh is not None and self.mesh.shape["sp"] > 1):
            raise ValueError(
                "sp_ring_layout='native' is a training-layout config (the "
                "loss wrapper permutes the batch into zig-zag placement); "
                "the logits view expects contiguous rows — use 'drop_in'")
        x, emb, _ = GPTBackbone(c, self.mesh, name="backbone")(
            input_ids, deterministic, positions=positions,
            use_cache=use_cache, kv_mask=kv_mask, start_index=start_index,
            kv_positions=kv_positions)
        if c.tie_embeddings:
            unembed = emb.astype(x.dtype).T
        else:
            unembed = self.param("lm_head",
                                 _part(_kernel_init(), ("embed", "vocab")),
                                 (c.hidden_size, c.vocab_size),
                                 c.param_dtype).astype(x.dtype)
        logits = (x @ unembed).astype(jnp.float32)
        if c.logits_divisor:
            logits = logits / c.logits_divisor
        if c.unembed_bias:
            logits = logits + self.param(
                "lm_head_bias", _part(nn.initializers.zeros, ("vocab",)),
                (c.vocab_size,), c.param_dtype).astype(jnp.float32)
        return logits


class GPTChunkedLoss(GPT):
    """GPT that always chunks the unembed+CE (defaults to 512-token chunks when
    ``cfg.loss_chunk`` is unset) — batch scales past the logits OOM wall."""

    def _loss_chunk(self) -> int:
        return self.cfg.loss_chunk or 512


def _mla_params(c: GPTConfig) -> int:
    """A latent-attention layer's parameters: wq (or wq_a, q_norm, wq_b),
    wkv_a, kv_norm, wkv_b, wo, the headwise gate, the indexer."""
    H = c.hidden_size
    nope, _, vd = mla_split(c)
    q_in = c.q_lora_rank or H
    n = (c.num_heads * (c.head_dim * q_in + vd * H
                        + c.kv_lora_rank * (nope + vd))
         + H * c.latent_dim + c.kv_lora_rank)
    if c.q_lora_rank:
        n += H * c.q_lora_rank + c.q_lora_rank
    if c.attn_gate_headwise:
        n += H * c.num_heads
    if c.index_topk:
        n += (c.q_lora_rank * c.index_n_heads * c.index_head_dim
              + H * c.index_head_dim + H * c.index_n_heads
              + 2 * c.index_head_dim)
    return n


def count_params(cfg: GPTConfig) -> int:
    """Parameters of the model ``cfg`` describes, as held here (an expert
    layer counts the experts it holds: ``cfg.local_experts``)."""
    H, M, V = cfg.hidden_size, cfg.mlp_dim, cfg.vocab_size
    norms = 1 if (cfg.parallel_block and cfg.parallel_norms == 1) else 2
    if cfg.sandwich_norm:
        norms += 2
    n_mat = 3 if cfg.gated_mlp else 2
    attn = (cfg.num_heads * cfg.head_dim * H * (3 if cfg.attn_gate else 2)
            + cfg.kv_heads * cfg.head_dim * H * 2              # wk, wv
            + (2 * cfg.head_dim if cfg.qk_norm else 0))
    per_norms = H * norms * (1 if cfg.use_rmsnorm else 2)
    n_scan, n_conv = len(cfg.scan_layers), len(cfg.conv_layers)
    # a scan layer's mixer: w_in, w_out, the conv, dt_bias/A_log/D, the norm
    scan = (H * (cfg.ssm_inner + cfg.ssm_conv_dim + cfg.ssm_heads)
            + cfg.ssm_inner * H
            + cfg.ssm_conv_dim * (cfg.ssm_conv + int(cfg.ssm_conv_bias))
            + 3 * cfg.ssm_heads + cfg.ssm_inner)
    # a short-conv layer's mixer: w_in [H, 3H], w_out [H, H], the conv
    conv = H * 3 * H + H * H + H * cfg.conv_taps
    attn = ((attn + per_norms) * (cfg.num_layers - n_scan - n_conv)
            + (scan + per_norms) * n_scan + (conv + per_norms) * n_conv)
    if cfg.mla:             # a layer's own geometry (GPTConfig.for_layer)
        attn = sum(_mla_params(cfg.for_layer(i))
                   + H * norms * (1 if cfg.use_rmsnorm else 2)
                   for i in range(cfg.num_layers))
    moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
    moe_ffn = (cfg.local_experts * H * cfg.expert_dim * n_mat
               + H * cfg.num_experts                            # router
               + (cfg.num_experts if cfg.moe_router_bias else 0)
               + 3 * H * cfg.moe_shared_dim)
    total = (attn + moe_ffn * moe_layers
             + H * M * n_mat * (cfg.num_layers - moe_layers) + V * H + H)
    if not cfg.use_rope and not cfg.use_alibi:
        total += cfg.max_seq_len * H
    if cfg.embed_norm:
        total += H * (1 if cfg.use_rmsnorm else 2)
    if not cfg.tie_embeddings:
        total += V * H
    if cfg.unembed_bias:
        total += V
    return total
