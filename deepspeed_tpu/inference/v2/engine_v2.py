"""InferenceEngineV2 — ragged continuous-batching serving engine ("FastGen").

Analog of the reference ``InferenceEngineV2`` (inference/v2/engine_v2.py:30):
``put(uids, tokens)`` runs ONE forward over a ragged batch and returns one
logit row per sequence (:107), ``query``/``can_schedule`` expose KV headroom
(:158,:184), ``flush`` frees state (:242).  ``generate`` adds the continuous-
batching driver with the Dynamic SplitFuse schedule (decodes first, prompt
chunks fill the remaining token budget — the policy the reference ships in
MII's ragged batching on top of this engine API).

The forward is one jitted XLA program over static shapes (token budget ×
sequence slots × blocks-per-seq); the paged KV cache is donated through each
step so it updates in place on device.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import Field

from deepspeed_tpu.config import DeepSpeedConfigModel
from deepspeed_tpu.inference.config import (GenerationConfig, _DTYPE_ALIASES)
from deepspeed_tpu.inference.v2.model import (PagedKVCache, named_partial,
                                              ragged_decode_burst,
                                              ragged_decode_forward,
                                              ragged_decode_sampled,
                                              ragged_decode_sampled_draft,
                                              ragged_forward,
                                              ragged_forward_sampled,
                                              ragged_forward_sampled_draft,
                                              speculative_burst,
                                              speculative_burst_sampled)
from deepspeed_tpu.inference.v2.ragged import (DSStateManager, RaggedBatch,
                                               build_ragged_batch)
from deepspeed_tpu.ops.sparse_index import masked_prefill
from deepspeed_tpu.runtime import faults
from deepspeed_tpu.telemetry.serving import (ServingTelemetry,
                                             ServingTelemetryConfig)
from deepspeed_tpu.telemetry.startup import ACCOUNT as _SETUP, init_span
from deepspeed_tpu.utils.logging import log_dist


# the sampled step programs of ``_step_sampled``: (kind, draft in lockstep)
_STEP_PROGRAMS = {
    ("decode", False): ragged_decode_sampled,
    ("decode", True): ragged_decode_sampled_draft,
    ("mixed", False): ragged_forward_sampled,
    ("mixed", True): ragged_forward_sampled_draft,
}


class _Round:
    """One scheduler round as a ``ds.round`` span, and the cursor of its
    phases: ``phase(name)`` closes the phase that was open and opens the
    next, so the phases tile the round whatever path (``continue``, an
    exception, a drain) leaves it.  ``phase(None)`` leaves none open, for
    a callee that opens its own (``_step_sampled``'s build / h2d /
    dispatch)."""

    __slots__ = ("stel", "span", "cur")

    def __init__(self, stel, **args):
        self.stel, self.cur = stel, None
        self.span = stel.span("round", **args)

    def __enter__(self):
        self.span.__enter__()
        return self

    def phase(self, name, **args):
        if self.cur is not None:
            self.cur.__exit__(None, None, None)
        self.cur = None if name is None else self.stel.span(name, **args)
        if self.cur is not None:
            self.cur.__enter__()

    def __exit__(self, *exc):
        self.phase(None)
        return self.span.__exit__(*exc)


class EngineDrained(RuntimeError):
    """``generate()`` stopped at a drain request (``request_drain()``):
    device records were materialized, live sequences flushed, and the
    not-yet-finished requests are waiting in ``export_pending_requests()``
    — the serving-side half of the PR-6 drain contract (stop admission,
    finish or migrate in-flight work)."""


class DSStateManagerConfig(DeepSpeedConfigModel):
    """reference: inference/v2/ragged/manager_configs.py DSStateManagerConfig."""

    max_tracked_sequences: int = 32
    max_ragged_batch_size: int = 256        # token budget per forward
    max_ragged_sequence_count: int = 32
    kv_block_size: int = 64
    num_kv_blocks: Optional[int] = None     # None = enough for all slots full
    # a model with sliding-window AND global layers keeps two page groups
    # (ragged.py): num_kv_blocks sizes the global group (pages a global
    # layer), this the window group (pages a window layer; None = a full
    # ring for every slot)
    num_kv_window_blocks: Optional[int] = None
    max_q_per_seq: int = 128                # prompt-chunk cap (SplitFuse)
    # "int8": per-token symmetric KV quantization — halves KV HBM (decode's
    # bandwidth bound) and doubles cache capacity for ~6% scale overhead
    # (the ZeRO-Inference trade applied to the KV side).  None = native dtype.
    kv_quant: Optional[str] = None
    # radix shared-prefix KV cache (ragged.RadixKVCache): new prompts alias
    # the pool blocks of every previously-served block-aligned prefix and
    # skip prefill for the matched tokens; retired blocks stay cached until
    # LRU eviction reclaims them under allocation pressure.  Greedy output
    # is token-exact with the cache on or off.  Off by default: it changes
    # pool-accounting observables (a flush no longer returns prompt blocks
    # to the free list immediately), so it is an explicit serving opt-in.
    prefix_cache: bool = False
    # SplitFuse round cap on TOTAL prompt-chunk tokens co-scheduled with
    # decode per forward (None = the full remaining token budget, the
    # pre-PR-15 behavior).  Bounding it keeps the mixed dispatch short so
    # in-flight decoders' TPOT stays flat while long prompts stream in.
    prefill_chunk_tokens: Optional[int] = None


class SLAClassConfig(DeepSpeedConfigModel):
    """One serving SLA class (``scheduler.sla_classes`` values).  Higher
    ``priority`` admits first and may preempt lower-priority decoders;
    ``ttft_slo_ms`` is the time-to-first-token objective that ARMS
    preemption (0 = no SLO: the class never preempts anyone)."""

    priority: int = 0
    ttft_slo_ms: float = 0.0


class SchedulerV2Config(DeepSpeedConfigModel):
    """``scheduler`` block: SLA-aware admission + preemption over the
    SplitFuse loop.  A request names its class via ``generate(...,
    sla=[...])``; unnamed requests ride the implicit ``default`` class
    (priority 0, no SLO).  When a waiting request with a TTFT SLO has
    burned ``preempt_margin`` of it and cannot be admitted (no sequence
    slot / no KV blocks even after cache eviction), the scheduler
    recompute-preempts the most recently admitted lower-priority running
    request — the PR 7 token-exact fold-back machinery, now driven by a
    policy instead of only pool deadlock."""

    sla_classes: Dict[str, SLAClassConfig] = Field(default_factory=dict)
    sla_preempt: bool = True
    preempt_margin: float = 0.5     # fraction of ttft_slo_ms before preempting


class V2TPConfig(DeepSpeedConfigModel):
    """reference: inference/v2/config_v2.py DeepSpeedTPConfig."""

    tp_size: int = 1


class SpeculativeConfig(DeepSpeedConfigModel):
    """Greedy draft-and-verify decoding (engine kwarg ``draft_model``/
    ``draft_params`` supplies the draft)."""

    gamma: int = 4              # draft tokens per verify
    outer_steps: int = 8        # draft+verify rounds fused per dispatch
    # serving default: ONE draft+verify dispatch covers every running
    # request (the spec program is slot-wide with an active mask, so the
    # per-dispatch floor — launch + host sync for the acceptance counts —
    # amortizes over the whole decode batch).  False dispatches each
    # request alone through the SAME compiled program (inactive lanes pass
    # their prev-token state through untouched, so the sequential runs are
    # token-identical to the batched one) — the per-request baseline the
    # bench's spec_batched_speedup_x compares against, not a serving mode
    batch_across_requests: bool = True


class V2QuantConfig(DeepSpeedConfigModel):
    """Quantized weight serving (reference
    inference/v2/modules/implementations/linear/quantized_linear.py W6A16 +
    inference/quantization/layers.py matmul-time dequant): weights live in
    HBM as int8 codes + group scales (~half the bf16 bytes) and every
    consumer dequantizes at its use site — the bf16 tree never exists at
    rest.  Composes with tensor parallelism (the store shards like the
    weights it replaces)."""

    enabled: bool = False
    # 8: int8 codes (½ the bf16 bytes), shards like the weights, W8A16
    # kernels.  4: nibble-PACKED codes (¼ the bf16 bytes) on single-shard
    # engines — the ZeRO-Inference HBM-fit point; with tp>1 it degrades to
    # int4-range codes at int8 bytes (packing breaks the sharding property)
    bits: int = 8
    group_size: int = 128       # scale granularity along each weight's dim 0


class AdapterLoRAConfig(DeepSpeedConfigModel):
    """Multi-tenant LoRA adapter serving (``adapters`` block): per-request
    adapter selection through ONE fused ragged dispatch (ops/lora_matmul.py
    batched gather), adapter A/B pages paged as refcounted residents of the
    KV block allocator (serving/adapters.py AdapterPool — the S-LoRA
    unified-pool design).  ``slots`` counts device-table lanes INCLUDING
    the reserved base-model identity slot 0; ``alpha``/``rank`` set the
    standard LoRA scale s = alpha / rank."""

    enabled: bool = False
    rank: int = 8
    alpha: float = 16.0
    slots: int = 8


class RaggedInferenceEngineConfig(DeepSpeedConfigModel):
    """reference: inference/v2/config_v2.py RaggedInferenceEngineConfig."""

    dtype: str = "bfloat16"
    tensor_parallel: V2TPConfig = Field(default_factory=V2TPConfig)
    state_manager: DSStateManagerConfig = Field(
        default_factory=DSStateManagerConfig)
    scheduler: SchedulerV2Config = Field(default_factory=SchedulerV2Config)
    generation: GenerationConfig = Field(default_factory=GenerationConfig)
    speculative: SpeculativeConfig = Field(default_factory=SpeculativeConfig)
    quant: V2QuantConfig = Field(default_factory=V2QuantConfig)
    adapters: AdapterLoRAConfig = Field(default_factory=AdapterLoRAConfig)
    telemetry: ServingTelemetryConfig = Field(
        default_factory=ServingTelemetryConfig)

    @classmethod
    def parse(cls, config):
        if config is None:
            return cls()
        if isinstance(config, cls):
            return config
        if isinstance(config, dict) and "dtype" in config:
            key = str(config["dtype"]).replace("torch.", "").lower()
            if key not in _DTYPE_ALIASES:
                raise ValueError(f"unsupported dtype {config['dtype']!r}; "
                                 f"expected one of {sorted(_DTYPE_ALIASES)}")
            config = {**config, "dtype": _DTYPE_ALIASES[key]}
        return cls.model_validate(config)

    @property
    def jnp_dtype(self):
        return {"float32": jnp.float32, "float16": jnp.float16,
                "bfloat16": jnp.bfloat16}[self.dtype]


@dataclasses.dataclass
class _Request:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    # host-materialized tokens (filled from the device records at sync points)
    generated: List[int] = dataclasses.field(default_factory=list)
    # tokens sampled ON DEVICE so far — the host schedules off this count and
    # only learns the VALUES at materialize time (device-resident feedback)
    sampled: int = 0
    # prefill complete: the next input token comes from device feedback
    decode_ready: bool = False
    # host-known continuation token (set after a preemption materialize; feeds
    # the first post-resume decode from the host instead of device feedback)
    held_token: Optional[int] = None
    done: bool = False
    # EOS was discovered at a materialize point (values are only inspected
    # there; post-EOS overshoot tokens are discarded)
    eos_hit: bool = False
    # set while re-prefilling after preemption: the completion logits must NOT
    # be sampled (the continuation token is already held in held_token)
    resume: bool = False
    # how many generated tokens have been folded into .prompt by preemptions
    folded: int = 0
    # ---- serving-telemetry lifecycle (ServingTelemetry.now() seconds).
    # Timestamps are taken when the relevant DISPATCH returns — with
    # telemetry.stream_sync (the streaming-server mode) the dispatch is
    # fenced first, so they reflect device completion; without it they
    # reflect host submission (a lower bound, disclosed in the docs).
    track: int = 0                         # trace tid for this request
    # ---- SLA class (scheduler.sla_classes, named per request via
    # generate(sla=[...])): priority orders admission and arms preemption
    # of lower-priority running decoders when ttft_slo_ms is at risk
    sla: str = "default"
    priority: int = 0
    ttft_slo_ms: float = 0.0
    # LoRA adapter id serving this request (0 = base model identity);
    # validated at generate() entry, made resident + pinned at admission
    adapter: int = 0
    t_arrival: Optional[float] = None
    t_admit: Optional[float] = None
    t_prefill_end: Optional[float] = None
    t_first: Optional[float] = None        # first generated token
    t_last: Optional[float] = None         # last generated token
    preempts: int = 0
    finished: bool = False                 # finish_request recorded
    # distributed TraceContext (telemetry/tracecontext.py): fleet-minted
    # when the request came through the router (generate(trace_ctx=...)),
    # engine-allocated (flowless) otherwise — its ids ride the request's
    # lifecycle spans so merged traces stitch per request
    trace: Optional[Any] = None


class InferenceEngineV2:
    """model: GPT-family module or GPTConfig; params: trained tree (optional —
    fresh init for testing).  See reference engine_v2.py:30."""

    def __init__(self, model, config=None, params=None, seed: int = 0,
                 mesh=None, draft_model=None, draft_params=None,
                 steps_cache: Optional[Dict[Any, Any]] = None,
                 telemetry_registry=None):
        if isinstance(model, (str, os.PathLike)):
            # only a model DIRECTORY needs the checkpoint package: its
            # import (orbax -> google.cloud.logging -> two walks of every
            # installed distribution's files) took 12-25 s of every serving
            # process's start-up, the whole of ds.engine_init but 0.2 s
            # (PERF.md section 6, PR 40)
            from deepspeed_tpu.checkpoint.hf import (is_hf_model_dir,
                                                     load_hf_checkpoint)
            if is_hf_model_dir(model):
                if params is not None:
                    raise ValueError(
                        "pass either an HF model dir or params, not both")
                model, params = load_hf_checkpoint(model)
        self.config = RaggedInferenceEngineConfig.parse(config)
        # request-level serving telemetry (telemetry/serving.py): lifecycle
        # spans + TTFT/TPOT histograms + KV-pool gauges + speculative
        # counters.  Engine-local registry by default so two engines in one
        # process (the bench runs seven) never blend their series; the fleet
        # passes a shared registry + a per-replica label instead.  First, so
        # that the construction below is itself a span (ds.engine_init).
        self.telemetry = ServingTelemetry(self.config.telemetry,
                                          registry=telemetry_registry)
        with init_span(self.telemetry.tracer, "engine_init", "inference_v2"):
            self._build(model, params, seed, mesh, draft_model, draft_params,
                        steps_cache)

    def _build(self, model, params, seed, mesh, draft_model, draft_params,
               steps_cache):
        from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits
        from deepspeed_tpu.parallel.metadata import unbox

        tp_size = self.config.tensor_parallel.tp_size
        if mesh is None and tp_size > 1:
            from deepspeed_tpu.parallel import mesh as mesh_lib
            mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(
                tp=tp_size, dp=1, fsdp=1))
        if tp_size > 1 and mesh.shape.get("tp", 1) != tp_size:
            raise ValueError(
                f"tensor_parallel.tp_size={tp_size} but the provided mesh has "
                f"tp={mesh.shape.get('tp', 1)}; pass a mesh with a matching "
                f"tp axis or omit the mesh")
        self.mesh = mesh if (mesh is not None
                             and mesh.shape.get("tp", 1) > 1) else None
        sm = self.config.state_manager
        model_cfg = model if isinstance(model, GPTConfig) else model.cfg
        model_cfg = dataclasses.replace(model_cfg, dtype=self.config.jnp_dtype,
                                        dropout=0.0)
        if model_cfg.num_experts and self.mesh is not None:
            raise NotImplementedError(
                "v2 MoE serving with tensor parallelism: the dropless expert "
                "route is single-shard; drop the tp config for MoE models")
        self.model_config = model_cfg

        with init_span(self.telemetry.tracer, "init_params",
                       "inference_v2"):             # the cast
            if params is None:
                lm = GPTLogits(model_cfg)
                params = unbox(lm.init(
                    jax.random.PRNGKey(seed),
                    jnp.zeros((1, 8), jnp.int32)))["params"]
            params = unbox(params)
            if isinstance(params, dict) and "params" in params:
                params = params["params"]
            dt = self.config.jnp_dtype

            def cast(path, p):
                p = jnp.asarray(p)
                # (a layer's sink logits stay float32: they stand beside
                # float32 scores in the softmax, models/gpt.py Attention)
                if (not jnp.issubdtype(p.dtype, jnp.floating)
                        or getattr(path[-1], "key", None) == "sink"):
                    return p
                return p.astype(dt)
            self.params = jax.tree_util.tree_map_with_path(cast, params)

        # ---- quantized weight store (config block ``quant``): int8 codes +
        # group scales in HBM; model.py's _w/_embed dequantize per use site
        # (reference quantized_linear.py:205 — weights stay quantized through
        # serving; the bf16 tree never exists at rest)
        qc = self.config.quant
        if qc.enabled:
            from deepspeed_tpu.ops.quantization import (quantize_weight,
                                                        quantize_weight4,
                                                        weight_group_size)
            pack4 = qc.bits == 4

            def pack(path, p):
                name = getattr(path[-1], "key", str(path[-1]))
                # wpe: positional gather stays direct.  gate: the MoE router
                # makes DISCRETE top-k decisions — int8 rounding near ties
                # flips expert assignment, an error no per-weight scale can
                # bound, for negligible savings (routers are conventionally
                # excluded from weight quantization)
                if (name in ("wpe", "gate")
                        or not jnp.issubdtype(p.dtype, jnp.floating)
                        or p.ndim < 2 or p.size < 8 * qc.group_size):
                    return p
                if name == "wte" and not weight_group_size(
                        (p.shape[0],), qc.group_size):
                    # odd vocabs (GPT-2's 50257) can't group along dim 0 —
                    # pad the table to the group so it quantizes at all and
                    # the tied transposed kernel can tile; padded rows are
                    # zero (scale 0, codes 0) and tied logits slice back to
                    # vocab_size (model._logits_out)
                    gpad = -(-p.shape[0] // qc.group_size) * qc.group_size
                    if pack4:
                        gpad = -(-gpad // 2) * 2
                    p = jnp.pad(p, ((0, gpad - p.shape[0]),)
                                + ((0, 0),) * (p.ndim - 1))
                # group along the kernel-preferred dim: attention wo
                # [heads, hd, H] contracts dims (0, 1), and only dim-1
                # grouping flattens to a uniform 2-D kernel view
                # (ops/wq_matmul.store_as_2d) — for everything else, dim 0
                # first; dim 1 rescues 3-D stacks whose leading dim is
                # small (MoE [E, in, out] experts)
                cand = ((1, 0) if (p.ndim == 3 and name == "wo")
                        else range(p.ndim - 1))
                for dim in cand:
                    if weight_group_size((p.shape[dim],), qc.group_size):
                        if (pack4 and dim == 0 and p.shape[0] % 2 == 0
                                and not (name == "wte"
                                         and model_cfg.tie_embeddings)):
                            # (tied tables stay int8: the transposed unembed
                            # kernel has no packed variant, and a per-step
                            # full-table dequant would cost more HBM than
                            # the packing saves)
                            # nibble-packed: ¼ the bf16 bytes; shards like
                            # the weight as long as shard boundaries keep
                            # row pairs + scale groups intact
                            # (quantization.store_shardings checks)
                            return quantize_weight4(p, group=qc.group_size)
                        return quantize_weight(p, bits=qc.bits,
                                               group=qc.group_size, dim=dim)
                return p
            with init_span(self.telemetry.tracer, "init_params",
                           "inference_v2"):         # the quantization
                self.params = jax.tree_util.tree_map_with_path(
                    pack, self.params)

        if self.mesh is not None:
            # TP: same logical-axis rules as the v1 engine (AutoTP analog) —
            # params shard over the tp axis, attention stays per-kv-head local
            # (reference inference/v2/model_implementations/sharding/qkv.py)
            from deepspeed_tpu.parallel import partition
            from deepspeed_tpu.parallel.metadata import annotate_abstract
            tp = self.mesh.shape["tp"]
            if model_cfg.kv_heads % tp:
                raise ValueError(
                    f"kv_heads={model_cfg.kv_heads} not divisible by tp={tp}; "
                    f"the paged KV pool shards over kv heads")
            lm = GPTLogits(model_cfg)
            boxed = jax.eval_shape(
                lambda r: lm.init(r, jnp.zeros((1, 8), jnp.int32)),
                jax.random.PRNGKey(0))
            annotated = annotate_abstract(boxed["params"])
            shardings = partition.param_shardings(annotated, self.mesh,
                                                  zero_stage=0)
            if qc.enabled:
                from deepspeed_tpu.ops.quantization import store_shardings
                shardings = store_shardings(self.params, shardings, self.mesh)
            with init_span(self.telemetry.tracer, "init_params",
                           "inference_v2"):         # the placement
                self.params = jax.device_put(self.params, shardings)

        from deepspeed_tpu.inference.v2.model import (kv_block_size_for,
                                                      kv_major_layout)
        from deepspeed_tpu.ops.paged_attention import _dma_layout_ok
        from deepspeed_tpu.ops.registry import would_use_pallas
        # only the Pallas kernels need 128-aligned kv-major pages; on the XLA
        # path any size works, so don't disturb the configured granularity
        # there.  attn_impl forces the choice; None asks the registry.
        eff_bs = sm.kv_block_size
        kernels = (model_cfg.attn_impl == "pallas"
                   or (model_cfg.attn_impl is None
                       and would_use_pallas("paged_attention")))
        if kernels:
            eff_bs = kv_block_size_for(model_cfg, sm.kv_block_size,
                                       quant=sm.kv_quant is not None)
        if eff_bs != sm.kv_block_size:
            log_dist(
                f"kv_block_size {sm.kv_block_size} -> {eff_bs}: the "
                f"kv-major page layout (head_dim={model_cfg.head_dim}) and "
                f"int8-quantized pages both need 128-aligned pages for the "
                f"Pallas DMA (ops/paged_attention.py)", ranks=[0])
        # what the registry will trace for this engine's decode/prefill
        # attention — said out loud at start-up, never discovered later
        self.paged_impl = "xla"
        if kernels and (model_cfg.attn_impl == "pallas" or _dma_layout_ok(
                model_cfg.latent_page_dim if model_cfg.mla
                else model_cfg.head_dim, eff_bs, kv_major_layout(model_cfg),
                quant=sm.kv_quant is not None)):
            self.paged_impl = "pallas"
        elif kernels and sm.kv_quant is not None:
            log_dist(
                f"WARNING: kv_quant=int8 with head_dim="
                f"{model_cfg.head_dim} cannot use the Pallas decode "
                f"kernel (int8 pages tile (32, 128)); decode falls back "
                f"to the XLA dequant path, which gathers full page spans "
                f"— expect MORE bandwidth than unquantized bf16, not "
                f"less", ranks=[0])
        blocks_per_seq = -(-model_cfg.max_seq_len // eff_bs)
        if sm.num_kv_blocks:
            # the user sized the pool in THEIR block units — preserve the
            # total-token budget (and HBM footprint) under a bump
            num_blocks = max(1, sm.num_kv_blocks * sm.kv_block_size // eff_bs)
        else:
            num_blocks = sm.max_tracked_sequences * blocks_per_seq
        # ---- page groups: layers with a sliding window beside layers
        # without one keep their pages apart, so that a window layer does
        # not hold what it will never read again (ragged.py; model.py
        # kv_page_layout).  Layers all alike: one group, as ever.
        windows = {model_cfg.window_for_layer(i)
                   for i in range(model_cfg.num_layers)}
        self.kv_window = (model_cfg.sliding_window
                          if len(windows) == 2 else None)
        window_blocks = 0
        # statics of every step program; {} for a plain model, whose
        # programs are then traced exactly as before
        self._model_static: Dict[str, Any] = {}
        if model_cfg.state_layers:
            # state layers (Mamba-2 scan layers, gated short convolutions)
            # keep one fixed-size state a sequence beside the attention
            # layers' pages (model.py PagedKVCache.ssm / .conv, which the
            # layer body hands the step's mixer).  What cannot be right
            # beside them yet:
            conv = not model_cfg.scan_layers
            noun, state = (("conv", "a conv tail") if conv
                           else ("scan", "a recurrent state"))
            for what, why in (
                    (sm.prefix_cache, f"the prefix cache: a shared prefix "
                     f"has pages and no state to resume the {noun} from (it "
                     f"needs state snapshots at page boundaries)"),
                    (draft_model is not None, "speculative decoding: a "
                     "rejected draft token has already moved the state, "
                     "which has no rollback"),
                    (self.mesh is not None, "a tp mesh: the state pool's "
                     + ("channels" if conv else "heads") + " and the "
                     "mixer's projections are not sharded"),
                    (self.config.adapters.enabled, f"LoRA adapter pages: a "
                     f"{noun} layer has no q/v projections for their "
                     f"deltas"),
                    (sm.kv_quant, "kv_quant: the pools are created "
                     "unquantised beside the state"),
                    (self.kv_window or model_cfg.mla, "window page groups "
                     "or latent pages: the state pool is built beside the "
                     "one plain page group only")):
                if what:
                    raise NotImplementedError(
                        f"{noun} layers (layer_types) keep {state} a "
                        f"sequence, which is not built with {why}")
        if model_cfg.block_topk:
            # a selection by blocks (ops/block_select.py): pooled keys page
            # for page beside plain GQA pages (model.py PagedKVCache.ki),
            # rows past block_dense_len choosing their blocks a KV head.
            # What is not built beside it:
            for what, why in (
                    (sm.prefix_cache, "the prefix cache: a shared prefix's "
                     "pooled keys would have to be shared page for page "
                     "with its keys, and the one whose span crosses into "
                     "the first private page completed there"),
                    (draft_model is not None, "speculative decoding: a "
                     "draft run's rows would each need their own choice of "
                     "blocks, and the verify core attends densely"),
                    (self.mesh is not None, "a tp mesh: the pooled keys and "
                     "the choice a KV head are not sharded with the kv "
                     "heads"),
                    (sm.kv_quant, "kv_quant: int8 keys would move the "
                     "pooled keys and with them the choice"),
                    (self.config.adapters.enabled, "LoRA adapter pages: not "
                     "tested over a selecting layer"),
                    (self.kv_window or model_cfg.mla, "window page groups "
                     "or latent pages: the pooled keys lie beside the one "
                     "plain page group only")):
                if what:
                    raise NotImplementedError(
                        f"a selection by blocks (block_topk) keeps pooled "
                        f"keys beside its pages, which is not built with "
                        f"{why}")
        if model_cfg.hc:
            # a multi-stream residual (hc_mult: rows [N, streams, H] that
            # every sublayer reads and writes through its hyper-connection,
            # model.py _HyperMix).  What is not built beside it:
            for what, why in (
                    (self.mesh is not None, "a tp mesh: the streams' "
                     "projection (phi) and the mix are not sharded"),
                    (draft_model is not None, "speculative decoding: the "
                     "verify core's dense [S, G, H] rows and a draft's one "
                     "stream are not built over [rows, streams, H]"),
                    (self.config.adapters.enabled, "LoRA adapter pages: "
                     "not tested over a mixed sublayer input"),
                    (sm.kv_quant, "kv_quant: not tested beside it"),
                    (model_cfg.state_layers, "scan or conv layers "
                     "(layer_types): their mixers read the one-stream "
                     "residual"),
                    (model_cfg.parallel_block or model_cfg.sandwich_norm
                     or model_cfg.residual_scale is not None,
                     "parallel_block, sandwich_norm or residual_scale: a "
                     "hyper-connection closes a pre-norm sublayer and "
                     "nothing else")):
                if what:
                    raise NotImplementedError(
                        f"a multi-stream residual (hc_mult) keeps "
                        f"{model_cfg.hc_mult} streams a row, which is not "
                        f"built with {why}")
        if model_cfg.mla:
            # latent attention: pools of latent rows (model.py
            # PagedKVCache; _layer_pages says which array holds a layer's),
            # read absorbed, and with a learned selection (index_topk) an
            # index-key pool beside the global group's.  What is not built
            # beside them:
            sel = bool(model_cfg.index_topk)
            for what, why in (
                    (sm.kv_quant, "kv_quant: an int8 latent row and its "
                     "scale are not built" + (
                         ", nor int8 index keys, whose rounding would move "
                         "the selection" if sel else "")),
                    (self.mesh is not None, "a tp mesh: every head reads "
                     "the one latent row, which has no head dim to shard"
                     + (", and every head attends over the one selection"
                        if sel else "")),
                    (draft_model is not None, "speculative decoding: the "
                     "verify core and a draft pool are not built over "
                     "latent pages" + (
                         ", and a draft run's rows would each need their "
                         "own selection" if sel else "")),
                    (self.config.adapters.enabled, "LoRA adapter pages: "
                     "their q/v deltas have no latent form"),
                    (sm.prefix_cache, "the prefix cache: not tested over "
                     "latent pages" + (
                         "; a shared prefix's index keys would have to be "
                         "shared page for page with its latent rows"
                         if sel else ""))):
                if what:
                    raise NotImplementedError(
                        f"latent attention (kv_lora_rank) keeps latent "
                        f"page pools, which are not built with {why}")
            if sel and not self.kv_window:
                raise NotImplementedError(
                    "a learned selection (index_topk) over latent rows "
                    "keeps its index keys in a pool beside the global page "
                    "group of a model with window AND full layers; " + (
                        "a model whose layers all have a window has "
                        "nothing to select" if None not in windows
                        else "a model whose layers are all full "
                        "(DeepSeek-V3.2's shape) keeps one page group, "
                        "whose pool holds no index keys: not built"))
        own_values = (not model_cfg.mla
                      and model_cfg.value_dim != model_cfg.head_dim)
        if model_cfg.attn_sink or own_values:
            # a learned sink logit a query head, or a value head narrower
            # than its key (ops/paged_attention.py: both kernels and both
            # fallbacks take either).  What is not built beside them:
            noun = " and ".join(
                n for n, on in (("a per-head sink (attn_sink)",
                                 model_cfg.attn_sink),
                                ("a value width of its own (v_head_dim)",
                                 own_values)) if on)
            for what, why in (
                    (sm.kv_quant, "kv_quant: the int8 kernels carry no sink "
                     "and the scale pools one width"),
                    (self.mesh is not None, "a tp mesh: the sink and the "
                     "value pool are not sharded with the kv heads"),
                    (draft_model is not None, "speculative decoding: a "
                     "draft's pool and the verify core are not built over "
                     "either"),
                    (self.config.adapters.enabled, "LoRA adapter pages: "
                     "their v deltas are sized by the key's width"),
                    (sm.prefix_cache and not model_cfg.sliding_window,
                     "the prefix cache: not tested over them")):
                if what:
                    raise NotImplementedError(
                        f"{noun} is not built with {why}")
        if self.kv_window:
            for what, why in (     # (prefix_cache: DSStateManager refuses)
                    (sm.kv_quant, "kv_quant: the scale pools are not "
                     "grouped"),
                    (self.mesh is not None, "a tp mesh: the grouped pool's "
                     "sharding is not built"),
                    (draft_model is not None, "speculative decoding: the "
                     "draft's pool and the verify core take one table"),
                    (self.config.adapters.enabled, "LoRA adapter pages: "
                     "they live in the one allocator")):
                if what:
                    raise NotImplementedError(
                        f"window and global layers in one model keep two "
                        f"page groups, which is not built with {why}")
            ring = (-(-(self.kv_window + max(sm.max_q_per_seq, 64))
                      // eff_bs) + 1)
            window_blocks = (sm.num_kv_window_blocks
                             or sm.max_tracked_sequences
                             * min(ring, blocks_per_seq))
            if window_blocks < min(ring, blocks_per_seq):
                raise ValueError(
                    f"num_kv_window_blocks={window_blocks} cannot hold one "
                    f"sequence's ring of {min(ring, blocks_per_seq)} pages "
                    f"(window {self.kv_window} + a chunk of "
                    f"{sm.max_q_per_seq} rows in pages of {eff_bs})")
            from deepspeed_tpu.inference.v2.model import (kv_groups_split,
                                                          kv_page_layout)
            self._model_static["kv_layout"] = kv_page_layout(
                model_cfg, num_blocks, window_blocks,
                split=kv_groups_split(model_cfg))
        if draft_model is None and model_cfg.num_experts and any(
                model_cfg.is_moe_layer(i)
                for i in range(model_cfg.num_layers)):
            self._model_static["moe_stats"] = True
        self.state = DSStateManager(
            max_tracked_sequences=sm.max_tracked_sequences,
            num_blocks=num_blocks, block_size=eff_bs,
            max_seq_len=model_cfg.max_seq_len,
            prefix_cache=sm.prefix_cache, window=self.kv_window,
            window_blocks=window_blocks)
        with init_span(self.telemetry.tracer, "init_cache", "inference_v2"):
            if self.kv_window and model_cfg.mla:
                self.cache = PagedKVCache.create_latent_groups(
                    model_cfg, num_blocks, window_blocks, eff_bs, dt)
            elif self.kv_window:
                self.cache = PagedKVCache.create_grouped(
                    model_cfg, num_blocks, window_blocks, eff_bs, dt)
            else:
                self.cache = PagedKVCache.create(
                    model_cfg, num_blocks, eff_bs, dt, quant=sm.kv_quant,
                    slots=sm.max_tracked_sequences)
        # (seq, MoE counter vector) of dispatches not yet read back (device
        # values the step programs return; folded into the telemetry once
        # ready, never waited for: _fold_moe_stats)
        self._moe_pending: List[Any] = []
        # the newest dispatch whose results a materialize has fetched: what
        # the next one's ``in_flight`` is counted from
        self._through_seq = 0
        # ---- speculative decoding draft (greedy draft-and-verify) ----
        self.draft_config = self.draft_params = self.draft_cache = None
        if draft_model is not None:
            if self.mesh is not None:
                raise NotImplementedError(
                    "speculative decoding with tensor parallelism: shard the "
                    "draft like the target (future work); drop tp or draft")
            dcfg = (draft_model if isinstance(draft_model, GPTConfig)
                    else draft_model.cfg)
            dcfg = dataclasses.replace(dcfg, dtype=dt, dropout=0.0)
            if dcfg.max_seq_len < model_cfg.max_seq_len:
                raise ValueError(
                    f"draft max_seq_len {dcfg.max_seq_len} < target "
                    f"{model_cfg.max_seq_len}")
            self.draft_config = dcfg
            if draft_params is None:
                dlm = GPTLogits(dcfg)
                draft_params = unbox(dlm.init(
                    jax.random.PRNGKey(seed + 1),
                    jnp.zeros((1, 8), jnp.int32)))["params"]
            draft_params = unbox(draft_params)
            if isinstance(draft_params, dict) and "params" in draft_params:
                draft_params = draft_params["params"]
            self.draft_params = jax.tree_util.tree_map(
                lambda p: jnp.asarray(p).astype(dt)
                if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating)
                else jnp.asarray(p), draft_params)
            # the draft shares the pool GEOMETRY (same block table indexes
            # both caches) but holds its own pages
            self.draft_cache = PagedKVCache.create(dcfg, num_blocks, eff_bs,
                                                   dt, quant=sm.kv_quant)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            kv_sh = NamedSharding(self.mesh, P(None, None, "tp", None, None))
            sc_sh = NamedSharding(self.mesh, P(None, None, "tp", None))
            self.cache = PagedKVCache(
                k=jax.device_put(self.cache.k, kv_sh),
                v=jax.device_put(self.cache.v, kv_sh),
                k_scale=(jax.device_put(self.cache.k_scale, sc_sh)
                         if self.cache.quantized else None),
                v_scale=(jax.device_put(self.cache.v_scale, sc_sh)
                         if self.cache.quantized else None))
        # jitted step per (Qmax, KVblocks) bucket: a decode-only step runs a
        # Q=1 program and short sequences gather few KV blocks — the static-
        # shape analog of the reference's atom decomposition (atom_builder);
        # buckets are powers of two so the compile cache stays small.
        # ``steps_cache`` lets identically-configured engines SHARE the
        # compiled set (serving/fleet.py: N replicas compile once, and a
        # respawned replica fast-resumes against the survivors' warm cache
        # — the serving analog of PR 6's persistent compilation cache).
        # The per-program keys encode only SCHEDULE shapes (bucket widths,
        # burst length), while the compiled fns close over the model
        # config / block size / mesh via functools.partial — so a shared
        # dict is namespaced by a config fingerprint: two differently-
        # configured engines handed the same cache get disjoint sub-caches
        # instead of silently dispatching each other's programs.
        if steps_cache is not None:
            ac_fp = self.config.adapters
            fp = repr((model_cfg, eff_bs, self.config.dtype,
                       self.draft_config,
                       tuple(sorted(self.mesh.shape.items()))
                       if self.mesh is not None else None,
                       qc.enabled, qc.bits, qc.group_size,
                       tuple(sorted(self._model_static.items())),
                       # adapter-enabled programs take extra batch operands
                       # (lora tables + per-slot selection) and bake the
                       # rank/scale geometry into their traced shapes — two
                       # engines differing in ANY of these must not share
                       # compiled steps (PR 7 fingerprint rule)
                       ac_fp.enabled, ac_fp.rank, ac_fp.alpha, ac_fp.slots))
            self._steps: Dict[Any, Any] = steps_cache.setdefault(fp, {})
        else:
            self._steps = {}
        # recompute-preemption observability: how many victims were taken in
        # steady decode vs mid-(re-)prefill (the latter must keep fold state)
        self.preempt_stats = {"decode_ready": 0, "mid_prefill": 0}
        # ---- fleet hooks (serving/fleet.py): a supervised replica can be
        # asked to drain (stop serving, export in-flight requests) and
        # reports liveness through heartbeat_fn each scheduler round
        self._drain_requested = threading.Event()
        self._serve_ctx: Optional[Dict[str, Any]] = None
        self.heartbeat_fn = None
        self._block_size = eff_bs
        self._one_table_width = bool(model_cfg.index_topk
                                     or model_cfg.block_topk
                                     or model_cfg.state_layers
                                     or model_cfg.hc)            # _buckets
        self.telemetry.set_kv_bytes_per_token(
            self.kv_bytes_per_token(), **self.kv_bytes_by_group())
        if model_cfg.block_topk:
            self.telemetry.set_block_selection(
                len(model_cfg.attention_layers))
        if model_cfg.state_layers:
            c = self.cache
            self.telemetry.set_scan_state(
                len(model_cfg.state_layers),
                sum(a.nbytes for a in (c.ssm, c.conv) if a is not None)
                // sm.max_tracked_sequences,
                kind="ssm" if model_cfg.scan_layers else "conv")
        if model_cfg.hc:
            self.telemetry.set_hc(
                2 * model_cfg.num_layers, model_cfg.hc_mult
                * model_cfg.hidden_size * jnp.dtype(model_cfg.dtype).itemsize)
        # ---- multi-tenant LoRA adapter pool (serving/adapters.py): A/B
        # pages live as block-granular refcounted residents of the SAME
        # allocator as the KV blocks, so adapters and KV contend under one
        # supply-accounting + LRU-eviction policy (the S-LoRA unified pool).
        # _adapter_slot maps sequence slot -> device-table slot and rides
        # every dispatch when the pool exists (slot 0 = identity).
        ac = self.config.adapters
        self.adapters = None
        self._adapter_slot = np.zeros(sm.max_tracked_sequences, np.int32)
        if ac.enabled:
            if self.draft_params is not None:
                raise NotImplementedError(
                    "speculative decoding with LoRA adapters: the draft has "
                    "no adapter pages to verify against; drop the draft or "
                    "the adapters config")
            from deepspeed_tpu.serving.adapters import AdapterPool
            self.adapters = AdapterPool(
                self.state.allocator, slots=ac.slots, rank=ac.rank,
                hidden=model_cfg.hidden_size,
                num_layers=model_cfg.num_layers,
                q_dim=model_cfg.num_heads * model_cfg.head_dim,
                v_dim=model_cfg.kv_heads * model_cfg.head_dim,
                block_bytes=self.kv_block_bytes(),
                scale=ac.alpha / ac.rank, dtype=self.config.dtype,
                telemetry=self.telemetry)
            self.state.adapters = self.adapters
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(self.params))
        kv_layout = ("latent" if model_cfg.mla else "kv-major"
                     if kv_major_layout(model_cfg) else "standard")
        log_dist(f"v2 ragged engine ready: params={n_params/1e6:.1f}M "
                 f"budget={sm.max_ragged_batch_size}tok "
                 f"slots={sm.max_tracked_sequences} "
                 f"kv_blocks={num_blocks}x{eff_bs}"
                 + (f"+window:{window_blocks}x{eff_bs}" if self.kv_window
                    else "") + f" kv_layout={kv_layout} "
                 f"paged_attention={self.paged_impl}", ranks=[0])

    # ------------------------------------------------ reference put() :107
    def put(self, uids: Sequence[int], tokens_list: Sequence[np.ndarray],
            with_routes: bool = False) -> np.ndarray:
        """Append tokens to each uid's sequence, run ONE ragged forward, return
        fp32 logits [len(uids), vocab] of each sequence's last token.

        ``with_routes`` (a model with expert layers): also, per uid, the
        experts its rows' routers chose, int32 ``[expert layers, rows, k]``
        over all the router's experts: what a comparison with a reference's
        routing needs to tell a near-tie from a fault.

        A call that holds more than one forward takes (a prompt longer
        than ``max_q_per_seq``, or more tokens together than
        ``max_ragged_batch_size``, after what the prefix cache matches)
        goes through ``put_chunked`` (not with ``with_routes``, which stays
        one forward and keeps ``_put_device``'s guards)."""
        sm = self.config.state_manager

        def over(sizes):
            return (max(sizes, default=0) > sm.max_q_per_seq
                    or sum(sizes) > sm.max_ragged_batch_size)
        toks = [np.asarray(t, np.int32).reshape(-1) for t in tokens_list]
        if not with_routes and over([len(t) for t in toks]):
            matches = self.state.peek_prefix_batch(
                [None if self.state.get(uid) is not None else t
                 for uid, t in zip(uids, toks)])[0]
            if over([len(t) - m for t, m in zip(toks, matches)]):
                return self.put_chunked(uids, toks)
        logits = self._put_device(uids, tokens_list, with_routes)
        if with_routes:
            logits, routes, rows = logits
        slots = [self.state.get(uid).slot for uid in uids]
        out = np.asarray(logits)[np.asarray(slots)]
        if not with_routes:
            return out
        return out, [np.asarray(routes)[:, r] for r in rows]

    def put_chunked(self, uids: Sequence[int],
                    tokens_list: Sequence[np.ndarray]) -> np.ndarray:
        """``put()`` for a call of any size, as SplitFuse would run it: as
        many forwards as it takes, each uid's tokens in order in chunks of
        at most ``max_q_per_seq`` rows, a forward filled in the order of
        ``uids``; the logits are each uid's after its last token."""
        sm = self.config.state_manager
        toks = [np.asarray(t, np.int32).reshape(-1) for t in tokens_list]
        out = [None] * len(uids)
        done = [0] * len(uids)
        while any(d < len(t) for d, t in zip(done, toks)):
            room, part = sm.max_ragged_batch_size, []
            for i, t in enumerate(toks):
                n = min(len(t) - done[i], sm.max_q_per_seq, room)
                if n > 0 and len(part) < sm.max_ragged_sequence_count:
                    part.append((i, n))
                    room -= n
            rows = self.put([uids[i] for i, _ in part],
                            [toks[i][done[i]:done[i] + n] for i, n in part])
            for row, (i, n) in zip(rows, part):
                out[i], done[i] = row, done[i] + n
        return np.stack(out)

    def _put_device(self, uids, tokens_list, with_routes: bool = False):
        """put() minus the host transfer: returns per-SLOT device logits
        [S, vocab] so generate() can sample on device and ship only token ids
        over the wire (the logits row is 200 KB; a token id is 4 bytes)."""
        sm = self.config.state_manager
        bs = self.state.block_size
        # validate BEFORE mutating any state (slots/blocks), so a rejected put
        # leaves the manager clean
        if len(set(uids)) != len(uids):
            # a duplicated uid in one batch would make both chunks compute
            # token_pos from the same stale seen_tokens and scatter into the
            # same KV slots, silently corrupting the sequence
            raise ValueError(f"duplicate uids in one put(): {list(uids)}")
        toks_np = [np.asarray(t, np.int32).reshape(-1) for t in tokens_list]
        # radix prefix match (peek only — nothing is acquired until the
        # validation below passes): matched tokens of a NEW sequence alias
        # cached blocks and never enter the scheduled batch, so every
        # effective length/budget check uses the post-match suffix
        matches, pinned, paths = self.state.peek_prefix_batch(
            [None if self.state.get(uid) is not None else toks
             for uid, toks in zip(uids, toks_np)])
        for uid, toks, m in zip(uids, toks_np, matches):
            if len(toks) - m > sm.max_q_per_seq:
                raise ValueError(
                    f"uid {uid}: {len(toks) - m} tokens exceeds max_q_per_seq="
                    f"{sm.max_q_per_seq}; split the prompt (SplitFuse) or use "
                    f"generate()")
            seen = (self.state.get(uid).seen_tokens
                    if self.state.get(uid) else 0)
            if seen + len(toks) > self.model_config.max_seq_len:
                raise ValueError(f"uid {uid} exceeds max_seq_len "
                                 f"{self.model_config.max_seq_len}")
        total = sum(len(t) - m for t, m in zip(toks_np, matches))
        if total > sm.max_ragged_batch_size:
            raise ValueError(f"batch of {total} tokens exceeds ragged budget "
                             f"{sm.max_ragged_batch_size}; check query() first")
        if len(uids) > sm.max_ragged_sequence_count:
            raise ValueError(f"{len(uids)} sequences exceeds "
                             f"max_ragged_sequence_count="
                             f"{sm.max_ragged_sequence_count}")
        new_uids = [u for u in uids if self.state.get(u) is None]
        if len(new_uids) > self.state.free_sequence_slots:
            raise RuntimeError(
                f"{len(new_uids)} new sequences but only "
                f"{self.state.free_sequence_slots} free slots; flush() first")
        # fresh blocks plus the evictable supply the batch's matches would
        # pin (unique across shared prefixes) — both come out of
        # available_blocks
        blocks_needed = pinned + sum(
            (self.state.get(u).kv_blocks_needed(len(t), bs)
             if self.state.get(u) else -(-len(t) // bs) - m // bs)
            for u, t, m in zip(uids, toks_np, matches))
        if blocks_needed > self.state.available_blocks:
            self.telemetry.alloc_failure("put")
            raise RuntimeError(
                f"batch needs {blocks_needed} KV blocks but only "
                f"{self.state.available_blocks} free; check query() first")
        if self.kv_window and not self.state.fits(
                [(self.state.get(u), len(t)) for u, t in zip(uids, toks_np)]):
            self.telemetry.alloc_failure("put")
            raise RuntimeError(
                f"batch does not fit the window page group "
                f"({self.state.wallocator.free_blocks} pages free); check "
                f"query() first")
        schedule = []
        for uid, toks, path in zip(uids, toks_np, paths):
            seq = self.state.get(uid)
            if seq is None:
                seq = self.state.create(uid)
                # put() serves the base model: clear any previous tenant's
                # adapter selection left on this recycled slot
                self._adapter_slot[seq.slot] = 0
                if self.state.radix is not None:
                    seq.host_tokens = toks
                    # reuse the validation walk: nothing mutated the trie
                    # since peek_prefix_batch (creates only)
                    matched = self.state.match_prefix(seq, toks, path=path)
                    self.telemetry.prefix_lookup(matched)
                    toks = toks[matched:]
            elif (self.state.radix is not None
                  and len(seq.host_tokens) == seq.seen_tokens):
                # contiguous host-known content (prompt chunks, put-fed
                # decode tokens) keeps extending the radix insert key; a
                # device-fed gap permanently stops it.  (Cache off: no
                # tracking at all — per-decode np.concatenate would make
                # a long put()-driven generation quadratic for nothing.)
                seq.host_tokens = np.concatenate([seq.host_tokens, toks])
            schedule.append((seq, toks))
        # blocks are reserved only after EVERY match acquired its holders:
        # an eviction triggered for one sequence must never reclaim blocks
        # another sequence in this batch just matched
        for seq, toks in schedule:
            self.state.ensure_blocks(seq, len(toks))
        for _, toks in schedule:
            self.telemetry.tokens("prefill" if len(toks) > 1 else "decode",
                                  len(toks))
        self.telemetry.ssm_rows([len(t) for _, t in schedule])
        rb = build_ragged_batch(schedule, self.state,
                                sm.max_ragged_batch_size, sm.max_q_per_seq)
        logits = self._run(rb, with_routes)
        for seq, toks in schedule:
            seq.seen_tokens += len(toks)
            # index newly completed full blocks (content is host-known; the
            # forward filling them is already in the dispatch chain, so any
            # later reader is ordered behind the writer)
            self.state.cache_insert(seq)
        self.telemetry.kv_sample(self.state)
        return logits

    def _buckets(self, rb: RaggedBatch):
        """Power-of-two compile buckets, shared by the logits (_run) and
        sampled (_step_sampled) paths so both compile identical program
        shapes for the same schedule: ``mb`` bounds the block-table WIDTH by
        the longest live KV, and ``nb`` slices the packed token arrays to the
        width covering the live tokens — a small step (one admission chunk
        between decode bursts) must not pay a forward padded to the full
        ragged budget.  ≤ log2(MB) × log2(budget) compiled programs total."""
        mb = rb.block_table.shape[1]
        if not self._one_table_width:
            # (a model that selects its keys keeps ONE table width, the
            # whole table's: its full layers score and sort over their
            # rows' own contexts at run time, index_select(width=), so a
            # narrower table would buy programs and save no work; so does a
            # model with scan or conv layers: the width is layout for its few
            # attention layers, whose kernels walk each slot's pages to its
            # own length, and every narrower table would be one more
            # program of all its layers to trace, lower and compile; and a
            # model with a multi-stream residual: a step program of it
            # holds its mix a sublayer, compiles in 1.6x the time and is a
            # 6 MB entry of the compile cache, so that 42 of them, a table
            # width x a token width, outgrew the cache a machine keeps and
            # every start was cold, 760 s; PERF.md section 6, PR 50)
            mb_used = max(1, -(-int(rb.kv_len.max()) // self._block_size))
            mb = min(1 << (mb_used - 1).bit_length(), mb)
        return mb, self._token_bucket(rb.total_tokens, rb.tokens.shape[0])

    @staticmethod
    def _token_bucket(tokens: int, budget: int) -> int:
        return min(max(64, 1 << (max(1, tokens) - 1).bit_length()), budget)

    def _prefill_items(self, rows):
        """(live work items, the grid's bound) of the ragged prefill kernel
        in the mixed step that serves ``rows`` (each sequence's), a layer:
        the kernel's own rule (ops/paged_attention.py) on the host."""
        from deepspeed_tpu.inference.v2.model import _attn_geometry
        from deepspeed_tpu.ops.paged_attention import (_prefill_chunk,
                                                       prefill_grid_items)
        sm = self.config.state_manager
        # (a model whose global layers select their keys sends only its
        # window layers through the kernel: their geometry)
        cfg = self.model_config
        cfg = cfg.for_layer(next(
            (i for i in range(cfg.num_layers)
             if cfg.index_topk and cfg.window_for_layer(i)),
            cfg.attention_layers[0]))
        nkv, _, vd, _ = _attn_geometry(cfg)
        nb = self._token_bucket(sum(rows), sm.max_ragged_batch_size)
        Q = min(sm.max_q_per_seq, nb)
        cq = _prefill_chunk(Q, cfg.num_heads // nkv, vd)
        return (sum(-(-n // cq) for n in rows if n > 1),
                prefill_grid_items(nb, self.state.max_tracked_sequences, Q,
                                   cq))

    def _with_lora(self, batch):
        """Thread the adapter selection + packed pages into a dispatch batch.
        The model gates on ``"lora" in batch`` at TRACE time, and an
        adapter-less engine adds NO keys at all — so its traced programs
        (and shared steps_cache entries) stay byte-identical to before the
        adapter subsystem existed, the zero-overhead base-model guarantee."""
        if self.adapters is None:
            return batch
        batch["adapter_slot"] = jnp.asarray(self._adapter_slot)
        batch["lora"] = self.adapters.tables()
        return batch

    def _run(self, rb: RaggedBatch, with_routes: bool = False):
        """Logits ``[S, vocab]`` of one scheduled step; ``with_routes``:
        (logits, the routers' choices ``[expert layers, rows, k]``, each
        scheduled sequence's rows of them)."""
        # small set of compiled programs: a decode-only step (Q=1, Pallas
        # paged attention — the steady-state hot path, ragged_decode_forward)
        # plus one mixed prefill step per power-of-two BLOCK-TABLE-WIDTH
        # bucket (≤ log2(MB) programs).  Since round 3 the bucket width only
        # bounds LAYOUT: the ragged-prefill Pallas kernel skips dead
        # (slot, q-chunk) tiles and walks each slot's pages up to its actual
        # kv length, and one-row slots go to the paged decode kernel, so
        # attention FLOPs/bandwidth scale with the live q-chunks and rows,
        # not the bucket (reference atom_builder + blocked_flash).
        sm = self.config.state_manager
        hc = self.telemetry.hc_rows(rb.q_len[rb.q_len > 0])
        if int(rb.q_len.max()) <= 1:
            return self._run_decode(rb, with_routes, hc)
        mb, nb = self._buckets(rb)
        routes = {"moe_routes": True} if with_routes else {}
        key = ("mixed", sm.max_q_per_seq, mb) + tuple(routes)
        if key not in self._steps:
            self._steps[key] = jax.jit(
                named_partial(ragged_forward, cfg=self.model_config,
                               block_size=self._block_size,
                               max_q_per_seq=sm.max_q_per_seq,
                               mesh=self.mesh, **self._model_static,
                               **routes),
                donate_argnums=(1,))
        batch = {"tokens": rb.tokens[:nb], "token_slot": rb.token_slot[:nb],
                 "token_pos": rb.token_pos[:nb],
                 **rb.table_operands(mb), "kv_len": rb.kv_len}
        batch = self._with_lora(jax.tree_util.tree_map(jnp.asarray, batch))
        self.telemetry.padding_waste(rb.total_tokens, nb)
        mark = _SETUP.booked
        with self.telemetry.dispatch_span(
                "mixed", self._steps[key], tokens=rb.total_tokens,
                bucket=nb, seqs=len(rb.logits_slots), **hc):
            out = self._steps[key](self.params, self.cache, batch)
        if _SETUP.booked != mark:      # a first call: jax traced or loaded
            _SETUP.close("put_mixed", mark, self.telemetry.tracer,
                         bucket=nb, table_width=mb)
        if with_routes:
            *out, chosen = out
        logits, self.cache = self._take_moe_stats(out)
        if with_routes:         # the packed token rows of each sequence
            return logits, chosen, [np.flatnonzero(rb.token_slot[:nb] == sl)
                                    for sl in rb.logits_slots]
        return logits

    def _run_decode(self, rb: RaggedBatch, with_routes: bool = False,
                    hc=None):
        S = self.state.max_tracked_sequences
        tokens = np.zeros(S, np.int32)
        active = np.zeros(S, bool)
        token_pos = np.zeros(S, np.int32)
        for i in range(rb.total_tokens):
            sl = rb.token_slot[i]
            tokens[sl] = rb.tokens[i]
            active[sl] = True
            token_pos[sl] = rb.token_pos[i]
        routes = {"moe_routes": True} if with_routes else {}
        key = ("decode", "moe_routes") if with_routes else "decode"
        if key not in self._steps:
            self._steps[key] = jax.jit(
                named_partial(ragged_decode_forward,
                               cfg=self.model_config,
                               block_size=self._block_size,
                               mesh=self.mesh, **self._model_static,
                               **routes),
                donate_argnums=(1,))
        batch = self._with_lora(jax.tree_util.tree_map(jnp.asarray, {
            "tokens": tokens, "active": active, "token_pos": token_pos,
            **rb.table_operands()}))
        mark = _SETUP.booked
        with self.telemetry.dispatch_span(
                "decode", self._steps[key], seqs=rb.total_tokens,
                **(hc or {})):
            out = self._steps[key](self.params, self.cache, batch)
        if _SETUP.booked != mark:
            _SETUP.close("put_decode", mark, self.telemetry.tracer, bucket=S)
        if with_routes:
            *out, chosen = out
        logits, self.cache = self._take_moe_stats(out)
        if with_routes:         # a decode program's rows are the slots
            return logits, chosen, [np.asarray([sl])
                                    for sl in rb.logits_slots]
        return logits

    def _take_moe_stats(self, out):
        """A step program's outputs without the MoE counter vector that a
        model with expert layers has its programs return last: that goes,
        with the ``seq`` of the dispatch just made, on the list
        ``_fold_moe_stats`` reads back once the device has it."""
        if "moe_stats" not in self._model_static:
            return out
        self._moe_pending.append((self.telemetry.seq, out[-1]))
        return out[:-1]

    def _fold_moe_stats(self, wait: bool = False) -> None:
        """Counter vectors of finished dispatches into the telemetry, oldest
        first, stopping at the first the device still owes (``wait``: at a
        drain or the end of a call, where the host syncs anyway).  Reading
        a ready 12-byte array is no fence."""
        pend = self._moe_pending
        while pend and (wait or pend[0][1].is_ready()):
            seq, vec = pend.pop(0)
            self.telemetry.moe_stats(np.asarray(vec), seq)

    def _materialize_note(self, through_seq: int) -> Dict[str, int]:
        """What a ``ds.materialize`` span says of the host's lead:
        ``through_seq``, the newest dispatch whose results it fetches, and
        ``in_flight``, the dispatches made since the one the materialize
        before it fetched through: how far ahead of the device the host
        was when it chose to wait."""
        note = {"through_seq": through_seq,
                "in_flight": self.telemetry.seq - self._through_seq}
        self._through_seq = through_seq
        return note

    def _sample_fn(self, gen):
        from deepspeed_tpu.inference.engine import _sample_token
        return functools.partial(_sample_token, do_sample=gen.do_sample,
                                 top_k=gen.top_k)

    def _spec_active(self, gen) -> bool:
        """Speculative decoding runs whenever a draft is loaded: greedy uses
        exact-match acceptance (token-identical output), sampling uses
        rejection-sampling acceptance (exactly target-distributed output) —
        both correct for ANY draft."""
        return self.draft_params is not None

    def _slot_schedule(self, reqs, steps: int):
        """The slot-indexed host schedule of a fused multi-step dispatch
        (decode burst, speculative burst): reserves each request's blocks
        for ``steps`` positions and consumes its held token.  Returns (the
        numpy batch, the live context the dispatch reads: the sum of
        ``seen_tokens`` over its slots)."""
        S = self.state.max_tracked_sequences
        tokens0 = np.zeros(S, np.int32)
        from_device = np.zeros(S, bool)
        active = np.zeros(S, bool)
        pos0 = np.zeros(S, np.int32)
        tables = self.state.tables()
        for r in reqs:
            seq = self.state.get(r.uid)
            self.state.ensure_blocks(seq, steps, decode=True)
            sl = seq.slot
            if r.held_token is not None:
                tokens0[sl] = r.held_token
                r.held_token = None
            else:
                from_device[sl] = True
            active[sl] = True
            pos0[sl] = seq.seen_tokens
            self.state.write_tables(tables, seq)
        return ({"tokens0": tokens0, "from_device": from_device,
                 "active": active, "pos0": pos0,
                 **self.state.table_operands(tables)},
                self._ctx_note(pos0[active], steps=steps))

    def _ctx_note(self, contexts, new=None, steps: int = 1,
                  table_tokens: Optional[int] = None) -> Dict[str, int]:
        """What a dispatch's span says of the contexts it reads:
        ``ctx_tokens``, their sum before the step, and for a model with
        window layers ``ctx_tokens_window``, the sum of ``min(context,
        window)``: what a window layer needs of them (the window-aware
        rooflines).  A mixed step (``new``: each sequence's rows) adds the
        query-key pairs its attention has to score, ``qk_pairs`` on a global
        layer (row ``i`` of a chunk at context ``c`` sees ``c + i + 1``
        keys) and ``qk_pairs_window`` on a window layer (at most the
        window), and how many of its slots hold one row and their contexts
        (``one_row_slots``, ``ctx_tokens_one_row``).

        A model whose global layers select their keys (``index_topk``) also
        counts, over those layers, the pairs its dispatch scores with the
        indexer, keeps for attention, and would read without a selection
        (``ServingTelemetry.index_pairs``; a fused dispatch: ``steps`` rows
        a slot; ``table_tokens``: the step program's table width in tokens,
        the whole table's by default: a program no wider than the selection
        reads every key and scores none).  A mixed step of such a model
        says how far its prompt chunks reach (``sel_reach``: the longest
        context after the step among the slots with more than one row, what
        the step program computes) and is counted where that sends them
        through the masked prefill kernel, by the program's own rule
        (``ops.sparse_index.masked_prefill``)."""
        contexts = np.asarray(contexts, np.int64)
        note = {"ctx_tokens": int(contexts.sum())}
        k = self.model_config.index_topk
        if k:
            mc = self.model_config
            q = (np.asarray(new, np.int64) if new is not None
                 else np.full(len(contexts), steps, np.int64))
            causal = q * contexts + q * (q + 1) // 2
            rising = np.clip(k - contexts, 0, q)   # rows that see <= k keys
            kept = (rising * contexts + rising * (rising + 1) // 2
                    + (q - rising) * k)
            if table_tokens is None:
                table_tokens = (-(-mc.max_seq_len // self._block_size)
                                * self._block_size)
            layers = sum(mc.window_for_layer(i) is None
                         for i in range(mc.num_layers))
            selects = table_tokens > k
            scored = int(causal.sum()) if selects else 0
            reach = int((contexts + q)[q > 1].max(initial=0))
            self.telemetry.index_pairs(
                layers * scored, layers * int(kept.sum()),
                layers * int(causal.sum()),
                masked_step=bool(new is not None and selects
                                 and masked_prefill(reach)))
            # this dispatch's own, on ONE selecting layer (the rooflines'
            # needs), and the one-row slots' part of what it keeps
            note.update(index_pairs_step=scored,
                        sel_pairs_step=int(kept.sum()))
            if new is not None:
                note.update(sel_pairs_one_row=int(kept[q == 1].sum()),
                            sel_reach=reach)
        geo = getattr(self.model_config, "block_geometry", None)
        if geo is not None:
            note.update(self._block_note(contexts, new, steps, geo))
        if new is not None:
            # sum over i < q of (c + 1 + i); the one-row slots' part of it
            # (they go to the paged decode kernel) is their contexts + count
            q = np.asarray(new, np.int64)
            note.update(
                qk_pairs=int((q * contexts + q * (q + 1) // 2).sum()),
                one_row_slots=int((q == 1).sum()),
                ctx_tokens_one_row=int(contexts[q == 1].sum()))
        win = self.model_config.sliding_window
        if not win:
            return note
        note["ctx_tokens_window"] = int(np.minimum(contexts, win).sum())
        if new is not None:
            # the keys a one-row slot's window layer reads: its own too
            note["ctx_tokens_window_one_row"] = int(
                np.minimum(contexts[q == 1] + 1, win).sum())
            # ... and of min(c + 1 + i, win): the first `rising` rows still
            # see fewer keys than the window
            rising = np.clip(win - contexts - 1, 0, q)
            note["qk_pairs_window"] = int(
                (rising * contexts + rising * (rising + 1) // 2
                 + (q - rising) * win).sum())
        return note

    def _block_note(self, contexts, new, steps: int, geo) -> Dict[str, int]:
        """A dispatch's part in a selection by blocks
        (``GPTConfig.block_topk``), counted on the host by the program's own
        rule (``ops/block_select.py``): a row at position ``t`` takes the
        dense path while ``t + 1 <= dense_len`` and past it keeps ``topk``
        blocks a KV head, its own block as far as ``t`` and the others
        whole, after scoring the pooled keys it can see.  Counted into the
        running totals over the selecting layers
        (``ServingTelemetry.block_rows``, and ``index_pairs`` with the
        pooled pairs scored, the pairs kept and the causal pairs) and
        returned as the span's arguments for ONE selecting layer:
        ``blk_pairs_step`` (kept pairs of the selecting rows),
        ``blk_pairs_one_row`` (the one-row slots' part), ``blk_pooled_pairs``
        (pooled keys scored by the rows of prompt chunks),
        ``blk_ctx_chunk`` / ``blk_pooled_chunk`` (the contexts after the
        step, and the pooled keys in sight, of the chunks that select)."""
        q = (np.asarray(new, np.int64) if new is not None
             else np.full(len(contexts), steps, np.int64))
        dense = sparse = kept = kept_one = pooled = pooled_chunk = 0
        ctx_chunk = keys_chunk = dense_pairs = 0
        for c, n in zip(contexts.tolist(), q.tolist()):
            t = np.arange(c, c + n, dtype=np.int64)      # the rows' positions
            sel = t + 1 > geo.dense_len
            dense += int(n - sel.sum())
            dense_pairs += int((t[~sel] + 1).sum())  # they keep what is causal
            if not sel.any():
                continue
            t = t[sel]
            sparse += len(t)
            pairs = int(((geo.topk - 1) * geo.block + t % geo.block
                         + 1).sum())
            seen = int(((t - (geo.kernel - 1)) // geo.stride + 1).sum())
            kept += pairs
            pooled += seen
            if n == 1 or new is None:   # (a burst: a row a slot a step)
                kept_one += pairs
            else:
                pooled_chunk += seen
                ctx_chunk += c + n
                keys_chunk += (c + n - (geo.kernel - 1)) // geo.stride + 1
        causal = int((q * contexts + q * (q + 1) // 2).sum())
        layers = len(self.model_config.attention_layers)
        self.telemetry.block_rows(layers * dense, layers * sparse,
                                  layers * sparse * geo.topk)
        self.telemetry.index_pairs(layers * pooled,
                                   layers * (kept + dense_pairs),
                                   layers * causal)
        return dict(blk_pairs_step=kept, blk_pairs_one_row=kept_one,
                    blk_pooled_pairs=pooled_chunk, blk_ctx_chunk=ctx_chunk,
                    blk_pooled_chunk=keys_chunk, blk_sparse_rows_step=sparse)

    def _run_spec(self, reqs, outer: int, gamma: int, gen, prev, rng):
        """One fused draft-and-verify dispatch over the running set, then ONE
        sync to learn the per-step acceptance counts (the host cannot
        schedule past a spec burst without them).  Returns
        (toks [outer, gamma+1, S] np, counts [outer, S] np, prev', rng')."""
        stel = self.telemetry
        with stel.span("build"):
            host, note = self._slot_schedule(reqs, outer * (gamma + 1))
            ctx_tokens = note["ctx_tokens"]
        with stel.span("h2d"):
            batch = jax.tree_util.tree_map(jnp.asarray, host)
        t_begin = stel.now()
        if gen.do_sample:
            key = ("spec_rs", outer, gamma, gen.top_k)
            if key not in self._steps:
                self._steps[key] = jax.jit(
                    named_partial(speculative_burst_sampled,
                                   cfg=self.model_config,
                                   draft_cfg=self.draft_config,
                                   block_size=self._block_size,
                                   gamma=gamma, steps=outer,
                                   top_k=gen.top_k, mesh=self.mesh),
                    donate_argnums=(2, 3))
            mark = _SETUP.booked
            with stel.dispatch_span(
                    "spec", self._steps[key], steps=outer, gamma=gamma,
                    seqs=len(reqs), ctx_tokens=ctx_tokens,
                    kv_bytes_per_token=stel.kv_bytes_per_token):
                toks, counts, prev, rng, self.cache, self.draft_cache = \
                    self._steps[key](self.params, self.draft_params,
                                     self.cache, self.draft_cache, batch,
                                     prev, rng, jnp.float32(gen.temperature),
                                     jnp.float32(gen.top_p))
        else:
            key = ("spec", outer, gamma)
            if key not in self._steps:
                self._steps[key] = jax.jit(
                    named_partial(speculative_burst,
                                   cfg=self.model_config,
                                   draft_cfg=self.draft_config,
                                   block_size=self._block_size,
                                   gamma=gamma, steps=outer,
                                   mesh=self.mesh),
                    donate_argnums=(2, 3))
            mark = _SETUP.booked
            with stel.dispatch_span(
                    "spec", self._steps[key], steps=outer, gamma=gamma,
                    seqs=len(reqs), ctx_tokens=ctx_tokens,
                    kv_bytes_per_token=stel.kv_bytes_per_token):
                toks, counts, prev, self.cache, self.draft_cache = \
                    self._steps[key](self.params, self.draft_params,
                                     self.cache, self.draft_cache, batch,
                                     prev)
        if _SETUP.booked != mark:
            _SETUP.close("spec", mark, stel.tracer, steps=outer, gamma=gamma)
        with stel.span("materialize", **self._materialize_note(stel.seq)):
            # the host cannot schedule past the burst without the counts —
            # this is THE disclosed sync of the speculative path
            toks_h, counts_h = jax.device_get([toks, counts])  # sync-ok
        emitted = int(np.asarray(counts_h)[
            :, [self.state.get(r.uid).slot for r in reqs]].sum())
        stel.spec_burst(outer=outer, n_seqs=len(reqs), gamma=gamma,
                        emitted=emitted, dur_ms=(stel.now() - t_begin) * 1e3)
        stel.tokens("spec", emitted)
        return np.asarray(toks_h), np.asarray(counts_h), prev, rng

    def _run_burst(self, reqs, steps: int, gen, prev, rng):
        """Fused T-step decode over the running set: one device dispatch for
        ``steps`` tokens per sequence (see model.ragged_decode_burst).  Each
        req's first-step token comes from ``held_token`` (host, post-preempt)
        or from the ``prev`` device feedback vector.  Blocks for all T
        positions are pre-allocated.  Returns (tokens [T, S] DEVICE array,
        prev', rng') — no host sync."""
        stel = self.telemetry
        with stel.span("build"):
            host, note = self._slot_schedule(reqs, steps)
            self._fold_moe_stats()
            stel.ssm_rows([1] * len(reqs), steps)
            note.update(stel.hc_rows([1] * len(reqs), steps))
        key = ("burst", steps, gen.do_sample, gen.top_k)
        if key not in self._steps:
            self._steps[key] = jax.jit(
                named_partial(ragged_decode_burst, cfg=self.model_config,
                               block_size=self._block_size, steps=steps,
                               sample_fn=self._sample_fn(gen),
                               mesh=self.mesh, **self._model_static),
                donate_argnums=(1,))
        with stel.span("h2d"):
            batch = self._with_lora(
                jax.tree_util.tree_map(jnp.asarray, host))
        mark = _SETUP.booked
        with stel.dispatch_span("burst", self._steps[key], steps=steps,
                                seqs=len(reqs), tokens=steps * len(reqs),
                                **note, **stel.counter_note(self.state)):
            toks, prev, rng, self.cache = self._take_moe_stats(
                self._steps[key](
                    self.params, self.cache, batch, prev, rng,
                    jnp.float32(gen.temperature), jnp.float32(gen.top_p)))
        if _SETUP.booked != mark:
            _SETUP.close("burst", mark, stel.tracer, steps=steps)
        stel.tokens("decode", steps * len(reqs))
        for r in reqs:
            self.state.get(r.uid).seen_tokens += steps
        return toks, prev, rng

    def _step_sampled(self, uids, toks_np, from_device, served_slots, gen,
                      prev, rng):
        """One scheduled step through the SAMPLED programs: same schedule
        construction as _put_device but with in-graph sampling and device
        token feedback — returns (prev', rng'), never touching the host.
        ``from_device`` marks tokens whose VALUE lives in prev[slot] (their
        host entry is a placeholder); ``served_slots`` are the slots whose
        freshly sampled token must be written into prev'.

        Three host phases, each a span: ``build`` (block reservation, the
        numpy schedule, the compile bucket), ``h2d`` (the arrays onto the
        device) and the ``*_dispatch`` around the jitted call."""
        sm = self.config.state_manager
        stel = self.telemetry
        S = self.state.max_tracked_sequences
        draft = self._spec_active(gen)
        with stel.span("build"):
            schedule = []
            for uid, toks in zip(uids, toks_np):
                seq = self.state.get(uid)
                if seq is None:
                    seq = self.state.create(uid)
                    self._adapter_slot[seq.slot] = 0
                self.state.ensure_blocks(seq, len(toks))
                schedule.append((seq, toks))
            served = np.zeros(S, bool)
            served[list(served_slots)] = True
            # what a reader needs to compute rates without the engine
            self._fold_moe_stats()
            rows = [len(t) for t in toks_np]
            mixed = max(rows) > 1
            stel.ssm_rows(rows)
            if mixed:
                stel.mixed_slots(rows, *self._prefill_items(rows))
            note = {"seqs": len(schedule), "tokens": sum(rows),
                    **stel.hc_rows(rows)}
            if not mixed:
                # decode-only: slot-indexed [S] program
                kind = "decode"
                tokens = np.zeros(S, np.int32)
                active = np.zeros(S, bool)
                token_pos = np.zeros(S, np.int32)
                fdev = np.zeros(S, bool)
                tables = self.state.tables()
                for (seq, toks), fd in zip(schedule, from_device):
                    sl = seq.slot
                    tokens[sl] = toks[0]
                    active[sl] = True
                    fdev[sl] = fd
                    token_pos[sl] = seq.seen_tokens
                    self.state.write_tables(tables, seq)
                host = {"tokens": tokens, "active": active,
                        "token_pos": token_pos,
                        **self.state.table_operands(tables),
                        "from_device": fdev, "served": served}
                note["bucket"] = S
                shape_key, static = (), {}
            else:
                kind = "mixed"
                rb = build_ragged_batch(schedule, self.state,
                                        sm.max_ragged_batch_size,
                                        sm.max_q_per_seq)
                fdev = np.zeros(rb.tokens.shape[0], bool)
                i = 0
                for (seq, toks), fd in zip(schedule, from_device):
                    fdev[i:i + len(toks)] = fd
                    i += len(toks)
                mb, nb = self._buckets(rb)
                stel.padding_waste(rb.total_tokens, nb)
                host = {"tokens": rb.tokens[:nb],
                        "token_slot": rb.token_slot[:nb],
                        "token_pos": rb.token_pos[:nb],
                        **rb.table_operands(mb),
                        "kv_len": rb.kv_len, "from_device": fdev[:nb],
                        "served": served}
                note["bucket"] = nb
                shape_key = (sm.max_q_per_seq, mb)
                static = {"max_q_per_seq": sm.max_q_per_seq}
            note.update(self._ctx_note(
                [seq.seen_tokens for seq, _ in schedule], rows,
                table_tokens=mb * self._block_size if mixed else None))
            note.update(stel.counter_note(self.state))
            # with a draft loaded it ingests every token in lockstep (dual
            # prefill and decode), so speculative acceptance has something
            # to work with; draft staleness can't affect correctness
            key = ((f"{kind}_sd" if draft else f"{kind}_s",) + shape_key
                   + (gen.do_sample, gen.top_k))
            if key not in self._steps:
                if draft:
                    static["draft_cfg"] = self.draft_config
                self._steps[key] = jax.jit(
                    named_partial(_STEP_PROGRAMS[kind, draft],
                                   cfg=self.model_config,
                                   block_size=self._block_size,
                                   sample_fn=self._sample_fn(gen),
                                   mesh=self.mesh, **static,
                                   **self._model_static),
                    donate_argnums=(2, 3) if draft else (1,))
        with stel.span("h2d"):
            batch = self._with_lora(
                jax.tree_util.tree_map(jnp.asarray, host))
        mark = _SETUP.booked
        if draft:
            with stel.dispatch_span(kind, self._steps[key], draft=True,
                                    **note):
                prev, rng, self.cache, self.draft_cache = self._steps[key](
                    self.params, self.draft_params, self.cache,
                    self.draft_cache, batch, prev, rng,
                    jnp.float32(gen.temperature), jnp.float32(gen.top_p))
        else:
            with stel.dispatch_span(kind, self._steps[key], **note):
                prev, rng, self.cache = self._take_moe_stats(
                    self._steps[key](
                        self.params, self.cache, batch, prev, rng,
                        jnp.float32(gen.temperature),
                        jnp.float32(gen.top_p)))
        if _SETUP.booked != mark:
            _SETUP.close(kind, mark, stel.tracer, bucket=note["bucket"],
                         **({"table_width": mb} if mixed else {}))
        for seq, toks in schedule:
            seq.seen_tokens += len(toks)
        return prev, rng

    # ----------------------------------------- reference query()/can_schedule
    def query(self) -> Dict[str, int]:
        """KV/slot headroom (reference engine_v2.query :158).  Also refreshes
        the KV-pool gauges (blocks used/free, internal fragmentation) so a
        scheduler polling ``query()`` keeps the pool view fresh in the
        telemetry snapshot for free."""
        sm = self.config.state_manager
        self.telemetry.kv_sample(self.state)
        used = (self.state.allocator.num_blocks
                - self.state.allocator.free_blocks)
        radix = self.state.radix
        return {
            "free_kv_blocks": self.state.allocator.free_blocks,
            "used_kv_blocks": used,
            # supply a scheduler can count on: free + LRU-evictable cached
            "available_kv_blocks": self.state.available_blocks,
            "cached_kv_blocks": radix.node_count if radix is not None else 0,
            "free_sequence_slots": self.state.free_sequence_slots,
            "token_budget": sm.max_ragged_batch_size,
            "max_q_per_seq": sm.max_q_per_seq,
            "kv_block_size": self._block_size,
        }

    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> bool:
        """reference engine_v2.can_schedule :184.  A rejection for want of
        blocks or slots counts into ``kv_alloc_failures_total`` — the
        overload signal an admission controller will key off."""
        sm = self.config.state_manager
        if sum(lengths) > sm.max_ragged_batch_size:
            self.telemetry.alloc_failure("can_schedule")
            return False
        if len(uids) > sm.max_ragged_sequence_count:
            self.telemetry.alloc_failure("can_schedule")
            return False
        steps = [(self.state.get(uid), n) for uid, n in zip(uids, lengths)]
        slots = sum(1 for seq, _ in steps if seq is None)
        ok = (self.state.fits(steps)
              and slots <= self.state.free_sequence_slots)
        if not ok:
            self.telemetry.alloc_failure("can_schedule")
        return ok

    def flush(self, uids: Sequence[int]) -> None:
        """reference engine_v2.flush :242."""
        for uid in uids:
            self.state.flush(uid)

    def prefix_cached_tokens(self, prompt) -> int:
        """Longest radix-cached block-aligned prefix of ``prompt`` resident
        on THIS engine (tokens; 0 with the cache off).  Read-only — no LRU
        stamps freshened, no references taken — and a pure host dict walk,
        so the fleet router may probe it cross-thread for residency-aware
        routing (``prefix_affinity``): a concurrent insert/evict can only
        make the answer stale, never corrupt the walk."""
        radix = self.state.radix
        if radix is None:
            return 0
        return radix.peek(np.asarray(prompt, np.int32).reshape(-1))

    def prefix_block_handles(self, prompt) -> Tuple[List[int], int]:
        """(pool block ids, matched token count) of ``prompt``'s longest
        radix-cached block-aligned prefix — the disaggregated fleet's
        KV-handoff probe.  Read-only like :meth:`prefix_cached_tokens`;
        the caller (the fleet dispatcher) pins the blocks with
        ``state.allocator.acquire`` — atomic validate-then-bump, so a
        block a concurrent evict freed between walk and pin raises there
        and the handoff degrades to accounting-free, never to a
        corrupted refcount.  ([], 0) with the cache off."""
        radix = self.state.radix
        if radix is None:
            return [], 0
        return radix.peek_blocks(np.asarray(prompt, np.int32).reshape(-1))

    def register_adapter(self, adapter_id: int, weights=None) -> None:
        """Make a LoRA adapter id loadable on this engine (host-side only;
        pool blocks and device traffic happen lazily when a request first
        selects the id).  ``weights=None`` generates deterministic per-id
        weights (bench/test tenants)."""
        if self.adapters is None:
            raise ValueError(
                "this engine has no adapter pool; enable config.adapters")
        self.adapters.register(adapter_id, weights)

    def adapter_resident(self, adapter_ids) -> int:
        """How many of ``adapter_ids`` have their pages resident on THIS
        engine right now (0 with adapters off; id 0 never counts).
        Read-only and a pure host dict peek — no LRU stamps freshened, no
        references taken — so the fleet router may probe it cross-thread
        as the adapter-affinity signal (``prefix_affinity``), exactly like
        :meth:`prefix_cached_tokens`: a concurrent load/evict can only
        make the answer stale, never corrupt the walk."""
        if self.adapters is None:
            return 0
        return self.adapters.resident_count(adapter_ids)

    def kv_block_bytes(self) -> int:
        """Device bytes one KV pool block holds (K + V across layers at
        the serving dtype) — the unit the fleet's stubbed multi-host
        handoff copy path accounts ``kv_handoff_bytes_total`` in.  An
        approximation by design: kv-quant stores int8 codes + scales, but
        the accounting models the FUTURE wire transfer, not today's
        resident bytes.  A latent pool's block is its real bytes: one padded
        row a token a layer."""
        mc = self.model_config
        try:
            itemsize = int(np.dtype(self.config.jnp_dtype).itemsize)
        except TypeError:       # bfloat16 without a numpy extension
            itemsize = 2
        if mc.mla:
            row = len(mc.attention_layers) * mc.latent_page_dim
        else:              # each layer at its own heads and widths
            row = sum(lc.kv_heads * (lc.head_dim + lc.value_dim)
                      for lc in map(mc.for_layer, mc.attention_layers))
        return int(self._block_size * row * itemsize)

    def kv_bytes_per_token(self) -> int:
        """Device bytes the pool stores for one cached token over all layers
        (the dispatch spans' ``kv_bytes_per_token``), pad columns of a
        latent row and int8 scales included: the pool's bytes over its
        tokens, or with two page groups, whose layers hold different page
        counts, a block's bytes over its tokens."""
        if self.cache.kw is not None:
            return sum(self.kv_bytes_by_group().values())
        if self.kv_window:
            return self.kv_block_bytes() // self._block_size
        c = self.cache      # (the pages: a scan layer's state is no token's)
        pool = sum(a.size * a.dtype.itemsize
                   for a in (c.k, c.v, c.k_scale, c.v_scale)
                   if a is not None)
        return int(pool // (self.cache.k.shape[1] * self._block_size))

    def kv_bytes_by_group(self) -> Dict[str, int]:
        """``kv_bytes_per_token`` split by what holds the bytes, for a
        model with a pool a page group (latent pages, or ordinary heads
        whose groups differ; else {}): a token's rows in the global layers'
        pool(s), in the window layers' (while the window holds it), each
        from its own pool's geometry, and its index keys
        (``index_bytes_per_token``)."""
        c = self.cache
        if c.kw is None and c.ki is not None:
            # a selection by blocks: the pooled keys beside the one group
            return {"index_bytes_per_token": int(
                c.ki.size * c.ki.dtype.itemsize
                // (c.ki.shape[1] * self._block_size))}
        if c.kw is None:
            return {}
        mc = self.model_config
        kinds = [mc.window_for_layer(i) is not None
                 for i in range(mc.num_layers)]

        def row(layers, *pools):      # a token's bytes over the group's layers
            return int(sum(
                layers * a.size * a.dtype.itemsize
                // (a.shape[1] * self._block_size)
                for a in pools if a is not None))
        out = {"kv_bytes_per_token_global": row(kinds.count(False), c.k, c.v),
               "kv_bytes_per_token_window": row(kinds.count(True), c.kw,
                                                c.vw)}
        if c.ki is not None:
            out["index_bytes_per_token"] = row(kinds.count(False), c.ki)
        return out

    # ------------------------------- continuous batching (Dynamic SplitFuse)
    def _stream_fence(self, value) -> None:
        """Streaming-latency mode (``telemetry.stream_sync`` / the
        open-loop bench): block until the just-dispatched step's on-device
        output exists, so the lifecycle timestamp taken next reflects
        device completion — the point a real streaming server could emit
        the token — instead of host submission.  Serializes the dispatch
        chain by design; never on in the throughput path."""
        jax.block_until_ready(value)    # sync-ok: opt-in streaming mode

    def _finish_request(self, r: "_Request",
                        outcome: str = "completed") -> None:
        """Record one retired request into the serving telemetry (idempotent
        — retirement is reachable from the spec, burst, step, and
        materialize paths)."""
        if r.finished:
            return
        r.finished = True
        self.telemetry.finish_request(
            uid=r.uid, track=r.track, t_arrival=r.t_arrival,
            t_admit=r.t_admit, t_prefill_end=r.t_prefill_end,
            t_first=r.t_first, t_last=r.t_last,
            n_prompt=len(r.prompt) - r.folded,
            n_generated=len(r.generated), preempts=r.preempts,
            outcome=outcome, trace=r.trace)

    # --------------------------------------- fleet drain/migration hooks
    def request_drain(self) -> None:
        """Ask a running ``generate()`` to stop at its next scheduler round
        (serving drain: stop admission, materialize device records, flush
        sequences, raise :class:`EngineDrained`).  Safe cross-thread — the
        fleet supervisor calls it from the dispatcher while the replica
        worker is inside ``generate``.  Latched until :meth:`clear_drain`."""
        self._drain_requested.set()

    def clear_drain(self) -> None:
        """Re-arm serving after a drain (a drained replica returning to the
        pool must not abort its next ``generate`` on the stale latch)."""
        self._drain_requested.clear()

    def export_pending_requests(self):
        """The requeue half of request migration: after ``generate()``
        stopped early — :class:`EngineDrained`, an injected replica death
        (``replica.mid_decode``), or any mid-serve exception — returns
        ``(completed, pending)``:

        - ``completed``: {prompt index -> np.int32 generated tokens} for
          requests that finished before the stop (nothing a survivor needs
          to redo — "no lost requests");
        - ``pending``: migration records ``{index, prompt, generated,
          max_new_tokens}`` where ``prompt`` is the original context plus
          every host-known generated token (folded exactly like
          recompute-preemption) and ``max_new_tokens`` is the REMAINING
          budget — a survivor replica re-prefills the folded prompt and
          greedy decoding continues token-exact; the final output is
          ``generated + survivor_output``.

        Host-state only — never touches the device — so it is safe on a
        dead replica: tokens sampled on device after the last materialize
        are simply recomputed by the survivor.  Idempotent until the next
        ``generate()`` resets the serve context."""
        ctx = self._serve_ctx
        if ctx is None:
            return {}, []
        completed: Dict[int, np.ndarray] = {}
        pending: List[Dict[str, Any]] = []
        for uid, r in ctx["results"].items():
            idx = -uid - 1
            gen = list(r.generated)
            if r.finished or (r.done and (r.eos_hit
                                          or len(gen) >= r.max_new_tokens)):
                # retired with its host token list final (EOS found at a
                # materialize, or budget reached and materialized)
                completed[idx] = np.asarray(gen, np.int32)
                continue
            prompt = r.prompt                 # includes prior preempt folds
            tail = gen[r.folded:]             # host-known, not yet folded
            if tail:
                prompt = np.concatenate(
                    [prompt, np.asarray(tail, np.int32)])
            pending.append({"index": idx, "prompt": prompt,
                            "generated": gen,
                            "max_new_tokens": r.max_new_tokens - len(gen)})
        return completed, pending

    def generate(self, prompts: Sequence[np.ndarray],
                 max_new_tokens=32, seed: int = 0,
                 arrival_times: Optional[Sequence[float]] = None,
                 now_fn=None, stream: Optional[bool] = None,
                 sla: Optional[Sequence[str]] = None,
                 adapter_ids: Optional[Sequence[int]] = None,
                 trace_ctx: Optional[Sequence[Any]] = None,
                 **gen_overrides) -> List[np.ndarray]:
        """Serve a set of prompts to completion with continuous batching.

        Dynamic SplitFuse (reference blogs/deepspeed-fastgen): every step first
        schedules 1 token for each running decode, then fills the remaining
        token budget with prompt chunks (long prompts split across steps);
        new requests are admitted as slots/blocks free up.

        The token feedback loop is DEVICE-RESIDENT: every step program samples
        in-graph and the next step reads its input tokens from the previous
        step's on-device output (model.ragged_forward_sampled /
        ragged_decode_sampled / ragged_decode_burst), so steady state chains
        async dispatches with no host sync.  Token VALUES are materialized in
        bulk — once at the end when no eos_token_id is set, else every
        ``sync_interval`` steps (sequences may overshoot their EOS by up to
        that many tokens plus at most one smallest-size burst; the extras are
        discarded at materialize time — bounded discarded decode work traded
        for eliminating per-step host round trips, which dominate on a
        high-latency host↔device link).

        max_new_tokens: int, or one int per prompt (heterogeneous completion
        budgets — the FastGen effective-throughput workload shape).

        arrival_times: open-loop mode — per-prompt arrival offsets in
        seconds from call start (e.g. a seeded Poisson process from the
        bench harness); requests only become admittable once their arrival
        time passes, and queue-wait spans measure arrival → admission.
        ``now_fn`` overrides the clock (deterministic tests — a fake clock
        must advance or an idle open loop spins).  ``stream`` fences each
        dispatch before timestamping (defaults to ``telemetry.stream_sync``)
        so TTFT/TPOT histograms reflect device completion.

        trace_ctx: one distributed TraceContext per prompt (or None
        entries) — the serving fleet threads each dispatch attempt's
        context through so this engine's request spans carry the
        fleet-wide trace/span ids and stitch into the merged cross-
        replica view.  Absent (single-engine use), flowless contexts are
        allocated locally so trace args stay uniformly present.

        sla: one ``scheduler.sla_classes`` name per prompt (default: the
        implicit ``default`` class, priority 0, no SLO).  Priority orders
        admission; a waiting request that has burned
        ``scheduler.preempt_margin`` of its ``ttft_slo_ms`` and still
        cannot be admitted preempts the most recently admitted
        lower-priority running request (token-exact recompute fold-back).

        adapter_ids: one LoRA adapter id per prompt (0 / omitted = base
        model).  Adapters must be :meth:`register_adapter`-ed; pages are
        hot-loaded into the shared paged pool at admission and the
        per-request selection rides the SAME fused ragged dispatch as the
        base model (ops/lora_matmul.py batched gather) — a mixed-adapter
        batch is token-exact vs serving each request alone on its own
        adapter.  An id whose pages can NEVER fit (unknown, or larger than
        the whole pool) fails THIS call with ``ValueError`` at dispatch —
        the PR 7 poison-request rule: a client input error must fail the
        request, never book a replica death.
        """
        gen = self.config.generation.model_copy(update=gen_overrides)
        self._serve_ctx = None   # never expose a PREVIOUS call's requests
        sm = self.config.state_manager
        S = self.state.max_tracked_sequences
        stel = self.telemetry
        now_fn = now_fn if now_fn is not None else stel.now
        stream = stel.stream_sync if stream is None else bool(stream)
        if isinstance(max_new_tokens, (int, np.integer)):
            max_list = [int(max_new_tokens)] * len(prompts)
        else:
            max_list = [int(m) for m in max_new_tokens]
            if len(max_list) != len(prompts):
                raise ValueError("max_new_tokens list must match prompts")
        if (arrival_times is not None
                and len(arrival_times) != len(prompts)):
            raise ValueError("arrival_times must match prompts")
        sched_cfg = self.config.scheduler
        classes = dict(sched_cfg.sla_classes)
        classes.setdefault("default", SLAClassConfig())
        if sla is not None and len(sla) != len(prompts):
            raise ValueError("sla list must match prompts")
        for name in (sla or ()):
            if name not in classes:
                raise ValueError(f"unknown SLA class {name!r}; expected one "
                                 f"of {sorted(classes)}")
        if trace_ctx is not None and len(trace_ctx) != len(prompts):
            raise ValueError("trace_ctx list must match prompts")
        if adapter_ids is not None:
            if len(adapter_ids) != len(prompts):
                raise ValueError("adapter_ids list must match prompts")
            if self.adapters is None and any(int(a) for a in adapter_ids):
                raise ValueError(
                    "adapter_ids passed but this engine has no adapter "
                    "pool; enable config.adapters")
        t_start = now_fn()
        waiting = [
            _Request(uid=-(i + 1), prompt=np.asarray(p, np.int32).reshape(-1),
                     max_new_tokens=m,
                     adapter=(int(adapter_ids[i])
                              if adapter_ids is not None else 0),
                     sla=(sla[i] if sla is not None else "default"),
                     priority=classes[sla[i] if sla is not None
                                      else "default"].priority,
                     ttft_slo_ms=classes[sla[i] if sla is not None
                                         else "default"].ttft_slo_ms)
            for i, (p, m) in enumerate(zip(prompts, max_list))]
        # SLA machinery only engages when some request actually differs from
        # the default class — the legacy FIFO paths stay byte-identical
        has_sla = any(r.priority != 0 or r.ttft_slo_ms > 0 for r in waiting)
        pool_blocks = self.state.allocator.num_blocks
        for i, r in enumerate(waiting):
            r.track = stel.new_track(f"req {i}")
            if trace_ctx is not None and trace_ctx[i] is not None:
                r.trace = trace_ctx[i]
            elif stel.enabled:
                # local root context (flow_id=None: a single-engine trace
                # has no cross-file hop to stitch, so no flow events)
                from deepspeed_tpu.telemetry import tracecontext
                r.trace = tracecontext.new_trace(with_flow=False)
            r.t_arrival = t_start + (float(arrival_times[i])
                                     if arrival_times is not None else 0.0)
            if (len(r.prompt) + r.max_new_tokens
                    > self.model_config.max_seq_len):
                raise ValueError(f"prompt {len(r.prompt)} + "
                                 f"{r.max_new_tokens} exceeds max_seq_len")
            need = -(-(len(r.prompt) + r.max_new_tokens)
                     // self.state.block_size)
            if need > pool_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks for its full context but "
                    f"the pool holds {pool_blocks}; raise num_kv_blocks "
                    f"(recompute-preemption cannot make a single sequence fit)")
            # (the window group holds any one sequence's ring: start-up
            # checked num_kv_window_blocks against it)
            if r.adapter and self.adapters is not None:
                # a permanently unservable adapter id is a CLIENT error —
                # reject at dispatch (the fleet maps this to a typed
                # invalid_request failure), never loop in admission
                bad = self.adapters.unfittable_reason(r.adapter)
                if bad:
                    raise ValueError(f"prompt {i}: {bad}")
                if need + self.adapters.blocks_per_adapter > pool_blocks:
                    # the request's own pinned adapter pages shrink the pool
                    # its KV must fit in — unservable at any load
                    raise ValueError(
                        f"prompt {i}: {need} KV blocks + "
                        f"{self.adapters.blocks_per_adapter} adapter-page "
                        f"blocks exceed the {pool_blocks}-block pool; raise "
                        f"num_kv_blocks")
        running: List[_Request] = []
        results: Dict[int, _Request] = {r.uid: r for r in waiting}
        # open loop: requests enter the waiting queue at their arrival time
        incoming: List[_Request] = []
        if arrival_times is not None:
            waiting.sort(key=lambda r: r.t_arrival)
            incoming, waiting = waiting, []
        # fleet migration hook: export_pending_requests() reads these live
        # views if this serve stops early (drain / injected death); the
        # lists are only MUTATED below (never rebound), so the references
        # stay current.  Cleared on normal completion.
        self._serve_ctx = {"waiting": waiting, "running": running,
                           "incoming": incoming, "results": results}

        eos = gen.eos_token_id
        sync_interval = 16 if eos is not None else None
        prev = jnp.zeros(S, jnp.int32)          # device feedback vector
        rng = jax.random.PRNGKey(seed)          # device-resident, threaded
        # device records: ("step", arr [S], [(uid, slot)], seq) or
        # ("burst", arr [T, S], [(uid, slot)], seq), seq the dispatch's that
        # made them — fetched in ONE transfer
        records: List[tuple] = []
        # requests retired while their tokens still sat in device records;
        # telemetry-finished at the next materialize, when .generated is
        # exact (a list, not a results.values() sweep — that would make
        # generate O(requests²) at open-loop scale)
        pending_finish: List[_Request] = []
        steps_since_sync = 0

        def _append(r: _Request, toks) -> None:
            for tok in toks:
                if r.eos_hit or len(r.generated) >= r.max_new_tokens:
                    return                      # discard overshoot
                r.generated.append(int(tok))
                if eos is not None and int(tok) == eos:
                    r.eos_hit = True
                    r.done = True

        def materialize() -> None:
            """Fetch every pending device record (one sync), fill
            .generated, and retire sequences whose EOS was discovered."""
            nonlocal steps_since_sync
            steps_since_sync = 0
            if not records:
                return
            with stel.span("materialize", records=len(records),
                           **self._materialize_note(records[-1][-1])):
                arrs = jax.device_get([rec[1] for rec in records])
                self._fold_moe_stats(wait=True)   # the sync just happened
                for rec, arr in zip(records, arrs):
                    if rec[0] == "step":
                        for uid, sl in rec[2]:
                            _append(results[uid], [arr[sl]])
                    else:
                        for uid, sl in rec[2]:
                            _append(results[uid], arr[:, sl])
                records.clear()
                for r in list(running):
                    if r.done:                  # EOS found on materialize
                        self.flush([r.uid])
                        running.remove(r)
                        pending_finish.append(r)
                # retired requests reach their final .generated here (their
                # pending device records just resolved) — record them into
                # the serving telemetry now, when the token count is exact
                for r in pending_finish:
                    self._finish_request(r)
                pending_finish.clear()

        def preempt(victim: _Request, reason: str) -> None:
            """Recompute-preempt one RUNNING request (the vLLM/FastGen
            policy): free its blocks and re-queue it with its full folded
            context; its re-prefill logits are not re-sampled (resume).
            ``reason`` is ``starvation`` (pool deadlock — the only
            pre-PR-15 trigger) or ``sla`` (a higher-priority waiting
            request would miss its TTFT SLO).  Callers materialize first
            so ``generated`` is exact at the fold."""
            running.remove(victim)
            kind = ("mid_prefill" if not victim.decode_ready
                    else "decode_ready")
            self.preempt_stats[kind] += 1
            stel.preemption(kind)
            if reason == "sla":
                stel.sla_preemption(victim.sla)
            victim.preempts += 1
            if victim.decode_ready:
                # fold generated-but-not-yet-refed tokens into the prompt
                # exactly once (folded tracks prior preemptions; the last
                # sampled token is NOT folded — it replays as a decode via
                # held_token)
                keep = victim.sampled - 1
                new_ctx = victim.generated[victim.folded:keep]
                if new_ctx:
                    victim.prompt = np.concatenate(
                        [victim.prompt, np.asarray(new_ctx, np.int32)])
                victim.folded = keep
                victim.resume = True
                victim.held_token = victim.generated[keep]
                victim.decode_ready = False
            # else: preempted mid-(re-)prefill — folded/resume/held_token
            # already describe everything sampled; recycle the request
            # unchanged (a second fold here would reset the state and
            # duplicate the held continuation token)
            self.state.flush(victim.uid)
            waiting.insert(0, victim)

        burst_sizes = (64, 32, 16, 8)
        n_round = 0
        while waiting or running or incoming:
            n_round += 1
            with _Round(stel, n=n_round, running=len(running),
                        waiting=len(waiting), incoming=len(incoming),
                        slots=S, host_ns=time.perf_counter_ns()) as rnd:
                # ---- gate: the fleet hooks, once per scheduler round (the
                # chaos site a replica death injects through,
                # kind@replica.mid_decode; the liveness beat the supervisor
                # deadlines on; the drain latch), then arrivals -> waiting
                now = now_fn()
                due = 0
                while due < len(incoming) and incoming[due].t_arrival <= now:
                    due += 1
                rnd.phase("gate", released=due, late_ms_max=(
                    (now - incoming[0].t_arrival) * 1e3 if due else 0.0))
                faults.fire("replica.mid_decode")
                if self.heartbeat_fn is not None:
                    self.heartbeat_fn()
                if self._drain_requested.is_set():
                    # serving drain (PR 6 semantics applied to requests instead
                    # of optimizer state): materialize so .generated is exact,
                    # free every live sequence, and hand the unfinished set to
                    # export_pending_requests() for migration
                    materialize()
                    for r in list(running):
                        self.state.flush(r.uid)
                    raise EngineDrained(
                        f"drain requested: {len(running)} running + "
                        f"{len(waiting) + len(incoming)} queued request(s) "
                        f"exported for migration")
                waiting.extend(incoming[:due])
                del incoming[:due]
                if not waiting and not running:
                    # open-loop idle: everything in flight is done and the
                    # next request hasn't arrived — flush pending records,
                    # then sleep to the next arrival (a fake now_fn just
                    # re-polls: it must advance on its own)
                    rnd.phase("idle_sleep")
                    materialize()
                    if now_fn is stel.now:
                        time.sleep(max(0.0,
                                       incoming[0].t_arrival - now_fn()))
                    continue
                # ---- admit: pool gauges, SLA order and preemption, the
                # fast-path decisions and the three scheduling passes
                rnd.phase("admit")
                stel.kv_sample(self.state)
                stel.occupancy(len(running), S)
                # ---- SLA-aware admission order + preemption.  Waiting sorts
                # by priority (stable: FIFO within a class, and a preemption
                # victim re-queued at the front keeps resuming first among its
                # peers).  When the head has burned preempt_margin of its TTFT
                # SLO and STILL cannot be admitted — no sequence slot, or no
                # blocks even counting cache-evictable ones — the most recently
                # admitted lower-priority running request is recompute-preempted
                # for it (the policy behind serving_preemptions_total).
                if has_sla and waiting:
                    waiting.sort(key=lambda r: -r.priority)
                    head = waiting[0]
                    lows = [r for r in running if r.priority < head.priority]
                    at_risk = (sched_cfg.sla_preempt and head.ttft_slo_ms > 0
                               and (now - head.t_arrival) * 1e3
                               >= sched_cfg.preempt_margin * head.ttft_slo_ms)
                    if lows and at_risk:
                        m, pin = self.state.peek_prefix_pinned(head.prompt)
                        # mirror the admission loop's chunk sizing exactly — a
                        # probe sized to max_q_per_seq would preempt a victim
                        # in rounds where the configured (smaller) chunk is
                        # perfectly admissible
                        first = min(len(head.prompt) - m, sm.max_q_per_seq,
                                    sm.max_ragged_batch_size,
                                    sm.prefill_chunk_tokens
                                    or sm.max_ragged_batch_size)
                        need = (-(-(m + first) // self.state.block_size)
                                - m // self.state.block_size + pin)
                        if (self.state.free_sequence_slots == 0
                                or need > self.state.available_blocks
                                or not self.state.fits([(None, first)])):
                            if records:
                                materialize()   # exact .generated at the fold
                                continue        # (retirements may change sets)
                            low_p = min(r.priority for r in lows)
                            victim = [r for r in lows if r.priority == low_p][-1]
                            stel.admission(head.sla, decision="preempted_for")
                            preempt(victim, "sla")
                            continue
                # ---- speculative draft-and-verify fast path: same eligibility
                # as the decode burst, preferred when a draft is loaded and
                # decoding is greedy.  Each outer step yields 1..gamma+1 tokens
                # per slot; the host syncs after the burst (it cannot schedule
                # without the acceptance counts), which also materializes EOS.
                if (self._spec_active(gen) and running
                        and (not waiting or self.state.free_sequence_slots == 0)
                        and all(r.decode_ready and not r.done for r in running)
                        and all(not self.state.get(r.uid).in_flight
                                for r in running)):
                    sp = self.config.speculative
                    worst = sp.gamma + 1            # tokens per outer step, max
                    n_before = len(running)
                    materialize()       # keep .generated chronological
                    if len(running) != n_before:
                        continue        # EOS retirements changed the set (maybe
                        # to empty) — recompute eligibility and sizing
                    # batched mode: the whole running set in one dispatch.
                    # Per-request baseline (batch_across_requests=False): one
                    # dispatch per request through the SAME slot-wide program —
                    # a request finishing mid-round simply drops out of later
                    # groups; inactive lanes pass prev through, so the token
                    # stream is identical either way
                    groups = ([list(running)] if sp.batch_across_requests
                              else [[r] for r in list(running)])
                    ran_any = False
                    for grp in groups:
                        rnd.phase("admit")
                        grp = [r for r in grp if r in running]
                        if not grp:
                            continue
                        need_max = max(r.max_new_tokens - r.sampled for r in grp)
                        cap = min(self.model_config.max_seq_len
                                  - self.state.get(r.uid).seen_tokens
                                  for r in grp)
                        # size for ~half acceptance (2x the full-acceptance
                        # need), then round DOWN to a power of two so the
                        # compile cache holds at most log2(outer_steps) spec
                        # programs
                        outer = min(sp.outer_steps, 2 * -(-need_max // worst),
                                    cap // worst)
                        if outer >= 1:
                            outer = 1 << (outer.bit_length() - 1)
                        while outer >= 1:
                            need = sum(self.state.get(r.uid).kv_blocks_needed(
                                outer * worst, self.state.block_size)
                                for r in grp)
                            if need <= self.state.available_blocks:
                                break
                            outer //= 2
                        if outer < 1:
                            continue
                        ran_any = True
                        pairs = [(r.uid, self.state.get(r.uid).slot)
                                 for r in grp]
                        rnd.phase(None)     # build / h2d / dispatch / sync
                        toks_h, counts_h, prev, rng = self._run_spec(
                            grp, outer, sp.gamma, gen, prev, rng)
                        rnd.phase("retire")
                        tnow = now_fn()     # _run_spec synced: completion time
                        for r, (uid, sl) in zip(list(grp), pairs):
                            total = int(counts_h[:, sl].sum())
                            self.state.get(uid).seen_tokens += total
                            vals = []
                            for k in range(outer):
                                c = int(counts_h[k, sl])
                                vals.extend(int(t) for t in toks_h[k, :c, sl])
                            _append(r, vals)
                            r.sampled += total
                            if total:
                                if r.t_first is None:
                                    r.t_first = tnow
                                r.t_last = tnow
                            if r.done or r.sampled >= r.max_new_tokens:
                                r.done = True
                                self.flush([r.uid])
                                running.remove(r)
                                self._finish_request(r)
                    if ran_any:
                        continue

                # ---- decode-burst fast path: every running sequence is in pure
                # decode and no slot is admittable -> fuse T steps into one
                # dispatch.  With requests WAITING the burst targets the earliest
                # retirement (free a slot, then admit); otherwise it covers the
                # longest remaining budget (finish everyone).  Sequences that
                # finish mid-burst cost nothing extra — the burst computes all
                # slots every step — and their overshoot tokens are discarded at
                # materialize.  Disabled while speculation is active: the plain
                # burst would advance the target without the draft, leaving
                # permanent draft-cache holes (single steps stay dual-model).
                if (running and not self._spec_active(gen)
                        and (not waiting or self.state.free_sequence_slots == 0)
                        and all(r.decode_ready and not r.done for r in running)
                        and all(not self.state.get(r.uid).in_flight
                                for r in running)):
                    rem_max = max(r.max_new_tokens - r.sampled for r in running)
                    if waiting:
                        # earliest retirement frees a slot — but floor the burst
                        # so retirements CLUMP and the freed slots refill in
                        # one fat admission step instead of one step per slot
                        rem_min = min(r.max_new_tokens - r.sampled
                                      for r in running)
                        need_max = max(rem_min, min(16, rem_max))
                    else:
                        need_max = rem_max
                    if sync_interval:
                        # budget the burst against the NEXT materialize point so
                        # EOS overshoot stays ~sync_interval (plus at most the
                        # smallest compiled burst), not 2x
                        need_max = min(need_max,
                                       max(1, sync_interval - steps_since_sync))
                    cap = min(self.model_config.max_seq_len
                              - self.state.get(r.uid).seen_tokens
                              for r in running)
                    target = min(need_max, cap)
                    fitting = [b for b in burst_sizes if b <= cap]
                    covering = [b for b in fitting if b >= target]
                    T = (min(covering) if covering
                         else (max(fitting) if fitting else 0))
                    # shrink the burst until its block reservation fits the pool
                    while T >= burst_sizes[-1]:
                        if self.state.fits([(self.state.get(r.uid), T)
                                            for r in running]):
                            break
                        T //= 2
                    if T >= burst_sizes[-1]:
                        pairs = [(r.uid, self.state.get(r.uid).slot)
                                 for r in running]
                        rnd.phase(None)     # build / h2d / burst_dispatch
                        toks, prev, rng = self._run_burst(running, T, gen,
                                                          prev, rng)
                        if stream:
                            rnd.phase("fence")
                            self._stream_fence(prev)
                        rnd.phase("retire")
                        tnow = now_fn()
                        records.append(("burst", toks, pairs, stel.seq))
                        for r in list(running):
                            r.sampled += T
                            if r.t_first is None:
                                # first token mid-burst: stamped at burst end
                                # (bursts only run once every slot is decode-
                                # ready, so in practice t_first predates them)
                                r.t_first = tnow
                            r.t_last = tnow
                            if r.sampled >= r.max_new_tokens:
                                r.done = True       # finish recorded at the
                                self.flush([r.uid])  # next materialize (records
                                running.remove(r)    # still hold its tokens)
                                pending_finish.append(r)
                        steps_since_sync += T
                        if sync_interval and steps_since_sync >= sync_interval:
                            materialize()
                        continue

                budget = sm.max_ragged_batch_size
                seq_budget = sm.max_ragged_sequence_count   # per-step seq cap
                # SplitFuse chunk bound: prompt-chunk tokens co-scheduled with
                # decode this round — keeps the mixed dispatch short so live
                # decoders' TPOT stays flat under long-prompt load
                prefill_budget = (sm.prefill_chunk_tokens
                                  if sm.prefill_chunk_tokens else budget)
                sched_uids: List[int] = []
                sched_toks: List[np.ndarray] = []
                sched_fdev: List[bool] = []
                served_slots: List[int] = []
                sampled_now: List[_Request] = []
                newly_ready: List[_Request] = []    # prefill completes this step
                n_decode_toks = n_prefill_toks = 0

                # 1) running decodes: one token each (decode-priority keeps
                #    latency flat while prompts stream in)
                for r in running:
                    seq = self.state.get(r.uid)
                    # a resumed request may be decode-ready while its re-prefill
                    # is still chunked in (in_flight) — its decode must wait
                    if r.done or not r.decode_ready or seq.in_flight:
                        continue
                    if budget <= 0 or len(sched_uids) >= seq_budget:
                        break
                    # reserve the block NOW (allocator state advances with each
                    # reservation, so later checks see the true remaining pool);
                    # a decode that can't get a block defers to a later round
                    if not self.state.fits([(seq, 1)]):
                        stel.alloc_failure("decode")
                        continue
                    self.state.ensure_blocks(seq, 1)
                    sched_uids.append(r.uid)
                    if r.held_token is not None:    # post-preempt continuation
                        sched_toks.append(np.asarray([r.held_token], np.int32))
                        sched_fdev.append(False)
                        r.held_token = None
                    else:                           # device feedback
                        sched_toks.append(np.zeros(1, np.int32))
                        sched_fdev.append(True)
                    served_slots.append(seq.slot)
                    sampled_now.append(r)
                    budget -= 1
                    n_decode_toks += 1

                # 2) prompt chunks fill the rest (running first, then admit new),
                #    bounded by the SplitFuse prefill_budget
                for r in list(running):
                    seq = self.state.get(r.uid)
                    if (seq is None or not seq.in_flight or budget <= 0
                            or prefill_budget <= 0
                            or len(sched_uids) >= seq_budget):
                        continue
                    chunk = min(len(seq.pending), sm.max_q_per_seq, budget,
                                prefill_budget)
                    if not self.state.fits([(seq, chunk)]):
                        stel.alloc_failure("prompt_chunk")
                        continue
                    self.state.ensure_blocks(seq, chunk)
                    toks, seq.pending = seq.pending[:chunk], seq.pending[chunk:]
                    sched_uids.append(r.uid)
                    sched_toks.append(toks)
                    sched_fdev.append(False)
                    n_prefill_toks += chunk
                    stel.prefill_chunk()
                    prefill_budget -= chunk
                    if not seq.in_flight:       # prompt complete -> decode next
                        r.decode_ready = True
                        newly_ready.append(r)
                        if r.resume:
                            r.resume = False    # continuation token already held
                        else:
                            served_slots.append(seq.slot)
                            sampled_now.append(r)
                    budget -= chunk

                while (waiting and budget > 0 and prefill_budget > 0
                       and self.state.free_sequence_slots
                       and len(sched_uids) < seq_budget):
                    r = waiting[0]
                    # radix prefix match FIRST (matching acquires the cached
                    # blocks, pinning them against eviction), THEN size and
                    # check the uncached suffix: after the match both the
                    # block need (kv_blocks_needed off the match boundary) and
                    # the supply (available_blocks no longer counts the pinned
                    # nodes) are exact, so an admitted request can never hit
                    # "KV cache exhausted" inside ensure_blocks.  On a
                    # shortfall the match is rolled back (flush releases the
                    # acquired holds) and the request retries next round.
                    waiting.pop(0)
                    seq = self.state.create(r.uid)
                    seq.host_tokens = r.prompt
                    matched = self.state.match_prefix(seq, r.prompt)
                    if self.adapters is not None:
                        # adapter residency BEFORE sizing: the load may consume
                        # free blocks (spilling cold adapters, then radix
                        # leaves), and the block check below must see the pool
                        # as it will be when the chunk dispatches.  A load the
                        # pool cannot fit RIGHT NOW (every page pinned by
                        # in-flight work) rolls back like a block shortfall and
                        # retries when a retirement releases pins.
                        try:
                            self.state.ensure_adapters([r.adapter])
                        except RuntimeError:
                            stel.alloc_failure("adapter_load")
                            self.state.flush(r.uid)
                            waiting.insert(0, r)
                            break
                        self.state.bind_adapter(seq, r.adapter)
                        self._adapter_slot[seq.slot] = \
                            self.adapters.slot_of(r.adapter)
                    chunk = min(len(r.prompt) - matched, sm.max_q_per_seq,
                                budget, prefill_budget)
                    if not self.state.fits([(seq, chunk)]):
                        stel.alloc_failure("admission")
                        self.state.flush(r.uid)
                        waiting.insert(0, r)
                        break
                    if self.state.radix is not None:
                        stel.prefix_lookup(matched)
                    seq.pending = r.prompt[matched:]
                    self.state.ensure_blocks(seq, chunk)
                    running.append(r)
                    if r.t_admit is None:
                        r.t_admit = now_fn()
                        stel.admission(r.sla)
                    toks, seq.pending = seq.pending[:chunk], seq.pending[chunk:]
                    sched_uids.append(r.uid)
                    sched_toks.append(toks)
                    sched_fdev.append(False)
                    n_prefill_toks += chunk
                    stel.prefill_chunk()
                    prefill_budget -= chunk
                    if not seq.in_flight:
                        r.decode_ready = True
                        newly_ready.append(r)
                        if r.resume:
                            r.resume = False
                        else:
                            served_slots.append(seq.slot)
                            sampled_now.append(r)
                    budget -= chunk

                if not sched_uids:
                    # nothing schedulable: first materialize (EOS retirement may
                    # free blocks), then preempt the most recently admitted
                    # sequence (pool starvation — the pre-SLA trigger)
                    if records:
                        materialize()
                        continue
                    if running:
                        preempt(running[-1], "starvation")
                        continue
                    raise RuntimeError(
                        "scheduler deadlock: the KV pool cannot fit even one "
                        "sequence; raise num_kv_blocks")

                pairs = [(r.uid, self.state.get(r.uid).slot)
                         for r in sampled_now]
                stel.tokens("decode", n_decode_toks)
                stel.tokens("prefill", n_prefill_toks)
                rnd.phase(None)         # build / h2d / *_dispatch
                prev, rng = self._step_sampled(sched_uids, sched_toks,
                                               sched_fdev, served_slots, gen,
                                               prev, rng)
                if stream:
                    rnd.phase("fence")
                    self._stream_fence(prev)
                rnd.phase("retire")
                tnow = now_fn()
                for r in newly_ready:
                    r.t_prefill_end = tnow
                    # index the completed prompt's full blocks into the radix:
                    # the forward that filled them was just dispatched, so any
                    # later program aliasing them is ordered behind the writer
                    self.state.cache_insert(self.state.get(r.uid))
                if pairs:
                    records.append(("step", prev, pairs, stel.seq))
                for r in sampled_now:
                    if r.t_first is None:
                        r.t_first = tnow
                    r.t_last = tnow
                    r.sampled += 1
                    if r.sampled >= r.max_new_tokens:
                        r.done = True       # finish recorded at materialize
                        self.flush([r.uid])
                        running.remove(r)
                        pending_finish.append(r)
                steps_since_sync += 1
                if sync_interval and steps_since_sync >= sync_interval:
                    materialize()

        materialize()
        self._serve_ctx = None      # clean completion: nothing to migrate
        return [np.asarray(results[-(i + 1)].generated, np.int32)
                for i in range(len(prompts))]
