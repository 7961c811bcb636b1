"""Config system.

TPU-native analog of the reference's ``DeepSpeedConfig`` (runtime/config.py:706) +
``DeepSpeedConfigModel`` pydantic base (runtime/config_utils.py:16).  We keep the same
JSON key surface for the blocks that transfer (batch triad, optimizer, scheduler,
fp16/bf16, zero_optimization, gradient_clipping, steps_per_print,
wall_clock_breakdown, comms_logger, monitor blocks) and add a ``mesh`` block for the
TPU device-mesh axes that replaces the reference's mpu/process-group plumbing.

``"auto"`` values (reference: HF/autotuner integration) are left as the AUTO sentinel
and resolved by the engine from runtime context (device count, model dims).
"""

from __future__ import annotations

import json
from typing import Any, Dict, Literal, Optional, Union

from pydantic import BaseModel, ConfigDict, Field, model_validator

from deepspeed_tpu.constants import AUTO


class DeepSpeedConfigModel(BaseModel):
    """Base config model (reference: runtime/config_utils.py:16).

    Accepts unknown keys (the reference warns but proceeds), rejects bad types.
    """

    model_config = ConfigDict(extra="allow", validate_assignment=True,
                              arbitrary_types_allowed=True, populate_by_name=True)

    @classmethod
    def parse(cls, config):
        """None → defaults, an instance → itself, anything else (dict)
        validated.  The one accept-a-loose-config entry point, so
        subsystem configs (fleet, ragged engine, ...) don't each grow a
        divergent copy; subclasses override to add coercions (e.g. the
        ragged engine's dtype aliasing)."""
        if config is None:
            return cls()
        if isinstance(config, cls):
            return config
        return cls.model_validate(config)


AutoInt = Union[Literal["auto"], int]
AutoFloat = Union[Literal["auto"], float]


class OptimizerConfig(DeepSpeedConfigModel):
    """reference: "optimizer" block, runtime/config.py get_optimizer_params."""

    type: str = "adamw"
    params: Dict[str, Any] = Field(default_factory=dict)


class SchedulerConfig(DeepSpeedConfigModel):
    """reference: "scheduler" block → runtime/lr_schedules.py."""

    type: str = "WarmupLR"
    params: Dict[str, Any] = Field(default_factory=dict)


class FP16Config(DeepSpeedConfigModel):
    """reference: "fp16" block (runtime/config.py, fp16/loss_scaler.py)."""

    enabled: bool = False
    loss_scale: float = 0.0  # 0 → dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0


class BF16Config(DeepSpeedConfigModel):
    """reference: "bf16" block (runtime/bf16_optimizer.py)."""

    enabled: bool = False


class OffloadConfig(DeepSpeedConfigModel):
    """reference: runtime/zero/offload_config.py (DeepSpeedZeroOffloadOptimizerConfig).

    device: "none" | "cpu" (host memory on the TPU VM) | "nvme" (local SSD via the
    native aio library, csrc equivalent deepspeed_tpu/csrc/aio).
    """

    device: Literal["none", "cpu", "nvme"] = "none"
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    pin_memory: bool = False
    # reference offload_config.py:96 (ZeRO-Offload++ partial offload): the
    # host tier here is all-or-nothing — any ratio < 1 warns inert
    ratio: float = 1.0
    # ZeRO-Offload delayed one-step update (reference "delayed parameter
    # update", DeepSpeedZeroConfig offload + stage_1_and_2 DPU): run the
    # host Adam of step N on a worker thread overlapped with step N+1's
    # device grad computation.  Step N+1's gradients then see parameters
    # ONE update stale — documented staleness, regression-tested; set False
    # for the bitwise-serial host step.  Read only on offload_optimizer
    # (ignored for offload_param, whose engine owns its own schedule).
    overlap_step: bool = True


class ZeroPPConfig(DeepSpeedConfigModel):
    """Wire-format knobs of the composable collective pipeline
    (runtime/zero.py; ZeRO++ arXiv:2306.10209, T3 arXiv:2401.16677,
    EQuARX arXiv:2506.17615).

    ``zero_quantized_weights`` / ``zero_quantized_gradients`` stay the
    on/off switches (reference parity); this block says HOW:

    - ``weight_bits``: int wire width of the qwZ forward param all-gather
      (8 = ZeRO++ default; 4 = nibble-packed, half the bytes again).
    - ``grad_bits``: int wire width of the qgZ gradient reduce (the
      chunked gather's transposed reduce-scatter at stage 3, and the
      data-axis all-to-all / EQuARX allreduce).
    - ``block_size``: values per quantization block (one fp32 scale each).
    - ``hierarchical``: per-axis wire policy — axes whose ring stays
      inside one host (all-ICI) keep full-width values, host-crossing
      axes quantize (the hpZ hierarchical design; pairs with
      ``zero_hpz_partition_size`` which keeps params intra-host).
    - ``quantized_allreduce``: block-quantized allreduce for the
      stage-0/1 dp grad path (EQuARX-style), where
      ``zero_quantized_gradients`` is rejected for lack of a scatter
      target.
    """

    weight_bits: int = 8
    grad_bits: int = 8
    block_size: int = 256
    hierarchical: bool = False
    quantized_allreduce: bool = False

    @model_validator(mode="after")
    def _check(self):
        for name in ("weight_bits", "grad_bits"):
            if getattr(self, name) not in (2, 4, 8):
                raise ValueError(
                    f"zeropp.{name} must be 2, 4, or 8 "
                    f"(got {getattr(self, name)})")
        if self.block_size < 8:
            raise ValueError(
                f"zeropp.block_size must be >= 8, got {self.block_size}")
        return self


class MoEConfig(DeepSpeedConfigModel):
    """Expert-parallel fast-path knobs (moe/layer.py, moe/comm.py).

    The MoE dispatch/combine all-to-alls are the dominant wire cost of an
    expert-parallel step; this block says how they go over the wire and how
    they schedule, mirroring ``zeropp`` for the ZeRO collectives:

    - ``wire_bits``: int wire width of both a2a directions (0 = bf16/fp32
      full width; 8 = blockwise int8 values + fp32 scales; 4 =
      nibble-packed).  Gradients of the combine a2a ride the same width
      (quantized-transpose custom_vjp).
    - ``block_size``: values per quantization block (one fp32 scale each).
    - ``hierarchical``: all-ICI ep axes stay full width, only host-crossing
      ep axes quantize (same per-axis policy as ``zeropp.hierarchical``).
    - ``num_chunks``: decompose dispatch-a2a -> expert FFN -> combine-a2a
      into this many expert sub-group chunks so expert GEMMs interleave
      with in-flight a2a chunks (T3-style overlap); 1 = single-shot.
    - ``expert_telemetry``: per-expert assigned-token gauges, drop
      counters, aux-loss/gate-entropy gauges computed inside the jitted
      step (one extra output, no steady-state recompile).
    """

    wire_bits: int = 0
    block_size: int = 256
    hierarchical: bool = False
    num_chunks: int = 1
    expert_telemetry: bool = True

    @model_validator(mode="after")
    def _check(self):
        if self.wire_bits not in (0, 4, 8):
            raise ValueError(
                f"moe.wire_bits must be 0 (full width), 4, or 8 "
                f"(got {self.wire_bits})")
        if self.block_size < 8:
            raise ValueError(
                f"moe.block_size must be >= 8, got {self.block_size}")
        if self.num_chunks < 1:
            raise ValueError(
                f"moe.num_chunks must be >= 1, got {self.num_chunks}")
        return self


class ZeroConfig(DeepSpeedConfigModel):
    """reference: runtime/zero/config.py (DeepSpeedZeroConfig).

    Stage semantics on TPU (SURVEY.md §7): sharding annotations over the ``fsdp``
    mesh axis —
      stage 0: params+grads+opt replicated (plain DP psum)
      stage 1: optimizer state sharded
      stage 2: + gradients reduce-scattered (same XLA program as stage 1; kept for
               config parity and grad-accum buffer sharding)
      stage 3: + parameters sharded (FSDP); XLA all-gathers per-layer and its
               latency-hiding scheduler overlaps — replacing the reference's
               hook/prefetch machinery (partitioned_param_coordinator.py).
    """

    stage: int = 0
    offload_optimizer: OffloadConfig = Field(default_factory=OffloadConfig)
    offload_param: OffloadConfig = Field(default_factory=OffloadConfig)
    overlap_comm: bool = True
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    # ZeRO++ analogs (reference zero/config.py zero_quantized_*):
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    zero_hpz_partition_size: int = 1
    # wire-format knobs for the quantized/hierarchical collective pipeline
    zeropp: ZeroPPConfig = Field(default_factory=ZeroPPConfig)
    # MiCS subgroup sharding (reference runtime/zero/mics.py): shard params
    # within groups of this many chips, replicate across groups; 0 = off
    mics_shard_size: int = 0
    # stage-3 knobs kept for config parity; XLA's scheduler supersedes most:
    stage3_max_live_parameters: int = 1_000_000_000
    stage3_prefetch_bucket_size: AutoInt = 50_000_000
    stage3_param_persistence_threshold: AutoInt = 100_000
    sub_group_size: int = 1_000_000_000


class OverlapConfig(DeepSpeedConfigModel):
    """Device-side compute–collective overlap (T3, arXiv:2401.16677; The Big
    Send-off, arXiv:2504.18658).  No single reference analog — the reference
    hides ZeRO-3 gathers with its prefetch coordinator
    (partitioned_param_coordinator.py); on TPU the same latency is hidden by
    (a) XLA's latency-hiding scheduler + async-collective fusion, steered by
    the flags this block composes (runtime/overlap.py — exported to
    LIBTPU_INIT_ARGS by the engine BEFORE client/backend init, because
    libtpu reads them once), (b) chunking
    the ZeRO-3 flat param all-gather / grad reduce-scatter into
    ``num_chunks`` per-layer-group collectives the scheduler can interleave
    with neighboring matmuls (runtime/zero.chunked_param_gather), and (c)
    explicit ``ppermute``-ring collective-matmul fusions on the TP
    row/column-parallel matmuls (ops/collective_matmul.py).

    Every trace records the scheduler regime it ran under: the resolved
    block + effective compiler flags land in the telemetry snapshot, the
    postmortem bundle, and ``python -m deepspeed_tpu`` (env_report).
    """

    enabled: bool = False
    # ZeRO-3 collective chunking: the per-step param gather (and its
    # transpose, the grad reduce-scatter) is decomposed into this many
    # byte-balanced per-layer-group flat collectives; 1 = leave the gathers
    # to XLA's per-consumer insertion (the seed behavior)
    num_chunks: int = 1
    # --xla_latency_hiding_scheduler_rerun=<n> (re-run the scheduler n extra
    # times with relaxed memory limits when it failed to hide latency)
    latency_hiding_scheduler: bool = True
    scheduler_rerun: int = 1
    # --xla_tpu_enable_async_collective_fusion* family: split collectives
    # into start/done pairs and let compute schedule between them
    async_collectives: bool = True
    # --xla_tpu_scheduler_percent_shared_memory_limit=<pct>: how much memory
    # headroom the latency-hiding scheduler may spend on in-flight
    # collectives (100 = the compiler default envelope)
    scheduler_memory_limit_pct: int = 100
    # route the TP row-parallel matmuls (gpt.py MLP down-projection and
    # attention output projection; linear.OptimizedLinear) through the
    # explicit ppermute-ring collective-matmul fusions
    collective_matmul: bool = False
    # escape hatch: extra --xla_* flags appended verbatim (validated shape;
    # libtpu exits on a name it does not know)
    extra_xla_flags: list = Field(default_factory=list)

    @model_validator(mode="after")
    def _check(self):
        if self.num_chunks < 1:
            raise ValueError(
                f"overlap.num_chunks must be >= 1, got {self.num_chunks}")
        if self.scheduler_rerun < 0:
            raise ValueError(
                f"overlap.scheduler_rerun must be >= 0, "
                f"got {self.scheduler_rerun}")
        if not 0 < self.scheduler_memory_limit_pct <= 1000:
            raise ValueError(
                f"overlap.scheduler_memory_limit_pct must be in (0, 1000], "
                f"got {self.scheduler_memory_limit_pct}")
        for f in self.extra_xla_flags:
            if not (isinstance(f, str) and f.startswith("--xla")
                    and "=" in f):
                raise ValueError(
                    f"overlap.extra_xla_flags entries must look like "
                    f"'--xla_...=value', got {f!r}")
        return self


class MeshConfig(DeepSpeedConfigModel):
    """TPU-specific: device mesh axis sizes (replaces reference mpu / groups.py).

    -1 = absorb remaining devices.  fsdp defaults to "auto": when any ZeRO stage
    is enabled the data-parallel world rides the fsdp axis (ZeRO shards over the
    whole DP world, reference semantics); otherwise fsdp=1 and dp absorbs.
    """

    pp: int = 1
    dp: int = -1
    fsdp: AutoInt = "auto"
    ep: int = 1
    sp: int = 1
    tp: int = 1


class CurriculumLearningConfig(DeepSpeedConfigModel):
    """reference: runtime/data_pipeline/config.py get_curriculum_learning."""

    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: dict = Field(default_factory=dict)


class RandomLTDConfig(DeepSpeedConfigModel):
    """reference: runtime/data_pipeline/config.py get_data_routing
    (random_ltd block)."""

    enabled: bool = False
    random_ltd_layer_ids: list = Field(default_factory=list)
    min_value: int = 128
    max_value: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: dict = Field(default_factory=dict)


class ProgressiveLayerDropConfig(DeepSpeedConfigModel):
    """reference: runtime/progressive_layer_drop.py (PLD, arXiv 2010.13369) —
    theta(t) = (1-theta)*exp(-gamma*t) + theta; layer l keeps its sublayers
    with prob 1 - (l/L)*(1-theta(t))."""

    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


class HybridEngineConfig(DeepSpeedConfigModel):
    """reference: inference/config.py DeepSpeedHybridEngineConfig (consumed by
    runtime/hybrid_engine.py via deepspeed.initialize)."""

    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8


class DataPipelineConfig(DeepSpeedConfigModel):
    """Host→device input pipeline (runtime/prefetch.py).

    ``prefetch_depth`` microbatch stacks are formed, sharded and
    ``device_put`` AHEAD of their step by a background worker when the
    loader is wrapped via ``engine.prefetch_loader(loader)`` /
    ``DeepSpeedDataLoader.prefetch(engine)`` — ``train_batch``'s
    ``host_to_device`` span then collapses to a queue pop.  The queue is
    bounded (backpressure: at most ``prefetch_depth`` staged batches pin
    device memory).  0 disables the worker (the wrapper prepares each batch
    synchronously, same API).  See docs/performance.md.
    """

    prefetch_depth: int = 2


class DataSamplingConfig(DeepSpeedConfigModel):
    curriculum_learning: CurriculumLearningConfig = Field(
        default_factory=CurriculumLearningConfig)


class DataRoutingConfig(DeepSpeedConfigModel):
    random_ltd: RandomLTDConfig = Field(default_factory=RandomLTDConfig)


class DataEfficiencyConfig(DeepSpeedConfigModel):
    """reference: runtime/data_pipeline/config.py get_data_efficiency_config."""

    enabled: bool = False
    seed: int = 1234
    data_sampling: DataSamplingConfig = Field(
        default_factory=DataSamplingConfig)
    data_routing: DataRoutingConfig = Field(default_factory=DataRoutingConfig)


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """reference: "activation_checkpointing" block
    (runtime/activation_checkpointing/checkpointing.py:1073 configure)."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    # TPU: remat policy name for jax.checkpoint
    policy: str = "nothing_saveable"


class CommsLoggerConfig(DeepSpeedConfigModel):
    """reference: "comms_logger" block (utils/comms_logging.py)."""

    enabled: bool = False
    verbose: bool = False


class TensorboardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    team: Optional[str] = None
    group: Optional[str] = None
    project: Optional[str] = None


class CometConfig(DeepSpeedConfigModel):
    """reference: monitor/comet.py CometConfig."""

    enabled: bool = False
    project: Optional[str] = None
    experiment_name: Optional[str] = None
    api_key: Optional[str] = None


class TelemetryHealthConfig(DeepSpeedConfigModel):
    """Numerics health monitor + postmortem flight recorder
    (telemetry/health.py, telemetry/flight_recorder.py).

    The reference engine treats numerics as a runtime signal (overflow
    detection, ``skipped_steps``, grad-norm monitor fan-out); this block adds
    the in-graph layer: per-module-group grad/param norms, NaN/Inf element
    counts and update-to-param ratios computed INSIDE the jitted train step
    (one extra small output — no recompile, no per-scalar syncs), a host-side
    ring buffer of the last ``recorder_steps`` structured step records, and
    anomaly rules.  On a non-finite loss, an overflow streak, an uncaught
    exception, or an explicit ``engine.dump_postmortem()`` the recorder dumps
    a timestamped postmortem bundle (records JSONL + Chrome trace +
    Prometheus snapshot + resolved config + env report) that
    ``python -m deepspeed_tpu.telemetry.postmortem <dir>`` summarizes.

    Enabling this forces one device→host fetch of the step scalars per step
    (the recorder needs every record) — the same cost class as
    ``trace_enabled``.
    """

    enabled: bool = False
    # module-path depth for health groups: params are grouped by the first N
    # path segments (the flax collection key "params" is skipped), so depth 2
    # buckets a GPT tree into backbone/wte, backbone/block_i, ...
    group_depth: int = 2
    # ring buffer capacity (structured step records kept for the postmortem)
    recorder_steps: int = 64
    # dump trigger: k consecutive overflow-skipped steps (0 disables)
    overflow_streak: int = 3
    # install a sys.excepthook that dumps the buffer on an uncaught exception
    crash_dump: bool = True
    # multi-host: gather the fleet min/mean/max view every N steps (plus
    # always on a dump trigger or anomaly).  The gather is a blocking
    # cross-host collective — per-step (1) would serialize every host's
    # bookkeeping path on the slowest process.  0 disables the cadence
    # (trigger-only).
    fleet_interval: int = 16
    # bundle directory; default <output_path>/<job_name>/postmortem
    dump_path: Optional[str] = None
    # ---- anomaly rules (one-shot warnings + labeled counter) ----
    anomaly_window: int = 32            # rolling history length
    loss_spike_zscore: float = 6.0      # z vs rolling loss mean/std
    grad_norm_factor: float = 10.0      # explosion = norm > factor x mean
    scale_collapse_factor: float = 16.0  # collapse = scale fell x16 in window


class TelemetryConfig(DeepSpeedConfigModel):
    """Unified step telemetry (deepspeed_tpu/telemetry/): host-phase trace
    spans, recompile watchdog, collective/memory counter registries, and the
    snapshot exporter.  No reference analog — this is the measurement layer
    the reference scatters across monitor/, utils/timer.py, and
    see_memory_usage, unified and extended with the TPU-specific hazards
    (silent jit recompiles, collective byte volume, HBM headroom).

    Paths default under ``<output_path>/<job_name>/``: ``trace.json``
    (Chrome-trace/Perfetto), ``snapshot.json``, ``metrics.prom``
    (Prometheus text exposition).
    """

    enabled: bool = False
    output_path: str = ""               # default "./telemetry"
    job_name: str = "DeepSpeedTPUJob"
    # span tracer: buffers the host-phase spans for trace.json (the spans
    # reach a jax.profiler trace as ds.* annotations either way); no sync
    trace_enabled: bool = True
    trace_path: Optional[str] = None
    snapshot_path: Optional[str] = None
    prometheus_path: Optional[str] = None
    # steps between snapshot/prometheus/trace file exports; 0 = only on an
    # explicit engine.telemetry.export() call
    snapshot_interval: int = 1
    # signature misses at step <= warmup are silent (first compiles and
    # known gas/curriculum shape buckets); later misses warn loudly
    recompile_warmup_steps: int = 1
    # per-executable compiled-HLO collective bytes + cost/memory analysis;
    # costs one extra (AOT) compile per new step signature
    hlo_stats: bool = True
    # fan the scalar subset through MonitorMaster (TensorBoard/CSV/W&B)
    monitor_fanout: bool = True
    max_trace_events: int = 200_000
    # numerics health monitor + flight recorder (active independently of the
    # parent ``enabled`` switch — a postmortem is wanted exactly when nothing
    # else is being watched)
    health: TelemetryHealthConfig = Field(
        default_factory=TelemetryHealthConfig)


class FlopsProfilerConfig(DeepSpeedConfigModel):
    """reference: "flops_profiler" block (profiling/flops_profiler)."""

    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class AIOConfig(DeepSpeedConfigModel):
    """reference: "aio" block (runtime/swap_tensor/aio_config.py).
    thread_count feeds the native pread/pwrite pool (csrc/aio.cpp); the
    libaio-specific knobs are accepted for schema parity and warned inert."""

    block_size: int = 1048576
    queue_depth: int = 8
    # reference default is 1; the threaded pread/pwrite pool here measured
    # best at 4 on the local SSDs, so that stays the default.  The libaio-
    # specific knobs (block_size/queue_depth/single_submit/overlap_events)
    # warn inert when changed (warn_inert_config).
    thread_count: int = 4
    single_submit: bool = False
    overlap_events: bool = True


class ElasticityJSONConfig(DeepSpeedConfigModel):
    """reference: "elasticity" ds_config block (elasticity/config.py
    ElasticityConfig) — when enabled, the SOLVER controls the batch triad
    (runtime/config.py:733)."""

    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: list = Field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10_000
    num_gpus_per_node: int = 1
    model_parallel_size: int = 1
    prefer_larger_batch: bool = True
    ignore_non_elastic_batch_info: bool = False
    version: float = 0.2


class ResilienceConfig(DeepSpeedConfigModel):
    """Preemption-tolerant operation (runtime/resilience.py; no reference
    analog — the reference's elasticity runtime assumes a full restart
    recompiles from scratch).  ``compilation_cache_dir`` points jax's
    persistent compilation cache at a shared path so a replacement host
    rebuilds its step programs from cache instead of recompiling;
    ``aot_warmup`` replays the drained host's executable fingerprints
    through an AOT compile pass on resume.  ``JAX_COMPILATION_CACHE_DIR``,
    when set, wins over ``compilation_cache_dir``.  See docs/resilience.md."""

    compilation_cache_dir: str = ""     # "" = persistent cache off
    aot_warmup: bool = True


class GuardianWatchdogConfig(DeepSpeedConfigModel):
    """Hang/straggler watchdog (runtime/guardian.py HangWatchdog): a
    monitor thread deadlines each training step against an EMA-adaptive
    budget.  On a trip it dumps a flight-recorder bundle carrying
    all-thread stacks, bumps ``hangs_total``, and initiates a drain —
    escalating to a hard ``EXIT_DRAINED`` exit after ``grace_s`` if the
    step is still wedged (a process stuck inside a collective cannot drain
    itself)."""

    enabled: bool = True
    # deadline = max(min_deadline_s, deadline_factor x EMA(step wall time))
    deadline_factor: float = 8.0
    min_deadline_s: float = 5.0
    # before the FIRST completed step the EMA is empty and the step
    # legitimately contains the XLA compile — the deadline is gated on
    # warm-up completion instead of booking a cold program as a hang (the
    # same first-call-compile hazard as the serving fleet's heartbeat)
    warmup_deadline_s: float = 600.0
    # after a trip: how long the watchdog waits for the step to come back
    # before the hard EXIT_DRAINED exit (the bundle is already on disk)
    grace_s: float = 10.0
    ema_alpha: float = 0.2
    poll_interval_s: float = 0.05

    @model_validator(mode="after")
    def _check(self):
        for knob in ("deadline_factor", "min_deadline_s",
                     "warmup_deadline_s", "grace_s", "poll_interval_s"):
            if getattr(self, knob) <= 0:
                raise ValueError(f"guardian.watchdog.{knob} must be > 0")
        if not 0 < self.ema_alpha <= 1:
            raise ValueError("guardian.watchdog.ema_alpha must be in (0, 1]")
        return self


class GuardianConfig(DeepSpeedConfigModel):
    """Self-healing training (runtime/guardian.py): a closed control loop
    converting the numerics-health anomaly signals into automatic
    remediation — rollback to the last health-verified ring checkpoint
    (checkpoint/ring.py), deterministic skip of the offending data window,
    LR/loss-scale clamp-down on repeated retries — under a bounded retry
    budget that escalates to postmortem-dump + graceful drain.  Requires
    ``telemetry.health.enabled`` (the anomaly signals are the health
    monitor's).  See docs/resilience.md "Self-healing"."""

    enabled: bool = False
    # steps between guarded-ring exports (checkpoint/ring.py)
    checkpoint_interval: int = 50
    ring_keep: int = 3
    # trailing anomaly-free steps before a ring export earns its
    # rollback-eligibility stamp
    clean_window: int = 8
    # rollbacks tolerated per incident (no net step progress) before the
    # guardian escalates to postmortem + drain
    max_rollbacks: int = 3
    # advance the data cursor past the replayed window (seed-stable skip of
    # the batches consumed since the rollback target)
    skip_data_window: bool = True
    # from the (clamp_after_rollbacks+1)-th rollback of one incident, clamp
    # the LR (re-jits the step programs) and the dynamic loss scale down
    clamp_after_rollbacks: int = 1
    lr_clamp_factor: float = 0.5
    loss_scale_clamp_factor: float = 0.5
    # anomaly signals that trigger a rollback; anything not listed is
    # observed (counted, recorded) but not remediated
    rollback_on: list = Field(default_factory=lambda: [
        "nonfinite_loss", "grad_nan", "overflow_streak", "loss_spike",
        "grad_norm_explosion", "loss_scale_collapse"])
    watchdog: GuardianWatchdogConfig = Field(
        default_factory=GuardianWatchdogConfig)

    @model_validator(mode="after")
    def _check(self):
        if self.checkpoint_interval < 1:
            raise ValueError("guardian.checkpoint_interval must be >= 1")
        if self.ring_keep < 1:
            raise ValueError("guardian.ring_keep must be >= 1")
        if self.clean_window < 1:
            raise ValueError("guardian.clean_window must be >= 1")
        if self.clean_window > self.ring_keep * self.checkpoint_interval:
            raise ValueError(
                f"guardian.clean_window={self.clean_window} exceeds the "
                f"ring's retention span ring_keep*checkpoint_interval="
                f"{self.ring_keep * self.checkpoint_interval}: every "
                f"export would be pruned off the keep tail before its "
                f"trailing window could prove clean, so no entry would "
                f"ever become rollback-eligible and the first anomaly "
                f"would escalate straight to drain")
        if self.max_rollbacks < 0:
            raise ValueError("guardian.max_rollbacks must be >= 0")
        if self.clamp_after_rollbacks < 0:
            raise ValueError("guardian.clamp_after_rollbacks must be >= 0")
        for knob in ("lr_clamp_factor", "loss_scale_clamp_factor"):
            if not 0 < getattr(self, knob) <= 1:
                raise ValueError(f"guardian.{knob} must be in (0, 1]")
        known = {"nonfinite_loss", "grad_nan", "overflow_streak",
                 "loss_spike", "grad_norm_explosion",
                 "loss_scale_collapse"}
        bad = [r for r in self.rollback_on if r not in known]
        if bad:
            raise ValueError(
                f"guardian.rollback_on: unknown signal(s) {bad}; "
                f"known: {sorted(known)}")
        return self


class GradientCompressionConfig(DeepSpeedConfigModel):
    """DCN-tier gradient compression (replaces reference 1-bit optimizers'
    error-feedback compression, runtime/fp16/onebit/ — see SURVEY.md: pointless over
    ICI, useful over DCN)."""

    enabled: bool = False
    dtype: Literal["bf16", "int8"] = "bf16"


class DeepSpeedTPUConfig(DeepSpeedConfigModel):
    """Top-level config (reference: DeepSpeedConfig, runtime/config.py:706)."""

    train_batch_size: AutoInt = AUTO
    train_micro_batch_size_per_gpu: AutoInt = AUTO
    gradient_accumulation_steps: AutoInt = AUTO

    optimizer: OptimizerConfig = Field(default_factory=OptimizerConfig)
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = Field(default_factory=FP16Config)
    bf16: BF16Config = Field(default_factory=BF16Config)
    zero_optimization: ZeroConfig = Field(default_factory=ZeroConfig)
    overlap: OverlapConfig = Field(default_factory=OverlapConfig)
    moe: MoEConfig = Field(default_factory=MoEConfig)
    mesh: MeshConfig = Field(default_factory=MeshConfig)
    activation_checkpointing: ActivationCheckpointingConfig = Field(
        default_factory=ActivationCheckpointingConfig)
    comms_logger: CommsLoggerConfig = Field(default_factory=CommsLoggerConfig)
    tensorboard: TensorboardConfig = Field(default_factory=TensorboardConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    comet: CometConfig = Field(default_factory=CometConfig)
    flops_profiler: FlopsProfilerConfig = Field(default_factory=FlopsProfilerConfig)
    telemetry: TelemetryConfig = Field(default_factory=TelemetryConfig)
    data_efficiency: DataEfficiencyConfig = Field(
        default_factory=DataEfficiencyConfig)
    data_pipeline: DataPipelineConfig = Field(
        default_factory=DataPipelineConfig)
    hybrid_engine: HybridEngineConfig = Field(
        default_factory=HybridEngineConfig)
    progressive_layer_drop: ProgressiveLayerDropConfig = Field(
        default_factory=ProgressiveLayerDropConfig)
    # reference deepspeed/compression/ config block (weight_quantization
    # groups; consumed by compression/basic.py via the engine loss hook)
    compression_training: Optional[dict] = None
    gradient_compression: GradientCompressionConfig = Field(
        default_factory=GradientCompressionConfig)
    elasticity: ElasticityJSONConfig = Field(
        default_factory=ElasticityJSONConfig)
    resilience: ResilienceConfig = Field(default_factory=ResilienceConfig)
    guardian: GuardianConfig = Field(default_factory=GuardianConfig)
    aio: AIOConfig = Field(default_factory=AIOConfig)

    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False
    seed: int = 42
    # reference: seq_parallel_communication_data_type (runtime/config.py)
    data_types: Dict[str, Any] = Field(default_factory=dict)

    @model_validator(mode="after")
    def _check_precision(self):
        if self.fp16.enabled and self.bf16.enabled:
            raise ValueError("fp16 and bf16 cannot both be enabled")
        return self

    # ---- batch triad resolution (reference runtime/config.py
    #      _configure_train_batch_size / _set_batch_related_parameters) ----
    def resolve_batch_size(self, dp_world_size: int) -> None:
        """Reconcile train_batch_size = micro_batch × grad_accum × dp_world_size.

        Any two of the three determine the third; a lone train_batch_size is split
        with gas=1; nothing set defaults to micro=1, gas=1.
        """
        tbs = self.train_batch_size
        mbs = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        tbs = None if tbs == AUTO else tbs
        mbs = None if mbs == AUTO else mbs
        gas = None if gas == AUTO else gas

        if tbs is not None and mbs is not None and gas is None:
            if tbs % (mbs * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size {tbs} not divisible by micro_batch "
                    f"{mbs} × dp_world {dp_world_size}")
            gas = tbs // (mbs * dp_world_size)
        elif tbs is not None and gas is not None and mbs is None:
            if tbs % (gas * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size {tbs} not divisible by grad_accum {gas} × "
                    f"dp_world {dp_world_size}")
            mbs = tbs // (gas * dp_world_size)
        elif mbs is not None:
            gas = gas or 1
            tbs = tbs or mbs * gas * dp_world_size
        elif tbs is not None:
            gas = 1
            if tbs % dp_world_size != 0:
                raise ValueError(
                    f"train_batch_size {tbs} not divisible by dp_world {dp_world_size}")
            mbs = tbs // dp_world_size
        else:
            mbs, gas = 1, 1
            tbs = dp_world_size

        if tbs != mbs * gas * dp_world_size:
            raise ValueError(
                f"batch triad inconsistent: {tbs} != {mbs} × {gas} × {dp_world_size}")
        self.train_batch_size = tbs
        self.train_micro_batch_size_per_gpu = mbs
        self.gradient_accumulation_steps = gas

    @property
    def compute_dtype(self):
        import jax.numpy as jnp
        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32


def warn_inert_config(cfg: DeepSpeedTPUConfig) -> list:
    """Warn LOUDLY about accepted-but-not-yet-implemented semantics.

    The reference silently honors every key it parses; round-1 review found
    several blocks here that were parsed and dropped.  Anything in this list is
    parsed for schema parity but changes no behavior yet — a user porting a
    ds_config.json must see that, not discover it from a flat loss curve.
    Implemented features must be REMOVED from this list as they land.
    """
    from deepspeed_tpu.utils.logging import logger
    inert = []
    z = cfg.zero_optimization
    for blk, name in ((z.offload_optimizer, "offload_optimizer"),
                      (z.offload_param, "offload_param")):
        if blk.device != "none" and blk.ratio != 1.0:
            inert.append(f"zero_optimization.{name}.ratio "
                         f"(partial offload — the host tier here is "
                         f"all-or-nothing; ratio={blk.ratio} will offload "
                         f"everything)")
    if z.zero_quantized_weights and z.stage < 3:
        inert.append("zero_optimization.zero_quantized_weights (qwZ is the "
                     "stage-3 weight all-gather; inert at stage "
                     f"{z.stage} — set stage 3 and an fsdp mesh axis > 1)")
    # reference top-level blocks that are accepted for schema parity but have
    # no TPU behavior (extra="allow" would otherwise swallow them silently)
    aio_defaults = AIOConfig()
    for knob in ("block_size", "queue_depth", "single_submit",
                 "overlap_events"):
        if getattr(cfg.aio, knob) != getattr(aio_defaults, knob):
            inert.append(f"aio.{knob} (libaio-specific; the native "
                         f"pread/pwrite pool honors thread_count only)")
    extras = getattr(cfg, "__pydantic_extra__", None) or {}
    for key, hint in (
            ("amp", "apex AMP is CUDA-specific; use bf16/fp16 blocks"),
            ("sparse_attention", "use ops.sparse_attention "
             "(SparsityConfig API) — the module-injection config block has "
             "no analog"),
            ("checkpoint", "orbax handles parallel/sharded writes natively"),
            ("communication_data_type", "see gradient_compression / "
             "data_types"),
            ("sparse_gradients", "no torch sparse-embedding analog")):
        if key in extras:
            inert.append(f"{key} ({hint})")
    # zero_hpz_partition_size at stage<3 is a hard engine error (not inert)
    ac = cfg.activation_checkpointing
    if ac.partition_activations or ac.cpu_checkpointing or ac.number_checkpoints:
        inert.append("activation_checkpointing.partition_activations/"
                     "cpu_checkpointing/number_checkpoints (TPU remat honors "
                     "only the jax.checkpoint 'policy' knob)")
    if cfg.prescale_gradients:
        inert.append("prescale_gradients (losses are globally averaged on the "
                     "global-batch jax.Array view; pre-scaling is a no-op)")
    if cfg.compression_training:
        # weight_quantization (compression/basic.py), the pruning family and
        # activation_quantization (compression/pruning.py) are LIVE; every
        # other reference sub-block must scream
        live = {"weight_quantization", "sparse_pruning", "row_pruning",
                "head_pruning", "activation_quantization"}
        for key in cfg.compression_training:
            if key not in live:
                inert.append(f"compression_training.{key} (implemented "
                             f"blocks: {sorted(live)})")
    for item in inert:
        logger.warning(f"config key accepted but NOT implemented on TPU yet: "
                       f"{item} — this run will NOT honor it")
    return inert


def parse_config(config: Union[str, dict, DeepSpeedTPUConfig, None]) -> DeepSpeedTPUConfig:
    """Load from a JSON path, dict, model instance, or None (all-defaults).

    reference: deepspeed.initialize(config=...) accepting path-or-dict
    (deepspeed/__init__.py:69, runtime/config.py:716).
    """
    if config is None:
        return DeepSpeedTPUConfig()
    if isinstance(config, DeepSpeedTPUConfig):
        return config
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    return DeepSpeedTPUConfig.model_validate(config)
