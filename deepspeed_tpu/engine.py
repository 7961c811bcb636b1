"""Training engine.

TPU-native analog of ``DeepSpeedEngine`` (reference runtime/engine.py:180, 3630 LoC).
The reference wraps a torch module and intercepts forward/backward/step with
hook-and-mutate machinery; here the engine *builds a jitted SPMD train step* from
(model, config) and owns the sharded train state.  Correspondences:

- ``engine.forward/backward/step``   → compatibility trio driving the same jitted
  grad/apply functions (reference engine.py:1785,1924,2123)
- ``engine.train_batch``             → one fused jitted step: scan over
  gradient-accumulation microbatches, ZeRO-sharded state update, loss-scale state
  machine (reference: the full fwd/bwd/step loop + stage_1_and_2/stage3 machinery)
- ZeRO stages                        → sharding choices (parallel/partition.py)
- fp16 dynamic loss scale            → runtime/precision.py inside the jitted step
- bf16 + fp32 master                 → runtime/zero.py with_master_weights
- gradient clipping                  → optax clip_by_global_norm in the chain
  (reference runtime/utils.py clip_grad_norm_)
- checkpoint save/load              → orbax (reference engine.py:2710-3554)
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.config import (DeepSpeedTPUConfig, parse_config,
                                  warn_inert_config)
from deepspeed_tpu.monitor import MonitorMaster
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.parallel import partition
from deepspeed_tpu.parallel.metadata import annotate_abstract, unbox
from deepspeed_tpu.runtime import faults, lr_schedules, optimizers, zero
from deepspeed_tpu.runtime.precision import (LossScaleState, grads_finite,
                                             init_loss_scale, update_loss_scale)
from deepspeed_tpu.telemetry.startup import ACCOUNT as _SETUP, init_span
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (DATA_TIMER, TRAIN_BATCH_TIMER,
                                       SynchronizedWallClockTimer,
                                       ThroughputTimer)


class TrainState(NamedTuple):
    """Functional train state — the analog of the reference engine's mutable
    (module, optimizer, loss_scaler) aggregate."""

    step: jnp.ndarray
    params: Any
    opt_state: Any
    loss_scale: LossScaleState
    rng: jax.Array


class StepMetrics(NamedTuple):
    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    loss_scale: jnp.ndarray
    skipped_steps: jnp.ndarray


# grad_norm reported for an overflow-skipped step: a FINITE sentinel instead
# of the raw NaN/Inf, on both the device and the offload path — downstream
# consumers (monitors, schedulers keying on get_global_grad_norm) must never
# see a non-finite norm for a step whose update was skipped; the per-group
# attribution of the overflow lives in the health stats.  Matches the
# reference's overflow contract (skipped_steps counts it, the norm stays
# usable).
OVERFLOW_GNORM = -1.0


def _cast_params(params, dtype):
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params)


def _moe_stats_to_python(moe_host):
    """Host-side MoE stats → plain python: the [E] expert-tokens vector
    becomes a list, scalars become floats (flight-recorder/JSON-safe)."""
    return {k: (v.tolist() if getattr(v, "ndim", 0) else float(v))
            for k, v in moe_host.items()}


def _reduce_moe_micros(moes):
    """Reduce [gas]-stacked per-micro MoE stats (moe/layer.py sows,
    aggregated per micro by ``aggregate_moe_stats``) to one step-level
    dict: token counts sum over microbatches, aux/entropy average."""
    if not moes:
        return {}
    return {k: (moes[k].mean(axis=0) if k in ("aux_loss", "gate_entropy")
                else moes[k].sum(axis=0)) for k in moes}


def _poison_first_float_leaf(params):
    """Engine-site payload of the ``nan`` fault kind at ``step.grads``:
    multiply the first floating-point parameter leaf by NaN (shape, dtype
    and sharding preserved).  The poisoned leaf drives this step's loss and
    gradients non-finite, and — whether the update is skipped by the
    overflow machinery or applied — the corruption PERSISTS in the live
    state, exactly the NaN-burst failure the guardian's rollback must heal
    (a replayed step without the fault cannot; only restoring a
    health-verified checkpoint can)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    for i, leaf in enumerate(leaves):
        if (hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            leaves[i] = leaf * jnp.array(jnp.nan, leaf.dtype)
            break
    return jax.tree_util.tree_unflatten(treedef, leaves)


class DeepSpeedTPUEngine:
    """Config-driven training engine over a device mesh.

    model contract: a flax linen Module whose ``__call__(batch)`` (after ``init``)
    returns a scalar loss, or a pair ``(init_fn, apply_fn)`` of pure functions with
    ``init_fn(rng, batch) -> params`` and ``apply_fn(params, batch, rng) -> loss``.
    """

    def __init__(self, model, config: DeepSpeedTPUConfig, example_batch,
                 mesh: Optional[Mesh] = None,
                 lr_scheduler: Optional[Callable[[int], float]] = None,
                 client_optimizer: Optional[optax.GradientTransformation] = None):
        self.config = config
        # overlap regime FIRST — libtpu parses its arguments once at backend
        # init, so the latency-hiding/async-collective flags must be
        # exported before any jax backend touch (runtime/overlap.py warns
        # when the backend beat us to it)
        from deepspeed_tpu.runtime.overlap import apply_overlap_flags
        apply_overlap_flags(config.overlap)
        comm.init_distributed()
        if config.resilience.compilation_cache_dir:
            # persistent XLA compilation cache: a replacement host rebuilds
            # its step programs from disk instead of recompiling for
            # minutes (runtime/resilience.py; jax binds the directory at
            # first COMPILE, so after distributed init is early enough)
            from deepspeed_tpu.runtime.resilience import \
                enable_compilation_cache
            enable_compilation_cache(config.resilience.compilation_cache_dir)
        # ---- observability (reference: MonitorMaster engine.py:1000) ----
        # unified step telemetry (telemetry/): span tracer + recompile
        # watchdog + counter/gauge registries + snapshot exporter.  Before
        # the rest, so that the construction is itself a span
        # (ds.engine_init; telemetry/startup.py)
        self.monitor = MonitorMaster(config)
        from deepspeed_tpu.telemetry import StepTelemetry
        self.telemetry = StepTelemetry(config, monitor=self.monitor)
        with init_span(self.telemetry.tracer, "engine_init", "train"):
            self._build(model, config, example_batch, mesh, lr_scheduler,
                        client_optimizer)

    def _build(self, model, config, example_batch, mesh, lr_scheduler,
               client_optimizer):
        comm.comms_logger.configure(config.comms_logger.enabled,
                                    config.comms_logger.verbose)
        warn_inert_config(config)

        # ---- mesh (replaces reference groups.initialize / mpu) ----
        if mesh is None:
            m = config.mesh
            dp, fsdp = m.dp, m.fsdp
            mics = config.zero_optimization.mics_shard_size
            if mics and mics > 0:
                # MiCS (reference runtime/zero/mics.py MiCS_Init:88): params
                # shard within SUBGROUPS of mics_shard_size chips and
                # replicate across groups — exactly fsdp=shard_size ×
                # dp=world/shard_size on this mesh, so the param all-gather
                # stays inside the (ICI-adjacent) subgroup and only the grad
                # reduce crosses groups (hierarchical_allgather analog)
                if config.zero_optimization.stage < 3:
                    raise ValueError("mics_shard_size requires zero stage 3")
                fsdp, dp = mics, -1
            elif not isinstance(fsdp, int):  # "auto": ZeRO shards over the
                # whole DP world (reference semantics), so data parallelism
                # rides the fsdp axis when any ZeRO stage is on
                if config.zero_optimization.stage >= 1:
                    fsdp = -1
                    dp = 1 if dp == -1 else dp
                else:
                    fsdp = 1
            spec = mesh_lib.MeshSpec(pp=m.pp, dp=dp, fsdp=fsdp, ep=m.ep,
                                     sp=m.sp, tp=m.tp)
            mesh = mesh_lib.build_mesh(spec)
        self.mesh = mesh
        self.dp_world_size = mesh.shape["dp"] * mesh.shape["fsdp"]
        if config.elasticity.enabled:
            # the SOLVER controls the batch triad (reference
            # runtime/config.py:733: elastic config overrides / rejects
            # user-set batch params)
            self._apply_elasticity_config(config)
        config.resolve_batch_size(self.dp_world_size)

        self.zero_stage = config.zero_optimization.stage
        self.compute_dtype = config.compute_dtype
        # ZeRO-Offload: optimizer state + fp32 masters live on the HOST
        # (runtime/offload.py); the device holds only compute-dtype params and
        # runs a grads-only program each step
        off = config.zero_optimization.offload_optimizer
        self.offloading = off.device != "none"
        if config.zero_optimization.offload_param.device != "none":
            raise ValueError(
                "offload_param is served by the Infinity engine — build via "
                "deepspeed_tpu.initialize() (which dispatches to "
                "runtime.infinity.InfinityEngine), not DeepSpeedTPUEngine "
                "directly")
        # master-weight mode iff low-precision params (reference: BF16_Optimizer /
        # fp16 fused optimizer wrap client optimizer the same way); under
        # offload the fp32 master lives host-side instead of in the opt state
        self.use_master_weights = ((config.bf16.enabled or config.fp16.enabled)
                                   and not self.offloading)
        self.gas = int(config.gradient_accumulation_steps)

        # ---- qgZ: quantized gradient reduce (reference ZeRO++ qgZ,
        # runtime/zero/stage3.py:1497 quantized gradient reduction; config
        # runtime/zero/config.py zero_quantized_gradients).  Grads are
        # computed per-device inside a collective-free shard_map over the
        # data axis, stacked, and reduced by the quantized pipeline
        # (runtime/zero.pipeline_grad_reduce: int-wire all-to-all
        # reduce-scatter / EQuARX-style quantized allreduce) instead of the
        # partitioner's implicit fp32 reduce.  ``zeropp.quantized_allreduce``
        # opens the same path at stage 0/1, where the dp grad exchange is a
        # plain allreduce (no scatter target needed — arXiv:2506.17615).
        self._qgz_axis = None
        zpp = config.zero_optimization.zeropp
        if (config.zero_optimization.zero_quantized_gradients
                or zpp.quantized_allreduce):
            nested_axes = {a: mesh.shape[a] for a in ("sp", "ep", "pp")
                           if mesh.shape[a] > 1}
            data_axes = [a for a in ("dp", "fsdp") if mesh.shape[a] > 1]
            if (self.zero_stage < 2
                    and config.zero_optimization.zero_quantized_gradients
                    and not zpp.quantized_allreduce):
                raise ValueError(
                    "zero_quantized_gradients requires zero stage >= 2 "
                    "(gradients must be partitioned for the quantized "
                    "reduce-scatter to have a scatter target); at stage "
                    "0/1 set zero_optimization.zeropp.quantized_allreduce "
                    "for the block-quantized allreduce instead")
            if nested_axes:
                # sp/ep/pp express their collectives with their OWN
                # shard_map (ring/Ulysses/MoE route/pipeline) — shardy
                # cannot nest a manual_computation inside the manual-dp
                # grad region ('operates on axis already bound by a
                # parent'), so these compose only via the auto path
                raise NotImplementedError(
                    f"zero_quantized_gradients with mesh axes {nested_axes}"
                    f": sequence/expert/pipeline parallelism run their own "
                    f"shard_map collectives, which cannot nest inside the "
                    f"manual data-axis gradient shard_map; tp composes "
                    f"(pure GSPMD), sp/ep/pp do not yet")
            # qgZ quantizes the CROSS-REPLICA dp reduce; everything else
            # (fsdp param-gather-fused reduce-scatter, tp activation
            # collectives) stays under GSPMD inside the partial-manual
            # body.  At stage >= 3 (and stage 2 with dp x fsdp) the fsdp
            # reduce rides intra-group ICI — the reference qgZ's
            # hierarchical design targets exactly the cross-group hop.
            if mesh.shape["dp"] > 1:
                self._qgz_axis = "dp"
            elif mesh.shape["fsdp"] > 1 and self.zero_stage < 3:
                self._qgz_axis = "fsdp"
            elif not data_axes:
                logger.warning(
                    "zero_quantized_gradients set but the data-parallel "
                    "world is 1 — there is no gradient reduce to quantize; "
                    "flag is inert on this mesh")
            elif config.zero_optimization.zero_quantized_gradients:
                # stage 3 with dp=1: no cross-replica reduce — the ONLY
                # gradient exchange is the fsdp reduce-scatter riding the
                # param-gather transpose, which the composable pipeline
                # quantizes (runtime/zero._qwire_exchange bwd); no manual
                # data-axis region needed
                log_dist(
                    "qgZ at stage 3 with dp=1: gradient quantization rides "
                    "the chunked gather's transpose (quantized "
                    "reduce-scatter over 'fsdp')", ranks=[0])
            else:
                logger.warning(
                    "zeropp.quantized_allreduce at stage 3 with dp=1: the "
                    "only gradient reduce is the fsdp reduce-scatter fused "
                    "with the param gather — set zero_quantized_gradients "
                    "to quantize it; the allreduce knob is inert here")
            if self._qgz_axis:
                auto = [a for a in ("fsdp", "tp")
                        if mesh.shape[a] > 1 and a != self._qgz_axis]
                if len(auto) > 1:
                    # two auto axes under one manual axis trips a fatal
                    # CHECK in XLA's SPMD partitioner
                    # (spmd_partitioner_util.cc replica-group mismatch) —
                    # refuse rather than crash the process; one auto axis
                    # (dp x fsdp, dp x tp) composes fine
                    raise NotImplementedError(
                        f"zero_quantized_gradients over '{self._qgz_axis}' "
                        f"with BOTH {auto[0]} > 1 and {auto[1]} > 1: XLA's "
                        f"partitioner cannot yet mix two auto axes under "
                        f"the manual gradient region (fatal partitioner "
                        f"check); drop one axis or disable qgZ")
                log_dist(f"qgZ: int8 gradient reduce over mesh axis "
                         f"'{self._qgz_axis}' "
                         f"({mesh.shape[self._qgz_axis]} ways"
                         + (f", {'/'.join(auto)} under GSPMD" if auto
                            else "") + ")", ranks=[0])

        # low-precision mode casts PARAMS, but flax models own their COMPUTE
        # dtype — fp32 activations silently demote every matmul off the bf16
        # MXU path (measured ~12 MFU points on GPT-2-small).  Warn when the
        # model's config disagrees with the precision block.
        mcfg = getattr(model, "cfg", None)
        if (mcfg is not None
                and getattr(mcfg, "dtype", None) == jnp.float32):
            want = ("bf16" if config.bf16.enabled
                    else "fp16" if config.fp16.enabled else None)
            if want:
                log_dist(
                    f"WARNING: {want} is enabled but the model computes in "
                    f"float32 (model cfg.dtype) — matmuls will not hit the "
                    f"low-precision MXU path.  Set dtype=jnp.{'bfloat16' if want == 'bf16' else 'float16'} "
                    f"in the model config for full throughput.", ranks=[0])

        # ---- model functions ----
        # bind the engine's mesh into mesh-aware models (MoE ep route,
        # Ulysses).  The model stays BOUND under qgZ too (round-4 verdict:
        # unbinding left the embedding path to GSPMD's layout whims inside
        # the manual grad shard_map): constraints naming auto axes
        # (fsdp/tp) apply inside the partial-manual body, and constraints
        # naming the manual data axis are dropped by the partitioner.
        if (hasattr(model, "clone") and hasattr(model, "mesh")
                and model.mesh is None):
            model = model.clone(mesh=self.mesh)
        # random-LTD: push the configured layer ids into the model config so
        # ds_config is the single source of truth (reference: the data_routing
        # block rewires layers at initialize() time)
        rl_cfg = config.data_efficiency.data_routing.random_ltd
        if (config.data_efficiency.enabled and rl_cfg.enabled
                and hasattr(model, "clone") and hasattr(model, "cfg")
                and hasattr(model.cfg, "random_ltd_layer_ids")):
            cfg_ids = tuple(rl_cfg.random_ltd_layer_ids)
            model_ids = tuple(model.cfg.random_ltd_layer_ids)
            if not model_ids:
                import dataclasses as _dc
                model = model.clone(cfg=_dc.replace(
                    model.cfg, random_ltd_layer_ids=cfg_ids))
            elif model_ids != cfg_ids:
                raise ValueError(
                    f"random_ltd_layer_ids mismatch: model cfg has "
                    f"{model_ids}, ds_config says {cfg_ids} — set them in "
                    f"ONE place")
        # activation quantization (reference compression QuantAct): the model
        # config carries the bits so the fake-quant happens inside the layers
        from deepspeed_tpu.compression.pruning import \
            parse_activation_quant_config
        act_bits = parse_activation_quant_config(
            config.compression_training or {})
        if act_bits:
            if not (hasattr(model, "clone") and hasattr(model, "cfg")
                    and hasattr(model.cfg, "act_quant_bits")):
                raise ValueError(
                    "compression_training.activation_quantization needs a "
                    "model whose config takes act_quant_bits (models/gpt.py "
                    "GPT); this model would silently ignore it")
            import dataclasses as _dc
            model = model.clone(cfg=_dc.replace(model.cfg,
                                                act_quant_bits=act_bits))
        # overlap.collective_matmul: route the model's TP row-parallel
        # matmuls through the explicit ppermute-ring fusions
        # (ops/collective_matmul.py) — ds_config is the single source of
        # truth, like the random-LTD / activation-quant knobs above
        if config.overlap.enabled and config.overlap.collective_matmul:
            if (hasattr(model, "clone") and hasattr(model, "cfg")
                    and hasattr(model.cfg, "tp_collective_matmul")):
                if not getattr(model.cfg, "tp_collective_matmul"):
                    import dataclasses as _dc
                    model = model.clone(cfg=_dc.replace(
                        model.cfg, tp_collective_matmul=True))
            else:
                logger.warning(
                    "overlap.collective_matmul set but the model config has "
                    "no tp_collective_matmul knob (models/gpt.py GPT) — the "
                    "ring collective-matmul fusions are inert for this model")
        # moe: push the ep a2a wire/overlap knobs into the model config so
        # ds_config is the single source of truth (moe/comm.py fast path),
        # like the random-LTD / activation-quant knobs above
        moe_cfg = config.moe
        if moe_cfg.wire_bits or moe_cfg.num_chunks > 1 or moe_cfg.hierarchical:
            if (hasattr(model, "clone") and hasattr(model, "cfg")
                    and hasattr(model.cfg, "moe_wire_bits")):
                import dataclasses as _dc
                model = model.clone(cfg=_dc.replace(
                    model.cfg, moe_wire_bits=moe_cfg.wire_bits,
                    moe_wire_block=moe_cfg.block_size,
                    moe_hierarchical=moe_cfg.hierarchical,
                    moe_num_chunks=moe_cfg.num_chunks))
            else:
                logger.warning(
                    "moe.* wire/overlap knobs set but the model config has "
                    "no moe_wire_bits knob (models/gpt.py GPT) — the MoE a2a "
                    "fast path is inert for this model")
        # progressive layer drop (reference engine.progressive_layer_drop
        # built at initialize() when the config block is enabled)
        pld_cfg = config.progressive_layer_drop
        if pld_cfg.enabled:
            if getattr(model, "is_pipeline", False) or isinstance(model,
                                                                  tuple):
                raise ValueError(
                    "progressive_layer_drop requires a flax LM that reads "
                    "batch['pld_theta'] (models/gpt.py GPT); pipeline and "
                    "duck-typed models would silently ignore it")
            from deepspeed_tpu.runtime.progressive_layer_drop import \
                ProgressiveLayerDrop
            self.pld = ProgressiveLayerDrop(theta=pld_cfg.theta,
                                            gamma=pld_cfg.gamma)
        else:
            self.pld = None
        # pipeline models consume all gas microbatches in one pipelined scan
        # (reference: PipelineEngine.train_batch owns the microbatch loop)
        self.gas_in_model = bool(getattr(model, "is_pipeline", False))
        self._apply_fn_stats = None     # flax models only (moe_stats sow)
        if isinstance(model, tuple):
            self._init_fn, self._apply_fn = model
            # rng=None signals "deterministic" by convention (PipeGPT does
            # the same); an apply_fn that ignores rng is unaffected
            self._apply_fn_det = (
                lambda params, batch, rng: self._apply_fn(params, batch,
                                                          None))
        else:
            import flax.linen as fnn
            self._init_fn = lambda rng, batch: model.init(rng, batch)
            if isinstance(model, fnn.Module):
                self._apply_fn = lambda params, batch, rng: model.apply(
                    params, batch, rngs={"dropout": rng})
                # expert-telemetry leg: same forward with the moe_stats sow
                # collection mutable — returns (out, {"moe_stats": ...})
                self._apply_fn_stats = \
                    lambda params, batch, rng: model.apply(
                        params, batch, rngs={"dropout": rng},
                        mutable=["moe_stats"])
                # deterministic leg for eval_batch (reference module.eval()):
                # only if the module's __call__ actually takes the optional
                # `deterministic` flag — the base contract (__call__(batch))
                # doesn't require it
                import inspect
                try:
                    takes_det = "deterministic" in inspect.signature(
                        type(model).__call__).parameters
                except (TypeError, ValueError):
                    takes_det = False
                if takes_det:
                    self._apply_fn_det = \
                        lambda params, batch, rng: model.apply(
                            params, batch, deterministic=True,
                            rngs={"dropout": rng})
                else:
                    self._apply_fn_det = self._apply_fn
            else:  # duck-typed (init/apply) object, e.g. PipeGPT
                self._apply_fn = lambda params, batch, rng: model.apply(
                    params, batch, rng)
                # PipeGPT contract: rng=None disables dropout
                self._apply_fn_det = lambda params, batch, rng: model.apply(
                    params, batch, None)
        self.model = model

        # ---- optimizer + schedule (reference engine._configure_optimizer
        #      engine.py:1219 + _configure_lr_scheduler :905) ----
        self.lr_schedule = lr_scheduler
        if self.lr_schedule is None and config.scheduler is not None:
            self.lr_schedule = lr_schedules.build_schedule(
                config.scheduler.type, config.scheduler.params)
        if self.offloading:
            from deepspeed_tpu.runtime.offload import OffloadAdam
            if client_optimizer is not None:
                raise ValueError(
                    "ZeRO-Offload builds its own host Adam (the reference "
                    "likewise swaps client optimizers for DeepSpeedCPUAdam); "
                    "drop the client optimizer or offload")
            self.offload_opt = OffloadAdam(
                config.optimizer.type, config.optimizer.params,
                device=off.device, nvme_path=off.nvme_path,
                aio_threads=max(1, int(config.aio.thread_count)))
            # API contract: initialize() returns the swapped-in host optimizer
            # (reference returns DeepSpeedCPUAdam on the offload path)
            self.optimizer = self.offload_opt
            self._opt_params = dict(config.optimizer.params)
        else:
            self.offload_opt = None
        # guardian clamp-down state: effective LR = configured LR x
        # _lr_scale (engine.clamp_lr); kept OUTSIDE the optimizer so the
        # offload host step reads it sync-free and the device paths rebuild
        # their chain from it on a clamp
        self._lr_scale = 1.0
        self._client_optimizer = client_optimizer
        if not self.offloading:
            with init_span(self.telemetry.tracer, "init_optimizer", "train"):
                self.optimizer, self._opt_params = self._build_tx(
                    client_optimizer)
        # overlapped host step (offload_optimizer.overlap_step): the CPU Adam
        # of step N runs on a worker thread while the device computes step
        # N+1's grads against one-update-stale params (reference ZeRO-Offload
        # delayed parameter update); runtime/offload.py HostStepWorker
        self._overlap_step = bool(self.offloading and off.overlap_step)
        self._host_worker = None
        if self._overlap_step:
            from deepspeed_tpu.runtime.offload import HostStepWorker
            self._host_worker = HostStepWorker()

        # normalize the example batch's leading dim to the global microbatch so
        # init tracing and the jitted step see shardable shapes; only leaves
        # sharing the example's batch dim are tiled (non-batch leaves pass through)
        micro_global = (int(config.train_micro_batch_size_per_gpu)
                        * self.dp_world_size)
        leaves = jax.tree_util.tree_leaves(example_batch)
        example_bs = np.asarray(leaves[0]).shape[0] if leaves else 0

        def _tile(x):
            x = np.asarray(x)
            if (x.ndim == 0 or x.shape[0] != example_bs
                    or x.shape[0] == micro_global):
                return x
            reps = -(-micro_global // x.shape[0])
            return np.tile(x, (reps,) + (1,) * (x.ndim - 1))[:micro_global]
        example_batch = jax.tree_util.tree_map(_tile, example_batch)

        # ---- abstract shapes + shardings (zero.Init analog: params are created
        #      already sharded; reference partition_parameters.py:808) ----
        rng = jax.random.PRNGKey(config.seed)
        boxed = jax.eval_shape(self._init_fn, rng, example_batch)
        annotated = annotate_abstract(boxed)

        # hpZ (reference zero_hpz_partition_size,
        # partition_parameters.py:1653): PARAMS shard only within the
        # fsdp subgroup (fwd/bwd gathers ride intra-group ICI) while
        # optimizer state + grads shard over the FULL (fsdp, dp) world
        hpz = config.zero_optimization.zero_hpz_partition_size
        self._state_fsdp_axes = ("fsdp",)
        if hpz and hpz > 1:
            if self.zero_stage < 3:
                raise ValueError("zero_hpz_partition_size requires stage 3")
            if mesh.shape["fsdp"] != hpz:
                raise ValueError(
                    f"zero_hpz_partition_size={hpz} must equal the fsdp mesh "
                    f"axis ({mesh.shape['fsdp']}); set mesh "
                    f"{{'fsdp': {hpz}, 'dp': -1}} so dp carries the "
                    f"cross-group replicas")
            self._state_fsdp_axes = ("fsdp", "dp")
        self.param_shardings = partition.param_shardings(
            annotated, mesh, self.zero_stage)
        abstract_params = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), annotated)
        if self.use_master_weights:
            abstract_params = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, self.compute_dtype)
                if jnp.issubdtype(l.dtype, jnp.floating) else l, abstract_params)
        if self.offloading:
            # optimizer state lives host-side; nothing on device
            abstract_opt = ()
            self.opt_shardings = ()
        else:
            abstract_opt = jax.eval_shape(self.optimizer.init, abstract_params)
            self.opt_shardings = partition.opt_state_shardings(
                abstract_opt, annotated, mesh, self.zero_stage,
                fsdp_axes=self._state_fsdp_axes)

        self.state_shardings = TrainState(
            step=NamedSharding(mesh, P()),
            params=self.param_shardings,
            opt_state=self.opt_shardings,
            loss_scale=jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), init_loss_scale(config.fp16)),
            rng=NamedSharding(mesh, P()),
        )
        # grad accumulation buffers: sharded like optimizer state at stage ≥ 2
        # (ZeRO-2 gradient partitioning, reference stage_1_and_2.py:1361)
        self.grad_shardings = partition.state_leaf_shardings(
            annotated, mesh, self.zero_stage if self.zero_stage >= 2 else 0,
            fsdp_axes=self._state_fsdp_axes)

        # staged QAT groups (compression/basic.py); empty = off
        from deepspeed_tpu.compression import parse_compression_config
        from deepspeed_tpu.compression.pruning import parse_pruning_config
        self._compression_specs = parse_compression_config(
            config.compression_training)
        if self._compression_specs:
            log_dist(f"compression: {len(self._compression_specs)} weight-"
                     f"quantization group(s) active", ranks=[0])
        # pruning family (compression/pruning.py; reference basic_layer.py
        # sparse/row/head pruning) — masks applied in-loss past each group's
        # schedule_offset
        nh = int(getattr(getattr(self.model, "cfg", None), "num_heads", 0)
                 or 0)
        self._pruning_specs = parse_pruning_config(
            config.compression_training or {}, num_heads=nh)
        if self._pruning_specs:
            log_dist(f"compression: {len(self._pruning_specs)} pruning "
                     f"group(s) active "
                     f"({sorted(set(s.kind for s in self._pruning_specs))})",
                     ranks=[0])

        # ZeRO++ qwZ: per-leaf fsdp-sharded dims (None = flag off / inert
        # mesh).  The pipeline recomputes its own dims (partition.
        # sharded_dim inside pipeline_param_gather); this tree survives as
        # the qwZ-active gate for the wire plan below and as the
        # introspection surface (tests/serving probes read it)
        self._qwz_dims = None
        if (config.zero_optimization.zero_quantized_weights
                and self.zero_stage >= 3 and mesh.shape["fsdp"] > 1):
            # -1 sentinel = leaf not fsdp-sharded; dims co-sharded with
            # another axis (tuple specs) keep the partitioner's implicit
            # gather (parallel/partition.py sharded_dim)
            self._qwz_dims = partition.fsdp_shard_dims(self.param_shardings)
        elif (config.zero_optimization.zero_quantized_weights
              and self.zero_stage >= 3):
            logger.warning("zero_quantized_weights set but the fsdp mesh axis "
                           "is 1 — there is no weight all-gather to quantize; "
                           "flag is inert on this mesh")

        # ---- composable collective pipeline (runtime/zero.py, ISSUE 14):
        # chunking (overlap.num_chunks), block quantization (qwZ fwd / qgZ
        # bwd wire bits from the zeropp block), and hierarchy
        # (zeropp.hierarchical per-axis wire policy) compose on ONE
        # stage-3 gather/reduce path.  The former either/or conflict gates
        # (chunks × qwZ, chunks × qgZ) are gone: quantization runs INSIDE
        # the chunk bodies, and the qgZ data-axis reduce consumes stacked
        # per-replica grads in its own full-manual region, so nothing
        # nests inside the manual grad shard_map anymore.
        ov = config.overlap
        # qgZ proper (zero_quantized_gradients) quantizes BOTH gradient
        # exchanges: the gather-transpose reduce-scatter (grad_bits in the
        # wire plan) and the data-axis reduce.  zeropp.quantized_allreduce
        # is scoped to the DATA-AXIS reduce only (its stage-0/1 reason for
        # existing) — it must never flip the fsdp reduce-scatter to lossy
        # wire on a config that didn't ask for qgZ, so it feeds
        # _dp_reduce_plan below but not this plan's grad_bits.
        qgz_on = bool(config.zero_optimization.zero_quantized_gradients)
        self._wire_plan = zero.WirePlan(
            num_chunks=max(1, int(ov.num_chunks) if ov.enabled else 1),
            weight_bits=(int(zpp.weight_bits)
                         if self._qwz_dims is not None else 0),
            grad_bits=int(zpp.grad_bits) if qgz_on else 0,
            block_size=int(zpp.block_size),
            hierarchical=bool(zpp.hierarchical),
        )
        self._dp_reduce_plan = self._wire_plan._replace(
            grad_bits=(int(zpp.grad_bits)
                       if (qgz_on or zpp.quantized_allreduce) else 0))
        # the explicit gather engages when ANY pipeline layer asks for it;
        # otherwise the partitioner's implicit per-consumer gathers stand
        # (the seed behavior)
        self._gather_chunks = 0
        self._pipeline_active = False
        want_pipeline = (self._wire_plan.num_chunks > 1
                         or self._wire_plan.weight_bits > 0
                         or (qgz_on and self.zero_stage >= 3))
        if want_pipeline:
            if self.zero_stage < 3 or mesh.shape["fsdp"] <= 1:
                if ov.enabled and ov.num_chunks > 1:
                    logger.warning(
                        "overlap.num_chunks=%d set but there is no stage-3 "
                        "param all-gather to chunk (stage %d, fsdp=%d) — "
                        "chunking is inert on this config; the XLA "
                        "scheduler flags still apply", ov.num_chunks,
                        self.zero_stage, mesh.shape["fsdp"])
            else:
                self._pipeline_active = True
                self._gather_chunks = self._wire_plan.num_chunks
                wb, gb = zero.resolve_wire_bits(self._wire_plan, mesh,
                                                "fsdp")
                log_dist(
                    f"pipeline: stage-3 param gather in "
                    f"{self._wire_plan.num_chunks} per-layer-group "
                    f"chunk(s) over 'fsdp' ({mesh.shape['fsdp']} ways), "
                    f"wire={'q%d' % wb if wb else 'full'} gather / "
                    f"{'q%d' % gb if gb else 'full'} reduce-scatter"
                    + (" [hierarchical]"
                       if self._wire_plan.hierarchical else ""),
                    ranks=[0])

        # numerics health monitor (telemetry.health): per-group stats are
        # traced INTO the step programs, so the flags must exist before
        # _build_step_functions
        self._health_enabled = bool(config.telemetry.health.enabled)
        self._health_depth = int(config.telemetry.health.group_depth)

        # expert-load telemetry (moe/layer.py _sow_stats): traced INTO the
        # step as one extra output (the health pattern — no steady-state
        # recompile); flax MoE models only, and not under the qgZ
        # partial-manual wrapper, whose shard_map can't carry the extra
        # mutable-collection output
        self._moe_stats_on = bool(
            config.moe.expert_telemetry
            and self._apply_fn_stats is not None
            and getattr(getattr(model, "cfg", None), "num_experts", 0) > 0
            and self._qgz_axis is None)
        self._last_moe_host = None

        # ---- build + jit the step functions ----
        self._jit_init = jax.jit(
            self._make_init(), out_shardings=self._as_shardings_tuple())
        self._build_step_functions()

        # out_shardings are explicit NamedShardings; the mesh context is
        # for user init_fns that resolve bare PartitionSpec constraints
        with init_span(self.telemetry.tracer, "init_state", "train"), \
                self.mesh:
            self.state = self._jit_init(rng, example_batch)
        if self.offloading:
            # stream the initial params to host: fp32 masters + moments are
            # built there (zero.Init-at-construction analog for the host tier)
            with init_span(self.telemetry.tracer, "init_optimizer", "train"):
                self.offload_opt.initialize(
                    jax.device_get(self.state.params))

        # forward/backward/step compatibility buffers
        self._accum_grads = None
        self._micro_losses = []
        self._micro_steps = 0
        self.global_steps = 0
        self._last_metrics: Optional[StepMetrics] = None
        # host mirror of the latest StepMetrics (+ health stats), filled by
        # the ONE sanctioned device fetch in _fetch_metrics —
        # get_global_grad_norm()/skipped_steps read this instead of syncing
        # per scalar
        self._last_metrics_host: Optional[StepMetrics] = None
        self._last_health = None          # device pytree (or host dict)
        self._last_health_host: dict = {}
        self._host_metrics_step = -1
        self._step_times = []

        # ---- observability (reference: EngineTimers :145, flops profiler
        #      hook :1797; the monitor and the telemetry: __init__) ----
        self.timers = SynchronizedWallClockTimer()
        # rate logging rides the engine's print cadence (reference
        # ThroughputTimer prints its own line at steps_per_output)
        self.tput_timer = ThroughputTimer(
            steps_per_output=int(config.steps_per_print or 0),
            warmup_steps=1)
        self.wall_clock_breakdown = bool(config.wall_clock_breakdown)

        # ---- data-efficiency pipeline (reference runtime/data_pipeline/) ----
        self.curriculum_scheduler = None
        self.random_ltd_scheduler = None
        de = config.data_efficiency
        if de.enabled and de.data_sampling.curriculum_learning.enabled:
            from deepspeed_tpu.data_pipeline import CurriculumScheduler
            cl = de.data_sampling.curriculum_learning
            if cl.curriculum_type != "seqlen":
                raise NotImplementedError(
                    "engine-integrated curriculum supports the seqlen metric; "
                    "other metrics go through data_pipeline."
                    "CurriculumDataSampler on the dataloader side")
            self.curriculum_scheduler = CurriculumScheduler(
                cl.model_dump(exclude={"enabled"}))
        if de.enabled and de.data_routing.random_ltd.enabled:
            from deepspeed_tpu.data_pipeline import RandomLTDScheduler
            rl = de.data_routing.random_ltd
            if self.gas_in_model:
                raise NotImplementedError(
                    "random-LTD inside the pipeline engine is unsupported")
            if not rl.random_ltd_layer_ids:
                raise ValueError("random_ltd.random_ltd_layer_ids is empty")
            if self.mesh.shape["sp"] > 1:
                raise NotImplementedError("random-LTD with Ulysses sequence "
                                          "parallelism is unsupported")
            self.random_ltd_scheduler = RandomLTDScheduler(rl.model_dump())
            self._ltd_layer_ids = tuple(rl.random_ltd_layer_ids)
            self._de_seed = de.seed
        self._flops_profiled = False
        self._last_batch = None
        if config.dump_state:
            log_dist("config state:\n" + config.model_dump_json(indent=2),
                     ranks=[0])

        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(annotated))
        self.num_parameters = n_params
        log_dist(
            f"engine ready: params={n_params/1e6:.1f}M zero_stage={self.zero_stage} "
            f"mesh={dict(self.mesh.shape)} dtype={self.compute_dtype.__name__} "
            f"micro_bs/gpu={config.train_micro_batch_size_per_gpu} gas={self.gas} "
            f"global_bs={config.train_batch_size}", ranks=[0])

    # ------------------------------------------------------------------ builders

    def _apply_elasticity_config(self, config):
        """ds_config "elasticity" block (reference runtime/config.py:733):
        solve the batch geometry for the CURRENT world size and take control
        of the batch triad; explicitly-set batch params are an error unless
        ignore_non_elastic_batch_info."""
        from deepspeed_tpu.constants import AUTO
        from deepspeed_tpu.elasticity import (ElasticityConfig,
                                              compute_elastic_config)
        e = config.elasticity
        triad_set = any(v != AUTO for v in (
            config.train_batch_size, config.train_micro_batch_size_per_gpu,
            config.gradient_accumulation_steps))
        if triad_set and not e.ignore_non_elastic_batch_info:
            raise ValueError(
                "batch-related parameters found in the ds_config while "
                "elasticity is enabled — elastic training controls "
                "train_batch_size/train_micro_batch_size_per_gpu/"
                "gradient_accumulation_steps; remove them or set "
                "elasticity.ignore_non_elastic_batch_info (reference "
                "ElasticityConfigError semantics)")
        if float(e.version) not in (0.1, 0.2):
            raise ValueError(
                f"elasticity.version {e.version} is not supported "
                f"(reference semantics: 0.1 chip-granular, 0.2 "
                f"host-granular)")
        chips = self.dp_world_size * e.model_parallel_size
        ec = ElasticityConfig(
            enabled=True,
            max_train_batch_size=e.max_train_batch_size,
            micro_batch_sizes=list(e.micro_batch_sizes),
            min_chips=e.min_gpus, max_chips=e.max_gpus,
            # v0.1 solves at CHIP granularity (reference elasticity.py
            # version gate); v0.2 adds the host-granular constraint.  The
            # chip-granular unit is one model replica (mp chips).
            chips_per_host=(e.num_gpus_per_node
                            if float(e.version) >= 0.2
                            else e.model_parallel_size),
            model_parallel_size=e.model_parallel_size,
            prefer_larger_batch=e.prefer_larger_batch,
            version=e.version)
        batch, valid_dp, micro = compute_elastic_config(ec, chips)
        if micro is None:
            raise ValueError(
                f"elasticity: no micro batch in {e.micro_batch_sizes} "
                f"divides batch {batch} at dp world {self.dp_world_size}")
        gas = batch // (micro * self.dp_world_size)
        config.train_batch_size = batch
        config.train_micro_batch_size_per_gpu = micro
        config.gradient_accumulation_steps = gas
        log_dist(f"[Elasticity] batch={batch} micro={micro} gas={gas} "
                 f"valid dp counts={valid_dp}", ranks=[0])

    def _build_tx(self, client_optimizer):
        cfg = self.config
        if client_optimizer is not None:
            inner = client_optimizer
            opt_params = {}
        else:
            params = dict(cfg.optimizer.params)
            if self.lr_schedule is not None:
                params["lr"] = self.lr_schedule
            scale = self._lr_scale
            base = params.get("lr", 1e-3)
            if scale != 1.0:
                # guardian clamp-down: scale whatever LR the chain would
                # have seen (schedule or constant) — the clamp survives a
                # re-jit because _build_tx is the single LR authority
                if callable(base):
                    params["lr"] = lambda s, _b=base, _k=scale: _b(s) * _k
                else:
                    params["lr"] = float(base) * scale
            inner, opt_params = optimizers.build_optimizer(
                cfg.optimizer.type, params)
            if scale != 1.0 and not callable(base):
                # readers (get_lr) apply _lr_scale themselves: keep the
                # resolved params UNSCALED so the clamp is applied once
                opt_params = dict(opt_params, lr=float(base))
        chain = []
        # error-feedback compressed grads (runtime/compression.py) — BEFORE
        # clipping so the clip sees the signal the optimizer will consume.
        # Requested either via the gradient_compression block or by a 1-bit
        # optimizer NAME (reference fp16/onebit/); one stage either way, with
        # the block's dtype as the single knob
        wants_onebit = (client_optimizer is None
                        and optimizers.is_onebit(cfg.optimizer.type))
        if cfg.gradient_compression.enabled or wants_onebit:
            from deepspeed_tpu.runtime.compression import compress_gradients
            dtype = (cfg.gradient_compression.dtype
                     if cfg.gradient_compression.enabled else "int8")
            chain.append(compress_gradients(dtype))
        if cfg.gradient_clipping and cfg.gradient_clipping > 0:
            chain.append(optax.clip_by_global_norm(cfg.gradient_clipping))
        chain.append(inner)
        tx = optax.chain(*chain) if len(chain) > 1 else inner
        if self.use_master_weights:
            tx = zero.with_master_weights(tx)
        return tx, opt_params

    def _as_shardings_tuple(self):
        return self.state_shardings

    def _build_step_functions(self):
        """(Re)jit the train/grad step programs.  Called at init and again by
        configure_moq — the compiled programs close over the compression
        specs at trace time, so a schedule change needs a re-trace."""
        tel = getattr(self, "telemetry", None)   # absent on the init call
        if tel is not None and tel.enabled:
            # fresh jit objects have empty caches: the next dispatch IS a
            # compile, and the old compiled-HLO figures are stale
            tel.invalidate()
        self._jit_eval = None              # rebuilt lazily by eval_batch
        self._jit_grad = jax.jit(self._make_grad_fn())
        if self.offloading:
            # device runs grads-only; optimizer step is host-side
            self._grads_batch_fn = self._make_grads_batch()
            self._train_batch_fn = self._grads_batch_fn  # flops profiler trace
            self._jit_grads_batch = jax.jit(
                self._grads_batch_fn,
                out_shardings=(self.grad_shardings, None, None, None))
            self._jit_train_batch = None
            self._jit_apply = None
            self._jit_gnorm = jax.jit(optax.global_norm)
            # trio (forward/backward/step) offload path: the accumulated
            # grads never pass through _jit_grads_batch, so health stats
            # need their own jitted program
            self._jit_health = None
            if self._health_enabled:
                from deepspeed_tpu.telemetry.health import (
                    compute_group_health)
                self._jit_health = jax.jit(
                    lambda params, grads: compute_group_health(
                        params, grads, depth=self._health_depth))
        else:
            self._train_batch_fn = self._make_train_batch()
            self._jit_train_batch = jax.jit(
                self._train_batch_fn,
                donate_argnums=(0,),
                out_shardings=(self._as_shardings_tuple(), None, None))
            self._jit_apply = jax.jit(
                self._make_apply_fn(), donate_argnums=(0,),
                out_shardings=(self._as_shardings_tuple(), None, None))

    def configure_moq(self, sample_batch, layer_paths=None, *,
                      multiplier: int = 4, max_iter: int = 20,
                      tol: float = 1e-2) -> dict:
        """Mixture-of-Quantization (reference runtime/quantize.py +
        engine.py:334 _configure_eigenvalue): measure per-layer Hessian
        eigenvalues on ``sample_batch``, stretch each layer's staged-QDQ
        quantization period by 1 + floor(λ_norm·multiplier), and re-jit.

        Call once after ``initialize`` (and optionally again at curriculum
        boundaries).  Returns {layer_path: λ}.
        """
        if not self._compression_specs:
            raise ValueError(
                "configure_moq needs a compression_training block with "
                "weight_quantization groups (none configured)")
        from deepspeed_tpu.compression.moq import moq_adjusted_specs
        from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

        if layer_paths is None:
            # key listing needs only tree structure — no host transfer
            root = self.state.params
            prefix = ""
            for key in ("params", "backbone"):   # flax collection + GPT tree
                if isinstance(root, dict) and key in root:
                    prefix += key + "/"
                    root = root[key]
            layer_paths = sorted(
                f"{prefix}{k}" for k in root
                if isinstance(root[k], dict) and k.startswith("block_"))
            if not layer_paths:
                raise ValueError("no block_* layers found; pass layer_paths")

        rng = jax.random.PRNGKey(self.config.seed)

        def loss_fn(p):
            return self._apply_fn(p, sample_batch, rng)

        ev = Eigenvalue(max_iter=max_iter, tol=tol)
        with self.mesh:
            eigenvalues = ev.compute(loss_fn, self.state.params, layer_paths)
        self._compression_specs = moq_adjusted_specs(
            self._compression_specs, eigenvalues, multiplier=multiplier)
        self._build_step_functions()
        log_dist(f"MoQ: adjusted quantization periods for "
                 f"{len(eigenvalues)} layers "
                 f"(λ_norm={Eigenvalue.quantization_ratios(eigenvalues)})",
                 ranks=[0])
        return eigenvalues

    def _make_init(self):
        compute_dtype = self.compute_dtype
        cast_at_init = self.use_master_weights or self.offloading
        fp16_cfg = self.config.fp16
        init_fn = self._init_fn
        tx = None if self.offloading else self.optimizer

        def init(rng, batch):
            params = unbox(init_fn(rng, batch))
            if cast_at_init:
                params = _cast_params(params, compute_dtype)
            opt_state = tx.init(params) if tx is not None else ()
            return TrainState(
                step=jnp.int32(0),
                params=params,
                opt_state=opt_state,
                loss_scale=init_loss_scale(fp16_cfg),
                rng=jax.random.fold_in(rng, 1),
            )
        return init

    def _prepare_params(self, params, step):
        """Differentiable param-side half of the loss: compute-dtype cast,
        staged QDQ/pruning, then the composable pipeline gather
        (runtime/zero.pipeline_param_gather — chunked, optionally
        quantized, hierarchy-aware).  Split out of ``_loss`` so the qgZ
        path can run it (and, via ``jax.vjp``, its transposed chunked/
        quantized reduce-scatter) OUTSIDE the manual data-axis region —
        shard_maps cannot nest, and this split is what lets chunking ×
        quantization × the manual qgZ reduce compose."""
        with jax.named_scope("prepare_params"):
            if not self.use_master_weights:
                params = _cast_params(params, self.compute_dtype)
            if self._compression_specs and step is not None:
                # staged QAT (compression/basic.py; reference compression/
                # compress.py): matching weights see their scheduled quant grid
                from deepspeed_tpu.compression import scheduled_weight_qdq
                params = scheduled_weight_qdq(params, self._compression_specs,
                                              step)
            if self._pruning_specs and step is not None:
                from deepspeed_tpu.compression.pruning import scheduled_pruning
                params = scheduled_pruning(params, self._pruning_specs, step)
            if self._pipeline_active:
                # explicit per-layer-group gather replaces the partitioner's
                # per-consumer all-gathers; the autodiff transpose is the
                # chunked (and, under qgZ, quantized) grad reduce-scatter
                params = zero.pipeline_param_gather(
                    params, self.param_shardings, self.mesh, self._wire_plan)
            return params

    def _loss(self, params, batch, rng, scale, step=None,
              deterministic=False, prepared=False):
        if not prepared:
            params = self._prepare_params(params, step)
        if self.pld is not None and step is not None:
            # theta is a pure function of the step — computed in-graph, so
            # PLD adds zero host↔device traffic (reference updates it on the
            # host each step, progressive_layer_drop.py update_state)
            batch = dict(batch, pld_theta=self.pld.theta_at(step))
        apply = self._apply_fn_det if deterministic else self._apply_fn
        loss = apply(params, batch, rng)
        return (loss * scale).astype(jnp.float32), loss

    def _loss_stats(self, params, batch, rng, scale, step=None):
        """``_loss`` with the ``moe_stats`` sow collection mutable — aux is
        ``(loss, stats)`` where stats aggregates the per-layer expert-load
        sows (moe/layer.py ``_sow_stats``) into one small dict that rides
        the step program as an extra output (the health pattern)."""
        from deepspeed_tpu.moe.layer import aggregate_moe_stats
        params = self._prepare_params(params, step)
        if self.pld is not None and step is not None:
            batch = dict(batch, pld_theta=self.pld.theta_at(step))
        loss, var = self._apply_fn_stats(params, batch, rng)
        stats = aggregate_moe_stats(var.get("moe_stats", {}))
        return (loss * scale).astype(jnp.float32), (loss, stats)

    def _grads_one_micro(self, state: TrainState, batch, idx):
        """One microbatch's (grads, loss, moe_stats) — moe_stats is {} off
        the expert-telemetry path (empty pytree, free under scan/jit)."""
        rng = jax.random.fold_in(state.rng, state.step * self.gas + idx)
        # autodiff marks the backward half ``transpose(jvp(...))`` inside
        # this scope; that is how a trace reader splits forward from backward
        with jax.named_scope("fwd_bwd"):
            if self._qgz_axis is not None:
                grads, loss = self._qgz_grads(state, batch, rng)
                return grads, loss, {}
            if self._moe_stats_on:
                (_, (loss, moe)), grads = jax.value_and_grad(
                    self._loss_stats, has_aux=True)(
                        state.params, batch, rng, state.loss_scale.scale,
                        state.step)
            else:
                (_, loss), grads = jax.value_and_grad(
                    self._loss, has_aux=True)(
                        state.params, batch, rng, state.loss_scale.scale,
                        state.step)
                moe = {}
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
            grads = jax.lax.with_sharding_constraint(
                grads, self.grad_shardings)
        return grads, loss, moe

    def _qgz_grads(self, state: TrainState, batch, rng):
        """qgZ grad computation, restructured as three composable stages
        (reference runtime/zero/stage3.py:1497 quantized gradient
        reduction; EQuARX, arXiv:2506.17615, for the allreduce form):

        1. **param pipeline** (outside any manual region): ``jax.vjp`` over
           ``_prepare_params`` — cast/QDQ/pruning plus, at stage 3, the
           chunked/quantized pipeline gather.  Its pullback, applied in
           stage 3b, is the chunked (and under qgZ quantized)
           reduce-scatter over fsdp.
        2. **per-replica grads** (partial-manual shard_map over the data
           axis, fsdp/tp auto): each replica computes grads on its own
           batch shard and emits them STACKED on a new leading axis — the
           region contains no manual-axis collectives beyond the loss
           pmean.
        3. **quantized data-axis reduce** (full-manual
           runtime/zero.pipeline_grad_reduce): int codes + fp32 block
           scales on the wire — all-to-all reduce-scatter into partitioned
           layouts, EQuARX-style quantized allreduce for replicated
           leaves, plain psum for scalars — then (3b) the pipeline
           pullback maps the reduced cotangent to sharded-param space.
        """
        from jax import shard_map
        from deepspeed_tpu.parallel.mesh import auto_axes_spec
        mesh, axis = self.mesh, self._qgz_axis
        size = mesh.shape[axis]

        # -- stage 1: param-side pipeline + its pullback, outside the
        #    manual region (shard_maps cannot nest)
        prepared, prep_vjp = jax.vjp(
            lambda p: self._prepare_params(p, state.step), state.params)

        def bspec(x):
            if getattr(x, "ndim", 0) < 1:
                return P()                       # scalars replicate
            if x.shape[0] % size:
                raise ValueError(
                    f"qgZ: batch leaf with shape {x.shape} has leading dim "
                    f"not divisible by mesh axis {axis}={size} — silently "
                    f"replicating it while other leaves split would pair "
                    f"mismatched rows across leaves; pad the batch so every "
                    f"leaf's leading dim divides the data-parallel size")
            return P(axis)
        bspecs = jax.tree_util.tree_map(bspec, batch)
        pspecs = jax.tree_util.tree_map(lambda _: P(), prepared)
        # stacked out_specs name ONLY the manual axis (legal on both
        # shard_map APIs); fsdp/tp layout rides the in-body anchor below +
        # the exit constraint
        stack_specs = jax.tree_util.tree_map(
            lambda g: P(axis, *([None] * getattr(g, "ndim", 0))), prepared)

        # in-body anchor (round-4 verdict item 4): each replica's cotangent
        # re-anchors to the AUTO part of its target layout inside the
        # region, so GSPMD emits the intra-replica reduce as a
        # reduce-scatter into that layout rather than an allreduce.  For
        # gathered (pipeline) leaves the anchor is the raw param sharding's
        # auto part (fsdp dims re-sharded for storage); otherwise the grad
        # sharding's.
        anchor_tree = (self.param_shardings if self._pipeline_active
                       else self.grad_shardings)
        auto_shardings = jax.tree_util.tree_map(
            lambda sh: NamedSharding(mesh, auto_axes_spec(sh.spec,
                                                          manual={axis})),
            anchor_tree)

        # -- stage 2: per-replica grads, stacked over the data axis
        def local(params, mb, rng, scale, step):
            # decorrelate dropout masks across data shards (the global-batch
            # path gets this for free from position-dependent masking)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            (_, loss), grads = jax.value_and_grad(
                self._loss, has_aux=True)(params, mb, rng, scale, step,
                                          prepared=True)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
            grads = jax.lax.with_sharding_constraint(grads, auto_shardings)
            return (jax.tree_util.tree_map(lambda g: g[None], grads),
                    jax.lax.pmean(loss, axis))

        stacked, loss = shard_map(
            local, mesh=mesh, in_specs=(pspecs, bspecs, P(), P(), P()),
            out_specs=(stack_specs, P()), check_vma=False,
            axis_names={axis})(
                prepared, batch, rng, state.loss_scale.scale, state.step)

        # -- stage 3: quantized data-axis reduce of the stacks, then the
        #    pipeline pullback (chunked/quantized fsdp reduce-scatter).
        #    Reduce target: with the pipeline active the cotangents live in
        #    GATHERED space (fsdp dims dropped by the gather — the dp
        #    reduce is an allreduce there and the pullback re-scatters);
        #    without it they live in raw-param space and scatter straight
        #    into the ZeRO grad partitioning (the qgZ-axis dims of
        #    grad_shardings).
        from deepspeed_tpu.parallel.partition import spec_without_axis
        if self._pipeline_active:
            target = jax.tree_util.tree_map(
                lambda sh: NamedSharding(
                    mesh, spec_without_axis(sh.spec, "fsdp")),
                self.param_shardings)
        else:
            target = self.grad_shardings
        stacked = jax.lax.with_sharding_constraint(
            stacked, jax.tree_util.tree_map(
                lambda sh: NamedSharding(
                    mesh, P(axis, *spec_without_axis(sh.spec, axis))),
                target))
        reduced = zero.pipeline_grad_reduce(
            stacked, target, mesh, axis, self._dp_reduce_plan, mean=True)
        (grads,) = prep_vjp(jax.tree_util.tree_map(
            lambda g, p: g.astype(p.dtype), reduced, prepared))
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
        grads = jax.lax.with_sharding_constraint(grads, self.grad_shardings)
        return grads, loss

    def _unscale(self, grads, scale, n_micro):
        # Note: gradient_predivide_factor is accepted for config parity but is a
        # no-op here — in the reference it pre-divides before allreduce and
        # post-multiplies after, netting out to the world-size average, which we
        # already get because loss is a global-batch mean computed on the global
        # jax.Array view (reduction order is XLA's concern, not ours).
        with jax.named_scope("grad_check"):
            denom = scale * n_micro
            return jax.tree_util.tree_map(lambda g: g / denom, grads)

    def _apply_update(self, state: TrainState, grads
                      ) -> Tuple[TrainState, StepMetrics, dict]:
        # overflow steps surface the finite OVERFLOW_GNORM sentinel, not the
        # raw NaN/Inf norm; skipped_steps records the overflow and the health
        # stats (below) carry the per-group attribution
        with jax.named_scope("grad_check"):
            finite = grads_finite(grads)
            grad_norm = jnp.where(finite, optax.global_norm(grads),
                                  jnp.float32(OVERFLOW_GNORM))
        with jax.named_scope("loss_scale"):
            new_ls = update_loss_scale(state.loss_scale, finite,
                                       self.config.fp16)

        def do_step(operand):
            params, opt_state, grads = operand
            updates, new_opt = self.optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_opt

        def skip_step(operand):
            params, opt_state, _ = operand
            return params, opt_state

        with jax.named_scope("optimizer"):
            new_params, new_opt = jax.lax.cond(
                finite, do_step, skip_step,
                (state.params, state.opt_state, grads))
        new_state = TrainState(
            # overflow-skipped steps do not advance the schedule clock (reference:
            # _take_model_step skips lr_scheduler.step() on overflow)
            step=state.step + jnp.where(finite, 1, 0).astype(jnp.int32),
            params=new_params,
            opt_state=new_opt,
            loss_scale=new_ls,
            rng=state.rng,
        )
        metrics = StepMetrics(
            loss=jnp.float32(0.0),  # filled by caller
            grad_norm=grad_norm,
            loss_scale=new_ls.scale,
            skipped_steps=new_ls.skipped,
        )
        # per-module-group numerics stats ride the step program as one extra
        # (tiny) output — same trace, no extra compile; {} when disabled
        health = {}
        if self._health_enabled:
            from deepspeed_tpu.telemetry.health import compute_group_health
            health = compute_group_health(state.params, grads,
                                          new_params=new_params,
                                          depth=self._health_depth)
        return new_state, metrics, health

    def _accumulate_grads(self, state: TrainState, batch):
        """Scan over gas microbatches accumulating fp32 grads — the ONE
        accumulation loop, shared by the fused train step and the offload
        grads program.  Returns (acc_grads, per-micro losses, per-micro
        moe stats — {} when expert telemetry is off).

        gas=1 bypasses the scan entirely: lax.scan lowers to a while loop
        whose carry is a SEPARATE fp32 accumulation buffer (4 bytes/param of
        peak HBM) that XLA cannot fold away — at billion-param scale that
        buffer is the difference between fitting and OOM."""
        if self.gas == 1:
            mb = jax.tree_util.tree_map(lambda x: x[0], batch)
            grads, loss, moe = self._grads_one_micro(state, mb, jnp.int32(0))
            return grads, loss[None], jax.tree_util.tree_map(
                lambda a: a[None], moe)

        def micro(carry, xs):
            idx, mb = xs
            grads, loss, moe = self._grads_one_micro(state, mb, idx)
            acc = jax.tree_util.tree_map(jnp.add, carry, grads)
            acc = jax.lax.with_sharding_constraint(acc, self.grad_shardings)
            return acc, (loss, moe)

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        zeros = jax.lax.with_sharding_constraint(zeros, self.grad_shardings)
        acc, (losses, moes) = jax.lax.scan(
            micro, zeros, (jnp.arange(self.gas), batch))
        return acc, losses, moes

    def _make_train_batch(self):
        if self.gas_in_model:
            # pipeline path: the model's pipelined scan IS the microbatch loop;
            # one grad computation over the whole [gas, micro, ...] batch
            def train_batch_pipe(state: TrainState, batch):
                grads, loss, _ = self._grads_one_micro(state, batch, 0)
                grads = self._unscale(grads, state.loss_scale.scale, 1)
                new_state, metrics, health = self._apply_update(state, grads)
                return new_state, metrics._replace(
                    loss=loss.astype(jnp.float32)), health
            return train_batch_pipe

        def train_batch(state: TrainState, batch):
            # batch leaves: [gas, micro_global, ...]
            acc, losses, moes = self._accumulate_grads(state, batch)
            grads = self._unscale(acc, state.loss_scale.scale, self.gas)
            new_state, metrics, health = self._apply_update(state, grads)
            metrics = metrics._replace(loss=jnp.mean(losses).astype(jnp.float32))
            if moes:
                # expert-load stats ride the health dict under a reserved
                # key; popped host-side before health post-processing
                health = dict(health, __moe__=_reduce_moe_micros(moes))
            return new_state, metrics, health
        return train_batch

    def _make_grads_batch(self):
        """Offload-mode device program: accumulated scaled fp32 grads + mean
        loss + grad norm (of the scaled sum) + health stats.  No optimizer
        state touched — that's the host's job (runtime/offload.py)."""
        def health_of(state, grads):
            # grads here are still loss-scaled sums; the host step rescales
            # the norms (NaN/Inf counts are scale-invariant).  No
            # update_ratio on this path — the update happens host-side.
            if not self._health_enabled:
                return {}
            from deepspeed_tpu.telemetry.health import compute_group_health
            return compute_group_health(state.params, grads,
                                        depth=self._health_depth)

        if self.gas_in_model:
            def grads_pipe(state: TrainState, batch):
                grads, loss, _ = self._grads_one_micro(state, batch, 0)
                return (grads, loss.astype(jnp.float32),
                        optax.global_norm(grads), health_of(state, grads))
            return grads_pipe

        def grads_batch(state: TrainState, batch):
            acc, losses, moes = self._accumulate_grads(state, batch)
            health = health_of(state, acc)
            if moes:
                health = dict(health, __moe__=_reduce_moe_micros(moes))
            return (acc, jnp.mean(losses).astype(jnp.float32),
                    optax.global_norm(acc), health)
        return grads_batch

    def _train_batch_offload(self, batch):
        # dispatch FIRST: the device starts this step's grads against the
        # params currently on device — under overlap_step those are ONE
        # update stale (the previous host Adam may still be in flight) —
        # and only then join the previous overlapped host step, so the CPU
        # Adam of step N-1 hides behind step N's device grad computation
        # (reference ZeRO-Offload delayed parameter update)
        grads, loss, gnorm, health = self._jit_grads_batch(self.state, batch)
        if self._overlap_step:
            self._join_host_step()
        n_micro = 1 if self.gas_in_model else self.gas
        return self._host_step(grads, loss, gnorm, n_micro, health_dev=health,
                               overlap=self._overlap_step)

    def _join_host_step(self) -> None:
        """Install the params produced by the overlapped ZeRO-Offload host
        step (``offload_optimizer.overlap_step``); no-op when nothing is in
        flight.  A worker failure re-raises HERE — one train_batch late, but
        a lost optimizer update never looks like a completed one.  Every API
        that reads committed params (eval/checkpoint/export/trio) fences
        through this first."""
        w = self._host_worker
        if w is None or not w.busy:
            return
        t0 = time.perf_counter()
        new_params = w.join()
        blocked = time.perf_counter() - t0
        if new_params is not None:
            self.state = self.state._replace(params=new_params)
        work = w.last_work_s
        if self.telemetry.enabled and work > 0.0:
            # 1.0 = the whole host step hid behind device compute; 0.0 = the
            # join blocked for the full host-step duration (no overlap won)
            self.telemetry.registry.gauge(
                "host_step_overlap_ratio",
                "fraction of the overlapped ZeRO-Offload host optimizer "
                "step hidden behind device compute (1.0 = fully overlapped)"
            ).set(max(0.0, 1.0 - blocked / work))

    def _host_step(self, grads_dev, loss_dev, gnorm_dev, n_micro,
                   health_dev=None, overlap=False) -> StepMetrics:
        """The offloaded optimizer step: fetch grads, host Adam on the fp32
        masters (cpu/nvme tier), stream compute-dtype params back.  Loss-scale
        bookkeeping runs in plain Python (reference: _take_model_step +
        DeepSpeedCPUAdam.step on the offload path).

        ``overlap=True`` (train_batch under ``overlap_step``) runs the
        grads fetch + Adam + params device_put on the HostStepWorker instead
        of inline — identical math on identical inputs, so the off-path is
        bitwise-reproduced; only WHEN the new params land differs (at the
        next step's ``_join_host_step``).  The scalar bookkeeping (loss
        scale, clip coefficient, schedule clock) stays on this thread either
        way: it needs only gnorm, which the single fetch below already
        blocks on."""
        from deepspeed_tpu.runtime.precision import update_loss_scale_host
        state = self.state
        # one host fetch for every scalar this step reads (gnorm, loss, the
        # loss-scale state machine, the schedule clock, health stats) — the
        # per-scalar float(...) pattern cost a device round trip each
        gnorm_scaled, loss_host, ls_host, step_host, health_host = \
            jax.device_get((gnorm_dev, loss_dev, state.loss_scale,
                            state.step, health_dev))
        gnorm_scaled = float(gnorm_scaled)  # sync-ok: host value from the fetch above
        scale = float(ls_host.scale)        # sync-ok: host value from the fetch above
        denom = scale * n_micro
        finite = bool(np.isfinite(gnorm_scaled))
        # overflow: finite sentinel + skipped_steps, matching the device
        # path's _apply_update contract (was: raw NaN/Inf leaked into the
        # reported norm)
        raw_norm = gnorm_scaled / denom if finite else OVERFLOW_GNORM
        if finite:
            clip = float(self.config.gradient_clipping or 0.0)  # sync-ok: config scalar
            coef = 1.0
            if clip > 0.0 and raw_norm > clip:
                coef = clip / (raw_norm + 1e-6)
            # optax schedules see the update count (0-based), matching the
            # device path's optax scheduling.  No worker is in flight here
            # (callers join before _host_step), so reading step_count — which
            # only the worker mutates — is race-free.
            lr = (float(self.lr_schedule(self.offload_opt.step_count))  # sync-ok: host schedule math
                  if self.lr_schedule is not None
                  else float(self._opt_params.get("lr", 1e-3)))  # sync-ok: config scalar
            lr *= self._lr_scale          # guardian clamp-down (1.0 normally)

            def host_update(grad_scale=coef / denom, lr=lr):
                # the heavy half: grads fetch + host Adam over the fp32
                # masters + compute-dtype params upload.  Under overlap this
                # body runs on the HostStepWorker while the caller dispatches
                # the next device step — same math on the same inputs as the
                # inline path, so off/on differ only in WHEN params land.
                grads_np = jax.device_get(grads_dev)
                new_params_np = self.offload_opt.update(
                    grads_np, lr=lr, grad_scale=grad_scale)
                with self.mesh:
                    return jax.device_put(new_params_np,
                                          self.param_shardings)

            if overlap:
                self._host_worker.submit(host_update)
                # stale on purpose (ZeRO-Offload delayed parameter update):
                # the next step's grads run against these params; the fresh
                # ones install at that step's _join_host_step
                new_params = state.params
            else:
                new_params = host_update()
            new_step = jnp.int32(int(step_host) + 1)
        else:
            # overflow: nothing to overlap — the step is skipped entirely
            # (no Adam, no staleness), only the loss-scale machine advances
            new_params, new_step = state.params, state.step
        new_ls = update_loss_scale_host(ls_host, finite, self.config.fp16)
        self.state = TrainState(step=new_step, params=new_params,
                                opt_state=(), loss_scale=new_ls,
                                rng=state.rng)
        if health_host:
            # device program measured the loss-scaled grad sums — rescale
            # the norms to match the reported raw_norm (counts and param
            # norms are scale-free)
            from deepspeed_tpu.telemetry.health import to_python
            if "__moe__" in health_host:   # [E] vector: not per-group stats
                self._last_moe_host = _moe_stats_to_python(
                    health_host.pop("__moe__"))
            health_host = to_python(health_host)
            for stats in health_host.values():
                gn = stats.get("grad_norm")
                if gn is not None and np.isfinite(gn):
                    stats["grad_norm"] = gn / denom
        self._last_health = health_host or {}
        return StepMetrics(
            loss=jnp.float32(float(loss_host)),  # sync-ok: host value from the fetch above
            grad_norm=jnp.float32(raw_norm),
            loss_scale=new_ls.scale,
            skipped_steps=new_ls.skipped)

    def _make_grad_fn(self):
        def grad_fn(state: TrainState, batch, idx):
            grads, loss, _ = self._grads_one_micro(state, batch, idx)
            return grads, loss
        return grad_fn

    def _make_apply_fn(self):
        def apply_fn(state: TrainState, grads, n_micro):
            grads = self._unscale(grads, state.loss_scale.scale, n_micro)
            return self._apply_update(state, grads)
        return apply_fn

    # ------------------------------------------------------------------ data

    def _apply_data_efficiency(self, batch):
        """Host-side curriculum seqlen truncation + random-LTD keep-index
        injection on the FLAT batch (reference: data_pipeline hooks in
        deepspeed.initialize / DataEfficiency tutorial).  Shape changes re-key
        jit per difficulty/keep bucket — difficulty_step / seq_per_step bound
        the program count."""
        if self.curriculum_scheduler is None \
                and self.random_ltd_scheduler is None:
            return batch
        if not isinstance(batch, dict):
            return batch
        batch = dict(batch)
        # normalize the pre-shaped [gas, micro_local, ...] form to flat rows —
        # ltd index shapes and truncation work on [rows, T]; train_batch's
        # shape check reshapes back afterwards
        ids0 = np.asarray(batch["input_ids"])
        local_bs = self.config.train_batch_size // jax.process_count()
        if (ids0.ndim >= 3 and ids0.shape[0] == self.gas
                and ids0.shape[1] == local_bs // self.gas):
            batch = {k: np.asarray(v).reshape(
                (-1,) + np.asarray(v).shape[2:]) for k, v in batch.items()}
        step = self.global_steps
        if self.curriculum_scheduler is not None:
            from deepspeed_tpu.data_pipeline import truncate_to_difficulty
            diff = self.curriculum_scheduler.update_difficulty(step)
            dstep = self.curriculum_scheduler.schedule_config.get(
                "difficulty_step", 1)
            batch = truncate_to_difficulty(batch, diff, dstep)
        if self.random_ltd_scheduler is not None:
            from deepspeed_tpu.data_pipeline import random_ltd_block_indices
            ids = np.asarray(batch["input_ids"])
            rows, T = ids.shape[0], ids.shape[-1]
            keep = self.random_ltd_scheduler.get_value(step)
            # decorrelate drop patterns across hosts: each process samples
            # for its own local rows
            idx = random_ltd_block_indices(
                step, keep, rows, T, len(self._ltd_layer_ids),
                seed=self._de_seed + 31337 * jax.process_index())
            batch["random_ltd_idx"] = np.moveaxis(idx, 0, 1)
        return batch

    def _shard_batch(self, batch, leading_gas: bool = False):
        """Place a host batch onto the mesh: batch dim over (dp, fsdp); the
        sequence dim (dim 1 of each microbatch) over sp when Ulysses sequence
        parallelism is active.

        Multi-process: each host passes its PROCESS-LOCAL rows and the global
        batch is assembled via jax.make_array_from_process_local_data —
        no host ever holds (or ships) the whole global batch (reference: each
        rank's dataloader feeds its own local microbatches)."""
        sp = "sp" if self.mesh.shape["sp"] > 1 else None
        multiproc = jax.process_count() > 1

        def put(x):
            x = np.asarray(x)
            extra = x.ndim - 1 - (1 if leading_gas else 0)
            dims = [("dp", "fsdp")] + [None] * extra
            if sp and extra >= 1:
                dims[1] = sp
            if leading_gas:
                dims = [None] + dims
            sharding = NamedSharding(self.mesh, P(*dims))
            if multiproc:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)
        return jax.tree_util.tree_map(put, batch)

    def _reshape_gas(self, batch):
        """[gas*micro_global, ...] → [gas, micro_global, ...]."""
        def r(x):
            x = np.asarray(x)
            return x.reshape((self.gas, x.shape[0] // self.gas) + x.shape[1:])
        return jax.tree_util.tree_map(r, batch)

    def _form_batch(self, batch):
        """Host-side half of batch preparation (no device traffic):
        data-efficiency transforms + normalization to the
        [gas, micro_local, ...] form; returns (batch, global tokens per
        optimizer step).  train_batch's ``batch_input`` phase, shared with
        ``prepare_batch`` so the prefetch worker forms batches identically."""
        batch = self._apply_data_efficiency(batch)
        first_shape = tuple(jax.tree_util.tree_leaves(batch)[0].shape)
        # multi-process: each host feeds its process-local slice of the
        # global batch (train_batch_size / process_count rows)
        local_bs = self.config.train_batch_size // jax.process_count()
        micro_local = local_bs // self.gas
        # disambiguate [gas, micro_local, ...] (pre-shaped) from the flat
        # [local_bs, ...] form by the SECOND dim too — when gas ==
        # local_bs the leading dim alone cannot tell them apart
        if (first_shape[0] == self.gas and len(first_shape) > 1
                and first_shape[1] == micro_local):
            pass                            # already [gas, micro_local, ...]
        elif first_shape[0] == local_bs:
            batch = self._reshape_gas(batch)
        else:
            raise ValueError(
                f"train_batch leading dims {first_shape[:2]} match "
                f"neither [gas={self.gas}, micro_local={micro_local}, "
                f"...] nor the flat process-local batch [{local_bs}, "
                f"...] (train_batch_size={self.config.train_batch_size} "
                f"/ {jax.process_count()} processes)")
        lead_shape = tuple(jax.tree_util.tree_leaves(batch)[0].shape)
        # [gas, micro_local, T, ...] → tokens per optimizer step (global)
        tokens = (int(np.prod(lead_shape[:3])) * jax.process_count()
                  if len(lead_shape) >= 3 else 0)
        return batch, tokens

    def prepare_batch(self, batch):
        """Form, shard, and ``device_put`` ONE host batch ahead of its step —
        the work of train_batch's ``batch_input`` + ``host_to_device``
        phases — returning a :class:`PreparedBatch` that ``train_batch``
        accepts directly.  This is the ``prepare_fn`` the prefetch worker
        runs (``prefetch_loader``); calling it inline is equivalent.

        Note: curriculum/random-LTD schedules read ``global_steps`` at
        PREPARE time, so under prefetch a difficulty change lands up to
        ``prefetch_depth`` steps late (bounded by the queue depth)."""
        from deepspeed_tpu.runtime.prefetch import PreparedBatch
        step = self.global_steps
        batch, tokens = self._form_batch(batch)
        batch = self._shard_batch(batch, leading_gas=True)
        return PreparedBatch(batch=batch, tokens=tokens, step_enqueued=step)

    def prefetch_loader(self, source, depth: Optional[int] = None):
        """Wrap an iterable of host batches in the background device-prefetch
        pipeline (runtime/prefetch.py): a worker thread keeps up to ``depth``
        batches formed/sharded/``device_put`` ahead of the step, so
        ``train_batch``'s ``host_to_device`` span collapses to a queue pop.
        ``depth`` defaults to ``data_pipeline.prefetch_depth``; 0 prepares
        each batch synchronously behind the same iterator surface.  Use as a
        context manager (or call ``.close()``) for clean worker shutdown."""
        from deepspeed_tpu.runtime.prefetch import (PrefetchIterator,
                                                    _InlinePrefetch)
        if depth is None:
            depth = int(self.config.data_pipeline.prefetch_depth)
        if depth <= 0:
            return _InlinePrefetch(source, self.prepare_batch)
        return PrefetchIterator(
            source, self.prepare_batch, depth=depth,
            registry=self.telemetry.registry if self.telemetry.enabled
            else None)

    # ------------------------------------------------------------------ API

    def train_batch(self, batch) -> StepMetrics:
        """One full optimizer step over ``gas`` microbatches.

        ``batch`` leaves are host arrays of global shape
        [gas × micro × dp_world, ...] (or already [gas, micro_global, ...]).
        Mirrors PipelineEngine.train_batch (runtime/pipe/engine.py:326) semantics
        for the non-pipelined engine.
        """
        from deepspeed_tpu.runtime.prefetch import PreparedBatch
        t0 = time.perf_counter()
        tel = self.telemetry
        step_id = self.global_steps + 1
        with tel.span("train_step", step=step_id,
                      host_ns=time.perf_counter_ns()):
            self.tput_timer.start()
            if isinstance(batch, PreparedBatch):
                # the prefetch worker already formed/sharded/device_put this
                # batch while the previous step ran (runtime/prefetch.py):
                # both input phases collapse to an unwrap
                self.timers(DATA_TIMER).start()
                with tel.span("host_to_device", step=step_id, prefetched=True):
                    batch, tokens = batch.batch, batch.tokens
                self.timers(DATA_TIMER).stop()
            else:
                with tel.span("batch_input", step=step_id):
                    batch, tokens = self._form_batch(batch)
                self.timers(DATA_TIMER).start()
                with tel.span("host_to_device", step=step_id):
                    batch = self._shard_batch(batch, leading_gas=True)
                self.timers(DATA_TIMER).stop()
            fp = self.config.flops_profiler
            profile_pending = (fp.enabled and not self._flops_profiled
                               and self.global_steps + 1 >= fp.profile_step)
            if profile_pending:
                # traced by the flops profiler, then freed
                self._last_batch = batch
            # chaos: ``nan@step.grads`` forces this step's gradient computation
            # non-finite (see _poison_first_float_leaf) — the signal the
            # guardian's rollback remediation is chaos-verified against
            if faults.fire("step.grads", step=step_id) == "nan":
                self.state = self.state._replace(
                    params=_poison_first_float_leaf(self.state.params))
            self.timers(TRAIN_BATCH_TIMER).start()
            with self.mesh:
                jfn = (self._jit_grads_batch if self.offloading
                       else self._jit_train_batch)
                if tel.enabled:
                    # recompile watchdog + (on a signature miss) compiled-HLO
                    # collective bytes / cost / memory figures
                    tel.before_dispatch(
                        "train_batch", batch, step_id,
                        lower=lambda: jfn.lower(self.state, batch))
                mark = _SETUP.booked
                # ``step`` is this dispatch's number and ``program`` what it
                # launches (the name ``XLA Modules`` prints after ``jit_``):
                # a trace reader joins the device run to the span by both
                with tel.span("dispatch", step=step_id,
                              program=jfn.__name__):
                    # chaos: ``sleep@step.dispatch`` models a hung collective /
                    # straggler stall: the guardian watchdog's deadline target
                    faults.fire("step.dispatch", step=step_id)
                    if self.offloading:
                        # sets _last_health (host dict) itself
                        metrics = self._train_batch_offload(batch)
                    else:
                        self.state, metrics, health = self._jit_train_batch(
                            self.state, batch)
                        self._last_health = health
                if _SETUP.booked != mark:  # a first call: jax traced or loaded
                    _SETUP.close("train_batch", mark, tel.tracer,
                                 step=step_id)
            if self.wall_clock_breakdown or profile_pending:
                # synchronize so the timer covers device execution, not just
                # dispatch.  Only for who asks: the span tracer does not
                # (blocking every step cost 4.3% of the one-chip GPT-2-medium
                # step rate, PERF.md PR 26); a step's completion is read in a
                # device trace, where the ds.* spans lie beside the device ops
                with tel.span("device_complete", step=step_id):
                    jax.block_until_ready(metrics.loss)
            self.timers(TRAIN_BATCH_TIMER).stop()
            self.global_steps += 1
            self._last_metrics = metrics
            self._step_times.append(time.perf_counter() - t0)
            self.tput_timer.stop(int(self.config.train_batch_size), tokens)
            with tel.span("step_bookkeeping", step=step_id):
                self._post_step_reporting(metrics)
        # after the span: the export this may trigger then holds the step whole
        tel.end_step(self.global_steps,
                     samples=self.global_steps
                     * int(self.config.train_batch_size),
                     tokens=tokens)
        return metrics

    def eval_batch(self, batch):
        """Deterministic evaluation loss on one global batch — no grads, no
        state mutation (reference PipelineEngine.eval_batch
        pipe/engine.py:415; plain-engine eval = module.eval() + forward).

        Weight-side semantics match training exactly (master-weight cast,
        staged QDQ at the CURRENT step, qwZ gather).  Dropout/PLD/random-LTD
        are off for models exposing a deterministic leg (a flax module with a
        ``deterministic`` flag, or an apply_fn treating ``rng=None`` as
        eval); other models run their training-mode forward with the current
        state rng.  Returns the scalar loss as a float32 jax array.
        """
        self._join_host_step()     # eval on committed params, never stale
        # no leading gas dim: pipeline models treat a flat [B, T] batch as a
        # single microbatch (pipe/module.py _3d)
        batch = self._shard_batch(batch)
        if self._jit_eval is None:
            def eval_fn(state, batch):
                _, loss = self._loss(state.params, batch, state.rng,
                                     jnp.float32(1.0), state.step,
                                     deterministic=True)
                return loss.astype(jnp.float32)
            self._jit_eval = jax.jit(eval_fn)
        if self.telemetry.enabled:
            # watchdog only (no HLO analysis: eval is off the hot path and
            # an AOT compile per eval shape isn't worth the figures)
            self.telemetry.before_dispatch("eval_batch", batch,
                                           self.global_steps)
        with self.mesh:
            return self._jit_eval(self.state, batch)

    def forward(self, batch):
        """Compatibility trio part 1 (reference engine.forward engine.py:1785):
        computes loss *and* grads for one microbatch, accumulating grads."""
        if self.gas_in_model:
            # parity: the reference PipelineEngine also only supports
            # train_batch/eval_batch (pipe/engine.py:56 "only via train_batch")
            raise RuntimeError(
                "pipeline models only support train_batch(), not the "
                "forward/backward/step trio")
        self._join_host_step()     # mixing trio + train_batch: fence first
        batch = self._apply_data_efficiency(batch)
        batch = self._shard_batch(batch)
        with self.mesh:
            grads, loss = self._jit_grad(self.state, batch,
                                         jnp.int32(self._micro_steps))
        if self._accum_grads is None:
            self._accum_grads = grads
        else:
            self._accum_grads = jax.tree_util.tree_map(
                jnp.add, self._accum_grads, grads)
        self._micro_losses.append(loss)
        self._micro_steps += 1
        return loss

    def backward(self, loss=None):
        """Grads were produced in forward() (JAX has no separate backward pass
        to intercept); kept for API parity (reference engine.py:1924)."""
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._micro_steps % self.gas == 0

    def step(self):
        """Apply the accumulated update at the gradient-accumulation boundary
        (reference engine.step engine.py:2123)."""
        if not self.is_gradient_accumulation_boundary():
            return None
        assert self._accum_grads is not None, "call forward() before step()"
        # the trio's host step runs inline (overlap is a train_batch-loop
        # optimization); a stray overlapped step must land before the
        # masters are touched again
        self._join_host_step()
        # one fetch for all micro losses (was a float() sync per microbatch)
        mean_loss = jnp.float32(np.mean(jax.device_get(self._micro_losses)))
        if self.offloading:
            with self.mesh:
                gnorm = self._jit_gnorm(self._accum_grads)
                health = (self._jit_health(self.state.params,
                                           self._accum_grads)
                          if self._jit_health is not None else None)
            metrics = self._host_step(self._accum_grads, mean_loss, gnorm,
                                      self.gas, health_dev=health)
        else:
            with self.mesh:
                self.state, metrics, health = self._jit_apply(
                    self.state, self._accum_grads, jnp.float32(self.gas))
            self._last_health = health
            metrics = metrics._replace(loss=mean_loss)
        self._accum_grads = None
        self._micro_losses = []
        self._micro_steps = 0
        self.global_steps += 1
        self._last_metrics = metrics
        self._post_step_reporting(metrics)
        return metrics

    def hybrid_engine(self, inference_config=None):
        """Train↔generate bridge for RLHF (runtime/hybrid_engine.py;
        reference DeepSpeedHybridEngine).  Built lazily, cached — enable via
        the ``hybrid_engine`` config block or call directly."""
        if getattr(self, "_hybrid", None) is None:
            from deepspeed_tpu.runtime.hybrid_engine import HybridEngine
            self._hybrid = HybridEngine(self, inference_config)
            self._hybrid_cfg = inference_config
        elif (inference_config is not None
              and inference_config != self._hybrid_cfg):
            raise ValueError(
                "hybrid_engine() was already built with a different "
                "inference_config; build a HybridEngine directly for a "
                "second configuration")
        return self._hybrid

    # ------------------------------------------------------------------ info

    @property
    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    @property
    def train_batch_size(self):
        return self.config.train_batch_size

    def get_lr(self):
        if self.lr_schedule is None:
            return [float(self._opt_params.get("lr", 0.0)) * self._lr_scale]
        host = self._last_metrics_host
        if host is not None and self._host_metrics_step == self.global_steps:
            # state.step mirror without a device sync: overflow-skipped
            # steps do not advance the schedule clock
            step = self.global_steps - host.skipped_steps
        else:
            step = int(self.state.step)  # sync-ok: cold path, no cached copy
        return [float(self.lr_schedule(step)) * self._lr_scale]

    def _fetch_metrics(self, metrics: StepMetrics,
                       health=None) -> StepMetrics:
        """THE sanctioned device→host fetch point for step scalars: ONE
        ``jax.device_get`` moves the whole StepMetrics (+ the small health
        pytree) and the host copy is cached for every later reader —
        ``get_global_grad_norm()``, ``skipped_steps``, prints, monitors,
        the flight recorder.  scripts/check_no_sync.py enforces that the
        step path performs host syncs only here (or via an explicit
        ``device_get`` / ``# sync-ok`` disclosure)."""
        from deepspeed_tpu.telemetry.health import to_python
        vals, health_host = jax.device_get((tuple(metrics), health))
        host = StepMetrics(loss=float(vals[0]), grad_norm=float(vals[1]),
                           loss_scale=float(vals[2]),
                           skipped_steps=int(vals[3]))
        self._last_metrics_host = host
        # expert-load stats ride the health pytree under a reserved key but
        # are NOT per-group numerics (expert_tokens is an [E] vector, which
        # to_python's float() would reject) — split them off first
        if isinstance(health_host, dict) and "__moe__" in health_host:
            self._last_moe_host = _moe_stats_to_python(
                health_host.pop("__moe__"))
        self._last_health_host = to_python(health_host)
        self._host_metrics_step = self.global_steps
        return host

    def _reset_host_metrics_cache(self) -> None:
        """Drop the cached host metrics — checkpoint loads rewind
        global_steps, which could otherwise alias a stale cache entry."""
        self._last_metrics = None
        self._last_metrics_host = None
        self._last_health = None
        self._last_health_host = {}
        self._host_metrics_step = -1
        self.telemetry.reset_numerics_baseline()

    def _host_metrics(self) -> Optional[StepMetrics]:
        """Cached host StepMetrics for the latest step (fetching once if a
        reader arrives before the reporting path did)."""
        if self._last_metrics is None:
            return None
        if (self._last_metrics_host is None
                or self._host_metrics_step != self.global_steps):
            self._fetch_metrics(self._last_metrics, self._last_health)
        return self._last_metrics_host

    def get_global_grad_norm(self):
        host = self._host_metrics()
        return None if host is None else host.grad_norm

    @property
    def skipped_steps(self):
        host = self._host_metrics()
        return 0 if host is None else host.skipped_steps

    def dump_postmortem(self, note: Optional[str] = None):
        """Explicitly dump the flight-recorder buffer as a postmortem bundle
        (requires ``telemetry.health.enabled``); returns the bundle dir."""
        return self.telemetry.dump_postmortem(note=note)

    def _maybe_print(self, host: StepMetrics):
        spp = self.config.steps_per_print
        if spp and self.global_steps % spp == 0:
            log_dist(
                f"step={self.global_steps} loss={host.loss:.4f} "
                f"lr={self.get_lr()[0]:.3e} "
                f"grad_norm={host.grad_norm:.3f} "
                f"loss_scale={host.loss_scale:.0f}", ranks=[0])

    def _post_step_reporting(self, metrics: StepMetrics):
        """Console print + monitor fan-out + flight recorder + timer log +
        flops profile, at their configured cadences (reference
        engine.py:2264 _write_monitor, :1797 flops profiler hook, :145
        EngineTimers).  All host reads go through the single
        ``_fetch_metrics`` fetch; steps where nothing reports skip the
        device sync entirely."""
        if self.pld is not None:
            # keep the host mirror in sync with the in-graph schedule so
            # get_theta()/get_state() report the effective value; the theta
            # applied THIS step was computed from the pre-increment state.step
            self.pld.update_state(self.global_steps - 1)
        spp = self.config.steps_per_print
        at_cadence = spp and self.global_steps % spp == 0
        # monitors write even when console printing is off (steps_per_print=0
        # means every step, matching the reference's monitor-independent
        # cadence; costs one device sync per write)
        monitor_cadence = at_cadence or (not spp and self.monitor.enabled)
        need_host = bool(at_cadence or (self.monitor.enabled
                                        and monitor_cadence)
                         or self._health_enabled or self._moe_stats_on)
        host = (self._fetch_metrics(metrics, self._last_health)
                if need_host else None)
        if host is not None and at_cadence:
            self._maybe_print(host)
        samples = self.global_steps * int(self.config.train_batch_size)
        if self.monitor.enabled and monitor_cadence and host is not None:
            # x-axis is samples seen, matching the reference's
            # Train/Samples/* convention (engine.py:2272)
            events = [
                ("Train/Samples/train_loss", host.loss, samples),
                ("Train/Samples/lr", self.get_lr()[0], samples),
                ("Train/Samples/grad_norm", host.grad_norm, samples),
                ("Train/Samples/loss_scale", host.loss_scale, samples),
            ]
            if self.tput_timer.avg_samples_per_sec:
                events.append(("Train/Samples/throughput_samples_per_sec",
                               self.tput_timer.avg_samples_per_sec, samples))
            if self.tput_timer.avg_tokens_per_sec:
                events.append(("Train/Samples/throughput_tokens_per_sec",
                               self.tput_timer.avg_tokens_per_sec, samples))
            self.monitor.write_events(events)
        if self._health_enabled and host is not None:
            # anomaly rules + ring buffer + dump triggers (nonfinite loss,
            # overflow streak) — telemetry/health.py, flight_recorder.py
            self.telemetry.health_step(
                self.global_steps, host, self._last_health_host,
                lr=self.get_lr()[0], samples=samples)
        if self._moe_stats_on and host is not None \
                and self._last_moe_host is not None:
            # per-expert load gauges + drop counters (telemetry registry) —
            # reads only the host copy fetched above, no device sync
            self.telemetry.moe_step(self._last_moe_host)
        if self.wall_clock_breakdown and at_cadence:
            self.timers.log([DATA_TIMER, TRAIN_BATCH_TIMER], normalizer=spp)
        fp = self.config.flops_profiler
        if (fp.enabled and not self._flops_profiled
                and self.global_steps >= fp.profile_step):
            self._flops_profiled = True
            self._print_flops_profile()
        if self.config.memory_breakdown and self.global_steps == 1:
            self._print_memory_breakdown()

    def _print_flops_profile(self):
        from deepspeed_tpu.profiling import FlopsProfiler
        if self._last_batch is None:
            logger.warning(
                "flops profiler: no traced batch available — the profiler "
                "supports the train_batch() API only, not the "
                "forward/backward/step trio")
            return
        fp = self.config.flops_profiler
        prof = FlopsProfiler(fp)
        try:
            prof.count(self._train_batch_fn, self.state, self._last_batch)
        except Exception as e:  # profiling must never kill training
            logger.warning(f"flops profiler failed to trace the step: {e!r}")
            return
        finally:
            self._last_batch = None  # free the pinned device batch
        # _step_times[-1] was synchronized (profile_pending forced a fetch)
        prof.latency = self._step_times[-1] if self._step_times else 0.0
        self.telemetry.record_flops(prof.as_metrics())
        prof.print_model_profile(params=self.state.params,
                                 module_depth=fp.module_depth,
                                 top_modules=fp.top_modules,
                                 detailed=fp.detailed,
                                 output_file=fp.output_file)

    def profile_comms(self, batch, iters: int = 2):
        """Measure the jitted train step's per-collective bytes + latency
        (comm.profile_jitted) and record them into the comms logger —
        ``comm.comms_logger.log_summary()`` then shows algo-BW for the
        jitted collectives (reference calc_bw_log role under XLA).

        Functional state is NOT mutated (the step runs on a copy of the
        inputs through an undonated jit)."""
        from deepspeed_tpu.comm.comm import profile_jitted
        self._join_host_step()
        batch = self._apply_data_efficiency(batch)
        first = tuple(jax.tree_util.tree_leaves(batch)[0].shape)
        local_bs = self.config.train_batch_size // jax.process_count()
        micro_local = local_bs // self.gas
        # same batch-form disambiguation as train_batch (incl. the
        # gas == local_bs ambiguity resolved by the SECOND dim)
        if (first[0] == self.gas and len(first) > 1
                and first[1] == micro_local):
            pass                            # already [gas, micro_local, ...]
        elif first[0] == local_bs:
            batch = self._reshape_gas(batch)
        else:
            raise ValueError(
                f"profile_comms batch leading dims {first[:2]} match "
                f"neither [gas={self.gas}, micro_local={micro_local}, ...] "
                f"nor the flat [{local_bs}, ...] form")
        batch = self._shard_batch(batch, leading_gas=True)
        with self.mesh:
            return profile_jitted(jax.jit(self._train_batch_fn),
                                  self.state, batch, iters=iters)

    def lower_train_batch(self, batch):
        """``jax.stages.Lowered`` of the step program ``train_batch`` runs
        for this host batch — where ahead-of-time inspection starts
        (``.compile().as_text()`` for the kernels and collectives the
        compiler put in, ``.memory_analysis()``).  Nothing executes and no
        state changes."""
        from deepspeed_tpu.telemetry.registry import \
            suppress_collective_recording
        batch, _ = self._form_batch(batch)
        batch = self._shard_batch(batch, leading_gas=True)
        jfn = (self._jit_grads_batch if self.offloading
               else self._jit_train_batch)
        # the lowering RETRACES the step: keep the trace-time collective
        # byte counters from double-counting
        with suppress_collective_recording(), self.mesh:
            return jfn.lower(self.state, batch)

    def _print_memory_breakdown(self):
        """reference: see_memory_usage / memory_breakdown config."""
        from deepspeed_tpu.utils.memory import collect_memory_stats
        lines = []
        for d, stats in zip(jax.local_devices(),
                            collect_memory_stats()["devices"]):
            if stats:
                used = stats.get("bytes_in_use", 0) / 2**30
                limit = stats.get("bytes_limit", 0) / 2**30
                peak = stats.get("peak_bytes_in_use", 0) / 2**30
                lines.append(f"  {d}: in_use={used:.2f}GiB "
                             f"peak={peak:.2f}GiB limit={limit:.2f}GiB")
        if lines:
            log_dist("device memory breakdown:\n" + "\n".join(lines),
                     ranks=[0])

    # ------------------------------------------------------------------ ckpt

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None,
                        async_save: bool = False):
        """reference engine.save_checkpoint (engine.py:3056): sharded save via
        orbax; every process participates (global-view jax.Arrays).
        ``async_save=True`` returns once device arrays are snapshotted
        (``checkpoint_snapshot`` span, blocking and short) and streams the
        serialize/write in the background (``checkpoint_write`` span,
        recorded at commit); an in-progress marker + commit-ordered 'latest'
        keep a crash mid-write from ever orphaning the previous checkpoint.
        Call ``engine.wait_for_checkpoint()`` before exiting (the checkpoint
        module also fences atexit)."""
        import os

        from deepspeed_tpu.checkpoint import save_train_state
        self._join_host_step()   # only committed params reach the snapshot
        self.wait_for_checkpoint()   # previous save commits (and zeroes the
        #                              backlog gauge) before this one starts
        tag = tag or f"global_step{self.global_steps}"
        tel = self.telemetry
        step = self.global_steps
        pre_commit = None
        if self.offloading and jax.process_index() == 0:
            # host-resident masters/moments ride alongside the orbax tree
            # (reference: _save_zero_checkpoint per-rank optimizer shards),
            # streamed as npz on the waiter thread, pre-commit: it lands
            # inside the in-progress window, before 'latest' can move,
            # without blocking the dispatch thread.  Only an async save
            # snapshots a COPY (state_dict returns live views the next host
            # step mutates; a blocking save writes before anything can, and
            # the copy would transiently double the optimizer-state
            # footprint on exactly the host-RAM-bound runs that offload)
            sd = self.offload_opt.state_dict()
            if async_save:
                sd = {k: (np.copy(v) if isinstance(v, np.ndarray) else v)
                      for k, v in sd.items()}
            npz_path = os.path.join(save_dir, tag, "offload_state.npz")

            def pre_commit(_sd=sd, _path=npz_path):
                np.savez(_path, **_sd)
        backlog = (tel.registry.gauge(
            "checkpoint_write_backlog",
            "async checkpoint writes still streaming in the background")
            if tel.enabled else None)

        def on_commit(write_s, _tag=tag, _step=step):
            # runs on the waiter thread for async saves, inline for blocking
            # ones — tracer.record/gauge.set are thread-safe appends
            if backlog is not None:
                backlog.set(0)
            if tel.tracer.enabled:
                end = tel.tracer.now_us()
                tel.tracer.record("checkpoint_write", end - write_s * 1e6,
                                  write_s * 1e6, step=_step, tag=_tag,
                                  op="save")

        if backlog is not None and async_save:
            backlog.set(1)
        from deepspeed_tpu.checkpoint import reshard
        with tel.span("checkpoint_snapshot", step=step, tag=tag, op="save"):
            save_train_state(save_dir, tag, self.state,
                             client_state=dict(
                                 client_state or {},
                                 global_steps=self.global_steps,
                                 # physical layout descriptor: a different
                                 # topology restoring this tag keys its
                                 # resharding transform on it
                                 layout=reshard.engine_layout(self)),
                             block=not async_save, on_commit=on_commit,
                             pre_commit=pre_commit)
        if self.telemetry.enabled and self.telemetry.snapshot_interval:
            # flush so the checkpoint_io span reaches the trace file even
            # when no further step follows (end-of-run checkpoints); same
            # samples x-axis as end_step so monitor series stay monotonic
            self.telemetry.export(
                step=self.global_steps,
                samples=self.global_steps * int(self.config.train_batch_size))
        return tag

    def drain(self, run_dir: str, *, reason: str = "preemption",
              out_dir: Optional[str] = None) -> Optional[str]:
        """Graceful drain on a preemption notice (runtime/resilience.py):
        fence the overlapped host step and any in-flight async checkpoint,
        commit a final universal export (+ executable fingerprints) under
        ``run_dir``, and record ``preemptions_total{reason}`` + the
        ``drain`` span.  Call from the step loop after
        ``PreemptionHandler.requested`` turns true; then exit with
        ``resilience.EXIT_DRAINED``."""
        from deepspeed_tpu.runtime import resilience
        return resilience.drain(self, run_dir, reason=reason,
                                out_dir=out_dir)

    def clamp_lr(self, factor: float) -> float:
        """Multiply the effective learning rate by ``factor`` from now on —
        the guardian's escalated-retry clamp-down.  On the device paths the
        LR is traced into the compiled update, so this rebuilds the
        optimizer chain and re-jits the step programs (one recompile; the
        recompile watchdog is invalidated so it doesn't warn).  The offload
        host step reads the scale directly — no recompile.  Returns the
        cumulative scale.  Refuses under a client optimizer: the engine
        cannot rebuild a chain it did not build."""
        if not 0 < factor <= 1:
            raise ValueError(f"clamp_lr factor must be in (0, 1], "
                             f"got {factor}")
        if self._client_optimizer is not None:
            raise ValueError(
                "clamp_lr cannot rebuild a client optimizer chain; clamp "
                "the LR inside your own optimizer/schedule instead")
        self._lr_scale *= float(factor)
        if not self.offloading:
            self.optimizer, self._opt_params = self._build_tx(None)
            self._build_step_functions()
        logger.warning(f"guardian: learning rate clamped x{factor:g} "
                       f"(cumulative scale {self._lr_scale:g})")
        return self._lr_scale

    def clamp_loss_scale(self, factor: float) -> None:
        """Scale the dynamic loss scale DOWN by ``factor`` (floored at
        ``fp16.min_loss_scale``) — a data-only state edit, no recompile.
        No-op outside dynamic fp16 scaling (bf16/fp32 run at the frozen
        unit scale)."""
        if not 0 < factor <= 1:
            raise ValueError(f"clamp_loss_scale factor must be in (0, 1], "
                             f"got {factor}")
        cfg = self.config.fp16
        if not cfg.enabled or cfg.loss_scale > 0:
            return
        ls = self.state.loss_scale
        new_scale = jnp.maximum(ls.scale * jnp.float32(factor),
                                jnp.float32(cfg.min_loss_scale))
        self.state = self.state._replace(
            loss_scale=ls._replace(scale=new_scale))

    def guardian(self, run_dir: str, *, batch_fn=None, cursor=None,
                 handler=None, config=None, **kwargs):
        """Build the self-healing control loop over this engine
        (runtime/guardian.py Guardian): guarded checkpoint ring, anomaly →
        rollback/skip/clamp remediation, hang watchdog.  ``batch_fn(i)``
        must be a pure (seed-stable) host-batch factory; alternatively pass
        a prepared ``DataCursor``.  Reads the ``guardian`` config block
        unless ``config`` overrides it."""
        from deepspeed_tpu.runtime.guardian import Guardian
        return Guardian(self, run_dir, batch_fn=batch_fn, cursor=cursor,
                        handler=handler, config=config, **kwargs)

    def resume_from_latest(self, run_dir: str,
                           warmup: Optional[bool] = None) -> Optional[str]:
        """Resume from the newest COMPLETE universal export under
        ``run_dir`` (``checkpoint.latest_universal``), AOT-warming the step
        programs from the drained host's fingerprints when
        ``resilience.aot_warmup`` is on.  Returns the export path, or None
        on a cold start.  Records ``restarts_total``, the
        ``time_to_resume_ms`` histogram, and the ``resume`` span."""
        from deepspeed_tpu.runtime import resilience
        return resilience.resume(self, run_dir, warmup=warmup)

    def wait_for_checkpoint(self) -> None:
        """Fence for the async checkpoint pipeline: block until any
        in-flight background write fully commits ('latest' moved, the
        in-progress marker removed), re-raising a failed write — a lost
        checkpoint must not look like a successful one.  Also registered
        atexit by the checkpoint module, so a forgotten fence degrades to a
        slow exit, not a torn checkpoint."""
        from deepspeed_tpu.checkpoint import wait_pending
        wait_pending()

    def save_16bit_model(self, save_dir: str,
                         filename: str = "model_states.safetensors") -> str:
        """Consolidated low-precision weight export (reference
        engine.save_16bit_model / _zero3_consolidated_16bit_state_dict
        engine.py:3485,3554): the FULL (unsharded) param tree in the compute
        dtype, one safetensors file with dotted names — loadable without this
        framework.  For HF-architecture models prefer
        checkpoint.hf.save_hf_checkpoint (adds config.json)."""
        import os as _os

        from deepspeed_tpu.checkpoint.universal import _flatten_params
        self._join_host_step()
        _os.makedirs(save_dir, exist_ok=True)
        params = jax.device_get(self.state.params)   # gathers sharded leaves
        flat = {k: np.asarray(v).astype(self.compute_dtype)
                if np.asarray(v).dtype.kind == "f"
                or np.asarray(v).dtype == jnp.bfloat16 else np.asarray(v)
                for k, v in _flatten_params(params).items()}
        path = _os.path.join(save_dir, filename)
        if jax.process_index() == 0:
            import safetensors.numpy
            safetensors.numpy.save_file(flat, path)
        return path

    def export_universal_checkpoint(self, out_dir: str, *,
                                    run_dir: Optional[str] = None) -> str:
        """reference checkpoint/ds_to_universal.py: dump per-parameter fp32
        fragments (+ Adam moments) in a framework-neutral LOGICAL layout any
        topology or toolchain can ingest (pipeline-stacked leaves are
        unstacked to per-layer fragments — checkpoint/reshard.py).  Written
        under the crash-safe commit protocol; ``run_dir`` additionally moves
        the ``latest_universal`` pointer post-commit so elastic workers find
        this export via ``checkpoint.latest_universal(run_dir)``."""
        from deepspeed_tpu.checkpoint import reshard
        from deepspeed_tpu.checkpoint import universal as _u
        self._join_host_step()
        layout = reshard.engine_layout(self)
        if self.offloading:
            return _u.export_universal_offload(
                jax.device_get(self.state.params), self.offload_opt, out_dir,
                step=self.global_steps, layout=layout, run_dir=run_dir)
        # step = global_steps (train_batch count), not state.step: an
        # overflow-skipped update leaves state.step behind, and the resume
        # contract (loss logs, TOTAL_STEPS loops) counts batches
        return _u.export_universal(jax.device_get(self.state), out_dir,
                                   step=self.global_steps, layout=layout,
                                   run_dir=run_dir)

    def _install_fragments(self, frags, step: int, *,
                           strict: bool = True) -> None:
        """Install TARGET-layout fragments into this engine's params /
        masters / Adam moments and re-place them onto the mesh (the
        device_put against ``state_shardings`` IS the resharding: any
        dp/fsdp/pp/tp placement follows from the specs alone)."""
        from deepspeed_tpu.checkpoint.universal import (
            apply_universal, offload_state_dict_from_fragments)
        host = jax.device_get(self.state)
        new = apply_universal(host, frags, strict=strict, step=step)
        new = new._replace(step=jnp.asarray(step, np.asarray(host.step).dtype))
        self.state = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), new, self.state_shardings)
        self.global_steps = step
        self._reset_host_metrics_cache()
        if self.offloading:
            sd = offload_state_dict_from_fragments(host.params, frags, step)
            if len(sd) > 1:
                self.offload_opt.load_state_dict(sd)

    def load_universal_checkpoint(self, universal_dir: str, *,
                                  strict: bool = True) -> dict:
        """reference checkpoint/universal_checkpoint.py
        load_hp_checkpoint_state: install fp32 fragments into this engine's
        params / masters / Adam moments regardless of the mesh, ZeRO stage,
        physical layout (pipeline stage-stacking included), or framework
        that produced them (torch ``fp32.pt`` fragments load too)."""
        from deepspeed_tpu.checkpoint import reshard
        from deepspeed_tpu.checkpoint.universal import load_universal
        self._join_host_step()   # an in-flight update must not overwrite
        frags, meta = load_universal(universal_dir)
        frags = reshard.convert_layout(frags, meta.get("layout"),
                                       reshard.engine_layout(self))
        step = meta.get("step")
        if step is None:
            step = int(np.asarray(jax.device_get(self.state.step)))
        self._install_fragments(frags, int(step), strict=strict)
        return meta

    def _load_cross_topology(self, load_dir: str, tag: str, cause) -> dict:
        """Resharding-restore fallback for load_checkpoint: when the saved
        pytree STRUCTURE does not match this engine (different physical
        layout — e.g. a plain dp/fsdp checkpoint restoring into a
        pipeline-stacked engine — or a different optimizer-state shape
        across ZeRO stages), reduce the tag to LOGICAL universal fragments
        and re-lay them out for this engine (checkpoint/reshard.py; per
        arXiv:2004.13336 this is a sharding-spec transform, not a
        checkpoint-format special case)."""
        import json as _json
        import os

        from deepspeed_tpu.checkpoint import reshard
        cs_path = os.path.join(load_dir, tag, "client_state.json")
        client_state = {}
        if os.path.exists(cs_path):
            with open(cs_path) as f:
                client_state = _json.load(f)
        log_dist(f"load_checkpoint: structured restore of '{tag}' does not "
                 f"match this engine ({cause}); falling back to the "
                 f"cross-topology resharding restore", ranks=[0])
        frags = reshard.fragments_from_orbax(load_dir, tag)
        frags = reshard.convert_layout(frags, client_state.get("layout"),
                                       reshard.engine_layout(self))
        self._install_fragments(frags, int(client_state.get(
            "global_steps", 0)))
        return client_state

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None):
        """reference engine.load_checkpoint (engine.py:2710).  Mesh
        resharding on load comes free from named shardings; a STRUCTURAL
        mismatch (pipeline stacking, cross-stage optimizer shape) falls
        back to the logical-fragment resharding transform
        (_load_cross_topology).  Raises ``checkpoint.CheckpointNotFound`` /
        ``checkpoint.CheckpointCorrupt`` instead of backend-dependent
        errors."""
        from deepspeed_tpu.checkpoint import (latest_tag,
                                              restore_train_state,
                                              wait_pending)
        self._join_host_step()   # an in-flight update must not overwrite
        # surface a failed async write NOW: a lost checkpoint must never be
        # misread as a layout mismatch by the fallback below
        wait_pending()
        tag = tag or latest_tag(load_dir)
        if tag is None:
            return None, {}
        structured = True
        with self.telemetry.span("checkpoint_io", step=self.global_steps,
                                 tag=tag, op="load"):
            try:
                self.state, client_state = restore_train_state(
                    load_dir, tag, self.state_shardings, self.state)
            except (ValueError, TypeError, KeyError) as e:
                # orbax reports a saved-vs-target pytree STRUCTURE mismatch
                # with these; missing/torn tags raise the typed
                # CheckpointNotFound/CheckpointCorrupt and propagate —
                # resharding cannot help those
                client_state = self._load_cross_topology(load_dir, tag, e)
                structured = False
        self.global_steps = int(client_state.get("global_steps", 0))
        self._reset_host_metrics_cache()
        if self.offloading and structured:
            # same-layout restore: host optimizer state rides the npz
            # sidecar.  The cross-topology fallback already installed
            # masters/moments from the LOGICAL fragments (converted for
            # this engine) — the source-physical npz must not clobber them,
            # and a non-offload source has no npz at all.
            import os
            p = os.path.join(load_dir, tag, "offload_state.npz")
            if not os.path.exists(p):
                from deepspeed_tpu.checkpoint import CheckpointCorrupt
                raise CheckpointCorrupt(
                    f"offload checkpoint missing {p}; this checkpoint was "
                    f"saved without offload_optimizer")
            with np.load(p) as sd:
                self.offload_opt.load_state_dict(dict(sd))
        return tag, client_state
