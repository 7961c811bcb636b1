"""deepspeed_tpu: a TPU-native distributed training + inference framework with the
capability surface of DeepSpeed, rebuilt on JAX/XLA/Pallas/pjit.

Top-level API parity (reference deepspeed/__init__.py):
- ``initialize()``     (reference :69)  → build a training engine from (model, config)
- ``init_inference()`` (reference :273) → build an inference engine  [milestone 7]
- ``comm``             (reference deepspeed/comm) → mesh collectives
"""

from __future__ import annotations

import time as _time
from typing import Any, Callable, Optional, Tuple

_IMPORT_T0 = _time.perf_counter()   # to the bottom: gauge import_seconds

from deepspeed_tpu import checkpointing, comm, telemetry, zero
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.runtime.lr_schedules import add_tuning_arguments
from deepspeed_tpu.zero import OnDevice
from deepspeed_tpu.config import DeepSpeedTPUConfig, parse_config
from deepspeed_tpu.engine import DeepSpeedTPUEngine, StepMetrics, TrainState
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader
from deepspeed_tpu.version import __version__

__all__ = [
    "initialize",
    "init_inference",
    "DeepSpeedTPUEngine",
    "DeepSpeedTPUConfig",
    "DeepSpeedDataLoader",
    "RepeatingLoader",
    "TrainState",
    "StepMetrics",
    "comm",
    "telemetry",
    "zero",
    "checkpointing",
    "get_accelerator",
    "default_inference_config",
    "add_tuning_arguments",
    "OnDevice",
    "__version__",
]


def default_inference_config() -> dict:
    """reference deepspeed.default_inference_config (:266): the default
    inference config as an editable dict."""
    from deepspeed_tpu.inference import DeepSpeedInferenceConfig
    return DeepSpeedInferenceConfig().model_dump()


def initialize(model=None,
               config=None,
               example_batch=None,
               training_data=None,
               lr_scheduler: Optional[Callable[[int], float]] = None,
               optimizer=None,
               mesh=None,
               collate_fn: Optional[Callable] = None,
               dist_init_required: Optional[bool] = None,
               args=None,
               config_params=None,
               **kwargs) -> Tuple[DeepSpeedTPUEngine, Any, Any, Any]:
    """Build the training engine (reference deepspeed.initialize,
    deepspeed/__init__.py:69; engine dispatch :166-208).

    Returns ``(engine, optimizer, dataloader, lr_scheduler)`` like the reference.
    The optimizer slot returns the engine's optax transformation; the dataloader is
    built when ``training_data`` is given.

    model: flax linen Module whose ``__call__(batch)`` returns a scalar loss, or an
    ``(init_fn, apply_fn)`` pair (see DeepSpeedTPUEngine docstring).
    example_batch: a host pytree with microbatch-shaped leaves used to trace
    ``model.init``; taken from ``training_data`` if omitted.
    """
    if config is None and config_params is None and args is not None:
        # reference deepspeed/__init__.py: the --deepspeed_config CLI flag
        # (add_config_arguments) supplies the config when none is passed
        config = (getattr(args, "deepspeed_config", None)
                  or getattr(args, "deepscale_config", None))
    cfg = parse_config(config if config is not None else config_params)
    if dist_init_required is None or dist_init_required:
        comm.init_distributed()

    dataloader = None
    if example_batch is None and training_data is not None:
        import itertools

        import jax
        import numpy as np
        it = iter(training_data)
        first = next(it)
        if it is training_data:
            # one-shot iterator/generator: don't lose the peeked example
            training_data = itertools.chain([first], it)
        example_batch = jax.tree_util.tree_map(
            lambda x: np.asarray(x)[None, ...], first)

    if example_batch is None:
        raise ValueError("initialize() needs example_batch or training_data "
                         "to trace model.init")

    if cfg.zero_optimization.offload_param.device != "none":
        # ZeRO-Infinity param offload: engine dispatch at initialize() time,
        # as the reference dispatches PipelineEngine vs DeepSpeedEngine
        # (deepspeed/__init__.py:166-208)
        from deepspeed_tpu.runtime.infinity import InfinityEngine
        if optimizer is not None:
            raise ValueError(
                "offload_param builds its own host Adam (the reference "
                "likewise swaps in DeepSpeedCPUAdam); drop the client "
                "optimizer or the offload")
        engine = InfinityEngine(model=model, config=cfg,
                                example_batch=example_batch, mesh=mesh,
                                lr_scheduler=lr_scheduler)
    else:
        engine = DeepSpeedTPUEngine(model=model, config=cfg,
                                    example_batch=example_batch, mesh=mesh,
                                    lr_scheduler=lr_scheduler,
                                    client_optimizer=optimizer)

    if training_data is not None:
        dataloader = DeepSpeedDataLoader(
            training_data,
            micro_batch_size_per_gpu=int(cfg.train_micro_batch_size_per_gpu),
            gradient_accumulation_steps=int(cfg.gradient_accumulation_steps),
            dp_world_size=engine.dp_world_size,
            collate_fn=collate_fn)

    return engine, engine.optimizer, dataloader, engine.lr_schedule


def init_inference(model=None, config=None, params=None, mesh=None, **kwargs):
    """Build an inference engine (reference deepspeed.init_inference,
    deepspeed/__init__.py:273 → inference/engine.py:39).

    model: GPT-family flax module, GPTConfig, or a path to an HF model
    directory (safetensors — llama/mistral/qwen2/gpt2, see checkpoint/hf.py);
    ``params`` takes trained weights (e.g. ``train_engine.state.params``).
    kwargs merge into the config dict for the reference's
    ``init_inference(model, tensor_parallel=.., dtype=..)`` calling style.
    """
    from deepspeed_tpu.inference import (DeepSpeedInferenceConfig,
                                         InferenceEngine)
    from deepspeed_tpu.checkpoint.hf import is_hf_model_dir, load_hf_checkpoint

    def as_dict(cfg):
        """config path/dict/model → plain dict (the shared normal form)."""
        if cfg is None:
            return {}
        if isinstance(cfg, dict):
            return dict(cfg)
        if isinstance(cfg, str):
            import json
            with open(cfg) as f:
                return json.load(f)
        if isinstance(cfg, DeepSpeedInferenceConfig):
            return cfg.model_dump(by_alias=False)
        raise TypeError(f"config must be dict/path/config model, got "
                        f"{type(cfg)!r}")

    from deepspeed_tpu.checkpoint.diffusion import is_diffusers_model_dir
    if is_diffusers_model_dir(model):
        # SD containers (reference module_inject/containers/{unet,vae}.py)
        from deepspeed_tpu.checkpoint.diffusion import _read_json
        from deepspeed_tpu.inference.config import _DTYPE_ALIASES
        from deepspeed_tpu.inference.diffusion import UNetEngine, VAEEngine
        import os as _os
        if params is not None:
            raise ValueError("pass either a diffusers model dir or params, "
                             "not both")
        if mesh is not None:
            raise ValueError("the SD containers are single-mesh jitted "
                             "forwards; mesh selection is not consumed — "
                             "drop the mesh argument")
        if isinstance(config, DeepSpeedInferenceConfig):
            # only fields the user actually SET count as intent — a full
            # model_dump would make every defaulted field warn
            merged = dict(config.model_dump(exclude_unset=True), **kwargs)
        else:
            merged = dict(as_dict(config), **kwargs)
        # fallback = the inference config class default, NOT a hardcoded
        # fp32 (they must never disagree)
        default_dt = DeepSpeedInferenceConfig().dtype
        raw_dt = str(merged.get("dtype", default_dt)).lower().replace(
            "torch.", "")
        float_aliases = {k: v for k, v in _DTYPE_ALIASES.items()
                         if v.startswith(("float", "bfloat"))}
        if raw_dt not in float_aliases:
            raise ValueError(f"SD containers serve float dtypes; got "
                             f"{merged.get('dtype')!r}, expected one of "
                             f"{sorted(float_aliases)}")
        dt = float_aliases[raw_dt]
        # inert-config-must-scream (config.warn_inert_config policy): the SD
        # engines consume only dtype/channels_last
        from deepspeed_tpu.utils.logging import logger as _logger
        for k in sorted(set(merged) - {"dtype", "channels_last"}):
            _logger.warning(f"inference config key {k!r} is not consumed by "
                            f"the SD containers (only dtype/channels_last "
                            f"are) — this run will NOT honor it")
        cls = _read_json(_os.path.join(str(model),
                                       "config.json"))["_class_name"]
        eng_cls = UNetEngine if cls == "UNet2DConditionModel" else VAEEngine
        return eng_cls(str(model), dtype=dt,
                       channels_last=bool(merged.get("channels_last",
                                                     False)))
    if is_hf_model_dir(model):
        if params is not None:
            raise ValueError("pass either an HF model dir or params, not both")
        import os as _os
        from deepspeed_tpu.checkpoint.hf import (_BERT_LIKE, _CLIP_LIKE,
                                                 _arch_of, _read_json,
                                                 load_hf_bert,
                                                 load_hf_clip_text)
        arch = _arch_of(_read_json(_os.path.join(model, "config.json")))
        if arch in _CLIP_LIKE:
            # clip text tower (reference module_inject/containers/clip.py)
            from deepspeed_tpu.inference.encoder import ClipTextEngine
            ccfg, ctree, extras = load_hf_clip_text(model)
            return ClipTextEngine(ccfg, ctree, extras,
                                  config=dict(as_dict(config), **kwargs),
                                  mesh=mesh)
        if arch in _BERT_LIKE:
            # encoder family: single-shot forward engine (reference bert
            # injection policies, module_inject/containers/bert.py)
            from deepspeed_tpu.inference.encoder import EncoderInferenceEngine
            bcfg, bparams = load_hf_bert(model)
            return EncoderInferenceEngine(bcfg, bparams,
                                          config=dict(as_dict(config),
                                                      **kwargs),
                                          mesh=mesh)
        model, params = load_hf_checkpoint(model)
    if kwargs:
        config = dict(as_dict(config), **kwargs)
    return InferenceEngine(model=model, config=config, params=params, mesh=mesh)


def add_config_arguments(parser):
    """Add the canonical DeepSpeed CLI flags to an argparse parser
    (reference deepspeed.add_config_arguments, deepspeed/__init__.py:250 →
    add_core_arguments): ``--deepspeed`` enable flag, ``--deepspeed_config``
    JSON path, ``--deepscale*`` legacy aliases."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag for user "
                            "scripts; initialize() is what activates it)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed-TPU JSON config file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias of --deepspeed_config")
    return parser


telemetry.startup.ACCOUNT.set_import_seconds(
    _time.perf_counter() - _IMPORT_T0)
