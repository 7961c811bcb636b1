"""Hybrid engine — train ↔ generate weight bridge for RLHF.

Reference parity: ``runtime/hybrid_engine.py:32 DeepSpeedHybridEngine`` — in
RLHF (DeepSpeed-Chat step 3) every PPO iteration interleaves a GENERATE phase
(actor rollouts, inference-optimized) with TRAIN phases on the same weights.
The reference re-layouts each trained module's tensors into its fused
inference containers before generate (``populate_all_inference_policies``,
``_fuse_lora``) and back after; here the "re-layout" is a dtype cast +
device_put into the v2 ragged serving engine's param tree — same flax tree
shape on both sides, so the sync is O(bytes), no graph surgery, and the
serving programs never recompile (shapes/dtypes are stable across syncs).

Usage::

    engine, *_ = deepspeed_tpu.initialize(model, config={
        ..., "hybrid_engine": {"enabled": True}})
    hybrid = HybridEngine(engine)                  # or engine.hybrid_engine()
    out = hybrid.generate(prompts, max_new_tokens=64)   # rollouts
    engine.train_batch(ppo_batch)                       # updates
    out = hybrid.generate(prompts)                      # sees new weights
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.utils.logging import log_dist


class HybridEngine:
    """Wraps a training engine with a v2 ragged serving engine sharing its
    weights (reference DeepSpeedHybridEngine.generate :238 / train-mode
    restore :351)."""

    def __init__(self, train_engine, inference_config: Optional[dict] = None,
                 seed: int = 0):
        from deepspeed_tpu.inference.v2 import InferenceEngineV2

        self.train_engine = train_engine
        model = train_engine.model
        cfg = getattr(model, "cfg", None)
        if cfg is None:
            raise TypeError(
                "HybridEngine needs a GPT-family model (with .cfg); got "
                f"{type(model).__name__}")
        inf_cfg = dict(inference_config or {})
        hx = getattr(train_engine.config, "hybrid_engine", None)
        self._max_out_tokens = None
        self._release_cache = False
        if hx is not None:
            if hx.inference_tp_size > 1:
                inf_cfg.setdefault("tensor_parallel",
                                   {"tp_size": hx.inference_tp_size})
            self._max_out_tokens = int(hx.max_out_tokens)
            self._release_cache = bool(hx.release_inference_cache)
            if not hx.pin_parameters or hx.tp_gather_partition_size != 8:
                log_dist("hybrid_engine.pin_parameters/"
                         "tp_gather_partition_size are GPU memory-pool knobs "
                         "with no TPU analog — accepted but inert", ranks=[0])
        self._serving = InferenceEngineV2(
            cfg, inf_cfg, params=self._train_params(), seed=seed)
        self._cache_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self._serving.cache)
        self._synced_step = int(train_engine.global_steps)
        self._in_generate = False
        log_dist("hybrid engine ready: serving tree synced from training "
                 f"params at step {self._synced_step}", ranks=[0])

    # ------------------------------------------------------------- weights
    def _train_params(self):
        from deepspeed_tpu.parallel.metadata import unbox
        params = unbox(self.train_engine.state.params)
        if isinstance(params, dict) and "params" in params:
            params = params["params"]
        return params

    def sync_weights(self) -> None:
        """Push current training weights into the serving tree (reference:
        the per-generate re-layout).  Serving shardings/dtypes are preserved,
        so compiled serving programs stay valid."""
        src = self._train_params()
        dst = self._serving.params

        def cast_like(s, d):
            s = jnp.asarray(s)
            if s.dtype != d.dtype:
                s = s.astype(d.dtype)
            return jax.device_put(s, d.sharding)
        self._serving.params = jax.tree_util.tree_map(cast_like, src, dst)
        self._synced_step = int(self.train_engine.global_steps)

    # ------------------------------------------------------------ generate
    def generate(self, prompts: Sequence[np.ndarray],
                 max_new_tokens: int = 32, **gen_overrides) -> List[Any]:
        """Rollout phase (reference hybrid_engine.generate :238): weights are
        re-synced iff training stepped since the last sync, then the ragged
        engine serves the prompts with continuous batching."""
        if self._max_out_tokens and max_new_tokens > self._max_out_tokens:
            raise ValueError(
                f"max_new_tokens {max_new_tokens} exceeds "
                f"hybrid_engine.max_out_tokens {self._max_out_tokens}")
        if int(self.train_engine.global_steps) != self._synced_step:
            self.sync_weights()
        if self._serving.cache is None:       # re-arm after a released phase
            self._serving.cache = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), self._cache_template)
        self._in_generate = True
        try:
            return self._serving.generate(prompts,
                                          max_new_tokens=max_new_tokens,
                                          **gen_overrides)
        finally:
            self._in_generate = False
            if self._release_cache:
                # free the paged KV pool's HBM between phases (reference
                # release_inference_cache → free_cache)
                for leaf in jax.tree_util.tree_leaves(self._serving.cache):
                    leaf.delete()
                self._serving.cache = None

    @property
    def serving_engine(self):
        return self._serving

    def eval(self):
        """API-parity mode toggles (reference eval() :351 / train() :364):
        phase bookkeeping only — there is no module graph to swap here."""
        return self

    def train(self):
        return self
