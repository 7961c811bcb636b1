"""HF checkpoint engine — stream safetensors checkpoints into the flax tree.

TPU-native analog of the reference's checkpoint engines + injection-policy
model zoo: ``HuggingFaceCheckpointEngine`` (inference/v2/checkpoint/
huggingface_engine.py:124) iterates safetensors shards and yields tensors;
``replace_module`` (module_inject/replace_module.py:183) + the per-arch
containers (module_inject/containers/) map them onto fused modules.  Here the
zoo is a NAME MAP per architecture onto the one GPT-family flax tree
(models/gpt.py) — llama/mistral/qwen2/gpt2 are all config points of the same
module, so "injection" is a dict of weight transposes, not graph surgery.

Entry points:
- ``config_from_hf(path)``   → GPTConfig from an HF ``config.json``
- ``load_hf_checkpoint(path)`` → (GPTConfig, params tree) streaming shards
- ``deepspeed_tpu.init_inference("path/to/hf")`` and the v2 engine accept an
  HF model directory directly.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.utils.logging import log_dist

# architectures served by the GPT-family tree (reference zoo:
# inference/v2/model_implementations/{llama_v2,mistral,mixtral,qwen_v2,opt,
# phi,falcon}, module_inject/containers/{gpt2,opt}.py)
_LLAMA_LIKE = {"LlamaForCausalLM", "MistralForCausalLM", "Qwen2ForCausalLM",
               "MixtralForCausalLM"}
_GPT2_LIKE = {"GPT2LMHeadModel"}
_OPT_LIKE = {"OPTForCausalLM"}
_PHI_LIKE = {"PhiForCausalLM"}
_FALCON_LIKE = {"FalconForCausalLM"}
_GPTJ_LIKE = {"GPTJForCausalLM"}
_NEOX_LIKE = {"GPTNeoXForCausalLM"}
_GPTNEO_LIKE = {"GPTNeoForCausalLM"}
_STABLELM_LIKE = {"StableLmForCausalLM"}
_BIGCODE_LIKE = {"GPTBigCodeForCausalLM"}
_GEMMA_LIKE = {"GemmaForCausalLM"}
_PHI3_LIKE = {"Phi3ForCausalLM"}
_BLOOM_LIKE = {"BloomForCausalLM"}
# config only (``afmoe_config``); no weight loader yet
_AFMOE_LIKE = {"AfmoeForCausalLM"}
SUPPORTED_ARCHITECTURES = sorted(_LLAMA_LIKE | _GPT2_LIKE | _OPT_LIKE
                                 | _PHI_LIKE | _FALCON_LIKE | _GPTJ_LIKE
                                 | _NEOX_LIKE | _BLOOM_LIKE | _GPTNEO_LIKE
                                 | _STABLELM_LIKE | _BIGCODE_LIKE
                                 | _GEMMA_LIKE | _PHI3_LIKE)


# HF ACT2FN name → models.gpt.mlp_activation name (HF "gelu" is exact erf;
# "gelu_new"/"gelu_pytorch_tanh" are the tanh approximation)
_HF_ACT = {"gelu": "gelu_exact", "gelu_new": "gelu",
           "gelu_pytorch_tanh": "gelu", "relu": "relu",
           "quick_gelu": "quick_gelu"}


def _map_activation(arch: str, name: str) -> str:
    try:
        return _HF_ACT[name]
    except KeyError:
        raise ValueError(f"{arch}: activation {name!r} is not implemented; "
                         f"supported: {sorted(_HF_ACT)}") from None


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _arch_of(hf: Dict[str, Any]) -> str:
    archs = hf.get("architectures") or []
    return archs[0] if archs else hf.get("model_type", "?")


def _reject_unsupported_semantics(hf: Dict[str, Any], arch: str,
                                  max_seq_len: Optional[int]) -> None:
    """Raise rather than silently serve a DIFFERENT model: config fields that
    change the math must be implemented or rejected (round-2 review)."""
    scaling = hf.get("rope_scaling")
    if scaling and scaling.get("rope_type", scaling.get("type")) not in (
            "default", "llama3", "linear", "longrope"):
        raise ValueError(
            f"{arch}: rope_scaling={scaling!r} is not implemented "
            f"(yarn/dynamic); logits would be silently wrong")
    if hf.get("mlp_bias"):
        raise ValueError(
            f"{arch}: mlp_bias=true (gate/up/down biases) is not implemented "
            f"in the SwiGLU body; logits would be silently wrong")


def _rope_scaling_of(hf: Dict[str, Any]):
    """HF rope_scaling dict → GPTConfig.rope_scaling tuple (llama-3.1
    piecewise scheme and linear position interpolation; anything else was
    rejected by _reject_unsupported_semantics)."""
    scaling = hf.get("rope_scaling")
    if not scaling:
        return None
    kind = scaling.get("rope_type", scaling.get("type"))
    try:
        if kind == "llama3":
            return ("llama3", float(scaling["factor"]),
                    float(scaling["low_freq_factor"]),
                    float(scaling["high_freq_factor"]),
                    float(scaling["original_max_position_embeddings"]))
        if kind == "linear":
            return ("linear", float(scaling["factor"]))
        if kind == "longrope":
            # phi-3 long-context (HF _compute_longrope_parameters): per-
            # channel short/long factors + the paper's attention factor.
            # HF precedence: a (top-level or scaling-dict) original_max
            # overrides rope_scaling["factor"] via msl/orig; with neither
            # the extension ratio is underivable — reject, don't guess.
            import math as _math
            short = tuple(float(x) for x in scaling["short_factor"])
            long_ = tuple(float(x) for x in scaling["long_factor"])
            msl = float(hf.get("max_position_embeddings", 2048))
            orig = (hf.get("original_max_position_embeddings")
                    or scaling.get("original_max_position_embeddings"))
            if orig is not None:
                orig = float(orig)
                factor = msl / orig
            elif scaling.get("factor") is not None:
                orig = msl            # HF fallback: orig = max_position
                factor = float(scaling["factor"])
            else:
                raise ValueError(
                    "rope_scaling longrope needs "
                    "original_max_position_embeddings (top-level or in "
                    "rope_scaling) or a 'factor' — neither present; the "
                    "attention factor and regime boundary are underivable")
            att = scaling.get("attention_factor")
            if att is None:
                att = (1.0 if factor <= 1.0 else
                       _math.sqrt(1.0 + _math.log(factor)
                                  / _math.log(orig)))
            return ("longrope", float(att), short, long_, orig)
    except KeyError as e:
        raise ValueError(
            f"rope_scaling type {kind!r} is missing required key {e} "
            f"(got keys {sorted(scaling)}) — corrupt or hand-edited "
            f"config.json") from None
    return None
def _sliding_window_of(hf: Dict[str, Any],
                       max_seq_len: Optional[int]) -> Optional[int]:
    """Effective sliding window (mistral/qwen2): None when disabled or when
    the window never binds at the serving length."""
    window = hf.get("sliding_window")
    uses_window = window is not None and (
        hf.get("use_sliding_window", True) if "use_sliding_window" in hf
        else True)
    if not uses_window:
        return None
    msl = hf.get("max_position_embeddings", 2048)
    eff = min(msl, max_seq_len or msl)
    return int(window) if window < eff else None


# afmoe (Arcee Trinity): published tensor name -> path in the GPT parameter
# tree ({i} a layer, {e} an expert; torch Linear weights are [out, in] and
# transpose on the way in, q/k/v/gate reshape to [H, heads, d], o to
# [heads, d, H], experts stack on a leading axis).  Names as the family's
# published modelling code has them; no checkpoint was at hand to check them
# against, and no loader reads this table yet.
AFMOE_WEIGHT_NAMES = {
    "model.embed_tokens.weight": "backbone/wte",
    "model.norm.weight": "backbone/final_norm/scale",
    "lm_head.weight": "lm_head",
    "model.layers.{i}.input_layernorm.weight": "backbone/block_{i}/Norm_0/scale",
    "model.layers.{i}.post_attention_layernorm.weight":
        "backbone/block_{i}/post_attn_norm/scale",
    "model.layers.{i}.pre_mlp_layernorm.weight": "backbone/block_{i}/Norm_1/scale",
    "model.layers.{i}.post_mlp_layernorm.weight":
        "backbone/block_{i}/post_ffn_norm/scale",
    "model.layers.{i}.self_attn.q_proj.weight": "backbone/block_{i}/Attention_0/wq",
    "model.layers.{i}.self_attn.k_proj.weight": "backbone/block_{i}/Attention_0/wk",
    "model.layers.{i}.self_attn.v_proj.weight": "backbone/block_{i}/Attention_0/wv",
    "model.layers.{i}.self_attn.o_proj.weight": "backbone/block_{i}/Attention_0/wo",
    "model.layers.{i}.self_attn.gate_proj.weight":
        "backbone/block_{i}/Attention_0/wgate",
    "model.layers.{i}.self_attn.q_norm.weight": "backbone/block_{i}/Attention_0/q_norm",
    "model.layers.{i}.self_attn.k_norm.weight": "backbone/block_{i}/Attention_0/k_norm",
    # dense layers (the first num_dense_layers)
    "model.layers.{i}.mlp.gate_proj.weight": "backbone/block_{i}/MLP_0/wg",
    "model.layers.{i}.mlp.up_proj.weight": "backbone/block_{i}/MLP_0/wi",
    "model.layers.{i}.mlp.down_proj.weight": "backbone/block_{i}/MLP_0/wo",
    # expert layers
    "model.layers.{i}.mlp.router.gate.weight": "backbone/block_{i}/moe/gate",
    "model.layers.{i}.mlp.expert_bias": "backbone/block_{i}/moe/expert_bias",
    "model.layers.{i}.mlp.experts.{e}.gate_proj.weight": "backbone/block_{i}/moe/wge",
    "model.layers.{i}.mlp.experts.{e}.up_proj.weight": "backbone/block_{i}/moe/wi",
    "model.layers.{i}.mlp.experts.{e}.down_proj.weight": "backbone/block_{i}/moe/wo",
    "model.layers.{i}.mlp.shared_experts.gate_proj.weight":
        "backbone/block_{i}/moe/shared_wg",
    "model.layers.{i}.mlp.shared_experts.up_proj.weight":
        "backbone/block_{i}/moe/shared_wi",
    "model.layers.{i}.mlp.shared_experts.down_proj.weight":
        "backbone/block_{i}/moe/shared_wo",
}


def afmoe_config(hf: Dict[str, Any], *, max_seq_len: Optional[int] = None,
                 dtype=None, experts_held: Optional[int] = None,
                 expert_offset: int = 0):
    """GPTConfig of a published ``afmoe`` ``config.json`` (Arcee Trinity):
    sigmoid-routed experts with a selection bias beside a shared expert after
    ``num_dense_layers`` dense layers; gated attention with q/k norms, a
    sliding window with RoPE on ``sliding_attention`` layers and no position
    on ``full_attention`` ones; sandwich norms; embeddings scaled by
    sqrt(hidden) under ``mup_enabled``.  ``experts_held``/``expert_offset``
    give one chip's share of the experts."""
    from deepspeed_tpu.models.gpt import GPTConfig
    if hf.get("score_func", "sigmoid") != "sigmoid" \
            or hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("afmoe: only sigmoid scores without expert groups "
                         "are implemented")
    if hf.get("rope_scaling"):
        raise ValueError("afmoe: rope_scaling is not implemented")
    hidden, layers = hf["hidden_size"], hf["num_hidden_layers"]
    every = hf.get("global_attn_every_n_layers", 4)
    types = hf.get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else "sliding_attention"
        for i in range(layers)]
    msl = hf.get("max_position_embeddings", 2048)
    return GPTConfig(
        vocab_size=hf["vocab_size"], num_layers=layers,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads"),
        head_dim=hf.get("head_dim") or hidden // hf["num_attention_heads"],
        hidden_size=hidden, mlp_dim_override=hf["intermediate_size"],
        max_seq_len=min(msl, max_seq_len or msl),
        use_rope=True, rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_layers="window", use_rmsnorm=True,
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)), gated_mlp=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        embed_scale=float(hidden) ** 0.5 if hf.get("mup_enabled") else None,
        sliding_window=hf["sliding_window"],
        local_attn_layers=tuple(i for i, t in enumerate(types)
                                if t == "sliding_attention"),
        attn_gate=True, qk_norm=True, sandwich_norm=True,
        num_experts=hf["num_experts"], moe_k=hf["num_experts_per_tok"],
        moe_dropless=True, moe_router="sigmoid",
        moe_route_norm=bool(hf.get("route_norm", True)),
        moe_route_scale=float(hf.get("route_scale", 1.0)),
        moe_router_bias=True,
        moe_shared_dim=hf["moe_intermediate_size"]
        * hf.get("num_shared_experts", 0),
        moe_expert_dim=hf["moe_intermediate_size"],
        moe_dense_layers=hf.get("num_dense_layers", 0),
        experts_held=experts_held, expert_offset=expert_offset,
        dtype=dtype or jnp.bfloat16)


# deepseek_v3 with latent attention (Moonlight; with a query latent,
# ``q_lora_rank``, ``q_proj`` gives way to DEEPSEEK_V3_QUERY_LATENT_NAMES):
# published tensor name -> path in the GPT parameter tree, as above: the names
# the loader (``_deepseek_v3_tree``) reads, held to it name by name in
# tests/test_moonlight.py.  The published weights pair
# NEIGHBOURING rotary columns, this model rotates halves: the 64 rope columns
# of each ``q_proj`` head and of ``kv_a_proj_with_mqa`` are permuted on the
# way in (``_rope_interleave_perm``).
DEEPSEEK_V3_WEIGHT_NAMES = {
    "model.embed_tokens.weight": "backbone/wte",
    "model.norm.weight": "backbone/final_norm/scale",
    "lm_head.weight": "lm_head",
    "model.layers.{i}.input_layernorm.weight": "backbone/block_{i}/Norm_0/scale",
    "model.layers.{i}.post_attention_layernorm.weight":
        "backbone/block_{i}/Norm_1/scale",
    "model.layers.{i}.self_attn.q_proj.weight": "backbone/block_{i}/Attention_0/wq",
    "model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight":
        "backbone/block_{i}/Attention_0/wkv_a",
    "model.layers.{i}.self_attn.kv_a_layernorm.weight":
        "backbone/block_{i}/Attention_0/kv_norm",
    "model.layers.{i}.self_attn.kv_b_proj.weight":
        "backbone/block_{i}/Attention_0/wkv_b",
    "model.layers.{i}.self_attn.o_proj.weight": "backbone/block_{i}/Attention_0/wo",
    # dense layers (the first first_k_dense_replace)
    "model.layers.{i}.mlp.gate_proj.weight": "backbone/block_{i}/MLP_0/wg",
    "model.layers.{i}.mlp.up_proj.weight": "backbone/block_{i}/MLP_0/wi",
    "model.layers.{i}.mlp.down_proj.weight": "backbone/block_{i}/MLP_0/wo",
    # expert layers
    "model.layers.{i}.mlp.gate.weight": "backbone/block_{i}/moe/gate",
    "model.layers.{i}.mlp.gate.e_score_correction_bias":
        "backbone/block_{i}/moe/expert_bias",
    "model.layers.{i}.mlp.experts.{e}.gate_proj.weight": "backbone/block_{i}/moe/wge",
    "model.layers.{i}.mlp.experts.{e}.up_proj.weight": "backbone/block_{i}/moe/wi",
    "model.layers.{i}.mlp.experts.{e}.down_proj.weight": "backbone/block_{i}/moe/wo",
    "model.layers.{i}.mlp.shared_experts.gate_proj.weight":
        "backbone/block_{i}/moe/shared_wg",
    "model.layers.{i}.mlp.shared_experts.up_proj.weight":
        "backbone/block_{i}/moe/shared_wi",
    "model.layers.{i}.mlp.shared_experts.down_proj.weight":
        "backbone/block_{i}/moe/shared_wo",
}


# ... in place of ``q_proj`` where the model has a query latent
DEEPSEEK_V3_QUERY_LATENT_NAMES = {
    "model.layers.{i}.self_attn.q_a_proj.weight":
        "backbone/block_{i}/Attention_0/wq_a",
    "model.layers.{i}.self_attn.q_a_layernorm.weight":
        "backbone/block_{i}/Attention_0/q_norm",
    "model.layers.{i}.self_attn.q_b_proj.weight":
        "backbone/block_{i}/Attention_0/wq_b",
}

# dots3_note (dots3-note-prev): deepseek_v3's names with a query latent on
# every layer, a headwise gate, and on the full layers the indexer under the
# names of the published DeepSeek-V3.2 indexer (whose RoPE already rotates
# halves: its columns are NOT permuted).  ASSUMED from the family: the
# catalog gives this model's config.json, not its tensor names.
DOTS3_NOTE_WEIGHT_NAMES = {
    **{k: v for k, v in DEEPSEEK_V3_WEIGHT_NAMES.items()
       if not k.endswith("q_proj.weight")},
    **DEEPSEEK_V3_QUERY_LATENT_NAMES,
    "model.layers.{i}.self_attn.g_proj.weight":
        "backbone/block_{i}/Attention_0/wgate",
    "model.layers.{i}.self_attn.indexer.wq_b.weight":
        "backbone/block_{i}/Attention_0/wq_idx",
    "model.layers.{i}.self_attn.indexer.wk.weight":
        "backbone/block_{i}/Attention_0/wk_idx",
    "model.layers.{i}.self_attn.indexer.k_norm.weight":
        "backbone/block_{i}/Attention_0/k_idx_norm_scale",
    "model.layers.{i}.self_attn.indexer.k_norm.bias":
        "backbone/block_{i}/Attention_0/k_idx_norm_bias",
    "model.layers.{i}.self_attn.indexer.weights_proj.weight":
        "backbone/block_{i}/Attention_0/ww_idx",
}


def dots3_note_config(hf: Dict[str, Any], *,
                      max_seq_len: Optional[int] = None, dtype=None):
    """GPTConfig of a published ``dots3_note`` ``config.json``
    (dots3-note-prev): deepseek_v3's expert layers; latent attention with a
    query latent and a headwise gate on every layer; the full layers
    (``layer_types``) select their ``index_topk`` keys with an indexer, the
    sliding ones have a latent geometry of their own (the ``swa_*`` keys)
    under ``sliding_window_size``.  ``apply_mla_qkv_lora_rescale`` and the
    gate are read as benchmark/configs/dots3-note-prev-5l-ep8.json says
    (``assumed``)."""
    import dataclasses
    for key, ok, what in (
            ("attention_gate_type",
             hf.get("attention_gate_type") == "headwise"
             == hf.get("swa_attention_gate_type"), "another gate"),
            ("layer_types", len(hf.get("layer_types", ()))
             >= hf["num_hidden_layers"], "a pattern shorter than the model"),
            ("topk_method", hf.get("topk_method", "noaux_tc") == "noaux_tc",
             "another selection of experts")):
        if not ok:
            raise NotImplementedError(
                f"dots3_note: {key}={hf.get(key)!r}: {what} is not built")
    base = deepseek_v3_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    return dataclasses.replace(
        base, mla_lora_rescale=bool(hf.get("apply_mla_qkv_lora_rescale")),
        attn_gate_headwise=True, index_topk=hf["index_topk"],
        index_n_heads=hf["index_n_heads"],
        index_head_dim=hf["index_head_dim"],
        sliding_window=int(hf["sliding_window_size"]),
        local_attn_layers=tuple(
            i for i in range(hf["num_hidden_layers"])
            if hf["layer_types"][i] == "sliding_attention"),
        window_attn=(
            ("num_heads", hf["swa_num_attention_heads"]),
            ("head_dim", hf["swa_qk_nope_head_dim"]
             + hf["swa_qk_rope_head_dim"]),
            ("v_head_dim", hf["swa_v_head_dim"]),
            ("kv_lora_rank", hf["swa_kv_lora_rank"]),
            ("q_lora_rank", hf["swa_q_lora_rank"]),
            ("qk_rope_head_dim", hf["swa_qk_rope_head_dim"]),
            ("rope_theta", float(hf["swa_rope_theta"]))))


def _yarn_of(hf: Dict[str, Any]):
    """A ``deepseek_v3``-style ``rope_scaling`` block of type yarn ->
    ``GPTConfig.rope_scaling`` ``("yarn", factor, beta_fast, beta_slow,
    original_max, mscale, mscale_all_dim)`` (models/gpt.py
    ``_scale_rope_freq``, ``yarn_factors``); None for anything else."""
    rs = hf.get("rope_scaling") or {}
    if rs.get("rope_type", rs.get("type")) != "yarn":
        return None
    return ("yarn", float(rs["factor"]), float(rs.get("beta_fast", 32)),
            float(rs.get("beta_slow", 1)),
            float(rs["original_max_position_embeddings"]),
            float(rs.get("mscale", 0)), float(rs.get("mscale_all_dim", 0)))


def xing4_0_config(hf: Dict[str, Any], *, max_seq_len: Optional[int] = None,
                   dtype=None):
    """GPTConfig of a published ``xing4_0`` ``config.json``
    (Xing4.0-29B-A4B): deepseek_v3's latent attention (with a query latent,
    under YaRN) and expert layers, and a residual of ``hc_mult`` streams
    mixed by manifold-constrained hyper-connections round every sublayer
    (models/gpt.py ``hc_maps``).  The multi-token-prediction module
    (``num_nextn_predict_layers``) is a draft head and is not built:
    ``deepseek_v3_config`` refuses it."""
    import dataclasses
    if (hf.get("hc_mult", 0) or 0) < 2:
        raise NotImplementedError(
            f"xing4_0: hc_mult={hf.get('hc_mult')!r}: an xing4_0 model "
            f"without residual streams is not built")
    base = deepseek_v3_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    return dataclasses.replace(
        base, hc_mult=int(hf["hc_mult"]),
        hc_sinkhorn_iters=int(hf.get("hc_sinkhorn_iters", 20)),
        hc_eps=float(hf.get("hc_eps", 1e-6)),
        hc_res_clamp=(float(hf.get("mhc_h_res_clamp_min", -30.0)),
                      float(hf.get("mhc_h_res_clamp_max", 30.0))))


def mimo_v2_flash_config(hf: Dict[str, Any], *,
                         max_seq_len: Optional[int] = None, dtype=None,
                         experts_held: Optional[int] = None,
                         expert_offset: int = 0):
    """GPTConfig of a published ``mimo_v2_flash`` ``config.json``
    (MiMo-V2-Flash): ``hybrid_layer_pattern`` (0 = full, 1 = sliding window)
    over grouped-query layers of two geometries, the full layers' (``num_*``,
    ``rope_theta``) and the window layers' (``swa_*``: their own kv heads and
    rope base, ``GPTConfig.window_attn``), keys ``head_dim`` wide and values
    ``v_head_dim``; the leading ``partial_rotary_factor`` of a head rotates;
    the values scaled by ``attention_value_scale``; a learned sink logit a
    head where ``add_swa_attention_sink_bias`` /
    ``add_full_attention_sink_bias`` say (``attn_sink``); ``moe_layer_freq``
    (a list: leading dense layers, then experts), sigmoid-routed experts with
    a selection bias (``noaux_tc``) and no shared expert.
    ``experts_held``/``expert_offset`` give one chip's share of the experts.
    The multi-token-prediction layers the family is described with are a
    draft head, named by no key of the config, and are not built.
    ``attention_chunk_size`` is carried by the config and read by nothing."""
    from deepspeed_tpu.models.gpt import GPTConfig
    n = hf["num_hidden_layers"]
    pattern = list(hf["hybrid_layer_pattern"])[:n]
    freq = hf.get("moe_layer_freq", 1)
    freq = [int(freq)] * n if isinstance(freq, int) else list(freq)[:n]
    dense = freq.index(1) if 1 in freq else n
    sinks = (bool(hf.get("add_swa_attention_sink_bias", False)),
             bool(hf.get("add_full_attention_sink_bias", False)))
    for key, ok, what in (
            ("n_group", hf.get("n_group", 1) == 1
             and hf.get("topk_group", 1) == 1, "group-limited routing"),
            ("scoring_func", hf.get("scoring_func", "sigmoid") == "sigmoid",
             "softmax scores"),
            ("topk_method", hf.get("topk_method", "noaux_tc") == "noaux_tc",
             "a selection without the noaux_tc bias"),
            ("n_shared_experts", not hf.get("n_shared_experts"),
             "shared experts"),
            ("moe_layer_freq", all(freq[dense:]) and len(freq) == n,
             "dense layers among the expert layers"),
            ("hybrid_layer_pattern", len(pattern) == n and set(pattern)
             <= {0, 1}, "a pattern that does not name every layer"),
            ("add_full_attention_sink_bias", sinks != (False, True),
             "a sink on the full layers alone"),
            ("attention_bias", not hf.get("attention_bias", False),
             "attention biases"),
            ("rope_scaling", (hf.get("rope_scaling") or {}).get(
                "rope_type", "default") == "default", "rope scaling")):
        if not ok:
            raise NotImplementedError(
                f"mimo_v2_flash: {key}={hf.get(key)!r}: {what} is not built")
    window_attn = tuple(
        (field, hf[swa]) for field, swa, full in (
            ("num_heads", "swa_num_attention_heads", "num_attention_heads"),
            ("num_kv_heads", "swa_num_key_value_heads",
             "num_key_value_heads"),
            ("head_dim", "swa_head_dim", "head_dim"),
            ("v_head_dim", "swa_v_head_dim", "v_head_dim"))
        if hf.get(swa, hf[full]) != hf[full])
    theta = float(hf.get("rope_theta", 10000.0))
    if float(hf.get("swa_rope_theta", theta)) != theta:
        window_attn += (("rope_theta", float(hf["swa_rope_theta"])),)
    msl = hf.get("max_position_embeddings", 2048)
    return GPTConfig(
        vocab_size=hf["vocab_size"], num_layers=n,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        v_head_dim=hf.get("v_head_dim", hf["head_dim"]),
        hidden_size=hf["hidden_size"],
        mlp_dim_override=hf["intermediate_size"],
        max_seq_len=min(msl, max_seq_len or msl),
        use_rope=True, rope_theta=theta,
        rope_pct=float(hf.get("partial_rotary_factor", 1.0)),
        use_rmsnorm=True, norm_eps=float(hf.get("layernorm_epsilon", 1e-5)),
        gated_mlp=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        sliding_window=int(hf["sliding_window"]),
        local_attn_layers=tuple(i for i, w in enumerate(pattern) if w),
        window_attn=window_attn,
        attn_sink={(True, True): "all", (True, False): "window",
                   (False, False): None}[sinks],
        attn_value_scale=(float(hf["attention_value_scale"])
                          if hf.get("attention_value_scale") else None),
        num_experts=hf["n_routed_experts"], moe_k=hf["num_experts_per_tok"],
        moe_dropless=True, moe_router="sigmoid",
        moe_route_norm=bool(hf.get("norm_topk_prob", True)),
        moe_route_scale=float(hf.get("routed_scaling_factor") or 1.0),
        moe_router_bias=True, moe_expert_dim=hf["moe_intermediate_size"],
        moe_dense_layers=dense, experts_held=experts_held,
        expert_offset=expert_offset, dtype=dtype or jnp.bfloat16)


def deepseek_v3_config(hf: Dict[str, Any], *,
                       max_seq_len: Optional[int] = None, dtype=None):
    """GPTConfig of a published ``deepseek_v3`` ``config.json`` of the shape
    Moonlight has: latent attention (keys and values from one normed latent
    of ``kv_lora_rank`` beside one rotated key part shared by all heads,
    queries straight from the hidden state, or through a query latent where
    ``q_lora_rank`` is set), then ``first_k_dense_replace``
    dense layers and sigmoid-routed experts with a selection bias beside
    shared experts (one SwiGLU of ``n_shared_experts`` widths)."""
    from deepspeed_tpu.models.gpt import GPTConfig
    for key, ok, what in (
            ("n_group", hf.get("n_group", 1) == 1
             and hf.get("topk_group", 1) == 1, "group-limited routing"),
            ("rope_scaling", _yarn_of(hf) is not None
             or not hf.get("rope_scaling"), "rope scaling other than yarn"),
            ("num_nextn_predict_layers",
             not hf.get("num_nextn_predict_layers", 0),
             "multi-token prediction layers"),
            ("scoring_func", hf.get("scoring_func", "sigmoid") == "sigmoid",
             "softmax scores"),
            ("moe_layer_freq", hf.get("moe_layer_freq", 1) == 1,
             "dense layers among the expert layers"),
            ("attention_bias", not hf.get("attention_bias", False),
             "attention biases")):
        if not ok:
            raise NotImplementedError(
                f"deepseek_v3: {key}={hf.get(key)!r}: {what} is not built")
    nope, rot = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    msl = hf.get("max_position_embeddings", 2048)
    return GPTConfig(
        vocab_size=hf["vocab_size"], num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"], head_dim=nope + rot,
        hidden_size=hf["hidden_size"],
        mlp_dim_override=hf["intermediate_size"],
        max_seq_len=min(msl, max_seq_len or msl),
        use_rope=True, rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=_yarn_of(hf),
        use_rmsnorm=True, norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        gated_mlp=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        kv_lora_rank=hf["kv_lora_rank"], qk_rope_head_dim=rot,
        v_head_dim=hf["v_head_dim"], q_lora_rank=hf.get("q_lora_rank") or 0,
        num_experts=hf["n_routed_experts"], moe_k=hf["num_experts_per_tok"],
        moe_dropless=True, moe_router="sigmoid",
        moe_route_norm=bool(hf.get("norm_topk_prob", True)),
        moe_route_scale=float(hf.get("routed_scaling_factor", 1.0)),
        moe_router_bias=True,
        moe_shared_dim=hf["moe_intermediate_size"]
        * hf.get("n_shared_experts", 0),
        moe_expert_dim=hf["moe_intermediate_size"],
        moe_dense_layers=hf.get("first_k_dense_replace", 0),
        dtype=dtype or jnp.bfloat16)


def _deepseek_v3_tree(r, cfg) -> Dict[str, Any]:
    """deepseek_v3 / dots3_note (latent attention) -> flax tree, by
    ``DEEPSEEK_V3_WEIGHT_NAMES`` (``DOTS3_NOTE_WEIGHT_NAMES``); ``r`` has
    ``get(name)`` (a ``_ShardReader``, or any mapping of published names to
    arrays).  Each layer is read at its own attention geometry
    (``cfg.for_layer``)."""
    from deepspeed_tpu.models.gpt import mla_split
    H = cfg.hidden_size

    def lin(name):                       # torch Linear: [out, in]
        return np.asarray(r.get(name)).T

    bb: Dict[str, Any] = {
        "wte": np.asarray(r.get("model.embed_tokens.weight")),
        "final_norm": {"scale": np.asarray(r.get("model.norm.weight"))}}
    for i in range(cfg.num_layers):
        lc = cfg.for_layer(i)
        nh, hd, rank = lc.num_heads, lc.head_dim, lc.kv_lora_rank
        nope, rot, vd = mla_split(lc)
        pairs = _rope_interleave_perm(rot, rot)   # the rope columns come last
        q_perm = np.concatenate([np.arange(nope), nope + pairs])
        kv_perm = np.concatenate([np.arange(rank), rank + pairs])
        p = f"model.layers.{i}."
        a = p + "self_attn."
        attn = {
            "wkv_a": lin(a + "kv_a_proj_with_mqa.weight")[:, kv_perm],
            "kv_norm": np.asarray(r.get(a + "kv_a_layernorm.weight")),
            "wkv_b": lin(a + "kv_b_proj.weight").reshape(
                rank, nh, nope + vd),
            "wo": lin(a + "o_proj.weight").reshape(nh, vd, H)}
        if lc.q_lora_rank:
            attn.update(
                wq_a=lin(a + "q_a_proj.weight"),
                q_norm=np.asarray(r.get(a + "q_a_layernorm.weight")),
                wq_b=lin(a + "q_b_proj.weight").reshape(
                    lc.q_lora_rank, nh, hd)[:, :, q_perm])
        else:
            attn["wq"] = lin(a + "q_proj.weight").reshape(H, nh, hd)[
                :, :, q_perm]
        if lc.attn_gate_headwise:
            attn["wgate"] = lin(a + "g_proj.weight")
        if lc.index_topk:
            x = a + "indexer."
            attn.update(
                wq_idx=lin(x + "wq_b.weight").reshape(
                    lc.q_lora_rank, lc.index_n_heads, lc.index_head_dim),
                wk_idx=lin(x + "wk.weight"),
                k_idx_norm_scale=np.asarray(r.get(x + "k_norm.weight")),
                k_idx_norm_bias=np.asarray(r.get(x + "k_norm.bias")),
                ww_idx=lin(x + "weights_proj.weight"))
        blk = {
            "Norm_0": {"scale": np.asarray(
                r.get(p + "input_layernorm.weight"))},
            "Norm_1": {"scale": np.asarray(
                r.get(p + "post_attention_layernorm.weight"))},
            "Attention_0": attn}
        m = p + "mlp."
        if cfg.is_moe_layer(i):
            def stack(what):
                return np.stack([lin(f"{m}experts.{e}.{what}.weight")
                                 for e in range(cfg.num_experts)])
            blk["moe"] = {
                "gate": lin(m + "gate.weight"),
                "expert_bias": np.asarray(
                    r.get(m + "gate.e_score_correction_bias")),
                "wge": stack("gate_proj"), "wi": stack("up_proj"),
                "wo": stack("down_proj"),
                "shared_wg": lin(m + "shared_experts.gate_proj.weight"),
                "shared_wi": lin(m + "shared_experts.up_proj.weight"),
                "shared_wo": lin(m + "shared_experts.down_proj.weight")}
        else:
            blk["MLP_0"] = {"wg": lin(m + "gate_proj.weight"),
                            "wi": lin(m + "up_proj.weight"),
                            "wo": lin(m + "down_proj.weight")}
        bb[f"block_{i}"] = blk
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = lin("lm_head.weight")
    return tree


# granitemoehybrid (Granite 4.0-H): published tensor name -> path in the GPT
# parameter tree, the names the loader (``_granite_hybrid_tree``) reads and
# ``granite_hybrid_state_dict`` writes back, held to each other in
# tests/test_granite_hybrid.py on a seeded tiny state dict (no published
# weights are in the repository).  ``shared_mlp.input_linear`` is ONE matrix
# whose leading half is the gate (``wg``) and trailing half the up projection
# (``wi``); ``mamba.in_proj`` is one matrix [z | xBC | dt] and stays one
# (``w_in``); ``mamba.conv1d.weight`` is [channels, 1, taps].  The head is
# tied: there is no ``lm_head.weight``.
GRANITE_HYBRID_WEIGHT_NAMES = {
    "model.embed_tokens.weight": "backbone/wte",
    "model.norm.weight": "backbone/final_norm/scale",
    "model.layers.{i}.input_layernorm.weight": "backbone/block_{i}/Norm_0/scale",
    "model.layers.{i}.post_attention_layernorm.weight":
        "backbone/block_{i}/Norm_1/scale",
    "model.layers.{i}.shared_mlp.input_linear.weight":
        "backbone/block_{i}/MLP_0/wg+wi",
    "model.layers.{i}.shared_mlp.output_linear.weight":
        "backbone/block_{i}/MLP_0/wo",
    # attention layers (layer_types[i] == "attention")
    "model.layers.{i}.self_attn.q_proj.weight": "backbone/block_{i}/Attention_0/wq",
    "model.layers.{i}.self_attn.k_proj.weight": "backbone/block_{i}/Attention_0/wk",
    "model.layers.{i}.self_attn.v_proj.weight": "backbone/block_{i}/Attention_0/wv",
    "model.layers.{i}.self_attn.o_proj.weight": "backbone/block_{i}/Attention_0/wo",
    # scan layers (layer_types[i] == "mamba")
    "model.layers.{i}.mamba.in_proj.weight": "backbone/block_{i}/Mamba2Mixer_0/w_in",
    "model.layers.{i}.mamba.conv1d.weight": "backbone/block_{i}/Mamba2Mixer_0/conv_w",
    "model.layers.{i}.mamba.conv1d.bias": "backbone/block_{i}/Mamba2Mixer_0/conv_b",
    "model.layers.{i}.mamba.dt_bias": "backbone/block_{i}/Mamba2Mixer_0/dt_bias",
    "model.layers.{i}.mamba.A_log": "backbone/block_{i}/Mamba2Mixer_0/A_log",
    "model.layers.{i}.mamba.D": "backbone/block_{i}/Mamba2Mixer_0/D",
    "model.layers.{i}.mamba.norm.weight": "backbone/block_{i}/Mamba2Mixer_0/norm",
    "model.layers.{i}.mamba.out_proj.weight":
        "backbone/block_{i}/Mamba2Mixer_0/w_out",
}


def granite_hybrid_config(hf: Dict[str, Any], *,
                          max_seq_len: Optional[int] = None, dtype=None):
    """GPTConfig of a published ``granitemoehybrid`` ``config.json`` of the
    shape granite-4.0-h-micro has: Mamba-2 scan layers and NoPE GQA
    attention layers by ``layer_types``, a gated MLP of
    ``shared_intermediate_size`` in every layer and no experts, a tied head,
    and Granite's four multipliers."""
    from deepspeed_tpu.models.gpt import GPTConfig
    for key, ok, what in (
            ("num_local_experts", not hf.get("num_local_experts", 0),
             "routed experts beside the shared MLP"),
            ("position_embedding_type",
             hf.get("position_embedding_type", "nope") == "nope",
             "rotary positions on the attention layers"),
            ("attention_bias", not hf.get("attention_bias", False),
             "attention biases"),
            ("mamba_proj_bias", not hf.get("mamba_proj_bias", False),
             "biases on the mixer's projections"),
            ("normalization_function",
             hf.get("normalization_function", "rmsnorm") == "rmsnorm",
             "a norm other than RMSNorm"),
            ("tie_word_embeddings", hf.get("tie_word_embeddings", True),
             "an untied head"),
            ("mamba_expand", hf["mamba_n_heads"] * hf["mamba_d_head"]
             == hf.get("mamba_expand", 2) * hf["hidden_size"],
             "an inner width other than heads x head width")):
        if not ok:
            raise NotImplementedError(
                f"granitemoehybrid: {key}={hf.get(key)!r}: {what} is not "
                f"built")
    msl = hf.get("max_position_embeddings", 2048)
    heads = hf["num_attention_heads"]
    return GPTConfig(
        vocab_size=hf["vocab_size"], num_layers=hf["num_hidden_layers"],
        num_heads=heads, num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // heads, hidden_size=hf["hidden_size"],
        mlp_dim_override=hf["shared_intermediate_size"],
        max_seq_len=min(msl, max_seq_len or msl),
        use_rope=True, rope_layers="none", use_rmsnorm=True,
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)), gated_mlp=True,
        tie_embeddings=True, layer_types=tuple(hf["layer_types"]),
        ssm_heads=hf["mamba_n_heads"], ssm_head_dim=hf["mamba_d_head"],
        ssm_state=hf["mamba_d_state"], ssm_groups=hf.get("mamba_n_groups", 1),
        ssm_conv=hf.get("mamba_d_conv", 4),
        ssm_chunk=hf.get("mamba_chunk_size", 256),
        ssm_conv_bias=bool(hf.get("mamba_conv_bias", True)),
        embed_scale=float(hf.get("embedding_multiplier", 1.0)),
        attn_scale=float(hf["attention_multiplier"]),
        residual_scale=float(hf.get("residual_multiplier", 1.0)),
        logits_divisor=float(hf.get("logits_scaling", 1.0)),
        dtype=dtype or jnp.bfloat16)


def _granite_hybrid_tree(r, cfg) -> Dict[str, Any]:
    """granitemoehybrid -> flax tree, by ``GRANITE_HYBRID_WEIGHT_NAMES``;
    ``r`` has ``get(name)`` (a ``_ShardReader``, or any mapping of published
    names to arrays)."""
    H, M = cfg.hidden_size, cfg.mlp_dim
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

    def lin(name):                       # torch Linear: [out, in]
        return np.asarray(r.get(name)).T

    bb: Dict[str, Any] = {
        "wte": np.asarray(r.get("model.embed_tokens.weight")),
        "final_norm": {"scale": np.asarray(r.get("model.norm.weight"))}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        w_in = lin(p + "shared_mlp.input_linear.weight")      # [H, 2M]
        blk: Dict[str, Any] = {
            "Norm_0": {"scale": np.asarray(
                r.get(p + "input_layernorm.weight"))},
            "Norm_1": {"scale": np.asarray(
                r.get(p + "post_attention_layernorm.weight"))},
            "MLP_0": {"wg": w_in[:, :M], "wi": w_in[:, M:],
                      "wo": lin(p + "shared_mlp.output_linear.weight")}}
        if cfg.is_scan_layer(i):
            m = p + "mamba."
            mixer = {
                "w_in": lin(m + "in_proj.weight"),
                "conv_w": np.asarray(r.get(m + "conv1d.weight"))[:, 0, :],
                "w_out": lin(m + "out_proj.weight"),
                "norm": np.asarray(r.get(m + "norm.weight")),
                **{k: np.asarray(r.get(m + k))
                   for k in ("dt_bias", "A_log", "D")}}
            if cfg.ssm_conv_bias:
                mixer["conv_b"] = np.asarray(r.get(m + "conv1d.bias"))
            blk["Mamba2Mixer_0"] = mixer
        else:
            a = p + "self_attn."
            blk["Attention_0"] = {
                "wq": lin(a + "q_proj.weight").reshape(H, nh, hd),
                "wk": lin(a + "k_proj.weight").reshape(H, nkv, hd),
                "wv": lin(a + "v_proj.weight").reshape(H, nkv, hd),
                "wo": lin(a + "o_proj.weight").reshape(nh, hd, H)}
        bb[f"block_{i}"] = blk
    return {"backbone": bb}


def granite_hybrid_state_dict(cfg, params) -> Dict[str, Any]:
    """The GPT parameter tree of a granitemoehybrid model under its
    published tensor names and shapes: ``_granite_hybrid_tree``'s inverse."""
    bb = params["backbone"]
    H = cfg.hidden_size
    out = {"model.embed_tokens.weight": np.asarray(bb["wte"]),
           "model.norm.weight": np.asarray(bb["final_norm"]["scale"])}
    for i in range(cfg.num_layers):
        blk, p = bb[f"block_{i}"], f"model.layers.{i}."
        mlp = blk["MLP_0"]
        out[p + "input_layernorm.weight"] = np.asarray(blk["Norm_0"]["scale"])
        out[p + "post_attention_layernorm.weight"] = np.asarray(
            blk["Norm_1"]["scale"])
        out[p + "shared_mlp.input_linear.weight"] = np.concatenate(
            [np.asarray(mlp["wg"]), np.asarray(mlp["wi"])], axis=1).T
        out[p + "shared_mlp.output_linear.weight"] = np.asarray(mlp["wo"]).T
        if cfg.is_scan_layer(i):
            s, m = blk["Mamba2Mixer_0"], p + "mamba."
            out[m + "in_proj.weight"] = np.asarray(s["w_in"]).T
            out[m + "out_proj.weight"] = np.asarray(s["w_out"]).T
            out[m + "conv1d.weight"] = np.asarray(s["conv_w"])[:, None, :]
            out[m + "norm.weight"] = np.asarray(s["norm"])
            if cfg.ssm_conv_bias:
                out[m + "conv1d.bias"] = np.asarray(s["conv_b"])
            for k in ("dt_bias", "A_log", "D"):
                out[m + k] = np.asarray(s[k])
        else:
            a, at = blk["Attention_0"], p + "self_attn."
            for k in "qkv":
                out[f"{at}{k}_proj.weight"] = np.asarray(
                    a["w" + k]).reshape(H, -1).T
            out[at + "o_proj.weight"] = np.asarray(a["wo"]).reshape(-1, H).T
    return out


# minicpm_sala (openbmb MiniCPM-SALA): published tensor name -> path in the GPT
# parameter tree, the names the loader (``_minicpm_sala_tree``) reads and
# ``minicpm_sala_state_dict`` writes back, held to each other in
# tests/test_minicpm_sala.py on a seeded tiny state dict (no published
# weights are in the repository; the names are the MiniCPM family's
# modelling code's as remembered, the gate's and the output norm's ASSUMED).
# A lightning layer's q, k, v and gate projections are four published
# matrices and ONE here (``w_in`` = [q | k | v | gate]); the head is untied.
MINICPM_SALA_WEIGHT_NAMES = {
    "model.embed_tokens.weight": "backbone/wte",
    "model.norm.weight": "backbone/final_norm/scale",
    "lm_head.weight": "lm_head",
    "model.layers.{i}.input_layernorm.weight": "backbone/block_{i}/Norm_0/scale",
    "model.layers.{i}.post_attention_layernorm.weight":
        "backbone/block_{i}/Norm_1/scale",
    "model.layers.{i}.mlp.gate_proj.weight": "backbone/block_{i}/MLP_0/wg",
    "model.layers.{i}.mlp.up_proj.weight": "backbone/block_{i}/MLP_0/wi",
    "model.layers.{i}.mlp.down_proj.weight": "backbone/block_{i}/MLP_0/wo",
    # both kinds of layer (mixer_types[i]): "minicpm4" -> Attention_0's wq,
    # wk, wv, wo, wgate, q_norm, k_norm; "lightning-attn" -> LightningMixer_0's
    # w_in (q, k, v, o_gate side by side), w_out, q_norm, k_norm, norm
    "model.layers.{i}.self_attn.q_proj.weight": "backbone/block_{i}/*/wq|w_in",
    "model.layers.{i}.self_attn.k_proj.weight": "backbone/block_{i}/*/wk|w_in",
    "model.layers.{i}.self_attn.v_proj.weight": "backbone/block_{i}/*/wv|w_in",
    "model.layers.{i}.self_attn.o_gate.weight":
        "backbone/block_{i}/*/wgate|w_in",
    "model.layers.{i}.self_attn.o_proj.weight":
        "backbone/block_{i}/*/wo|w_out",
    "model.layers.{i}.self_attn.q_norm.weight": "backbone/block_{i}/*/q_norm",
    "model.layers.{i}.self_attn.k_norm.weight": "backbone/block_{i}/*/k_norm",
    "model.layers.{i}.self_attn.o_norm.weight":
        "backbone/block_{i}/LightningMixer_0/norm",
}

# MiniCPM4's published sparse_config (InfLLM-V2), which MiniCPM-SALA's
# config.json does not repeat: taken where the config is silent
MINICPM_SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16,
                           "block_size": 64, "topk": 64, "window_size": 2048,
                           "init_blocks": 1, "dense_len": 8192}


def minicpm_sala_config(hf: Dict[str, Any], *,
                        max_seq_len: Optional[int] = None, dtype=None):
    """GPTConfig of a published ``minicpm_sala`` ``config.json``: lightning
    attention layers (a matrix state a head under the fixed ALiBi-slope
    decay, q/k norms and RoPE inside, a norm a head and a sigmoid gate on
    the way out) beside ``minicpm4`` attention layers (GQA without RoPE,
    q/k norms, an output gate, a selection of blocks from pooled keys) by
    ``mixer_types``, a SwiGLU in every layer, an untied head and MiniCPM's
    three multipliers.  ``layers_kept`` (a benchmark file's cut) reads
    ``mixer_types`` at those layers; ``sparse_config`` is taken where given
    and MiniCPM4's published sizes where the config is silent."""
    from deepspeed_tpu.models.gpt import GPTConfig
    kinds = {"minicpm4": "attention", "lightning-attn": "lightning"}
    unknown = sorted(set(hf["mixer_types"]) - set(kinds))
    for key, ok, what in (
            ("mixer_types", not unknown, f"mixers {unknown}"),
            ("hidden_act", hf.get("hidden_act", "silu") == "silu",
             "an activation other than silu"),
            ("attention_bias", not hf.get("attention_bias", False),
             "attention biases"),
            ("qk_norm", hf.get("qk_norm", True), "heads without q/k norms"),
            ("lightning_scale",
             hf.get("lightning_scale", "1/sqrt(d)") == "1/sqrt(d)",
             "a lightning scale other than 1/sqrt(d)"),
            ("lightning_nkv", hf["lightning_nkv"] == hf["lightning_nh"],
             "lightning keys shared between heads"),
            ("use_output_gate", hf.get("use_output_gate", True)
             and hf.get("use_output_norm", True),
             "a lightning layer without its output norm or gate"),
            ("attn_use_output_gate", hf.get("attn_use_output_gate", True),
             "an attention layer without its output gate"),
            ("attn_use_rope", not hf.get("attn_use_rope", False)
             or hf.get("lightning_use_rope", True),
             "RoPE on the attention layers alone"),
            ("tie_word_embeddings", not hf.get("tie_word_embeddings", False),
             "a tied head")):
        if not ok:
            raise NotImplementedError(
                f"minicpm_sala: {key}={hf.get(key)!r}: {what} is not built")
    sparse = {**MINICPM_SPARSE_DEFAULTS, **hf.get("sparse_config", {})}
    extra = sorted(set(sparse) - set(MINICPM_SPARSE_DEFAULTS))
    if extra:
        raise NotImplementedError(
            f"minicpm_sala: sparse_config keys {extra} are not known")
    kept = hf.get("layers_kept", range(len(hf["mixer_types"])))
    layer_types = tuple(kinds[hf["mixer_types"][i]] for i in kept)
    if hf["num_hidden_layers"] != len(layer_types):
        raise ValueError(
            f"minicpm_sala: num_hidden_layers {hf['num_hidden_layers']} but "
            f"{len(layer_types)} layers kept of mixer_types")
    depth = hf.get("published", {}).get("num_hidden_layers",
                                        len(hf["mixer_types"]))
    rope = ("all" if hf.get("attn_use_rope", False) else
            "state" if hf.get("lightning_use_rope", True) else "none")
    msl = hf.get("max_position_embeddings", 2048)
    return GPTConfig(
        vocab_size=hf["vocab_size"], num_layers=len(layer_types),
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        hidden_size=hf["hidden_size"],
        mlp_dim_override=hf["intermediate_size"],
        max_seq_len=min(msl, max_seq_len or msl),
        use_rope=True, rope_layers=rope,
        rope_theta=float(hf.get("rope_theta", 10000.0)), use_rmsnorm=True,
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)), gated_mlp=True,
        tie_embeddings=False, qk_norm=True, attn_gate=True,
        layer_types=layer_types, ssm_heads=hf["lightning_nh"],
        ssm_head_dim=hf["lightning_head_dim"],
        ssm_state=hf["lightning_head_dim"], ssm_groups=hf["lightning_nkv"],
        ssm_chunk=128, embed_scale=float(hf.get("scale_emb", 1.0)),
        residual_scale=float(hf.get("scale_depth", 1.0)) / depth ** 0.5,
        logits_divisor=hf["hidden_size"] / float(hf["dim_model_base"]),
        block_topk=sparse["topk"], block_size=sparse["block_size"],
        block_kernel=sparse["kernel_size"],
        block_stride=sparse["kernel_stride"],
        block_window=sparse["window_size"], block_init=sparse["init_blocks"],
        block_dense_len=sparse["dense_len"], dtype=dtype or jnp.bfloat16)


def _minicpm_sala_tree(r, cfg) -> Dict[str, Any]:
    """minicpm_sala -> flax tree, by ``MINICPM_SALA_WEIGHT_NAMES``; ``r`` has
    ``get(name)`` (a ``_ShardReader``, or any mapping of published names to
    arrays)."""
    H = cfg.hidden_size
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

    def lin(name):                       # torch Linear: [out, in]
        return np.asarray(r.get(name)).T

    def vec(name):
        return np.asarray(r.get(name))

    bb: Dict[str, Any] = {
        "wte": vec("model.embed_tokens.weight"),
        "final_norm": {"scale": vec("model.norm.weight")}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        blk: Dict[str, Any] = {
            "Norm_0": {"scale": vec(p + "input_layernorm.weight")},
            "Norm_1": {"scale": vec(p + "post_attention_layernorm.weight")},
            "MLP_0": {"wg": lin(p + "mlp.gate_proj.weight"),
                      "wi": lin(p + "mlp.up_proj.weight"),
                      "wo": lin(p + "mlp.down_proj.weight")}}
        norms = {"q_norm": vec(a + "q_norm.weight"),
                 "k_norm": vec(a + "k_norm.weight")}
        if cfg.is_scan_layer(i):
            blk["LightningMixer_0"] = {
                "w_in": np.concatenate(
                    [lin(a + f"{k}.weight")
                     for k in ("q_proj", "k_proj", "v_proj", "o_gate")], 1),
                "w_out": lin(a + "o_proj.weight"),
                "norm": vec(a + "o_norm.weight"), **norms}
        else:
            blk["Attention_0"] = {
                "wq": lin(a + "q_proj.weight").reshape(H, nh, hd),
                "wk": lin(a + "k_proj.weight").reshape(H, nkv, hd),
                "wv": lin(a + "v_proj.weight").reshape(H, nkv, hd),
                "wgate": lin(a + "o_gate.weight").reshape(H, nh, hd),
                "wo": lin(a + "o_proj.weight").reshape(nh, hd, H), **norms}
        bb[f"block_{i}"] = blk
    return {"backbone": bb, "lm_head": lin("lm_head.weight")}


def minicpm_sala_state_dict(cfg, params) -> Dict[str, Any]:
    """The GPT parameter tree of a minicpm_sala model under its published
    tensor names and shapes: ``_minicpm_sala_tree``'s inverse."""
    bb = params["backbone"]
    H, inner = cfg.hidden_size, cfg.ssm_inner
    out = {"model.embed_tokens.weight": np.asarray(bb["wte"]),
           "model.norm.weight": np.asarray(bb["final_norm"]["scale"]),
           "lm_head.weight": np.asarray(params["lm_head"]).T}
    for i in range(cfg.num_layers):
        blk, p = bb[f"block_{i}"], f"model.layers.{i}."
        a, mlp = p + "self_attn.", blk["MLP_0"]
        out[p + "input_layernorm.weight"] = np.asarray(blk["Norm_0"]["scale"])
        out[p + "post_attention_layernorm.weight"] = np.asarray(
            blk["Norm_1"]["scale"])
        for theirs, ours in (("gate_proj", "wg"), ("up_proj", "wi"),
                             ("down_proj", "wo")):
            out[f"{p}mlp.{theirs}.weight"] = np.asarray(mlp[ours]).T
        if cfg.is_scan_layer(i):
            s = blk["LightningMixer_0"]
            w_in = np.asarray(s["w_in"])
            for n, k in enumerate(("q_proj", "k_proj", "v_proj", "o_gate")):
                out[f"{a}{k}.weight"] = w_in[:, n * inner:(n + 1) * inner].T
            out[a + "o_proj.weight"] = np.asarray(s["w_out"]).T
            out[a + "o_norm.weight"] = np.asarray(s["norm"])
        else:
            s = blk["Attention_0"]
            for theirs, ours in (("q_proj", "wq"), ("k_proj", "wk"),
                                 ("v_proj", "wv"), ("o_gate", "wgate")):
                out[f"{a}{theirs}.weight"] = np.asarray(s[ours]).reshape(
                    H, -1).T
            out[a + "o_proj.weight"] = np.asarray(s["wo"]).reshape(-1, H).T
        out[a + "q_norm.weight"] = np.asarray(s["q_norm"])
        out[a + "k_norm.weight"] = np.asarray(s["k_norm"])
    return out


# lfm2_moe (LiquidAI LFM2-MoE): published tensor name -> path in the GPT
# parameter tree, the names the loader (``_lfm2_moe_tree``) reads and
# ``lfm2_moe_state_dict`` writes back, held to each other in
# tests/test_lfm2_moe.py on a seeded tiny state dict (no published weights
# are in the repository; the names are the family's published modelling
# code's as remembered).  ``conv.in_proj`` is one matrix [B | C | X] and
# stays one (``w_in``); ``conv.conv.weight`` is [channels, 1, taps]; an
# expert layer's ``experts.{e}.w1 / w3 / w2`` are stacked over ``e`` into
# ``wge / wi / wo``.  The head is tied: there is no ``lm_head.weight``.
LFM2_MOE_WEIGHT_NAMES = {
    "model.embed_tokens.weight": "backbone/wte",
    "model.embedding_norm.weight": "backbone/final_norm/scale",
    "model.layers.{i}.operator_norm.weight": "backbone/block_{i}/Norm_0/scale",
    "model.layers.{i}.ffn_norm.weight": "backbone/block_{i}/Norm_1/scale",
    # conv layers (layer_types[i] == "conv")
    "model.layers.{i}.conv.in_proj.weight":
        "backbone/block_{i}/ShortConvMixer_0/w_in",
    "model.layers.{i}.conv.conv.weight":
        "backbone/block_{i}/ShortConvMixer_0/conv_w",
    "model.layers.{i}.conv.out_proj.weight":
        "backbone/block_{i}/ShortConvMixer_0/w_out",
    # attention layers (layer_types[i] == "full_attention")
    "model.layers.{i}.self_attn.q_proj.weight": "backbone/block_{i}/Attention_0/wq",
    "model.layers.{i}.self_attn.k_proj.weight": "backbone/block_{i}/Attention_0/wk",
    "model.layers.{i}.self_attn.v_proj.weight": "backbone/block_{i}/Attention_0/wv",
    "model.layers.{i}.self_attn.out_proj.weight": "backbone/block_{i}/Attention_0/wo",
    "model.layers.{i}.self_attn.q_layernorm.weight":
        "backbone/block_{i}/Attention_0/q_norm",
    "model.layers.{i}.self_attn.k_layernorm.weight":
        "backbone/block_{i}/Attention_0/k_norm",
    # dense layers (i < num_dense_layers)
    "model.layers.{i}.feed_forward.w1.weight": "backbone/block_{i}/MLP_0/wg",
    "model.layers.{i}.feed_forward.w3.weight": "backbone/block_{i}/MLP_0/wi",
    "model.layers.{i}.feed_forward.w2.weight": "backbone/block_{i}/MLP_0/wo",
    # expert layers
    "model.layers.{i}.feed_forward.gate.weight": "backbone/block_{i}/moe/gate",
    "model.layers.{i}.feed_forward.expert_bias":
        "backbone/block_{i}/moe/expert_bias",
    "model.layers.{i}.feed_forward.experts.{e}.w1.weight":
        "backbone/block_{i}/moe/wge[e]",
    "model.layers.{i}.feed_forward.experts.{e}.w3.weight":
        "backbone/block_{i}/moe/wi[e]",
    "model.layers.{i}.feed_forward.experts.{e}.w2.weight":
        "backbone/block_{i}/moe/wo[e]",
}


def lfm2_moe_config(hf: Dict[str, Any], *, max_seq_len: Optional[int] = None,
                    dtype=None):
    """GPTConfig of a published ``lfm2_moe`` ``config.json``: gated short
    convolutions and RoPE GQA attention with q/k norms by ``layer_types``,
    ``num_dense_layers`` leading SwiGLU layers and then sigmoid-routed
    experts with a selection bias, a tied head."""
    from deepspeed_tpu.models.gpt import GPTConfig
    rope = hf.get("rope_parameters") or {}
    kinds = set(hf["layer_types"])
    for key, ok, what in (
            ("conv_bias", not hf.get("conv_bias", False),
             "a bias on the short convolution and its projections"),
            ("layer_types", kinds <= {"conv", "full_attention"},
             "a kind of layer other than conv|full_attention"),
            ("rope_parameters", rope.get("rope_type", "default") == "default"
             and not hf.get("rope_scaling"), "scaled rotary positions"),
            ("use_expert_bias", hf.get("use_expert_bias", True),
             "a router without its selection bias"),
            ("tie_word_embeddings", hf.get("tie_word_embeddings", True),
             "an untied head"),
            ("num_experts", hf.get("num_experts", 0) > 0,
             "an lfm2_moe model without experts")):
        if not ok:
            raise NotImplementedError(
                f"lfm2_moe: {key}={hf.get(key)!r}: {what} is not built")
    msl = hf.get("max_position_embeddings", 2048)
    heads = hf["num_attention_heads"]
    return GPTConfig(
        vocab_size=hf["vocab_size"], num_layers=hf["num_hidden_layers"],
        num_heads=heads, num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // heads, hidden_size=hf["hidden_size"],
        mlp_dim_override=hf["intermediate_size"],
        max_seq_len=min(msl, max_seq_len or msl),
        use_rope=True, rope_layers="all",
        rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 1e6))),
        use_rmsnorm=True, norm_eps=float(hf.get("norm_eps", 1e-5)),
        gated_mlp=True, tie_embeddings=True, qk_norm=True,
        layer_types=tuple("attention" if t == "full_attention" else t
                          for t in hf["layer_types"]),
        conv_taps=hf.get("conv_L_cache", 3),
        num_experts=hf["num_experts"], moe_k=hf["num_experts_per_tok"],
        moe_dropless=True, moe_router="sigmoid", moe_router_bias=True,
        moe_route_norm=bool(hf.get("norm_topk_prob", True)),
        moe_route_scale=float(hf.get("routed_scaling_factor", 1.0)),
        moe_route_eps=1e-6,
        moe_expert_dim=hf["moe_intermediate_size"],
        moe_dense_layers=hf.get("num_dense_layers", 0),
        dtype=dtype or jnp.bfloat16)


def _lfm2_moe_tree(r, cfg) -> Dict[str, Any]:
    """lfm2_moe -> flax tree, by ``LFM2_MOE_WEIGHT_NAMES``; ``r`` has
    ``get(name)`` (a ``_ShardReader``, or any mapping of published names to
    arrays)."""
    H = cfg.hidden_size
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

    def lin(name):                       # torch Linear: [out, in]
        return np.asarray(r.get(name)).T

    bb: Dict[str, Any] = {
        "wte": np.asarray(r.get("model.embed_tokens.weight")),
        "final_norm": {"scale": np.asarray(
            r.get("model.embedding_norm.weight"))}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        blk: Dict[str, Any] = {
            "Norm_0": {"scale": np.asarray(r.get(p + "operator_norm.weight"))},
            "Norm_1": {"scale": np.asarray(r.get(p + "ffn_norm.weight"))}}
        if cfg.is_conv_layer(i):
            c = p + "conv."
            blk["ShortConvMixer_0"] = {
                "w_in": lin(c + "in_proj.weight"),
                "conv_w": np.asarray(r.get(c + "conv.weight"))[:, 0, :],
                "w_out": lin(c + "out_proj.weight")}
        else:
            a = p + "self_attn."
            blk["Attention_0"] = {
                "wq": lin(a + "q_proj.weight").reshape(H, nh, hd),
                "wk": lin(a + "k_proj.weight").reshape(H, nkv, hd),
                "wv": lin(a + "v_proj.weight").reshape(H, nkv, hd),
                "wo": lin(a + "out_proj.weight").reshape(nh, hd, H),
                "q_norm": np.asarray(r.get(a + "q_layernorm.weight")),
                "k_norm": np.asarray(r.get(a + "k_layernorm.weight"))}
        f = p + "feed_forward."
        if cfg.is_moe_layer(i):
            def stack(w):
                return np.stack([lin(f"{f}experts.{e}.{w}.weight")
                                 for e in range(cfg.num_experts)])
            blk["moe"] = {"gate": lin(f + "gate.weight"),
                          "expert_bias": np.asarray(r.get(f + "expert_bias")),
                          "wge": stack("w1"), "wi": stack("w3"),
                          "wo": stack("w2")}
        else:
            blk["MLP_0"] = {"wg": lin(f + "w1.weight"),
                            "wi": lin(f + "w3.weight"),
                            "wo": lin(f + "w2.weight")}
        bb[f"block_{i}"] = blk
    return {"backbone": bb}


def lfm2_moe_state_dict(cfg, params) -> Dict[str, Any]:
    """The GPT parameter tree of an lfm2_moe model under its published
    tensor names and shapes: ``_lfm2_moe_tree``'s inverse."""
    bb = params["backbone"]
    H = cfg.hidden_size
    out = {"model.embed_tokens.weight": np.asarray(bb["wte"]),
           "model.embedding_norm.weight": np.asarray(
               bb["final_norm"]["scale"])}
    for i in range(cfg.num_layers):
        blk, p = bb[f"block_{i}"], f"model.layers.{i}."
        out[p + "operator_norm.weight"] = np.asarray(blk["Norm_0"]["scale"])
        out[p + "ffn_norm.weight"] = np.asarray(blk["Norm_1"]["scale"])
        if cfg.is_conv_layer(i):
            c, m = blk["ShortConvMixer_0"], p + "conv."
            out[m + "in_proj.weight"] = np.asarray(c["w_in"]).T
            out[m + "out_proj.weight"] = np.asarray(c["w_out"]).T
            out[m + "conv.weight"] = np.asarray(c["conv_w"])[:, None, :]
        else:
            a, at = blk["Attention_0"], p + "self_attn."
            for k in "qkv":
                out[f"{at}{k}_proj.weight"] = np.asarray(
                    a["w" + k]).reshape(H, -1).T
            out[at + "out_proj.weight"] = np.asarray(a["wo"]).reshape(-1, H).T
            out[at + "q_layernorm.weight"] = np.asarray(a["q_norm"])
            out[at + "k_layernorm.weight"] = np.asarray(a["k_norm"])
        f = p + "feed_forward."
        if cfg.is_moe_layer(i):
            m = blk["moe"]
            out[f + "gate.weight"] = np.asarray(m["gate"]).T
            out[f + "expert_bias"] = np.asarray(m["expert_bias"])
            for e in range(cfg.num_experts):
                for w, k in (("w1", "wge"), ("w3", "wi"), ("w2", "wo")):
                    out[f"{f}experts.{e}.{w}.weight"] = np.asarray(m[k][e]).T
        else:
            m = blk["MLP_0"]
            for w, k in (("w1", "wg"), ("w3", "wi"), ("w2", "wo")):
                out[f"{f}{w}.weight"] = np.asarray(m[k]).T
    return out


def config_from_hf(model_path: str, *, max_seq_len: Optional[int] = None,
                   dtype=None):
    """Build a GPTConfig from ``<model_path>/config.json``.

    max_seq_len caps the (often huge) HF ``max_position_embeddings`` — it only
    sizes KV caches here, rope needs no table.
    """
    from deepspeed_tpu.models.gpt import GPTConfig

    hf = _read_json(os.path.join(model_path, "config.json"))
    if hf.get("model_type") == "afmoe":
        return afmoe_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    if hf.get("model_type") == "deepseek_v3":
        return deepseek_v3_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    if hf.get("model_type") == "dots3_note":
        return dots3_note_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    if hf.get("model_type") == "xing4_0":
        return xing4_0_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    if hf.get("model_type") == "mimo_v2_flash":
        return mimo_v2_flash_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    if hf.get("model_type") == "granitemoehybrid":
        return granite_hybrid_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    if hf.get("model_type") == "lfm2_moe":
        return lfm2_moe_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    if hf.get("model_type") == "minicpm_sala":
        return minicpm_sala_config(hf, max_seq_len=max_seq_len, dtype=dtype)
    arch = _arch_of(hf)

    if arch in _LLAMA_LIKE:
        _reject_unsupported_semantics(hf, arch, max_seq_len)
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        head_dim = hf.get("head_dim") or hidden // heads
        msl = hf.get("max_position_embeddings", 2048)
        attn_bias = bool(hf.get("attention_bias", False))
        # sliding window (mistral/qwen2); qwen2 gates SWA to layers
        # >= max_window_layers (modeling_qwen2 per-layer check)
        swa = _sliding_window_of(hf, max_seq_len)
        swa_layers: tuple = ()
        mwl = hf.get("max_window_layers")
        if swa and mwl is not None:
            mwl = int(mwl)
            if mwl >= hf["num_hidden_layers"]:
                swa = None                 # no layer ever windows
            elif mwl > 0:
                swa_layers = tuple(range(mwl, hf["num_hidden_layers"]))
        moe_kw = {}
        if arch == "MixtralForCausalLM":
            # every layer is MoE with SwiGLU experts (modeling_mixtral.py
            # MixtralSparseMoeBlock); gated_mlp=True drives the per-expert
            # gate in moe/layer.py
            # dropless routing: inference must never drop tokens (the
            # capacity path is a training trade-off), and it matches HF's
            # exact top-k + renormalize semantics
            moe_kw = dict(num_experts=hf["num_local_experts"],
                          moe_k=hf["num_experts_per_tok"],
                          moe_every=1, moe_dropless=True)
        return GPTConfig(
            **moe_kw,
            vocab_size=hf["vocab_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            head_dim=head_dim,
            hidden_size=hidden,
            mlp_dim_override=hf["intermediate_size"],
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=True, use_rmsnorm=True, gated_mlp=True,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rope_scaling=_rope_scaling_of(hf),
            norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            qkv_bias=(arch == "Qwen2ForCausalLM") or attn_bias,
            attn_out_bias=attn_bias,
            sliding_window=swa, local_attn_layers=swa_layers,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _GPT2_LIKE:
        hidden = hf["n_embd"]
        n_inner = hf.get("n_inner") or 4 * hidden
        msl = hf.get("n_positions", 1024)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["n_layer"],
            num_heads=hf["n_head"],
            head_dim=hidden // hf["n_head"],
            hidden_size=hidden,
            mlp_dim_override=n_inner,
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=False, use_rmsnorm=False, gated_mlp=False,
            tie_embeddings=True,
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            qkv_bias=True, attn_out_bias=True, mlp_bias=True,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _OPT_LIKE:
        # reference module_inject/containers/opt.py (HFOPTLayerPolicy):
        # learned positions (offset-2 table, sliced at load), LayerNorm,
        # ReLU MLP, biases everywhere, tied embeddings
        hidden = hf["hidden_size"]
        if hf.get("word_embed_proj_dim", hidden) != hidden:
            raise ValueError(
                f"{arch}: word_embed_proj_dim != hidden_size (opt-350m-style "
                "embedding projections) is not implemented")
        if not hf.get("do_layer_norm_before", True):
            raise ValueError(
                f"{arch}: do_layer_norm_before=false (post-norm opt-350m) "
                "is not implemented; logits would be silently wrong")
        if not hf.get("enable_bias", True) or not hf.get(
                "layer_norm_elementwise_affine", True):
            raise ValueError(f"{arch}: enable_bias/layer_norm_elementwise_"
                             "affine=false variants are not implemented")
        act = _map_activation(arch, hf.get("activation_function", "relu"))
        msl = hf.get("max_position_embeddings", 2048)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            head_dim=hidden // hf["num_attention_heads"],
            hidden_size=hidden,
            mlp_dim_override=hf["ffn_dim"],
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=False, use_rmsnorm=False, gated_mlp=False,
            activation=act,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            norm_eps=1e-5,
            qkv_bias=True, attn_out_bias=True, mlp_bias=True,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _PHI_LIKE:
        # reference inference/v2/model_implementations/phi: parallel
        # attention+MLP off one shared LayerNorm, partial rotary, biased
        # projections and lm_head
        _reject_unsupported_semantics(hf, arch, max_seq_len)
        if hf.get("qk_layernorm"):
            raise ValueError(f"{arch}: qk_layernorm=true is not implemented")
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        msl = hf.get("max_position_embeddings", 2048)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            head_dim=hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=hf["intermediate_size"],
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=True, use_rmsnorm=False, gated_mlp=False,
            activation=_map_activation(arch, hf.get("hidden_act",
                                                    "gelu_new")),
            parallel_block=True, parallel_norms=1,
            rope_pct=float(hf.get("partial_rotary_factor", 0.5)),
            num_kv_heads=hf.get("num_key_value_heads") or heads,
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rope_scaling=_rope_scaling_of(hf),
            norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
            qkv_bias=True, attn_out_bias=True, mlp_bias=True,
            unembed_bias=True,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _FALCON_LIKE:
        # reference inference/v2/model_implementations/falcon: rotary + MQA/
        # GQA, LayerNorm, bias-free projections, parallel attention (7b: one
        # shared input norm; 40b new_decoder_architecture: ln_attn + ln_mlp)
        _reject_unsupported_semantics(hf, arch, max_seq_len)
        use_alibi = bool(hf.get("alibi", False))     # falcon-rw lineage
        has_bias = bool(hf.get("bias", False))
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        new_arch = bool(hf.get("new_decoder_architecture", False))
        if new_arch:
            # HF FalconConfig defaults num_kv_heads to num_attention_heads
            nkv = hf.get("num_kv_heads") or heads
        elif hf.get("multi_query", True):
            nkv = 1
        else:
            nkv = heads
        # HF Falcon ignores parallel_attn entirely when
        # new_decoder_architecture is set (modeling_falcon: the new layout is
        # always parallel ln_attn/ln_mlp) — honoring a parallel_attn=false
        # there would silently serve a sequential-residual model
        parallel = new_arch or bool(hf.get("parallel_attn", True))
        # falcon-40b pairs ln_attn/ln_mlp; falcon-11B (num_ln_in_parallel_attn
        # =1) shares one input_layernorm like the 7b layout
        num_ln = hf.get("num_ln_in_parallel_attn")
        two_norms = new_arch and (num_ln is None or num_ln == 2)
        msl = hf.get("max_position_embeddings", 2048)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            head_dim=hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=hf.get("ffn_hidden_size") or 4 * hidden,
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=not use_alibi, use_alibi=use_alibi,
            alibi_prescale=use_alibi,
            use_rmsnorm=False, gated_mlp=False,
            activation=_map_activation(arch, hf.get("activation", "gelu")),
            parallel_block=parallel,
            parallel_norms=2 if (parallel and two_norms) else 1,
            num_kv_heads=nkv,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rope_scaling=_rope_scaling_of(hf),
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            qkv_bias=has_bias, attn_out_bias=has_bias, mlp_bias=has_bias,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _GPTJ_LIKE:
        # reference module_inject/containers/gptj.py: parallel residual off
        # one shared ln, partial INTERLEAVED rotary (converted to half-split
        # by a head-dim permutation in _gptj_tree), bias-free attention,
        # biased fc + lm_head
        hidden = hf["n_embd"]
        heads = hf["n_head"]
        hd = hidden // heads
        msl = hf.get("n_positions", 2048)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["n_layer"],
            num_heads=heads,
            head_dim=hd,
            hidden_size=hidden,
            mlp_dim_override=hf.get("n_inner") or 4 * hidden,
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=True, use_rmsnorm=False, gated_mlp=False,
            activation=_map_activation(arch, hf.get("activation_function",
                                                    "gelu_new")),
            parallel_block=True, parallel_norms=1,
            rope_pct=(hf.get("rotary_dim") or hd) / hd,  # null = full rotary
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            mlp_bias=True, unembed_bias=True,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _NEOX_LIKE:
        # reference module_inject/containers/gptneox.py: fused per-head qkv,
        # half-split partial rotary (native layout), dual-norm parallel
        # residual when use_parallel_residual
        _reject_unsupported_semantics(hf, arch, max_seq_len)
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        parallel = bool(hf.get("use_parallel_residual", True))
        msl = hf.get("max_position_embeddings", 2048)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            head_dim=hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=hf["intermediate_size"],
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=True, use_rmsnorm=False, gated_mlp=False,
            activation=_map_activation(arch, hf.get("hidden_act", "gelu")),
            parallel_block=parallel,
            parallel_norms=2 if parallel else 1,
            rope_pct=float(hf.get("rotary_pct", 0.25)),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            rope_theta=float(hf.get("rotary_emb_base", 10000.0)),
            rope_scaling=_rope_scaling_of(hf),
            norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
            qkv_bias=True, attn_out_bias=True, mlp_bias=True,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _GPTNEO_LIKE:
        # reference module_inject/containers/gptneo.py: learned positions,
        # UNSCALED attention logits, alternating global/local layers with a
        # 256-token window, bias-free qkv
        hidden = hf["hidden_size"]
        heads = hf["num_heads"] if "num_heads" in hf else hf["num_attention_heads"]  # noqa: E501
        layers = hf.get("num_layers") or hf["num_hidden_layers"]
        att_types = hf.get("attention_types") or [[["global", "local"],
                                                   layers // 2]]
        layer_kinds: list = []
        for kinds, rep in att_types:
            layer_kinds += list(kinds) * rep
        local_ids = tuple(i for i, k in enumerate(layer_kinds)
                          if k == "local")
        msl = hf.get("max_position_embeddings", 2048)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=layers,
            num_heads=heads,
            head_dim=hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=hf.get("intermediate_size") or 4 * hidden,
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=False, use_rmsnorm=False, gated_mlp=False,
            activation=_map_activation(arch, hf.get("activation_function",
                                                    "gelu_new")),
            attn_scale=1.0,               # gpt-neo does not scale by 1/√d
            sliding_window=(int(hf.get("window_size", 256))
                            if local_ids else None),
            local_attn_layers=local_ids,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            attn_out_bias=True, mlp_bias=True,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _STABLELM_LIKE:
        # stablelm-2/zephyr: llama weight layout with LayerNorm (scale+bias)
        # and partial rotary; SwiGLU MLP
        _reject_unsupported_semantics(hf, arch, max_seq_len)
        if hf.get("use_parallel_residual"):
            raise ValueError(f"{arch}: use_parallel_residual=true "
                             "(stablelm-alpha) is not implemented")
        if hf.get("qk_layernorm"):
            raise ValueError(f"{arch}: qk_layernorm=true is not implemented")
        if hf.get("hidden_act", "silu") != "silu":
            raise ValueError(
                f"{arch}: hidden_act={hf['hidden_act']!r} is not implemented "
                "(the gated MLP gate is silu); logits would be silently "
                "wrong")
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        msl = hf.get("max_position_embeddings", 4096)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            head_dim=hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=hf["intermediate_size"],
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=True, use_rmsnorm=False, gated_mlp=True,
            rope_pct=float(hf.get("partial_rotary_factor", 0.25)),
            num_kv_heads=hf.get("num_key_value_heads", heads),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rope_scaling=_rope_scaling_of(hf),
            norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
            qkv_bias=bool(hf.get("use_qkv_bias", False)),
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _PHI3_LIKE:
        # phi-3 (reference inference/v2/model_implementations/phi3): llama
        # semantics with FUSED qkv_proj and gate_up_proj (split in the tree
        # builder); longrope scaling is LIVE (short/long factor tables
        # selected in-graph by sequence length, models/gpt.py rope)
        _reject_unsupported_semantics(hf, arch, max_seq_len)
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        msl = hf.get("max_position_embeddings", 4096)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            head_dim=hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=hf["intermediate_size"],
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=True, use_rmsnorm=True, gated_mlp=True,
            rope_pct=float(hf.get("partial_rotary_factor", 1.0)),
            num_kv_heads=hf.get("num_key_value_heads", heads),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rope_scaling=_rope_scaling_of(hf),
            norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
            sliding_window=_sliding_window_of(hf, max_seq_len),
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _GEMMA_LIKE:
        # gemma: llama layout with (1+w) RMSNorm scales (absorbed at load),
        # √H-scaled embeddings (unembed unscaled), GeGLU, explicit head_dim
        _reject_unsupported_semantics(hf, arch, max_seq_len)
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        msl = hf.get("max_position_embeddings", 8192)
        # HF IGNORES gemma's legacy hidden_act field and forces
        # gelu_pytorch_tanh when hidden_activation is absent (GemmaMLP warns)
        act = hf.get("hidden_activation") or "gelu_pytorch_tanh"
        gemma_bias = bool(hf.get("attention_bias", False))
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            head_dim=hf.get("head_dim") or hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=hf["intermediate_size"],
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=True, use_rmsnorm=True, gated_mlp=True,
            gate_act=_map_activation(arch, act),
            embed_scale=float(hidden) ** 0.5,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rope_scaling=_rope_scaling_of(hf),
            norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            qkv_bias=gemma_bias, attn_out_bias=gemma_bias,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _BIGCODE_LIKE:
        # starcoder/santacoder (reference v1 injection served these as
        # gpt2-family): gpt2 layout with torch-Linear weights, MQA fused
        # q|k|v rows, tanh-gelu
        hidden = hf["n_embd"]
        heads = hf["n_head"]
        msl = hf.get("n_positions", 2048)
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["n_layer"],
            num_heads=heads,
            head_dim=hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=hf.get("n_inner") or 4 * hidden,
            max_seq_len=min(msl, max_seq_len or msl),
            use_rope=False, use_rmsnorm=False, gated_mlp=False,
            activation=_map_activation(arch, hf.get("activation_function",
                                                    "gelu_pytorch_tanh")),
            num_kv_heads=1 if hf.get("multi_query", True) else heads,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            qkv_bias=True, attn_out_bias=True, mlp_bias=True,
            dtype=dtype or jnp.bfloat16,
        )
    if arch in _BLOOM_LIKE:
        # reference module_inject/containers/bloom.py: alibi positions (no
        # table), embedding LayerNorm, fused per-head qkv, tied embeddings
        hidden = hf.get("hidden_size") or hf["n_embed"]  # bloom legacy key
        heads = hf.get("n_head") or hf["num_attention_heads"]
        layers = hf.get("n_layer") or hf["num_hidden_layers"]
        msl = max_seq_len or 2048      # alibi: no positional table to bound
        return GPTConfig(
            vocab_size=hf["vocab_size"],
            num_layers=layers,
            num_heads=heads,
            head_dim=hidden // heads,
            hidden_size=hidden,
            mlp_dim_override=4 * hidden,
            max_seq_len=msl,
            use_rope=False, use_rmsnorm=False, gated_mlp=False,
            use_alibi=True, embed_norm=True,
            activation="gelu",          # BloomGelu = tanh approximation
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            qkv_bias=True, attn_out_bias=True, mlp_bias=True,
            dtype=dtype or jnp.bfloat16,
        )
    raise ValueError(
        f"unsupported HF architecture {arch!r}; supported: "
        f"{SUPPORTED_ARCHITECTURES} (reference zoo: module_inject/"
        f"replace_module.py replace_policies)")


class _ShardReader:
    """Iterate tensors across safetensors shards without loading a shard twice
    (reference huggingface_engine.py:124 parameters() generator)."""

    def __init__(self, model_path: str):
        self.path = model_path
        index = os.path.join(model_path, "model.safetensors.index.json")
        single = os.path.join(model_path, "model.safetensors")
        if os.path.exists(index):
            weight_map = _read_json(index)["weight_map"]
            self.name_to_file = {k: os.path.join(model_path, v)
                                 for k, v in weight_map.items()}
        elif os.path.exists(single):
            from safetensors import safe_open
            with safe_open(single, framework="np") as f:
                names = list(f.keys())
            self.name_to_file = {k: single for k in names}
        else:
            raise FileNotFoundError(
                f"no model.safetensors[.index.json] under {model_path} "
                f"(torch .bin checkpoints are not supported — convert with "
                f"save_pretrained(safe_serialization=True))")
        self._open: Dict[str, Any] = {}

    def names(self):
        return self.name_to_file.keys()

    def get(self, name: str) -> np.ndarray:
        # framework="pt" + a zero-copy bf16 view keeps tensors HOST-resident
        # (framework="flax" would commit every tensor to device-0 HBM before
        # the engine gets to shard/cast it; framework="np" rejects bf16)
        from safetensors import safe_open
        file = self.name_to_file[name]
        if file not in self._open:
            self._open[file] = safe_open(file, framework="pt")
        t = self._open[file].get_tensor(name)
        import torch
        if t.dtype == torch.bfloat16:
            import ml_dtypes
            return t.view(torch.int16).numpy().view(ml_dtypes.bfloat16)
        return t.numpy()

    def has(self, name: str) -> bool:
        return name in self.name_to_file


def _llama_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    H, nh, nkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                      cfg.head_dim)

    def lin(name, out_first=True):
        w = r.get(name)          # torch Linear: [out, in]
        return w.T               # → [in, out]

    def norm(name):
        # rmsnorm = scale only; stablelm-style LayerNorm adds a bias
        out = {"scale": r.get(name + ".weight")}
        if not cfg.use_rmsnorm:
            out["bias"] = r.get(name + ".bias")
        return out

    bb: Dict[str, Any] = {"wte": r.get("model.embed_tokens.weight"),
                          "final_norm": norm("model.norm")}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        att = {
            "wq": lin(p + "self_attn.q_proj.weight").reshape(H, nh, hd),
            "wk": lin(p + "self_attn.k_proj.weight").reshape(H, nkv, hd),
            "wv": lin(p + "self_attn.v_proj.weight").reshape(H, nkv, hd),
            "wo": lin(p + "self_attn.o_proj.weight").reshape(nh, hd, H),
        }
        if cfg.qkv_bias:
            att["bq"] = r.get(p + "self_attn.q_proj.bias").reshape(nh, hd)
            att["bk"] = r.get(p + "self_attn.k_proj.bias").reshape(nkv, hd)
            att["bv"] = r.get(p + "self_attn.v_proj.bias").reshape(nkv, hd)
        if cfg.attn_out_bias:
            att["bo"] = r.get(p + "self_attn.o_proj.bias")
        blk = {
            "Attention_0": att,
            "Norm_0": norm(p + "input_layernorm"),
            "Norm_1": norm(p + "post_attention_layernorm"),
        }
        if cfg.is_moe_layer(i):
            # Mixtral MoE block (modeling_mixtral.py MixtralSparseMoeBlock):
            # gate router + per-expert w1(gate)/w3(up)/w2(down)
            m = p + "block_sparse_moe."
            blk["moe"] = {
                "gate": lin(m + "gate.weight"),                  # [H, E]
                "wge": np.stack([lin(m + f"experts.{e}.w1.weight")
                                 for e in range(cfg.num_experts)]),
                "wi": np.stack([lin(m + f"experts.{e}.w3.weight")
                                for e in range(cfg.num_experts)]),
                "wo": np.stack([lin(m + f"experts.{e}.w2.weight")
                                for e in range(cfg.num_experts)]),
            }
        else:
            blk["MLP_0"] = {
                "wi": lin(p + "mlp.up_proj.weight"),
                "wg": lin(p + "mlp.gate_proj.weight"),
                "wo": lin(p + "mlp.down_proj.weight"),
            }
        bb[f"block_{i}"] = blk
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        if r.has("lm_head.weight"):
            tree["lm_head"] = r.get("lm_head.weight").T      # [H, V]
        else:   # tie flag missing but head absent → tied in practice
            tree["lm_head"] = bb["wte"].T
    return tree


def _gpt2_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim

    def g(name):
        # checkpoints saved from GPT2LMHeadModel prefix with "transformer."
        return r.get(name if r.has(name) else "transformer." + name)

    bb: Dict[str, Any] = {
        "wte": g("wte.weight"),
        "wpe": g("wpe.weight")[:cfg.max_seq_len],
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"h.{i}."
        # Conv1D stores [in, out] — no transpose (module_inject/containers/
        # gpt2.py marks these via HFGPT2LayerPolicy)
        ca = g(p + "attn.c_attn.weight")                     # [H, 3H]
        cb = g(p + "attn.c_attn.bias")                       # [3H]
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": ca[:, :H].reshape(H, nh, hd),
                "wk": ca[:, H:2 * H].reshape(H, nh, hd),
                "wv": ca[:, 2 * H:].reshape(H, nh, hd),
                "bq": cb[:H].reshape(nh, hd),
                "bk": cb[H:2 * H].reshape(nh, hd),
                "bv": cb[2 * H:].reshape(nh, hd),
                "wo": g(p + "attn.c_proj.weight").reshape(nh, hd, H),
                "bo": g(p + "attn.c_proj.bias"),
            },
            "Norm_0": {"scale": g(p + "ln_1.weight"),
                       "bias": g(p + "ln_1.bias")},
            "Norm_1": {"scale": g(p + "ln_2.weight"),
                       "bias": g(p + "ln_2.bias")},
            "MLP_0": {
                "wi": g(p + "mlp.c_fc.weight"),
                "bi": g(p + "mlp.c_fc.bias"),
                "wo": g(p + "mlp.c_proj.weight"),
                "bo": g(p + "mlp.c_proj.bias"),
            },
        }
    return {"backbone": bb}


def _opt_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """OPT → flax tree (reference module_inject/containers/opt.py maps the
    same q/k/v/out + fc1/fc2 + twin-LayerNorm layout).  The learned position
    table carries OPT's +2 offset in rows; slicing it off here lets the model
    keep plain arange positions."""
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim

    def g(name):
        return r.get("model." + name if r.has("model." + name) else name)

    bb: Dict[str, Any] = {
        "wte": g("decoder.embed_tokens.weight"),
        "wpe": g("decoder.embed_positions.weight")[2:2 + cfg.max_seq_len],
        "final_norm": {"scale": g("decoder.final_layer_norm.weight"),
                       "bias": g("decoder.final_layer_norm.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"decoder.layers.{i}."
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": g(p + "self_attn.q_proj.weight").T.reshape(H, nh, hd),
                "wk": g(p + "self_attn.k_proj.weight").T.reshape(H, nh, hd),
                "wv": g(p + "self_attn.v_proj.weight").T.reshape(H, nh, hd),
                "bq": g(p + "self_attn.q_proj.bias").reshape(nh, hd),
                "bk": g(p + "self_attn.k_proj.bias").reshape(nh, hd),
                "bv": g(p + "self_attn.v_proj.bias").reshape(nh, hd),
                "wo": g(p + "self_attn.out_proj.weight").T.reshape(nh, hd, H),
                "bo": g(p + "self_attn.out_proj.bias"),
            },
            "Norm_0": {"scale": g(p + "self_attn_layer_norm.weight"),
                       "bias": g(p + "self_attn_layer_norm.bias")},
            "Norm_1": {"scale": g(p + "final_layer_norm.weight"),
                       "bias": g(p + "final_layer_norm.bias")},
            "MLP_0": {
                "wi": g(p + "fc1.weight").T,
                "bi": g(p + "fc1.bias"),
                "wo": g(p + "fc2.weight").T,
                "bo": g(p + "fc2.bias"),
            },
        }
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("lm_head.weight").T
                           if r.has("lm_head.weight") else bb["wte"].T)
    return tree


def _phi_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """Phi → flax tree (reference inference/v2/model_implementations/phi):
    parallel attention+MLP sharing one input LayerNorm, biased projections,
    biased untied lm_head."""
    H, nh, nkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                      cfg.head_dim)

    bb: Dict[str, Any] = {
        "wte": r.get("model.embed_tokens.weight"),
        "final_norm": {"scale": r.get("model.final_layernorm.weight"),
                       "bias": r.get("model.final_layernorm.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": r.get(p + "self_attn.q_proj.weight").T.reshape(H, nh,
                                                                     hd),
                "wk": r.get(p + "self_attn.k_proj.weight").T.reshape(H, nkv,
                                                                     hd),
                "wv": r.get(p + "self_attn.v_proj.weight").T.reshape(H, nkv,
                                                                     hd),
                "bq": r.get(p + "self_attn.q_proj.bias").reshape(nh, hd),
                "bk": r.get(p + "self_attn.k_proj.bias").reshape(nkv, hd),
                "bv": r.get(p + "self_attn.v_proj.bias").reshape(nkv, hd),
                "wo": r.get(p + "self_attn.dense.weight").T.reshape(nh, hd,
                                                                    H),
                "bo": r.get(p + "self_attn.dense.bias"),
            },
            "Norm_0": {"scale": r.get(p + "input_layernorm.weight"),
                       "bias": r.get(p + "input_layernorm.bias")},
            "MLP_0": {
                "wi": r.get(p + "mlp.fc1.weight").T,
                "bi": r.get(p + "mlp.fc1.bias"),
                "wo": r.get(p + "mlp.fc2.weight").T,
                "bo": r.get(p + "mlp.fc2.bias"),
            },
        }
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("lm_head.weight").T
                           if r.has("lm_head.weight") else bb["wte"].T)
    if cfg.unembed_bias:
        tree["lm_head_bias"] = (r.get("lm_head.bias")
                                if r.has("lm_head.bias")
                                else np.zeros(cfg.vocab_size, np.float32))
    return tree


def _falcon_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """Falcon → flax tree (reference inference/v2/model_implementations/
    falcon).  The fused query_key_value weight is grouped kv-major:
    [nkv, g+2, hd, H] with g query heads then one k and one v row per group —
    matching the model's group-major GQA head order."""
    H, nh, nkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                      cfg.head_dim)
    g_per = nh // nkv

    bb: Dict[str, Any] = {
        "wte": r.get("transformer.word_embeddings.weight"),
        "final_norm": {"scale": r.get("transformer.ln_f.weight"),
                       "bias": r.get("transformer.ln_f.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        w = r.get(p + "self_attention.query_key_value.weight")   # [out, H]
        # grouped kv-major fused layout; nkv == nh degenerates to the
        # falcon-rw interleaved [nh, 3, hd] layout (g_per == 1)
        w4 = w.reshape(nkv, g_per + 2, hd, H)
        wq_ = w4[:, :g_per].reshape(nh, hd, H)
        wk_, wv_ = w4[:, g_per], w4[:, g_per + 1]                # [nkv, hd, H]
        att = {
            "wq": np.transpose(wq_, (2, 0, 1)),
            "wk": np.transpose(wk_, (2, 0, 1)),
            "wv": np.transpose(wv_, (2, 0, 1)),
            "wo": r.get(p + "self_attention.dense.weight").T.reshape(nh, hd,
                                                                     H),
        }
        mlp = {"wi": r.get(p + "mlp.dense_h_to_4h.weight").T,
               "wo": r.get(p + "mlp.dense_4h_to_h.weight").T}
        if cfg.qkv_bias:         # falcon-rw bias=true
            b4 = r.get(p + "self_attention.query_key_value.bias"
                       ).reshape(nkv, g_per + 2, hd)
            att["bq"] = b4[:, :g_per].reshape(nh, hd)
            att["bk"], att["bv"] = b4[:, g_per], b4[:, g_per + 1]
            att["bo"] = r.get(p + "self_attention.dense.bias")
            mlp["bi"] = r.get(p + "mlp.dense_h_to_4h.bias")
            mlp["bo"] = r.get(p + "mlp.dense_4h_to_h.bias")
        blk = {
            "Attention_0": att,
            "MLP_0": mlp,
        }
        if cfg.parallel_block and cfg.parallel_norms == 2:
            blk["Norm_0"] = {"scale": r.get(p + "ln_attn.weight"),
                             "bias": r.get(p + "ln_attn.bias")}
            blk["Norm_1"] = {"scale": r.get(p + "ln_mlp.weight"),
                             "bias": r.get(p + "ln_mlp.bias")}
        else:
            blk["Norm_0"] = {"scale": r.get(p + "input_layernorm.weight"),
                             "bias": r.get(p + "input_layernorm.bias")}
            if not cfg.parallel_block:
                blk["Norm_1"] = {
                    "scale": r.get(p + "post_attention_layernorm.weight"),
                    "bias": r.get(p + "post_attention_layernorm.bias")}
        bb[f"block_{i}"] = blk
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("lm_head.weight").T
                           if r.has("lm_head.weight") else bb["wte"].T)
    return tree


def _rope_interleave_perm(head_dim: int, rot: int) -> np.ndarray:
    """Head-dim permutation converting gpt-j's INTERLEAVED rotary pairing
    ((0,1),(2,3),…) to this model's half-split pairing ((0,rot/2),…).

    Valid because attention scores are invariant under a shared q/k head-dim
    permutation and half_rope(x[perm]) == interleaved_rope(x)[perm] — so
    permuting wq/wk rows once at load time makes the native kernel exact."""
    return np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2),
                           np.arange(rot, head_dim)])


def _gptj_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """GPT-J → flax tree (reference module_inject/containers/gptj.py)."""
    from deepspeed_tpu.models.gpt import rotary_dim
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    perm = _rope_interleave_perm(hd, rotary_dim(hd, cfg.rope_pct))

    bb: Dict[str, Any] = {
        "wte": r.get("transformer.wte.weight"),
        "final_norm": {"scale": r.get("transformer.ln_f.weight"),
                       "bias": r.get("transformer.ln_f.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        wq = r.get(p + "attn.q_proj.weight").T.reshape(H, nh, hd)
        wk = r.get(p + "attn.k_proj.weight").T.reshape(H, nh, hd)
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": wq[:, :, perm],
                "wk": wk[:, :, perm],
                "wv": r.get(p + "attn.v_proj.weight").T.reshape(H, nh, hd),
                "wo": r.get(p + "attn.out_proj.weight").T.reshape(nh, hd, H),
            },
            "Norm_0": {"scale": r.get(p + "ln_1.weight"),
                       "bias": r.get(p + "ln_1.bias")},
            "MLP_0": {
                "wi": r.get(p + "mlp.fc_in.weight").T,
                "bi": r.get(p + "mlp.fc_in.bias"),
                "wo": r.get(p + "mlp.fc_out.weight").T,
                "bo": r.get(p + "mlp.fc_out.bias"),
            },
        }
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("lm_head.weight").T
                           if r.has("lm_head.weight") else bb["wte"].T)
    if cfg.unembed_bias:
        tree["lm_head_bias"] = (r.get("lm_head.bias")
                                if r.has("lm_head.bias")
                                else np.zeros(cfg.vocab_size, np.float32))
    return tree


def _neox_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """GPT-NeoX → flax tree (reference module_inject/containers/gptneox.py).
    Fused qkv is per-head interleaved: rows [h·3hd:(h+1)·3hd] hold head h's
    q, k, v stripes."""
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim

    bb: Dict[str, Any] = {
        "wte": r.get("gpt_neox.embed_in.weight"),
        "final_norm": {"scale": r.get("gpt_neox.final_layer_norm.weight"),
                       "bias": r.get("gpt_neox.final_layer_norm.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"gpt_neox.layers.{i}."
        w4 = r.get(p + "attention.query_key_value.weight"
                   ).reshape(nh, 3, hd, H)
        b3 = r.get(p + "attention.query_key_value.bias").reshape(nh, 3, hd)
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": np.transpose(w4[:, 0], (2, 0, 1)),
                "wk": np.transpose(w4[:, 1], (2, 0, 1)),
                "wv": np.transpose(w4[:, 2], (2, 0, 1)),
                "bq": b3[:, 0], "bk": b3[:, 1], "bv": b3[:, 2],
                "wo": r.get(p + "attention.dense.weight").T.reshape(nh, hd,
                                                                    H),
                "bo": r.get(p + "attention.dense.bias"),
            },
            "Norm_0": {"scale": r.get(p + "input_layernorm.weight"),
                       "bias": r.get(p + "input_layernorm.bias")},
            "Norm_1": {
                "scale": r.get(p + "post_attention_layernorm.weight"),
                "bias": r.get(p + "post_attention_layernorm.bias")},
            "MLP_0": {
                "wi": r.get(p + "mlp.dense_h_to_4h.weight").T,
                "bi": r.get(p + "mlp.dense_h_to_4h.bias"),
                "wo": r.get(p + "mlp.dense_4h_to_h.weight").T,
                "bo": r.get(p + "mlp.dense_4h_to_h.bias"),
            },
        }
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("embed_out.weight").T
                           if r.has("embed_out.weight") else bb["wte"].T)
    return tree


def _gptneo_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """GPT-Neo → flax tree (reference module_inject/containers/gptneo.py).
    torch Linear layout everywhere (unlike gpt2's Conv1D), bias-free qkv."""
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim

    def g(name):
        # prefixed (GPTNeoForCausalLM) first; bare GPTNeoModel keys otherwise
        return r.get(name if r.has(name)
                     else name[len("transformer."):])

    bb: Dict[str, Any] = {
        "wte": g("transformer.wte.weight"),
        "wpe": g("transformer.wpe.weight")[:cfg.max_seq_len],
        "final_norm": {"scale": g("transformer.ln_f.weight"),
                       "bias": g("transformer.ln_f.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": g(p + "attn.attention.q_proj.weight").T.reshape(
                    H, nh, hd),
                "wk": g(p + "attn.attention.k_proj.weight").T.reshape(
                    H, nh, hd),
                "wv": g(p + "attn.attention.v_proj.weight").T.reshape(
                    H, nh, hd),
                "wo": g(p + "attn.attention.out_proj.weight").T.reshape(
                    nh, hd, H),
                "bo": g(p + "attn.attention.out_proj.bias"),
            },
            "Norm_0": {"scale": g(p + "ln_1.weight"),
                       "bias": g(p + "ln_1.bias")},
            "Norm_1": {"scale": g(p + "ln_2.weight"),
                       "bias": g(p + "ln_2.bias")},
            "MLP_0": {
                "wi": g(p + "mlp.c_fc.weight").T,
                "bi": g(p + "mlp.c_fc.bias"),
                "wo": g(p + "mlp.c_proj.weight").T,
                "bo": g(p + "mlp.c_proj.bias"),
            },
        }
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("lm_head.weight").T
                           if r.has("lm_head.weight") else bb["wte"].T)
    return tree


def _phi3_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """Phi-3 → flax tree: llama layout with fused qkv_proj
    (q[nh·hd] | k[nkv·hd] | v[nkv·hd] rows) and gate_up_proj
    (gate[M] | up[M] rows)."""
    H, nh, nkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                      cfg.head_dim)
    M = cfg.mlp_dim
    qw, kvw = nh * hd, nkv * hd

    bb: Dict[str, Any] = {"wte": r.get("model.embed_tokens.weight"),
                          "final_norm": {"scale": r.get("model.norm.weight")}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        w = r.get(p + "self_attn.qkv_proj.weight").T   # [H, qw + 2·kvw]
        gu = r.get(p + "mlp.gate_up_proj.weight").T    # [H, 2M]
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": w[:, :qw].reshape(H, nh, hd),
                "wk": w[:, qw:qw + kvw].reshape(H, nkv, hd),
                "wv": w[:, qw + kvw:].reshape(H, nkv, hd),
                "wo": r.get(p + "self_attn.o_proj.weight").T.reshape(nh, hd,
                                                                     H),
            },
            "Norm_0": {"scale": r.get(p + "input_layernorm.weight")},
            "Norm_1": {
                "scale": r.get(p + "post_attention_layernorm.weight")},
            "MLP_0": {
                "wg": gu[:, :M],
                "wi": gu[:, M:],
                "wo": r.get(p + "mlp.down_proj.weight").T,
            },
        }
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("lm_head.weight").T
                           if r.has("lm_head.weight") else bb["wte"].T)
    return tree


def _gemma_absorb_norm_offset(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Gemma's RMSNorm multiplies by (1 + weight) in fp32
    (modeling_gemma GemmaRMSNorm) — absorb the +1 into the stored scales
    (fp32 so the offset is exact) and the stock rms_norm serves it."""
    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k.startswith(("Norm_", "final_norm")) and "scale" in v:
                    out[k] = dict(v, scale=np.asarray(v["scale"],
                                                      np.float32) + 1.0)
                else:
                    out[k] = walk(v)
            return out
        return node

    return walk(tree)


def _bigcode_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """GPT-BigCode (starcoder) → flax tree: fused c_attn rows are
    q[H] | k[nkv·hd] | v[nkv·hd] (MQA: nkv=1)."""
    H, nh, nkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                      cfg.head_dim)

    def g(name):
        return r.get(name if r.has(name) else name[len("transformer."):])

    bb: Dict[str, Any] = {
        "wte": g("transformer.wte.weight"),
        "wpe": g("transformer.wpe.weight")[:cfg.max_seq_len],
        "final_norm": {"scale": g("transformer.ln_f.weight"),
                       "bias": g("transformer.ln_f.bias")},
    }
    kvw = nkv * hd
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        w = g(p + "attn.c_attn.weight").T          # [H, H + 2·nkv·hd]
        b = g(p + "attn.c_attn.bias")
        if nkv == nh:
            # MHA variant interleaves q|k|v WITHIN each head ([nh, 3, hd])
            w4 = w.reshape(H, nh, 3, hd)
            b3 = b.reshape(nh, 3, hd)
            att = {"wq": w4[:, :, 0], "wk": w4[:, :, 1], "wv": w4[:, :, 2],
                   "bq": b3[:, 0], "bk": b3[:, 1], "bv": b3[:, 2]}
        else:
            # MQA: flat q rows then one k stripe and one v stripe
            att = {"wq": w[:, :H].reshape(H, nh, hd),
                   "wk": w[:, H:H + kvw].reshape(H, nkv, hd),
                   "wv": w[:, H + kvw:].reshape(H, nkv, hd),
                   "bq": b[:H].reshape(nh, hd),
                   "bk": b[H:H + kvw].reshape(nkv, hd),
                   "bv": b[H + kvw:].reshape(nkv, hd)}
        att["wo"] = g(p + "attn.c_proj.weight").T.reshape(nh, hd, H)
        att["bo"] = g(p + "attn.c_proj.bias")
        bb[f"block_{i}"] = {
            "Attention_0": att,
            "Norm_0": {"scale": g(p + "ln_1.weight"),
                       "bias": g(p + "ln_1.bias")},
            "Norm_1": {"scale": g(p + "ln_2.weight"),
                       "bias": g(p + "ln_2.bias")},
            "MLP_0": {
                "wi": g(p + "mlp.c_fc.weight").T,
                "bi": g(p + "mlp.c_fc.bias"),
                "wo": g(p + "mlp.c_proj.weight").T,
                "bo": g(p + "mlp.c_proj.bias"),
            },
        }
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("lm_head.weight").T
                           if r.has("lm_head.weight") else bb["wte"].T)
    return tree


def _bloom_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """BLOOM → flax tree (reference module_inject/containers/bloom.py).
    Fused qkv interleaves q/k/v WITHIN each head: [nh, 3, hd]."""
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim

    def g(name):
        return r.get("transformer." + name
                     if r.has("transformer." + name) else name)

    bb: Dict[str, Any] = {
        "wte": g("word_embeddings.weight"),
        "embed_norm": {"scale": g("word_embeddings_layernorm.weight"),
                       "bias": g("word_embeddings_layernorm.bias")},
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"h.{i}."
        w4 = g(p + "self_attention.query_key_value.weight"
               ).reshape(nh, 3, hd, H)
        b3 = g(p + "self_attention.query_key_value.bias").reshape(nh, 3, hd)
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": np.transpose(w4[:, 0], (2, 0, 1)),
                "wk": np.transpose(w4[:, 1], (2, 0, 1)),
                "wv": np.transpose(w4[:, 2], (2, 0, 1)),
                "bq": b3[:, 0], "bk": b3[:, 1], "bv": b3[:, 2],
                "wo": g(p + "self_attention.dense.weight").T.reshape(nh, hd,
                                                                     H),
                "bo": g(p + "self_attention.dense.bias"),
            },
            "Norm_0": {"scale": g(p + "input_layernorm.weight"),
                       "bias": g(p + "input_layernorm.bias")},
            "Norm_1": {"scale": g(p + "post_attention_layernorm.weight"),
                       "bias": g(p + "post_attention_layernorm.bias")},
            "MLP_0": {
                "wi": g(p + "mlp.dense_h_to_4h.weight").T,
                "bi": g(p + "mlp.dense_h_to_4h.bias"),
                "wo": g(p + "mlp.dense_4h_to_h.weight").T,
                "bo": g(p + "mlp.dense_4h_to_h.bias"),
            },
        }
    tree: Dict[str, Any] = {"backbone": bb}
    if not cfg.tie_embeddings:
        tree["lm_head"] = (r.get("lm_head.weight").T
                           if r.has("lm_head.weight") else bb["wte"].T)
    return tree


_DISTILBERT_LIKE = {"DistilBertForMaskedLM", "DistilBertModel",
                    "DistilBertForSequenceClassification"}
_CLIP_LIKE = {"CLIPTextModel", "CLIPTextModelWithProjection", "CLIPModel"}
_ROBERTA_LIKE = {"RobertaForMaskedLM", "RobertaModel",
                 "RobertaForSequenceClassification",
                 "XLMRobertaForMaskedLM", "XLMRobertaModel",
                 "XLMRobertaForSequenceClassification"}
_BERT_LIKE = ({"BertForMaskedLM", "BertModel", "BertForPreTraining",
               "BertForSequenceClassification"}
              | _DISTILBERT_LIKE | _ROBERTA_LIKE)


def _distilbert_tree(r: _ShardReader, cfg) -> Dict[str, Any]:
    """DistilBERT → the same flax encoder tree (reference
    module_inject/containers/distil_bert.py): q/k/v/out lin, sa_layer_norm +
    output_layer_norm, no token types, tied vocab_projector head."""
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim

    def g(name):
        return r.get("distilbert." + name
                     if r.has("distilbert." + name) else name)

    enc: Dict[str, Any] = {
        "wte": g("embeddings.word_embeddings.weight"),
        "wpe": g("embeddings.position_embeddings.weight"),
        "embed_norm": {"scale": g("embeddings.LayerNorm.weight"),
                       "bias": g("embeddings.LayerNorm.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"transformer.layer.{i}."
        enc[f"block_{i}"] = {
            "attn": {
                "wq": g(p + "attention.q_lin.weight").T.reshape(H, nh, hd),
                "bq": g(p + "attention.q_lin.bias").reshape(nh, hd),
                "wk": g(p + "attention.k_lin.weight").T.reshape(H, nh, hd),
                "bk": g(p + "attention.k_lin.bias").reshape(nh, hd),
                "wv": g(p + "attention.v_lin.weight").T.reshape(H, nh, hd),
                "bv": g(p + "attention.v_lin.bias").reshape(nh, hd),
                "wo": g(p + "attention.out_lin.weight").T.reshape(nh, hd, H),
                "bo": g(p + "attention.out_lin.bias"),
            },
            "attn_norm": {"scale": g(p + "sa_layer_norm.weight"),
                          "bias": g(p + "sa_layer_norm.bias")},
            "mlp": {
                "wi": g(p + "ffn.lin1.weight").T,
                "bi": g(p + "ffn.lin1.bias"),
                "wo": g(p + "ffn.lin2.weight").T,
                "bo": g(p + "ffn.lin2.bias"),
            },
            "mlp_norm": {"scale": g(p + "output_layer_norm.weight"),
                         "bias": g(p + "output_layer_norm.bias")},
        }
    tree: Dict[str, Any] = {"encoder": enc}
    if r.has("vocab_transform.weight"):
        tree.update({
            "transform_w": r.get("vocab_transform.weight").T,
            "transform_b": r.get("vocab_transform.bias"),
            "transform_norm": {"scale": r.get("vocab_layer_norm.weight"),
                               "bias": r.get("vocab_layer_norm.bias")},
            "decoder_bias": r.get("vocab_projector.bias"),
        })
    elif r.has("classifier.weight"):     # DistilBertForSequenceClassification
        tree.update({
            "pooler_w": r.get("pre_classifier.weight").T,
            "pooler_b": r.get("pre_classifier.bias"),
            "cls_w": r.get("classifier.weight").T,
            "cls_b": r.get("classifier.bias"),
        })
    return tree


def load_hf_clip_text(model_path: str, *, dtype=None):
    """CLIP text encoder → (GPTConfig, tree, extras) (reference
    module_inject/containers/clip.py — the text-encoder leg of the stable-
    diffusion serving stack).

    CLIP's text tower IS a pre-LN causal transformer with learned positions,
    quick-gelu MLPs and biases everywhere — exactly the GPT backbone — so the
    weights stream into the same tree and the TPU attention paths serve it
    unchanged.  extras: {"text_projection": [H, P] or None, "eos_token_id"}.
    """
    from deepspeed_tpu.models.gpt import GPTConfig

    full = _read_json(os.path.join(model_path, "config.json"))
    # CLIPModel nests the text config ("text_config_dict" on legacy openai
    # hub checkpoints, CLIPConfig back-compat)
    tc = full.get("text_config") or full.get("text_config_dict") or full
    hidden = tc["hidden_size"]
    heads = tc["num_attention_heads"]
    cfg = GPTConfig(
        vocab_size=tc["vocab_size"],
        num_layers=tc["num_hidden_layers"],
        num_heads=heads,
        head_dim=hidden // heads,
        hidden_size=hidden,
        mlp_dim_override=tc["intermediate_size"],
        max_seq_len=tc.get("max_position_embeddings", 77),
        use_rope=False, use_rmsnorm=False, gated_mlp=False,
        activation=_map_activation("CLIPText", tc.get("hidden_act",
                                                      "quick_gelu")),
        norm_eps=float(tc.get("layer_norm_eps", 1e-5)),
        qkv_bias=True, attn_out_bias=True, mlp_bias=True,
        tie_embeddings=True,
        dtype=dtype or jnp.float32,
    )
    r = _ShardReader(model_path)

    def g(name):
        return r.get("text_model." + name
                     if r.has("text_model." + name) else name)

    H, nh, hd = hidden, heads, cfg.head_dim
    bb: Dict[str, Any] = {
        "wte": g("embeddings.token_embedding.weight"),
        "wpe": g("embeddings.position_embedding.weight"),
        "final_norm": {"scale": g("final_layer_norm.weight"),
                       "bias": g("final_layer_norm.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"encoder.layers.{i}."
        bb[f"block_{i}"] = {
            "Attention_0": {
                "wq": g(p + "self_attn.q_proj.weight").T.reshape(H, nh, hd),
                "bq": g(p + "self_attn.q_proj.bias").reshape(nh, hd),
                "wk": g(p + "self_attn.k_proj.weight").T.reshape(H, nh, hd),
                "bk": g(p + "self_attn.k_proj.bias").reshape(nh, hd),
                "wv": g(p + "self_attn.v_proj.weight").T.reshape(H, nh, hd),
                "bv": g(p + "self_attn.v_proj.bias").reshape(nh, hd),
                "wo": g(p + "self_attn.out_proj.weight").T.reshape(nh, hd,
                                                                   H),
                "bo": g(p + "self_attn.out_proj.bias"),
            },
            "Norm_0": {"scale": g(p + "layer_norm1.weight"),
                       "bias": g(p + "layer_norm1.bias")},
            "Norm_1": {"scale": g(p + "layer_norm2.weight"),
                       "bias": g(p + "layer_norm2.bias")},
            "MLP_0": {
                "wi": g(p + "mlp.fc1.weight").T,
                "bi": g(p + "mlp.fc1.bias"),
                "wo": g(p + "mlp.fc2.weight").T,
                "bo": g(p + "mlp.fc2.bias"),
            },
        }
    extras = {
        "text_projection": (r.get("text_projection.weight").T
                            if r.has("text_projection.weight") else None),
        "eos_token_id": int(tc.get("eos_token_id", 49407)),
    }
    log_dist(f"loaded HF CLIP text checkpoint {model_path} "
             f"({cfg.num_layers}L/{H}H)", ranks=[0])
    return cfg, {"backbone": bb}, extras


def load_hf_bert(model_path: str, *, dtype=None) -> Tuple[Any,
                                                          Dict[str, Any]]:
    """BERT-family encoder checkpoint → (BertConfig, flax params tree)
    (reference module_inject/containers/{bert,distil_bert}.py)."""
    from deepspeed_tpu.models.bert import BertConfig

    hf = _read_json(os.path.join(model_path, "config.json"))
    arch = _arch_of(hf)
    if arch in _DISTILBERT_LIKE:
        cfg = BertConfig(
            vocab_size=hf["vocab_size"],
            num_layers=hf["n_layers"],
            num_heads=hf["n_heads"],
            hidden_size=hf["dim"],
            mlp_dim=hf["hidden_dim"],
            max_seq_len=hf.get("max_position_embeddings", 512),
            type_vocab_size=0,
            norm_eps=1e-12,
            activation=_map_activation(arch, hf.get("activation", "gelu")),
            pooler_act="relu",       # distilbert pre_classifier uses relu
            dtype=dtype or jnp.float32,
        )
        tree = _distilbert_tree(_ShardReader(model_path), cfg)
        log_dist(f"loaded HF DistilBERT checkpoint {model_path} "
                 f"({cfg.num_layers}L/{cfg.hidden_size}H)", ranks=[0])
        return cfg, tree
    is_roberta = arch in _ROBERTA_LIKE
    # roberta positions start at padding_idx+1; the table keeps its offset
    # rows (pad tokens take row padding_idx), so only the USABLE length
    # shrinks
    rob_pad = int(hf.get("pad_token_id") or 1) if is_roberta else None
    pos_off = (rob_pad + 1) if is_roberta else 0
    cfg = BertConfig(
        vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        hidden_size=hf["hidden_size"],
        mlp_dim=hf["intermediate_size"],
        max_seq_len=hf.get("max_position_embeddings", 512) - pos_off,
        type_vocab_size=hf.get("type_vocab_size", 2),
        norm_eps=float(hf.get("layer_norm_eps", 1e-12)),
        activation=_map_activation(_arch_of(hf), hf.get("hidden_act",
                                                        "gelu")),
        pos_pad_token=rob_pad,
        dtype=dtype or jnp.float32,
    )
    r = _ShardReader(model_path)

    def g(name):
        # headed checkpoints prefix with "bert."/"roberta."; bare models don't
        for pre in ("bert.", "roberta."):
            if r.has(pre + name):
                return r.get(pre + name)
        return r.get(name)

    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    enc: Dict[str, Any] = {
        "wte": g("embeddings.word_embeddings.weight"),
        "wpe": g("embeddings.position_embeddings.weight"),
        "wtt": g("embeddings.token_type_embeddings.weight"),
        "embed_norm": {
            "scale": g("embeddings.LayerNorm.weight"),
            "bias": g("embeddings.LayerNorm.bias")},
    }
    for i in range(cfg.num_layers):
        p = f"encoder.layer.{i}."
        enc[f"block_{i}"] = {
            "attn": {
                "wq": g(p + "attention.self.query.weight").T.reshape(
                    H, nh, hd),
                "bq": g(p + "attention.self.query.bias").reshape(nh, hd),
                "wk": g(p + "attention.self.key.weight").T.reshape(
                    H, nh, hd),
                "bk": g(p + "attention.self.key.bias").reshape(nh, hd),
                "wv": g(p + "attention.self.value.weight").T.reshape(
                    H, nh, hd),
                "bv": g(p + "attention.self.value.bias").reshape(nh, hd),
                "wo": g(p + "attention.output.dense.weight").T.reshape(
                    nh, hd, H),
                "bo": g(p + "attention.output.dense.bias"),
            },
            "attn_norm": {
                "scale": g(p + "attention.output.LayerNorm.weight"),
                "bias": g(p + "attention.output.LayerNorm.bias")},
            "mlp": {
                "wi": g(p + "intermediate.dense.weight").T,
                "bi": g(p + "intermediate.dense.bias"),
                "wo": g(p + "output.dense.weight").T,
                "bo": g(p + "output.dense.bias"),
            },
            "mlp_norm": {
                "scale": g(p + "output.LayerNorm.weight"),
                "bias": g(p + "output.LayerNorm.bias")},
        }
    tree: Dict[str, Any] = {"encoder": enc}
    if r.has("cls.predictions.transform.dense.weight"):
        tree.update({
            "transform_w": r.get("cls.predictions.transform.dense.weight").T,
            "transform_b": r.get("cls.predictions.transform.dense.bias"),
            "transform_norm": {
                "scale": r.get(
                    "cls.predictions.transform.LayerNorm.weight"),
                "bias": r.get("cls.predictions.transform.LayerNorm.bias")},
            "decoder_bias": r.get("cls.predictions.bias"),
        })
    elif r.has("lm_head.dense.weight"):  # roberta MLM head naming
        tree.update({
            "transform_w": r.get("lm_head.dense.weight").T,
            "transform_b": r.get("lm_head.dense.bias"),
            "transform_norm": {"scale": r.get("lm_head.layer_norm.weight"),
                               "bias": r.get("lm_head.layer_norm.bias")},
            "decoder_bias": r.get("lm_head.bias"),
        })
    elif r.has("classifier.out_proj.weight"):
        # roberta classification head: dense→tanh→out_proj on [CLS]
        tree.update({
            "pooler_w": r.get("classifier.dense.weight").T,
            "pooler_b": r.get("classifier.dense.bias"),
            "cls_w": r.get("classifier.out_proj.weight").T,
            "cls_b": r.get("classifier.out_proj.bias"),
        })
    elif r.has("classifier.weight"):     # BertForSequenceClassification
        tree.update({
            "pooler_w": g("pooler.dense.weight").T,
            "pooler_b": g("pooler.dense.bias"),
            "cls_w": r.get("classifier.weight").T,
            "cls_b": r.get("classifier.bias"),
        })
    log_dist(f"loaded HF BERT checkpoint {model_path} "
             f"({cfg.num_layers}L/{H}H)", ranks=[0])
    return cfg, tree


def load_hf_checkpoint(model_path: str, *, max_seq_len: Optional[int] = None,
                       dtype=None) -> Tuple[Any, Dict[str, Any]]:
    """Load an HF model directory → (GPTConfig, flax params tree).

    Weights keep their checkpoint dtype (engines cast to their serving dtype);
    ``dtype`` sets the config's COMPUTE dtype only.
    """
    cfg = config_from_hf(model_path, max_seq_len=max_seq_len, dtype=dtype)
    if cfg.hc:
        raise NotImplementedError(
            "xing4_0 checkpoints: the config maps (xing4_0_config), but the "
            "published tensor names of the hyper-connections are not in the "
            "config and none is guessed here: a map under a real model's "
            "name that reads the wrong tensors is worse than none")
    if cfg.attn_sink or cfg.window_attn and not cfg.mla:
        raise NotImplementedError(
            "mimo_v2_flash checkpoints: the config maps "
            "(mimo_v2_flash_config), but the published tensor names (the "
            "sinks', the two attention geometries') are not in the catalog's "
            "row, which gives this model's config.json and not its "
            "checkpoint: there is no name map to hold a loader to, and none "
            "is guessed here; build the model from the config and pass "
            "weights of your own")
    if cfg.mla:
        return cfg, _deepseek_v3_tree(_ShardReader(model_path), cfg)
    if cfg.conv_layers:
        return cfg, _lfm2_moe_tree(_ShardReader(model_path), cfg)
    if "lightning" in cfg.layer_types:
        return cfg, _minicpm_sala_tree(_ShardReader(model_path), cfg)
    if cfg.layer_types:
        return cfg, _granite_hybrid_tree(_ShardReader(model_path), cfg)
    if cfg.moe_router == "sigmoid":
        raise NotImplementedError(
            "afmoe checkpoints: the config maps (afmoe_config) and "
            "AFMOE_WEIGHT_NAMES names the tensors, but no loader reads "
            "them yet")
    r = _ShardReader(model_path)
    arch = _arch_of(_read_json(os.path.join(model_path, "config.json")))
    if arch in _GPT2_LIKE:
        tree = _gpt2_tree(r, cfg)
    elif arch in _OPT_LIKE:
        tree = _opt_tree(r, cfg)
    elif arch in _PHI_LIKE:
        tree = _phi_tree(r, cfg)
    elif arch in _FALCON_LIKE:
        tree = _falcon_tree(r, cfg)
    elif arch in _GPTJ_LIKE:
        tree = _gptj_tree(r, cfg)
    elif arch in _NEOX_LIKE:
        tree = _neox_tree(r, cfg)
    elif arch in _BLOOM_LIKE:
        tree = _bloom_tree(r, cfg)
    elif arch in _GPTNEO_LIKE:
        tree = _gptneo_tree(r, cfg)
    elif arch in _BIGCODE_LIKE:
        tree = _bigcode_tree(r, cfg)
    elif arch in _GEMMA_LIKE:
        tree = _gemma_absorb_norm_offset(_llama_tree(r, cfg))
    elif arch in _PHI3_LIKE:
        tree = _phi3_tree(r, cfg)
    else:
        tree = _llama_tree(r, cfg)
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(tree))
    log_dist(f"loaded HF checkpoint {model_path} ({arch}): {n/1e6:.1f}M params",
             ranks=[0])
    return cfg, tree


def is_hf_model_dir(path: Any) -> bool:
    return (isinstance(path, (str, os.PathLike))
            and os.path.isdir(path)
            and os.path.exists(os.path.join(path, "config.json")))


# ----------------------------------------------------------- export direction
def save_hf_checkpoint(cfg, params, model_path: str) -> None:
    """Export a flax GPT tree as an HF model directory (config.json +
    model.safetensors) — the cross-framework leg of universal checkpointing
    (reference checkpoint/ds_to_universal.py exports framework-neutral
    fragments; here the neutral format IS the HF layout, so the exported
    model loads straight into ``transformers`` or back through
    ``load_hf_checkpoint``).

    Supports the llama family (rope+rmsnorm+SwiGLU) and gpt2 config points of
    the GPT module — the same coverage as the import direction.
    """
    import torch
    from safetensors.torch import save_file

    if getattr(cfg, "embed_scale", None) or \
            getattr(cfg, "gate_act", "silu") != "silu":
        raise ValueError(
            "export supports llama/gpt2 semantics only: embed_scale/GeGLU "
            "(gemma) configs would silently export a DIFFERENT model under "
            "a llama architecture tag")
    params = dict(params)
    if "params" in params:
        params = params["params"]
    bb = params["backbone"]
    H, nh, nkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                      cfg.head_dim)
    os.makedirs(model_path, exist_ok=True)

    def t(x):
        arr = np.asarray(jax.device_get(x))
        if arr.dtype.name == "bfloat16":
            return torch.from_numpy(
                arr.view(np.int16).copy()).view(torch.bfloat16)
        return torch.from_numpy(np.ascontiguousarray(arr))

    tensors: Dict[str, Any] = {}
    if cfg.use_rope and cfg.use_rmsnorm and cfg.gated_mlp:
        moe = bool(cfg.num_experts)
        if moe and cfg.moe_every != 1:
            raise ValueError("Mixtral export requires MoE on every layer "
                             "(moe_every=1)")
        if moe:
            arch = "MixtralForCausalLM"
        else:
            arch = "Qwen2ForCausalLM" if cfg.qkv_bias else "LlamaForCausalLM"
        hf_cfg = {
            "architectures": [arch],
            "model_type": arch.replace("ForCausalLM", "").lower(),
            "vocab_size": cfg.vocab_size,
            "hidden_size": H,
            "intermediate_size": cfg.mlp_dim,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": nh,
            "num_key_value_heads": nkv,
            "head_dim": hd,
            "max_position_embeddings": cfg.max_seq_len,
            "rms_norm_eps": cfg.norm_eps or 1e-6,
            "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": bool(cfg.tie_embeddings),
            "hidden_act": "silu",
            "torch_dtype": "float32",
        }
        if moe:
            hf_cfg["num_local_experts"] = cfg.num_experts
            hf_cfg["num_experts_per_tok"] = cfg.moe_k
        tensors["model.embed_tokens.weight"] = t(bb["wte"])
        tensors["model.norm.weight"] = t(bb["final_norm"]["scale"])
        for i in range(cfg.num_layers):
            blk = bb[f"block_{i}"]
            ap = blk["Attention_0"]
            p = f"model.layers.{i}."
            tensors[p + "self_attn.q_proj.weight"] = t(
                np.asarray(ap["wq"]).reshape(H, nh * hd).T)
            tensors[p + "self_attn.k_proj.weight"] = t(
                np.asarray(ap["wk"]).reshape(H, nkv * hd).T)
            tensors[p + "self_attn.v_proj.weight"] = t(
                np.asarray(ap["wv"]).reshape(H, nkv * hd).T)
            tensors[p + "self_attn.o_proj.weight"] = t(
                np.asarray(ap["wo"]).reshape(nh * hd, H).T)
            if cfg.qkv_bias:
                tensors[p + "self_attn.q_proj.bias"] = t(
                    np.asarray(ap["bq"]).reshape(-1))
                tensors[p + "self_attn.k_proj.bias"] = t(
                    np.asarray(ap["bk"]).reshape(-1))
                tensors[p + "self_attn.v_proj.bias"] = t(
                    np.asarray(ap["bv"]).reshape(-1))
            tensors[p + "input_layernorm.weight"] = t(blk["Norm_0"]["scale"])
            tensors[p + "post_attention_layernorm.weight"] = t(
                blk["Norm_1"]["scale"])
            if moe:
                m = p + "block_sparse_moe."
                mo = blk["moe"]
                tensors[m + "gate.weight"] = t(np.asarray(mo["gate"]).T)
                for e in range(cfg.num_experts):
                    tensors[m + f"experts.{e}.w1.weight"] = t(
                        np.asarray(mo["wge"][e]).T)
                    tensors[m + f"experts.{e}.w3.weight"] = t(
                        np.asarray(mo["wi"][e]).T)
                    tensors[m + f"experts.{e}.w2.weight"] = t(
                        np.asarray(mo["wo"][e]).T)
            else:
                mp = blk["MLP_0"]
                tensors[p + "mlp.up_proj.weight"] = t(np.asarray(mp["wi"]).T)
                tensors[p + "mlp.gate_proj.weight"] = t(
                    np.asarray(mp["wg"]).T)
                tensors[p + "mlp.down_proj.weight"] = t(
                    np.asarray(mp["wo"]).T)
        if not cfg.tie_embeddings:
            tensors["lm_head.weight"] = t(np.asarray(params["lm_head"]).T)
    elif not cfg.use_rope and not cfg.use_rmsnorm and not cfg.gated_mlp:
        if not cfg.tie_embeddings:
            raise ValueError(
                "GPT2LMHeadModel always ties wte/lm_head — an untied "
                "gpt2-point model cannot round-trip through the gpt2 "
                "architecture; train with tie_embeddings=True to export")
        hf_cfg = {
            "architectures": ["GPT2LMHeadModel"],
            "model_type": "gpt2",
            "vocab_size": cfg.vocab_size,
            "n_embd": H, "n_layer": cfg.num_layers, "n_head": nh,
            "n_positions": cfg.max_seq_len, "n_ctx": cfg.max_seq_len,
            "n_inner": cfg.mlp_dim,
            "layer_norm_epsilon": cfg.norm_eps or 1e-5,
            "torch_dtype": "float32",
        }
        tensors["wte.weight"] = t(bb["wte"])
        tensors["wpe.weight"] = t(bb["wpe"])
        tensors["ln_f.weight"] = t(bb["final_norm"]["scale"])
        tensors["ln_f.bias"] = t(bb["final_norm"]["bias"])
        for i in range(cfg.num_layers):
            blk = bb[f"block_{i}"]
            ap, mp = blk["Attention_0"], blk["MLP_0"]
            p = f"h.{i}."
            ca = np.concatenate([np.asarray(ap[k]).reshape(H, -1)
                                 for k in ("wq", "wk", "wv")], axis=1)
            cb = np.concatenate([np.asarray(ap[k]).reshape(-1)
                                 for k in ("bq", "bk", "bv")])
            tensors[p + "attn.c_attn.weight"] = t(ca)        # Conv1D [in,out]
            tensors[p + "attn.c_attn.bias"] = t(cb)
            tensors[p + "attn.c_proj.weight"] = t(
                np.asarray(ap["wo"]).reshape(nh * hd, H))
            tensors[p + "attn.c_proj.bias"] = t(ap["bo"])
            tensors[p + "ln_1.weight"] = t(blk["Norm_0"]["scale"])
            tensors[p + "ln_1.bias"] = t(blk["Norm_0"]["bias"])
            tensors[p + "ln_2.weight"] = t(blk["Norm_1"]["scale"])
            tensors[p + "ln_2.bias"] = t(blk["Norm_1"]["bias"])
            tensors[p + "mlp.c_fc.weight"] = t(mp["wi"])
            tensors[p + "mlp.c_fc.bias"] = t(mp["bi"])
            tensors[p + "mlp.c_proj.weight"] = t(mp["wo"])
            tensors[p + "mlp.c_proj.bias"] = t(mp["bo"])
    else:
        raise ValueError(
            "export supports llama-family (rope+rmsnorm+SwiGLU) and gpt2 "
            "(learned-pos+LN+GELU) config points; got a mixed configuration")

    with open(os.path.join(model_path, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=2)
    save_file(tensors, os.path.join(model_path, "model.safetensors"))
    log_dist(f"exported HF checkpoint → {model_path} "
             f"({hf_cfg['architectures'][0]}, {len(tensors)} tensors)",
             ranks=[0])
