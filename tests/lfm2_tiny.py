"""What ``tests/test_lfm2_moe.py`` (the mixers, the model, the gradient, the
parameter count, the checkpoint) and ``tests/test_lfm2_moe_engine.py`` (the
serving engine's paths) share: the tiny sizes, the seeded weights and the
reference's logits.  Two files, because a file runs on one worker."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark",
                                "reference"))

import _lfm2_faults as faults  # noqa: E402,F401
import _lfm2_moe as ref  # noqa: E402

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits  # noqa: E402
from deepspeed_tpu.parallel.metadata import unbox  # noqa: E402

TOL = 2e-4
# both dense layers (conv), then one attention and three conv expert layers
SIZES = dict(
    model_type="lfm2_moe", conv_bias=False, conv_L_cache=3, hidden_size=32,
    intermediate_size=64, moe_intermediate_size=16, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=6, num_dense_layers=2,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=1.0, use_expert_bias=True, norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000.0, "rope_type": "default"},
    vocab_size=128, max_position_embeddings=256,
    run={"state_manager": {"max_q_per_seq": 32}})
STATE_MANAGER = {"max_tracked_sequences": 4, "max_ragged_sequence_count": 4,
                 "max_ragged_batch_size": 64, "max_q_per_seq": 32,
                 "kv_block_size": 16, "num_kv_blocks": 64}


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig(**ref.program_config(SIZES), max_seq_len=256)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights with the matrices six times the usual 0.02, so that at
    a hidden width of 32 every branch carries a visible share of the
    residual stream and the attention's scores move off zero; the selection
    bias large enough that selection and weights differ."""
    tree = unbox(jax.jit(lambda key: GPTLogits(cfg).init(
        key, jnp.zeros((1, 8), jnp.int32)))(jax.random.PRNGKey(3)))["params"]

    def scale(path, a):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            return a * 20
        return a * 6 if a.ndim >= 2 and "conv_w" not in name else a
    return jax.tree_util.tree_map_with_path(scale, tree)


@pytest.fixture(scope="module")
def seqs():
    rng = np.random.default_rng(11)
    return [rng.integers(0, SIZES["vocab_size"], size=n).astype(np.int32)
            for n in (75, 23)]


@pytest.fixture(scope="module")
def want(params, seqs):
    """The reference's logits of both sequences, every row."""
    return [np.asarray(ref.logits(params, s, SIZES)) for s in seqs]


def engine(cfg, params, steps, **over):
    return InferenceEngineV2(
        cfg, {"dtype": "float32",
              "state_manager": {**STATE_MANAGER, **over}},
        params=params, steps_cache=steps)
