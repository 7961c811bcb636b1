"""What ``tests/test_mimo_v2.py`` (the ops, the model, the faults, the
checkpoint config) and ``tests/test_mimo_v2_engine.py`` (the serving engine's
paths) share: the tiny sizes, the seeded weights and the reference's logits.
Two files, because a file runs on one worker."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark",
                                "reference"))

import _mimo_v2 as ref  # noqa: E402
import _mimo_faults as faults  # noqa: E402,F401

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits  # noqa: E402
from deepspeed_tpu.parallel.metadata import unbox  # noqa: E402

TOL = 2e-4
# layer 0 full and dense, then experts: window, full, window.  The full
# layers have 2 kv heads (groups of 4), the window layers 4 (groups of 2);
# keys 24 wide (8 columns rotate), values 16; a window of one page of 16;
# 4 of the router's 8 experts held, from the third on
SIZES = dict(
    model_type="mimo_v2_flash", attention_bias=False, hidden_act="silu",
    attention_value_scale=0.707, hidden_size=64, intermediate_size=128,
    max_position_embeddings=256, num_attention_heads=8, head_dim=24,
    num_hidden_layers=4, num_key_value_heads=2, layernorm_epsilon=1e-5,
    rope_theta=5000000, tie_word_embeddings=False, vocab_size=128,
    partial_rotary_factor=0.334, sliding_window=16, swa_rope_theta=10000,
    v_head_dim=16, hybrid_layer_pattern=[0, 1, 0, 1],
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    sliding_window_size=16, attention_chunk_size=16,
    moe_layer_freq=[0, 1, 1, 1], moe_intermediate_size=32,
    n_routed_experts=4, router_width=8, expert_offset=2,
    n_shared_experts=None, num_experts_per_tok=2, norm_topk_prob=True,
    scoring_func="sigmoid", n_group=1, topk_group=1, topk_method="noaux_tc",
    routed_scaling_factor=None, swa_num_attention_heads=8,
    swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16)
STATE_MANAGER = {"max_tracked_sequences": 4, "max_ragged_sequence_count": 4,
                 "max_ragged_batch_size": 64, "max_q_per_seq": 32,
                 "kv_block_size": 16, "num_kv_blocks": 64}


def make_cfg(sizes=SIZES, **over):
    return GPTConfig(**{**ref.program_config(sizes), "max_seq_len": 256,
                        **over})


def make_params(cfg, seed=3):
    """Seeded weights with the matrices six times the usual 0.02, so that at
    a hidden width of 64 every branch carries a visible share of the residual
    and the attention's scores move off zero; the selection bias large enough
    that selection and weights differ.  The sinks keep their own law
    (``models/gpt.py:_sink_init``, Normal(4, 1))."""
    tree = unbox(jax.jit(lambda key: GPTLogits(cfg).init(
        key, jnp.zeros((1, 8), jnp.int32)))(jax.random.PRNGKey(seed)))[
            "params"]

    def scale(path, a):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            return a * 20
        return a * 6 if a.ndim >= 2 else a
    return jax.tree_util.tree_map_with_path(scale, tree)


@pytest.fixture(scope="module")
def cfg():
    return make_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return make_params(cfg)


@pytest.fixture(scope="module")
def seqs():
    rng = np.random.default_rng(11)
    return [rng.integers(0, SIZES["vocab_size"], size=n).astype(np.int32)
            for n in (115, 63)]


@pytest.fixture(scope="module")
def want(params, seqs):
    """The reference's logits of both sequences, every row."""
    return [np.asarray(ref.logits(params, s, SIZES)) for s in seqs]


def engine(cfg, params, steps=None, dtype="float32", **over):
    return InferenceEngineV2(
        cfg, {"dtype": dtype, "state_manager": {**STATE_MANAGER, **over}},
        params=params, steps_cache=steps)
