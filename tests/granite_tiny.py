"""What ``tests/test_granite_hybrid.py`` (the operations, the model, the
gradient, the checkpoint) and ``tests/test_granite_hybrid_engine.py`` (the
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

import _granite_faults as faults  # noqa: E402,F401
import _granite_hybrid as ref  # noqa: E402

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits  # noqa: E402
from deepspeed_tpu.parallel.metadata import unbox  # noqa: E402

TOL = 2e-4
SIZES = dict(
    model_type="granitemoehybrid", hidden_act="silu",
    normalization_function="rmsnorm", position_embedding_type="nope",
    num_local_experts=0, attention_bias=False, mamba_proj_bias=False,
    tie_word_embeddings=True, hidden_size=32, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=4,
    layer_types=["mamba", "attention", "mamba", "mamba"],
    shared_intermediate_size=64, vocab_size=128, rms_norm_eps=1e-5,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
    mamba_d_conv=4, mamba_chunk_size=8, mamba_conv_bias=True, mamba_expand=2,
    embedding_multiplier=12.0, attention_multiplier=0.125,
    residual_multiplier=0.22, logits_scaling=8.0,
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
    a hidden width of 32 the mixers' states carry as much of the signal as
    they do at published widths: steps near 1 (``dt_bias`` 0.5) and decays
    near 0.87 a step (``A`` near 0.14), a memory of some eight rows against
    a chunk of 8; the conv's bias moved off zero."""
    tree = unbox(GPTLogits(cfg).init(jax.random.PRNGKey(3),
                                     jnp.zeros((1, 8), jnp.int32)))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))

    def scale(path, a):
        name = jax.tree_util.keystr(path)
        if "conv_b" in name:
            return 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "dt_bias" in name:
            return jnp.full_like(a, 0.5)
        if "A_log" in name:
            return -2.0 + 0.3 * jax.random.normal(next(keys), a.shape,
                                                  a.dtype)
        return a * 6 if a.ndim >= 2 else a
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


