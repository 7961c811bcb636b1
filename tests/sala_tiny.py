"""What ``tests/test_minicpm_sala.py`` (the operations, the model, the
planted faults, the checkpoint) and ``tests/test_minicpm_sala_engine.py`` (the
serving engine's paths) share: the tiny MiniCPM-SALA (the benchmark
configuration's ``rehearsal`` sizes: kernel 4, stride 2, block 8, top 4,
window 16, ``dense_len`` 32; heads of 128, so that pages are row-major as at
published widths), the seeded weights and the reference's logits.  Two files,
because a file runs on one worker."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "benchmark", "reference"))

import _minicpm_sala as ref  # noqa: E402
import _sala_faults as faults  # noqa: E402,F401

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits  # noqa: E402
from deepspeed_tpu.parallel.metadata import unbox  # noqa: E402

TOL = 2e-4


def sizes():
    """The configuration file at its rehearsal sizes."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala-12l.json")) as f:
        full = json.load(f)
    return {**full, **full["rehearsal"]}


SIZES = sizes()
STATE_MANAGER = {"max_tracked_sequences": 4, "max_ragged_sequence_count": 4,
                 "max_ragged_batch_size": 64, "max_q_per_seq": 32,
                 "kv_block_size": 16, "num_kv_blocks": 64}
N_DEC = 8


def config(sz=SIZES, **kw):
    kw.setdefault("max_seq_len", 256)
    return GPTConfig(**ref.program_config(sz), dropout=0.0, **kw)


def weights(c, seed=3, scale=6.0):
    """Seeded float32 weights; the matrices times ``scale`` (at the
    initialiser's 0.02 a tiny model's scores are all near zero, its
    softmaxes uniform and a wrong choice of blocks invisible)."""
    tree = unbox(GPTLogits(c).init(jax.random.PRNGKey(seed),
                                   jnp.zeros((1, 8), jnp.int32)))["params"]
    return jax.tree_util.tree_map(
        lambda a: a * scale if a.ndim >= 2 else a, tree)


@pytest.fixture(scope="module")
def cfg():
    return config()


@pytest.fixture(scope="module")
def params(cfg):
    return weights(cfg)


@pytest.fixture(scope="module")
def seqs():
    """77 tokens (a prompt of 69: three forwards of 32, 32 and 5 rows, the
    second crossing ``dense_len`` 32; then 8 positions) and 35 (a prompt of
    27, whose 8 decoded positions cross ``dense_len``)."""
    rng = np.random.default_rng(11)
    return [rng.integers(0, SIZES["vocab_size"], size=n).astype(np.int32)
            for n in (77, 35)]


@pytest.fixture(scope="module")
def want(params, seqs):
    return [np.asarray(ref.logits(params, s, SIZES)) for s in seqs]


def engine(cfg, params, steps, config=None, **state_manager):
    return InferenceEngineV2(
        cfg, {"dtype": "float32", **(config or {}),
              "state_manager": {**STATE_MANAGER, **state_manager}},
        params=params, steps_cache=steps)
