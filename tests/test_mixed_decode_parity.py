"""One layer body, three kinds of step (``inference/v2/model.py:_layer``): a
prompt run as ONE mixed step and the same prompt run token by token through
the decode program must leave the same logits for the next token and the
same pool contents, for every kind of model the serving cells run.  The
mixed step's parity with a plain reference is each model's own file's; this
file holds the decode program to the mixed one, layer kind by layer kind, so
that a slip in what one kind of step supplies to the body shows as a wrong
answer here and not only on the chip.

Tiny sizes in float32 on the CPU (the paged ops' XLA forms), so a difference
is summation order: the neighbours' tolerances (2e-5 to 2e-4 on logits of
order 0.1 to 1), and one int8 code where the pool is quantised."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import v2_engine

from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits
from deepspeed_tpu.parallel.metadata import unbox

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))

MANAGER = {"max_tracked_sequences": 4, "max_ragged_sequence_count": 4,
           "max_ragged_batch_size": 64, "max_q_per_seq": 32,
           "kv_block_size": 16, "num_kv_blocks": 64}


def _seeded(cfg, gain=6.0):
    """Seeded weights, the matrices ``gain`` times the usual 0.02 so that at
    a hidden width of 32 every branch carries a visible share."""
    tree = unbox(GPTLogits(cfg).init(jax.random.PRNGKey(5),
                                     jnp.zeros((1, 8), jnp.int32)))["params"]
    return jax.tree_util.tree_map(lambda a: a * gain if a.ndim >= 2 else a,
                                  tree)


def _llama(hidden, **kw):
    cfg = dataclasses.replace(
        GPTConfig.llama(num_layers=2, hidden=hidden, heads=2, num_kv_heads=1,
                        vocab_size=96, max_seq_len=256, dtype=None),
        dtype=jnp.float32)
    return cfg, _seeded(cfg, 2.0), kw


def _trinity():
    # a window of 12 over pages of 4: rows 12 and 13 no longer see the
    # prompt's first rows, and no page of the ring has been given back yet
    import test_trinity as t
    cfg, params = t.model(t.sizes(window=12))
    return cfg, params, dict(kv_block_size=4, num_kv_window_blocks=24)


def _moonlight():
    import test_moonlight as t
    return (*t.model(t.sizes()), {})


def _dots3():
    # a selection of 8 binds from the ninth row on; the window of 9 over
    # pages of 16 gives no page back within 24 rows
    import test_dots3_note as t
    return (*t.model(t.sizes(index_topk=8)), {})


def _granite():
    import _granite_hybrid
    from granite_tiny import SIZES
    cfg = GPTConfig(**_granite_hybrid.program_config(SIZES), max_seq_len=256)
    return cfg, _seeded(cfg), {}


def _lfm2():
    import _lfm2_moe
    from lfm2_tiny import SIZES
    cfg = GPTConfig(**_lfm2_moe.program_config(SIZES), max_seq_len=256)
    return cfg, _seeded(cfg), {}


PRESETS = {
    "dense-rowmajor": (lambda: _llama(256), 20, 1e-4),
    "kvmajor-hd64": (lambda: _llama(128), 20, 1e-4),
    "int8-pool": (lambda: _llama(256, kv_quant="int8"), 20, 2e-2),
    "trinity-grouped-moe": (_trinity, 14, 2e-5),
    "moonlight-latent-moe": (_moonlight, 24, 3e-5),
    "dots3-latent-groups-select": (_dots3, 24, 1e-4),
    "granite-scan": (_granite, 24, 2e-4),
    "lfm2-conv-moe": (_lfm2, 24, 2e-4),
}


@pytest.mark.parametrize("preset", list(PRESETS))
def test_one_mixed_step_is_the_prompt_token_by_token(preset):
    build, n, tol = PRESETS[preset]
    cfg, params, sm = build()
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, size=n).astype(np.int32)

    def engine():
        return v2_engine(cfg, {"dtype": "float32",
                               "state_manager": {**MANAGER, **sm}},
                         params=params)
    mixed, stepped = engine(), engine()
    want = mixed.put([1], [prompt])[0]                  # one mixed step
    for tok in prompt:                                  # n decode steps
        got = stepped.put([1], [tok[None]])[0]
    assert mixed.telemetry.c_dispatch.value(kind="mixed") == 1
    assert stepped.telemetry.c_dispatch.value(kind="decode") == n
    assert stepped.telemetry.c_dispatch.value(kind="mixed") == 0
    np.testing.assert_allclose(got, want, atol=tol)
    for name, a, b in zip(mixed.cache._fields, mixed.cache, stepped.cache):
        assert (a is None) == (b is None), name
        if a is None:
            continue
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.any(), name
        if a.dtype == np.int8:      # a code apart where a row's amax moved
            assert np.abs(a.astype(np.int32) - b).max() <= 1, name
        else:
            np.testing.assert_allclose(b, a, atol=tol, err_msg=name)
