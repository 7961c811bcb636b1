"""Hybrid engine (RLHF train↔generate) tests — reference pattern:
tests/unit/hybrid_engine/test_he_*.py (generate matches, weights track
training)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import GPT, GPTConfig


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig.tiny(vocab_size=96, max_seq_len=64)
    config = {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 5e-2}},
        "mesh": {"dp": 1},
        "steps_per_print": 0,
        "hybrid_engine": {"enabled": True},
    }
    rng = np.random.default_rng(0)
    pool = rng.integers(0, 96, size=(8, 64)).astype(np.int32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), config=config, example_batch={"input_ids": pool})
    return cfg, engine, pool


class TestHybridEngine:
    def test_generate_matches_standalone_v2(self, setup, rng):
        """Hybrid rollouts must be token-exact vs a fresh v2 engine given the
        same weights (the layout conversion is exact, reference he_all tests)."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2

        cfg, engine, _ = setup
        hybrid = engine.hybrid_engine(
            {"dtype": "fp32", "generation": {"do_sample": False},
             "state_manager": {"max_tracked_sequences": 4,
                               "kv_block_size": 8}})
        prompts = [rng.integers(0, 96, size=n).astype(np.int32)
                   for n in (7, 12)]
        got = hybrid.generate(prompts, max_new_tokens=8)

        fresh = InferenceEngineV2(
            cfg, {"dtype": "fp32", "generation": {"do_sample": False},
                  "state_manager": {"max_tracked_sequences": 4,
                                    "kv_block_size": 8}},
            params=hybrid._train_params())
        want = fresh.generate(prompts, max_new_tokens=8)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_weights_resync_after_training(self, setup, rng):
        """Training between generate phases must change rollouts (the bridge
        re-syncs on the step clock)."""
        cfg, engine, pool = setup
        hybrid = engine.hybrid_engine()
        prompts = [rng.integers(0, 96, size=10).astype(np.int32)]
        before = hybrid.generate(prompts, max_new_tokens=12, do_sample=False)
        step0 = hybrid._synced_step
        for _ in range(30):
            engine.train_batch({"input_ids": pool})
        after = hybrid.generate(prompts, max_new_tokens=12, do_sample=False)
        assert hybrid._synced_step > step0
        assert not np.array_equal(before[0], after[0])

    def test_requires_gpt_family(self):
        class Fake:
            pass
        from deepspeed_tpu.runtime.hybrid_engine import HybridEngine
        fake_engine = type("E", (), {"model": Fake(), "config": None,
                                     "global_steps": 0})()
        with pytest.raises(TypeError, match="GPT-family"):
            HybridEngine(fake_engine)
