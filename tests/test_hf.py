"""HF checkpoint engine tests — logits parity vs transformers.

Reference pattern: tests/unit/inference/test_inference.py loads real HF models
through the injection policies and checks outputs vs the vanilla HF forward.
Here: build a TINY randomly-initialized HF model per supported architecture,
``save_pretrained`` → safetensors, stream it into the flax tree
(checkpoint/hf.py), and compare fp32 logits against the torch forward.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.checkpoint.hf import (config_from_hf, is_hf_model_dir,
                                         load_hf_checkpoint)

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


def _save(tmp_path, model, name):
    path = os.path.join(tmp_path, name)
    model.save_pretrained(path, safe_serialization=True)
    return path


def _torch_logits(model, ids):
    with torch.no_grad():
        return model(torch.tensor(ids, dtype=torch.long)).logits.numpy()


def _our_logits(path, ids):
    cfg, params = load_hf_checkpoint(path, dtype=jnp.float32)
    eng = deepspeed_tpu.init_inference(
        cfg, config={"dtype": "fp32"}, params=params)
    return np.asarray(eng.forward(ids))


def _check(path, model, rng, vocab, atol=2e-3):
    ids = rng.integers(0, vocab, (2, 12)).astype(np.int32)
    want = _torch_logits(model, ids)
    got = _our_logits(path, ids)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=atol, rtol=1e-3)


@pytest.fixture(scope="module")
def tmp_models(tmp_path_factory):
    """Directory of tiny HF fixture models, built ON DEMAND so any test (or
    -k selection) can run in isolation."""
    root = str(tmp_path_factory.mktemp("hf_models"))

    def ensure(name):
        path = os.path.join(root, name)
        if os.path.exists(os.path.join(path, "config.json")):
            return path
        if name == "llama":
            torch.manual_seed(0)
            model = transformers.LlamaForCausalLM(transformers.LlamaConfig(
                vocab_size=128, hidden_size=64, intermediate_size=172,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=64,
                rms_norm_eps=1e-5, rope_theta=10000.0,
                tie_word_embeddings=False))
        elif name == "gpt2":
            torch.manual_seed(3)
            model = transformers.GPT2LMHeadModel(transformers.GPT2Config(
                vocab_size=128, n_positions=64, n_embd=64, n_layer=2,
                n_head=4))
        else:
            raise KeyError(name)
        model.eval().save_pretrained(path, safe_serialization=True)
        return path

    root_path = type("Models", (str,), {"ensure": staticmethod(ensure)})(root)
    return root_path


class TestLlamaFamily:
    def test_llama_logits_match(self, tmp_models, rng):
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
            tie_word_embeddings=False)
        torch.manual_seed(0)
        model = transformers.LlamaForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "llama")
        _check(path, model, rng, 128)

    def test_llama31_rope_scaling_logits_match(self, tmp_models, rng):
        """llama-3.1 piecewise rope scaling (HF rope_type='llama3') —
        round 3: previously REJECTED, now implemented and parity-tested."""
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rms_norm_eps=1e-5, rope_theta=10000.0,
            tie_word_embeddings=False,
            rope_scaling={"rope_type": "llama3", "factor": 8.0,
                          "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                          "original_max_position_embeddings": 32})
        torch.manual_seed(7)
        model = transformers.LlamaForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "llama31")
        _check(path, model, rng, 128)
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        c = config_from_hf(path)
        assert c.rope_scaling is not None and c.rope_scaling[0] == "llama3"
        # the scaling must actually CHANGE the logits vs unscaled rope
        import dataclasses
        _, params = load_hf_checkpoint(path, dtype=jnp.float32)
        ids = rng.integers(0, 128, (1, 12)).astype(np.int32)
        e1 = deepspeed_tpu.init_inference(c, config={"dtype": "fp32"},
                                          params=params)
        e2 = deepspeed_tpu.init_inference(
            dataclasses.replace(c, rope_scaling=None),
            config={"dtype": "fp32"}, params=params)
        d = np.abs(np.asarray(e1.forward(ids))
                   - np.asarray(e2.forward(ids))).max()
        assert d > 1e-4

    def test_linear_rope_scaling_logits_match(self, tmp_models, rng):
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rms_norm_eps=1e-5, rope_theta=10000.0,
            tie_word_embeddings=False,
            rope_scaling={"rope_type": "linear", "factor": 2.0})
        torch.manual_seed(8)
        model = transformers.LlamaForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "llama_linear_rope")
        _check(path, model, rng, 128)

    def test_yarn_rope_scaling_still_rejected(self, tmp_models):
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            tie_word_embeddings=False,
            rope_scaling={"rope_type": "yarn", "factor": 2.0})
        model = transformers.LlamaForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "llama_yarn")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        with pytest.raises(ValueError, match="rope_scaling"):
            config_from_hf(path)

    def test_mistral_logits_match(self, tmp_models, rng):
        cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=1e6,
            sliding_window=None, tie_word_embeddings=False)
        torch.manual_seed(1)
        model = transformers.MistralForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "mistral")
        _check(path, model, rng, 128)

    def test_qwen2_logits_match(self, tmp_models, rng):
        cfg = transformers.Qwen2Config(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=1e6,
            tie_word_embeddings=False)
        torch.manual_seed(2)
        model = transformers.Qwen2ForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "qwen2")
        # qwen2 has qkv biases — make them nonzero so the mapping is exercised
        with torch.no_grad():
            for layer in model.model.layers:
                for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                             layer.self_attn.v_proj):
                    proj.bias.normal_(0, 0.02)
        path = _save(tmp_models, model, "qwen2")
        _check(path, model, rng, 128)

    def test_config_mapping(self, tmp_models):
        cfg = config_from_hf(os.path.join(tmp_models, "qwen2"))
        assert cfg.qkv_bias and cfg.use_rope and cfg.use_rmsnorm
        assert cfg.gated_mlp and not cfg.tie_embeddings
        assert cfg.mlp_dim == 172 and cfg.num_kv_heads == 2
        assert cfg.rope_theta == 1e6


class TestGPT2:
    def test_gpt2_logits_match(self, tmp_models, rng):
        cfg = transformers.GPT2Config(
            vocab_size=128, n_positions=64, n_embd=64, n_layer=2, n_head=4)
        torch.manual_seed(3)
        model = transformers.GPT2LMHeadModel(cfg).eval()
        path = _save(tmp_models, model, "gpt2")
        _check(path, model, rng, 128)


class TestOptPhiFalcon:
    """The non-llama zoo rows (reference module_inject/containers/opt.py,
    inference/v2/model_implementations/{phi,falcon}): learned-position ReLU
    OPT, parallel-residual partial-rotary Phi, parallel-residual MQA/GQA
    Falcon."""

    def test_opt_logits_match(self, tmp_models, rng):
        cfg = transformers.OPTConfig(
            vocab_size=128, hidden_size=64, ffn_dim=192,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, word_embed_proj_dim=64,
            do_layer_norm_before=True)
        torch.manual_seed(4)
        model = transformers.OPTForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "opt")
        _check(path, model, rng, 128)

    def test_opt_rejects_post_norm_and_proj(self, tmp_models):
        path = os.path.join(tmp_models, "opt350")
        os.makedirs(path, exist_ok=True)
        base = dict(architectures=["OPTForCausalLM"], hidden_size=64,
                    vocab_size=128, ffn_dim=192, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump({**base, "do_layer_norm_before": False}, f)
        with pytest.raises(ValueError, match="do_layer_norm_before"):
            config_from_hf(path)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump({**base, "word_embed_proj_dim": 32}, f)
        with pytest.raises(ValueError, match="word_embed_proj_dim"):
            config_from_hf(path)

    def test_phi_logits_match(self, tmp_models, rng):
        cfg = transformers.PhiConfig(
            vocab_size=128, hidden_size=64, intermediate_size=192,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            partial_rotary_factor=0.5, rope_theta=10000.0,
            tie_word_embeddings=False)
        torch.manual_seed(5)
        model = transformers.PhiForCausalLM(cfg).eval()
        # exercise the lm_head bias mapping
        with torch.no_grad():
            model.lm_head.bias.normal_(0, 0.05)
        path = _save(tmp_models, model, "phi")
        _check(path, model, rng, 128)

    def test_phi_config_mapping(self, tmp_models):
        cfg = config_from_hf(os.path.join(tmp_models, "phi"))
        assert cfg.parallel_block and cfg.parallel_norms == 1
        assert cfg.rope_pct == 0.5 and cfg.unembed_bias
        assert cfg.qkv_bias and not cfg.use_rmsnorm

    def test_falcon7b_style_logits_match(self, tmp_models, rng):
        """multi_query=True (nkv=1), parallel_attn, shared input norm."""
        cfg = transformers.FalconConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, new_decoder_architecture=False,
            multi_query=True, parallel_attn=True, bias=False, alibi=False,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(6)
        model = transformers.FalconForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "falcon7b")
        _check(path, model, rng, 128)

    def test_falcon40b_style_logits_match(self, tmp_models, rng):
        """new_decoder_architecture: GQA groups + ln_attn/ln_mlp pair."""
        cfg = transformers.FalconConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_kv_heads=2,
            new_decoder_architecture=True, parallel_attn=True, bias=False,
            alibi=False, max_position_embeddings=64,
            tie_word_embeddings=False)
        torch.manual_seed(7)
        model = transformers.FalconForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "falcon40b")
        _check(path, model, rng, 128)

    def test_falcon11b_style_logits_match(self, tmp_models, rng):
        """new_decoder_architecture + num_ln_in_parallel_attn=1 (falcon-11B):
        GQA grouped qkv but one shared input_layernorm."""
        cfg = transformers.FalconConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_kv_heads=2,
            new_decoder_architecture=True, num_ln_in_parallel_attn=1,
            parallel_attn=True, bias=False, alibi=False,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(8)
        model = transformers.FalconForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "falcon11b")
        _check(path, model, rng, 128)

    def test_gptj_logits_match(self, tmp_models, rng):
        """GPT-J: parallel residual + partial INTERLEAVED rotary, handled by
        the load-time head-dim permutation (_rope_interleave_perm)."""
        cfg = transformers.GPTJConfig(
            vocab_size=128, n_embd=64, n_layer=2, n_head=4, rotary_dim=8,
            n_positions=64, tie_word_embeddings=False)
        torch.manual_seed(9)
        model = transformers.GPTJForCausalLM(cfg).eval()
        with torch.no_grad():
            model.lm_head.bias.normal_(0, 0.05)
        path = _save(tmp_models, model, "gptj")
        _check(path, model, rng, 128)

    def test_neox_logits_match(self, tmp_models, rng):
        """GPT-NeoX: fused per-head qkv, dual-norm parallel residual,
        partial half-split rotary."""
        cfg = transformers.GPTNeoXConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=192, rotary_pct=0.25,
            max_position_embeddings=64, use_parallel_residual=True,
            tie_word_embeddings=False)
        torch.manual_seed(10)
        model = transformers.GPTNeoXForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "neox")
        _check(path, model, rng, 128)

    def test_neox_sequential_variant(self, tmp_models, rng):
        """use_parallel_residual=False (pythia-70m-style sequential)."""
        cfg = transformers.GPTNeoXConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=192, rotary_pct=0.5,
            max_position_embeddings=64, use_parallel_residual=False,
            tie_word_embeddings=False)
        torch.manual_seed(11)
        model = transformers.GPTNeoXForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "neox_seq")
        _check(path, model, rng, 128)

    def test_bloom_logits_match(self, tmp_models, rng):
        """BLOOM: alibi bias (no positional table), embedding LayerNorm,
        per-head-interleaved fused qkv, tied embeddings."""
        cfg = transformers.BloomConfig(
            vocab_size=128, hidden_size=64, n_layer=2, n_head=4)
        torch.manual_seed(12)
        model = transformers.BloomForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "bloom")
        _check(path, model, rng, 128)

    def test_bloom_v2_serving(self, tmp_models, rng):
        """alibi through the ragged prefill AND the paged decode fallback ==
        HF greedy generate."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        cfg = transformers.BloomConfig(
            vocab_size=128, hidden_size=64, n_layer=2, n_head=4)
        torch.manual_seed(12)
        model = transformers.BloomForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "bloom")
        prompt = rng.integers(0, 128, (1, 9)).astype(np.int32)
        with torch.no_grad():
            want = model.generate(
                torch.tensor(prompt, dtype=torch.long), max_new_tokens=6,
                do_sample=False).numpy()[0, 9:]
        eng = InferenceEngineV2(
            path, {"dtype": "fp32", "max_seq_len": 64,
                   "state_manager": {"max_tracked_sequences": 2,
                                     "kv_block_size": 8},
                   "generation": {"do_sample": False}})
        got = eng.generate([prompt[0]], max_new_tokens=6)[0]
        np.testing.assert_array_equal(got, want)

    def test_falcon_rw_alibi_logits_match(self, tmp_models, rng):
        """falcon-rw lineage: alibi + bias=True + sequential residual."""
        cfg = transformers.FalconConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, new_decoder_architecture=False,
            multi_query=False, parallel_attn=False, bias=True, alibi=True,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(13)
        model = transformers.FalconForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "falcon_rw")
        _check(path, model, rng, 128)


class TestBertEncoder:
    """Encoder family (reference module_inject/containers/bert.py
    HFBertLayerPolicy): MLM logits parity + padding-mask correctness."""

    def _model(self):
        cfg = transformers.BertConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=64, type_vocab_size=2)
        torch.manual_seed(20)
        return transformers.BertForMaskedLM(cfg).eval()

    def test_bert_mlm_logits_match(self, tmp_models, rng):
        model = self._model()
        path = _save(tmp_models, model, "bert")
        ids = rng.integers(0, 128, (2, 12)).astype(np.int32)
        types = (rng.integers(0, 2, (2, 12))).astype(np.int32)
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long),
                         token_type_ids=torch.tensor(types, dtype=torch.long)
                         ).logits.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        got = np.asarray(eng.forward(ids, token_type_ids=types))
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)

    def test_bert_padding_mask(self, tmp_models, rng):
        model = self._model()
        path = _save(tmp_models, model, "bert")
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        ids = rng.integers(0, 128, (1, 10)).astype(np.int32)
        mask = np.ones((1, 10), np.int32)
        mask[0, 7:] = 0
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long),
                         attention_mask=torch.tensor(mask,
                                                     dtype=torch.long)
                         ).logits.numpy()
        got = np.asarray(eng.forward(ids, attention_mask=mask))
        # compare only non-pad rows (HF computes pad rows too but they are
        # meaningless; ours match on the attended positions)
        np.testing.assert_allclose(got[0, :7], want[0, :7], atol=2e-3,
                                   rtol=1e-3)

    def test_bare_bertmodel_hidden_states(self, tmp_models, rng):
        """architectures=['BertModel'] (no 'bert.' prefix, no MLM head) →
        last-hidden-state parity."""
        cfg = transformers.BertConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=64)
        torch.manual_seed(21)
        model = transformers.BertModel(cfg).eval()
        path = _save(tmp_models, model, "bert_bare")
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        assert not eng.has_mlm_head
        ids = rng.integers(0, 128, (2, 10)).astype(np.int32)
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long)
                         ).last_hidden_state.numpy()
        np.testing.assert_allclose(np.asarray(eng.forward(ids)), want,
                                   atol=2e-3, rtol=1e-3)

    def test_distilbert_mlm_logits_match(self, tmp_models, rng):
        """DistilBERT (reference module_inject/containers/distil_bert.py):
        no token types, tied vocab projector."""
        cfg = transformers.DistilBertConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, hidden_dim=128,
            max_position_embeddings=64)
        torch.manual_seed(22)
        model = transformers.DistilBertForMaskedLM(cfg).eval()
        path = _save(tmp_models, model, "distilbert")
        ids = rng.integers(0, 128, (2, 12)).astype(np.int32)
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        np.testing.assert_allclose(np.asarray(eng.forward(ids)), want,
                                   atol=2e-3, rtol=1e-3)

    def test_bert_sequence_classification(self, tmp_models, rng):
        cfg = transformers.BertConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=64, num_labels=3)
        torch.manual_seed(23)
        model = transformers.BertForSequenceClassification(cfg).eval()
        path = _save(tmp_models, model, "bert_cls")
        ids = rng.integers(0, 128, (2, 12)).astype(np.int32)
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        assert eng.has_cls_head
        got = np.asarray(eng.forward(ids))
        assert got.shape == (2, 3)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)

    def test_bert_seq_len_guard(self, tmp_models):
        model = self._model()
        path = _save(tmp_models, model, "bert")
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.forward(np.zeros((1, 65), np.int32))


class TestV2Serving:
    def test_v2_engine_serves_hf_checkpoint(self, tmp_models, rng):
        """Greedy tokens from the ragged engine == HF greedy generate."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2

        path = tmp_models.ensure("llama")
        torch_model = transformers.LlamaForCausalLM.from_pretrained(path).eval()
        prompt = rng.integers(0, 128, (1, 10)).astype(np.int32)
        with torch.no_grad():
            want = torch_model.generate(
                torch.tensor(prompt, dtype=torch.long), max_new_tokens=8,
                do_sample=False).numpy()[0, 10:]
        eng = InferenceEngineV2(
            path, {"dtype": "fp32",
                   "state_manager": {"max_tracked_sequences": 2,
                                     "kv_block_size": 8},
                   "generation": {"do_sample": False}})
        got = eng.generate([prompt[0]], max_new_tokens=8)[0]
        np.testing.assert_array_equal(got, want)

    def test_v2_serves_rope_scaled_checkpoint(self, tmp_models, rng):
        """llama-3.1 rope scaling through the ragged engine (prefill +
        paged decode both apply the scaled frequencies) == HF greedy."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rms_norm_eps=1e-5, rope_theta=10000.0,
            tie_word_embeddings=False,
            rope_scaling={"rope_type": "llama3", "factor": 8.0,
                          "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                          "original_max_position_embeddings": 32})
        torch.manual_seed(9)
        torch_model = transformers.LlamaForCausalLM(cfg).eval()
        path = _save(tmp_models, torch_model, "llama31_v2")
        prompt = rng.integers(0, 128, (1, 10)).astype(np.int32)
        with torch.no_grad():
            want = torch_model.generate(
                torch.tensor(prompt, dtype=torch.long), max_new_tokens=8,
                do_sample=False).numpy()[0, 10:]
        eng = InferenceEngineV2(
            path, {"dtype": "fp32",
                   "state_manager": {"max_tracked_sequences": 2,
                                     "kv_block_size": 8},
                   "generation": {"do_sample": False}})
        got = eng.generate([prompt[0]], max_new_tokens=8)[0]
        np.testing.assert_array_equal(got, want)

    def test_v2_engine_serves_parallel_block_arch(self, tmp_models, rng):
        """Falcon-style parallel residual through the ragged engine (prefill
        scatter + paged decode) == HF greedy generate."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2

        cfg = transformers.FalconConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, new_decoder_architecture=False,
            multi_query=True, parallel_attn=True, bias=False, alibi=False,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(6)
        model = transformers.FalconForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "falcon7b")
        prompt = rng.integers(0, 128, (1, 9)).astype(np.int32)
        with torch.no_grad():
            want = model.generate(
                torch.tensor(prompt, dtype=torch.long), max_new_tokens=6,
                do_sample=False).numpy()[0, 9:]
        eng = InferenceEngineV2(
            path, {"dtype": "fp32",
                   "state_manager": {"max_tracked_sequences": 2,
                                     "kv_block_size": 8},
                   "generation": {"do_sample": False}})
        got = eng.generate([prompt[0]], max_new_tokens=6)[0]
        np.testing.assert_array_equal(got, want)


class TestErrors:
    def test_unsupported_architecture(self, tmp_models):
        path = os.path.join(tmp_models, "weird")
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump({"architectures": ["MambaForCausalLM"]}, f)
        with pytest.raises(ValueError, match="unsupported HF architecture"):
            config_from_hf(path)

    def test_is_hf_model_dir(self, tmp_models):
        assert is_hf_model_dir(tmp_models.ensure("llama"))
        assert not is_hf_model_dir("/nonexistent")
        assert not is_hf_model_dir({"not": "a path"})


class TestExport:
    """Universal-checkpoint export leg: flax tree → HF directory →
    transformers (reference checkpoint/ds_to_universal.py cross-framework
    goal)."""

    def test_llama_export_roundtrip_via_transformers(self, tmp_models, rng):
        from deepspeed_tpu.checkpoint.hf import (load_hf_checkpoint,
                                                 save_hf_checkpoint)
        src = tmp_models.ensure("llama")
        cfg, params = load_hf_checkpoint(src, dtype=jnp.float32)
        out = os.path.join(tmp_models, "llama_exported")
        save_hf_checkpoint(cfg, params, out)
        model = transformers.LlamaForCausalLM.from_pretrained(out).eval()
        ids = rng.integers(0, 128, (2, 10)).astype(np.int32)
        want = _torch_logits(model, ids)
        got = _our_logits(src, ids)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)

    def test_gpt2_export_roundtrip(self, tmp_models, rng):
        from deepspeed_tpu.checkpoint.hf import (load_hf_checkpoint,
                                                 save_hf_checkpoint)
        src = tmp_models.ensure("gpt2")
        cfg, params = load_hf_checkpoint(src, dtype=jnp.float32)
        out = os.path.join(tmp_models, "gpt2_exported")
        save_hf_checkpoint(cfg, params, out)
        # reload through OUR importer too (full cycle)
        cfg2, params2 = load_hf_checkpoint(out, dtype=jnp.float32)
        a = jax.tree_util.tree_leaves(params)
        b = jax.tree_util.tree_leaves(params2)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32), atol=1e-6)
        model = transformers.GPT2LMHeadModel.from_pretrained(out).eval()
        ids = rng.integers(0, 128, (2, 10)).astype(np.int32)
        want = _torch_logits(model, ids)
        got = _our_logits(src, ids)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


class TestMixtral:
    """Mixtral MoE: HF import + MoE serving through both engines
    (reference inference/v2/model_implementations/mixtral)."""

    def _tiny(self, tmp_models):
        path = os.path.join(tmp_models, "mixtral")
        if not os.path.exists(os.path.join(path, "config.json")):
            torch.manual_seed(5)
            model = transformers.MixtralForCausalLM(transformers.MixtralConfig(
                vocab_size=128, hidden_size=64, intermediate_size=96,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=64,
                num_local_experts=4, num_experts_per_tok=2,
                rms_norm_eps=1e-5, sliding_window=None,
                tie_word_embeddings=False)).eval()
            model.save_pretrained(path, safe_serialization=True)
        return path

    def test_logits_match_transformers(self, tmp_models, rng):
        path = self._tiny(tmp_models)
        model = transformers.MixtralForCausalLM.from_pretrained(path).eval()
        cfg, params = load_hf_checkpoint(path, dtype=jnp.float32)
        assert cfg.num_experts == 4 and cfg.moe_k == 2 and cfg.moe_dropless
        ids = rng.integers(0, 128, (2, 12)).astype(np.int32)
        want = _torch_logits(model, ids)
        eng = deepspeed_tpu.init_inference(
            cfg, config={"dtype": "fp32"}, params=params)
        got = np.asarray(eng.forward(ids))
        np.testing.assert_allclose(got, want, atol=3e-3, rtol=2e-3)

    def test_v2_moe_serving_matches_hf_greedy(self, tmp_models, rng):
        from deepspeed_tpu.inference.v2 import InferenceEngineV2

        path = self._tiny(tmp_models)
        model = transformers.MixtralForCausalLM.from_pretrained(path).eval()
        prompt = rng.integers(0, 128, (1, 9)).astype(np.int32)
        with torch.no_grad():
            want = model.generate(
                torch.tensor(prompt, dtype=torch.long), max_new_tokens=8,
                do_sample=False).numpy()[0, 9:]
        eng = InferenceEngineV2(
            path, {"dtype": "fp32",
                   "state_manager": {"max_tracked_sequences": 2,
                                     "kv_block_size": 8},
                   "generation": {"do_sample": False}})
        got = eng.generate([prompt[0]], max_new_tokens=8)[0]
        np.testing.assert_array_equal(got, want)

    def test_mixtral_export_roundtrip(self, tmp_models, rng):
        from deepspeed_tpu.checkpoint.hf import (load_hf_checkpoint,
                                                 save_hf_checkpoint)
        src = self._tiny(tmp_models)
        cfg, params = load_hf_checkpoint(src, dtype=jnp.float32)
        out = os.path.join(tmp_models, "mixtral_exported")
        save_hf_checkpoint(cfg, params, out)
        model = transformers.MixtralForCausalLM.from_pretrained(out).eval()
        ids = rng.integers(0, 128, (2, 10)).astype(np.int32)
        want = _torch_logits(model, ids)
        got = _our_logits(src, ids)
        np.testing.assert_allclose(got, want, atol=3e-3, rtol=2e-3)


class TestDistilBertClassifier:
    def test_distilbert_classification_logits_match(self, tmp_models, rng):
        cfg = transformers.DistilBertConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, hidden_dim=128,
            max_position_embeddings=64, num_labels=3, seq_classif_dropout=0.0)
        torch.manual_seed(24)
        model = transformers.DistilBertForSequenceClassification(cfg).eval()
        path = _save(tmp_models, model, "distilbert_cls")
        ids = rng.integers(0, 128, (2, 12)).astype(np.int32)
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        got = np.asarray(eng.forward(ids))
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)

    def test_token_types_rejected_for_distilbert(self, tmp_models, rng):
        cfg = transformers.DistilBertConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, hidden_dim=128,
            max_position_embeddings=64)
        torch.manual_seed(22)
        model = transformers.DistilBertForMaskedLM(cfg).eval()
        path = _save(tmp_models, model, "distilbert")
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        with pytest.raises(ValueError, match="token-type"):
            eng.forward(np.zeros((1, 8), np.int32),
                        token_type_ids=np.zeros((1, 8), np.int32))


class TestRoberta:
    """RoBERTa/XLM-R (offset-2 learned positions, lm_head naming, dense->
    tanh->out_proj classification head)."""

    def test_roberta_mlm_logits_match(self, tmp_models, rng):
        cfg = transformers.RobertaConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=66, type_vocab_size=1)
        torch.manual_seed(25)
        model = transformers.RobertaForMaskedLM(cfg).eval()
        path = _save(tmp_models, model, "roberta")
        ids = rng.integers(0, 128, (2, 12)).astype(np.int32)
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        np.testing.assert_allclose(np.asarray(eng.forward(ids)), want,
                                   atol=2e-3, rtol=1e-3)

    def test_roberta_pad_positions_match_hf(self, tmp_models, rng):
        """Inputs CONTAINING the pad id (1): HF's position counter skips
        them — ours must too (create_position_ids_from_input_ids parity)."""
        cfg = transformers.RobertaConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=66, type_vocab_size=1)
        torch.manual_seed(25)
        model = transformers.RobertaForMaskedLM(cfg).eval()
        path = _save(tmp_models, model, "roberta")
        ids = rng.integers(0, 128, (2, 12)).astype(np.int32)
        ids[0, 3] = 1
        ids[1, 0] = 1          # pad id mid-sequence and at the front
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        np.testing.assert_allclose(np.asarray(eng.forward(ids)), want,
                                   atol=2e-3, rtol=1e-3)

    def test_roberta_classification_logits_match(self, tmp_models, rng):
        cfg = transformers.RobertaConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=66, type_vocab_size=1, num_labels=4,
            classifier_dropout=0.0, hidden_dropout_prob=0.0)
        torch.manual_seed(26)
        model = transformers.RobertaForSequenceClassification(cfg).eval()
        path = _save(tmp_models, model, "roberta_cls")
        ids = rng.integers(0, 128, (2, 12)).astype(np.int32)
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        got = np.asarray(eng.forward(ids))
        assert got.shape == (2, 4)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


class TestSlidingWindow:
    """Windowed attention (mistral sliding_window; gpt-neo local layers) —
    previously rejected, now exact."""

    def test_mistral_sliding_window_logits_match(self, tmp_models, rng):
        cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=1e4,
            sliding_window=5, tie_word_embeddings=False,
            attn_implementation="eager")
        torch.manual_seed(27)
        model = transformers.MistralForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "mistral_swa")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        assert config_from_hf(path).sliding_window == 5
        _check(path, model, rng, 128)

    def test_qwen2_max_window_layers_logits_match(self, tmp_models, rng):
        """qwen2 gates SWA per layer: layers < max_window_layers keep full
        attention (modeling_qwen2 layer_idx check)."""
        cfg = transformers.Qwen2Config(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=1e4,
            sliding_window=5, use_sliding_window=True, max_window_layers=1,
            tie_word_embeddings=False, attn_implementation="eager")
        torch.manual_seed(29)
        model = transformers.Qwen2ForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "qwen2_swa")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        c = config_from_hf(path)
        assert c.sliding_window == 5 and c.local_attn_layers == (1,)
        _check(path, model, rng, 128)

    def test_gptneo_logits_match(self, tmp_models, rng):
        cfg = transformers.GPTNeoConfig(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            attention_types=[[["global", "local"], 1]], window_size=4,
            max_position_embeddings=64, tie_word_embeddings=True)
        torch.manual_seed(28)
        model = transformers.GPTNeoForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "gptneo")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        c = config_from_hf(path)
        assert c.attn_scale == 1.0 and c.local_attn_layers == (1,)
        assert c.sliding_window == 4
        _check(path, model, rng, 128)

    def test_windowed_v2_serving(self, tmp_models, rng):
        """Sliding window through ragged prefill + paged decode fallback."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=1e4,
            sliding_window=5, tie_word_embeddings=False,
            attn_implementation="eager")
        torch.manual_seed(27)
        model = transformers.MistralForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "mistral_swa")
        prompt = rng.integers(0, 128, (1, 9)).astype(np.int32)
        with torch.no_grad():
            want = model.generate(
                torch.tensor(prompt, dtype=torch.long), max_new_tokens=6,
                do_sample=False).numpy()[0, 9:]
        eng = InferenceEngineV2(
            path, {"dtype": "fp32",
                   "state_manager": {"max_tracked_sequences": 2,
                                     "kv_block_size": 8},
                   "generation": {"do_sample": False}})
        got = eng.generate([prompt[0]], max_new_tokens=6)[0]
        np.testing.assert_array_equal(got, want)


class TestEncoderTP:
    def test_bert_tp2_matches_tp1(self, tmp_models, rng):
        """tp=2 encoder serving == tp=1 (heads/mlp split over the tp axis
        like the decoder engine's AutoTP analog)."""
        cfg = transformers.BertConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=64)
        torch.manual_seed(30)
        model = transformers.BertForMaskedLM(cfg).eval()
        path = _save(tmp_models, model, "bert_tp")
        ids = rng.integers(0, 128, (2, 10)).astype(np.int32)
        eng1 = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        got1 = np.asarray(eng1.forward(ids))
        # int shorthand, like the decoder engine accepts
        eng2 = deepspeed_tpu.init_inference(
            path, config={"dtype": "fp32", "tensor_parallel": 2})
        assert eng2.mesh.shape["tp"] == 2
        got2 = np.asarray(eng2.forward(ids))
        np.testing.assert_allclose(got2, got1, atol=2e-4, rtol=2e-4)
        with torch.no_grad():
            want = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        np.testing.assert_allclose(got2, want, atol=2e-3, rtol=1e-3)


class TestClipText:
    """CLIP text tower (reference module_inject/containers/clip.py):
    last-hidden-state and text_embeds parity vs transformers."""

    def _cfg(self, eos=2):
        return transformers.CLIPTextConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=32, eos_token_id=eos, bos_token_id=1)

    def test_clip_text_with_projection(self, tmp_models, rng):
        """eos_token_id=2 → HF's LEGACY argmax-of-ids pooling path."""
        torch.manual_seed(31)
        model = transformers.CLIPTextModelWithProjection(self._cfg()).eval()
        path = _save(tmp_models, model, "clip_text_proj")
        ids = rng.integers(3, 128, (2, 10)).astype(np.int32)
        ids[:, -1] = 2                      # eos terminates each prompt
        with torch.no_grad():
            out = model(torch.tensor(ids, dtype=torch.long))
            want_h = out.last_hidden_state.numpy()
            want_e = out.text_embeds.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        hidden, embeds = eng.forward(ids)
        np.testing.assert_allclose(np.asarray(hidden), want_h, atol=2e-3,
                                   rtol=1e-3)
        np.testing.assert_allclose(np.asarray(embeds), want_e, atol=2e-3,
                                   rtol=1e-3)

    def test_clip_text_plain_pooled(self, tmp_models, rng):
        """non-legacy eos (≠2) → pool at the FIRST eos position."""
        torch.manual_seed(32)
        model = transformers.CLIPTextModel(self._cfg(eos=100)).eval()
        path = _save(tmp_models, model, "clip_text")
        ids = rng.integers(3, 100, (2, 10)).astype(np.int32)
        ids[:, 6] = 100                     # eos mid-sequence: pool there
        with torch.no_grad():
            out = model(torch.tensor(ids, dtype=torch.long))
            want_h = out.last_hidden_state.numpy()
            want_p = out.pooler_output.numpy()
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        hidden, pooled = eng.forward(ids)
        np.testing.assert_allclose(np.asarray(hidden), want_h, atol=2e-3,
                                   rtol=1e-3)
        np.testing.assert_allclose(np.asarray(pooled), want_p, atol=2e-3,
                                   rtol=1e-3)


class TestStableLM:
    def test_stablelm_logits_match(self, tmp_models, rng):
        """StableLM-2 lineage: llama weight layout + LayerNorm(+bias) +
        partial rotary + SwiGLU."""
        cfg = transformers.StableLmConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, partial_rotary_factor=0.25,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(33)
        model = transformers.StableLmForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "stablelm")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        c = config_from_hf(path)
        assert not c.use_rmsnorm and c.gated_mlp and c.rope_pct == 0.25
        _check(path, model, rng, 128)

    def test_stablelm_qkv_bias_variant(self, tmp_models, rng):
        cfg = transformers.StableLmConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, partial_rotary_factor=0.5,
            use_qkv_bias=True, max_position_embeddings=64,
            tie_word_embeddings=False)
        torch.manual_seed(34)
        model = transformers.StableLmForCausalLM(cfg).eval()
        with torch.no_grad():
            for layer in model.model.layers:
                for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                             layer.self_attn.v_proj):
                    proj.bias.normal_(0, 0.02)
        path = _save(tmp_models, model, "stablelm_bias")
        _check(path, model, rng, 128)


class TestGPTBigCode:
    def test_starcoder_mqa_logits_match(self, tmp_models, rng):
        """starcoder lineage: MQA (one kv head) fused q|k|v rows."""
        cfg = transformers.GPTBigCodeConfig(
            vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
            multi_query=True)
        torch.manual_seed(35)
        model = transformers.GPTBigCodeForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "bigcode_mqa")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        assert config_from_hf(path).kv_heads == 1
        _check(path, model, rng, 128)

    def test_bigcode_mha_variant(self, tmp_models, rng):
        cfg = transformers.GPTBigCodeConfig(
            vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
            multi_query=False)
        torch.manual_seed(36)
        model = transformers.GPTBigCodeForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "bigcode_mha")
        _check(path, model, rng, 128)


class TestGemma:
    def test_gemma_logits_match(self, tmp_models, rng):
        """Gemma: (1+w) rmsnorm absorbed at load, sqrt(H)-scaled embeddings
        with UNSCALED tied unembed, GeGLU, explicit head_dim != H/heads."""
        cfg = transformers.GemmaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32,
            max_position_embeddings=64, rms_norm_eps=1e-6)
        torch.manual_seed(37)
        model = transformers.GemmaForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "gemma")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        c = config_from_hf(path)
        assert c.gate_act == "gelu" and c.head_dim == 32
        assert c.embed_scale == pytest.approx(8.0)
        _check(path, model, rng, 128)

    def test_gemma_generate_token_exact(self, tmp_models, rng):
        cfg = transformers.GemmaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32,
            max_position_embeddings=64)
        torch.manual_seed(37)
        model = transformers.GemmaForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "gemma")
        prompt = rng.integers(3, 128, (1, 9)).astype(np.int32)
        with torch.no_grad():
            want = model.generate(
                torch.tensor(prompt, dtype=torch.long), max_new_tokens=6,
                do_sample=False).numpy()[0, 9:]
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        got = np.asarray(eng.generate(prompt, max_new_tokens=6,
                                      do_sample=False))[0]
        np.testing.assert_array_equal(got, want)


class TestPhi3:
    def test_phi3_logits_match(self, tmp_models, rng):
        """Phi-3: llama semantics with fused qkv_proj / gate_up_proj."""
        cfg = transformers.Phi3Config(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            pad_token_id=0, eos_token_id=1, bos_token_id=2,
            tie_word_embeddings=False)
        torch.manual_seed(38)
        model = transformers.Phi3ForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "phi3")
        _check(path, model, rng, 128)

    def test_phi3_generate_token_exact(self, tmp_models, rng):
        cfg = transformers.Phi3Config(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            pad_token_id=0, eos_token_id=1, bos_token_id=2,
            tie_word_embeddings=False)
        torch.manual_seed(38)
        model = transformers.Phi3ForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "phi3")
        prompt = rng.integers(3, 128, (1, 9)).astype(np.int32)
        with torch.no_grad():
            want = model.generate(
                torch.tensor(prompt, dtype=torch.long), max_new_tokens=6,
                do_sample=False).numpy()[0, 9:]
        eng = deepspeed_tpu.init_inference(path, config={"dtype": "fp32"})
        got = np.asarray(eng.generate(prompt, max_new_tokens=6,
                                      do_sample=False))[0]
        np.testing.assert_array_equal(got, want)

    def test_phi3_longrope_short_and_long_regimes(self, tmp_models, rng):
        """Phi-3 longrope (round 3: previously rejected): per-channel
        short/long factor tables selected by sequence length + the paper's
        attention factor — parity vs HF in BOTH regimes."""
        hd_half = (64 // 4) // 2
        r = np.random.default_rng(5)
        cfg = transformers.Phi3Config(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2,
            max_position_embeddings=64,
            original_max_position_embeddings=16,
            pad_token_id=0, eos_token_id=1, bos_token_id=2,
            tie_word_embeddings=False,
            rope_scaling={
                "type": "longrope",
                "short_factor": (1.0 + r.random(hd_half) * 0.2).tolist(),
                "long_factor": (2.0 + r.random(hd_half)).tolist()})
        torch.manual_seed(40)
        model = transformers.Phi3ForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "phi3_longrope")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        c = config_from_hf(path)
        assert c.rope_scaling is not None and c.rope_scaling[0] == "longrope"
        # short regime: seq 12 <= original 16
        ids = rng.integers(3, 128, (2, 12)).astype(np.int32)
        np.testing.assert_allclose(_our_logits(path, ids),
                                   _torch_logits(model, ids),
                                   atol=2e-3, rtol=1e-3)
        # long regime: seq 24 > original 16 → the LONG factor table
        ids = rng.integers(3, 128, (2, 24)).astype(np.int32)
        np.testing.assert_allclose(_our_logits(path, ids),
                                   _torch_logits(model, ids),
                                   atol=2e-3, rtol=1e-3)

    def test_phi3_longrope_cobatched_regimes_independent(self, tmp_models,
                                                         rng):
        """A LONG sequence co-scheduled with a SHORT one in the ragged engine
        must not flip the short one onto the long factor table: each slot
        selects by ITS OWN kv length (per-token seq_lens in rope)."""
        from conftest import v2_engine
        d = os.path.join(str(tmp_models), "phi3_longrope")
        assert os.path.exists(os.path.join(d, "config.json")), \
            "run test_phi3_longrope_short_and_long_regimes first (fixture)"
        sm = {"dtype": "fp32",
              "state_manager": {"max_tracked_sequences": 3,
                                "kv_block_size": 8},
              "generation": {"do_sample": False}}
        short_p = rng.integers(3, 128, (6,)).astype(np.int32)   # < orig 16
        long_p = rng.integers(3, 128, (22,)).astype(np.int32)   # > orig 16
        eng_solo = v2_engine(d, sm)
        want_short = eng_solo.generate([short_p], max_new_tokens=4)[0]
        del eng_solo
        eng_both = v2_engine(d, sm)
        got = eng_both.generate([short_p, long_p], max_new_tokens=4)
        np.testing.assert_array_equal(got[0], want_short)

    def test_phi3_partial_rotary_variant(self, tmp_models, rng):
        """phi-4-mini-style partial_rotary_factor under the Phi3 arch."""
        cfg = transformers.Phi3Config(
            vocab_size=128, hidden_size=64, intermediate_size=172,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            pad_token_id=0, eos_token_id=1, bos_token_id=2,
            partial_rotary_factor=0.75, tie_word_embeddings=False)
        torch.manual_seed(39)
        model = transformers.Phi3ForCausalLM(cfg).eval()
        path = _save(tmp_models, model, "phi3_partial")
        from deepspeed_tpu.checkpoint.hf import config_from_hf
        assert config_from_hf(path).rope_pct == 0.75
        _check(path, model, rng, 128)
