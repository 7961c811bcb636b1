"""LFM2-MoE (``GPTConfig.layer_types`` with ``conv`` layers: gated short
convolutions beside RoPE GQA attention with q/k norms, two dense layers and
then sigmoid-routed experts with a selection bias) against the plain
reference (``benchmark/reference/_lfm2_moe.py``), at tiny sizes in float32:
each mixer alone against its equation, the model, the planted faults, the
gradient, the parameter count at tiny, published and cut sizes, and the
checkpoint name map (the serving engine's paths are in
``test_lfm2_moe_engine.py``: a file runs on one worker).

Tolerances: everything here is float32 on the CPU, so a difference is
summation order: 2e-4 absolute on values of order 1, and a planted fault
must read at least five times that."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lfm2_tiny import (HERE, SIZES, TOL, cfg, faults, params, ref,  # noqa: F401
                       seqs, want)

from deepspeed_tpu import ops
from deepspeed_tpu.models.gpt import (GPT, Attention, GPTConfig, GPTLogits,
                                      ShortConvMixer, count_params)


# ---------------------------------------------------------------- the mixers

@pytest.mark.parametrize("activation", [None, "silu"])
@pytest.mark.parametrize("cuts", [(), (1,), (2, 3), (7, 8, 20)])
def test_conv_fed_in_pieces_carries_its_tail(activation, cuts):
    """``causal_conv1d`` with and without its SiLU, one call against the
    same rows fed piece by piece from the tail the last piece left."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 23, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)
    zero = jnp.zeros((2, 2, 8), jnp.float32)
    whole, tail = ops.causal_conv1d(x, w, None, zero, activation=activation)
    padded = jnp.concatenate([zero, x], 1)
    plain = sum(padded[:, j:j + 23] * w[:, j] for j in range(3))
    if activation:
        plain = jax.nn.silu(plain)
    np.testing.assert_allclose(whole, plain, atol=1e-6)
    np.testing.assert_array_equal(tail, x[:, -2:])
    got, t = [], zero
    for a, b in zip((0,) + cuts, cuts + (23,)):
        out, t = ops.causal_conv1d(x[:, a:b], w, None, t,
                                   activation=activation)
        got.append(out)
    np.testing.assert_allclose(jnp.concatenate(got, 1), whole, atol=1e-6)
    np.testing.assert_array_equal(t, tail)


def test_conv_refuses_an_activation_it_does_not_know():
    z = jnp.zeros((1, 2, 4))
    with pytest.raises(ValueError, match="silu or nothing"):
        ops.causal_conv1d(z, jnp.zeros((4, 3)), None, z, activation="gelu")


def test_the_conv_mixer_is_its_equation(cfg, params):
    mp = params["backbone"]["block_0"]["ShortConvMixer_0"]
    u = jax.random.normal(jax.random.PRNGKey(9), (21, cfg.hidden_size))
    got = ShortConvMixer(cfg).apply({"params": mp}, u[None])[0]
    with jax.default_matmul_precision("highest"):
        bcx = u @ mp["w_in"]
        H = cfg.hidden_size
        b, c, x = bcx[:, :H], bcx[:, H:2 * H], bcx[:, 2 * H:]
        g = np.asarray(b * x)
        v = np.zeros_like(g)
        for t in range(21):                  # left to right, tap by tap
            for j in range(3):
                if t - 2 + j >= 0:
                    v[t] += np.asarray(mp["conv_w"])[:, j] * g[t - 2 + j]
        exp = (c * v) @ mp["w_out"]
    np.testing.assert_allclose(got, exp, atol=TOL)


def test_the_attention_mixer_is_its_equation(cfg, params):
    """q/k norms over each head BEFORE the rotation, halves rotated, base
    1e6, groups of query heads over a key/value head, 1/sqrt(d)."""
    ap = params["backbone"]["block_2"]["Attention_0"]
    u = jax.random.normal(jax.random.PRNGKey(9), (21, cfg.hidden_size))
    got = Attention(cfg).apply({"params": ap}, u[None], jnp.arange(21)[None],
                               True)[0]
    with jax.default_matmul_precision("highest"):
        exp = ref._attention(
            dict(wq=ap["wq"], wk=ap["wk"], wv=ap["wv"], wo=ap["wo"],
                 qn=ap["q_norm"], kn=ap["k_norm"]), u, cfg.norm_eps,
            cfg.rope_theta)
    np.testing.assert_allclose(got, exp, atol=TOL)


# ------------------------------------------------------------------ the model

def test_the_model_is_the_reference(cfg, params, seqs, want):
    got = jax.jit(GPTLogits(cfg).apply)(         # one program, not one an op
        {"params": params}, seqs[0][None])[0]
    np.testing.assert_allclose(got, want[0], atol=TOL)


def test_the_bias_selects_and_does_not_weigh(cfg, params, seqs):
    """The tiny weights' bias moves the selection on some row (else the
    comparison above could not tell the two readings of it apart)."""
    p = ref.tree(params)["layers"][2]
    m = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))
    chosen, _, _ = ref.route(m, p["router"], p["bias"], 2, True, 1.0)
    plain, _, _ = ref.route(m, p["router"], 0 * p["bias"], 2, True, 1.0)
    assert (np.sort(chosen, -1) != np.sort(plain, -1)).any()


def test_layer_kinds(cfg):
    assert cfg.conv_layers == (0, 1, 3, 4, 5) and cfg.scan_layers == ()
    assert cfg.state_layers == cfg.conv_layers
    assert cfg.attention_layers == (2,) and cfg.for_layer(2) is cfg
    assert [cfg.is_moe_layer(i) for i in range(6)] == [False] * 2 + [True] * 4
    with pytest.raises(ValueError, match="short-convolution layer"):
        cfg.for_layer(0)
    with pytest.raises(ValueError,
                       match="attention\\|mamba\\|lightning\\|conv"):
        dataclasses.replace(cfg, layer_types=("conv",) * 5 + ("lstm",)
                            ).layer_kind(5)
    from deepspeed_tpu.inference.v2.model import state_mixer
    with pytest.raises(NotImplementedError, match="one kind"):
        state_mixer(dataclasses.replace(
            cfg, layer_types=("conv", "mamba") + ("attention",) * 4))


@pytest.mark.parametrize("fault", faults.FAULTS + (faults.CONTROL,))
def test_a_planted_fault_reads_as_a_fault(params, seqs, want, fault):
    """Each planted fault moves the reference's own logits by far more than
    the tolerance the program is held to: the comparison would catch it."""
    with faults.planted(fault, params, SIZES) as (bad_params, bad_sizes):
        bad = np.asarray(ref.logits(bad_params, seqs[0], bad_sizes))
    assert np.abs(bad - want[0]).max() > 5 * TOL, fault
    again = np.asarray(ref.logits(params, seqs[0], SIZES))
    np.testing.assert_array_equal(again, want[0])    # and is taken out again


def test_the_loss_differentiates_through_every_layer(cfg, params):
    loss = lambda p: GPT(cfg).apply(  # noqa: E731
        {"params": p}, {"input_ids": jnp.arange(8)[None] % 128},
        deterministic=True)
    g = jax.jit(jax.grad(loss))(params)      # one program, not one an op
    assert all(np.isfinite(a).all() for a in jax.tree_util.tree_leaves(g))
    for i in cfg.conv_layers:
        mixer = g["backbone"][f"block_{i}"]["ShortConvMixer_0"]
        assert all(float(jnp.abs(a).max()) > 0 for a in mixer.values())


def test_gradient_through_the_conv_mixer(cfg, params):
    """``jax.grad`` through the mixer equals the gradient of the reference's
    sum over shifted rows, for the input and every weight."""
    mp = params["backbone"]["block_0"]["ShortConvMixer_0"]
    u = jax.random.normal(jax.random.PRNGKey(9), (21, cfg.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(10), (21, cfg.hidden_size))

    def ours(mp, u):
        return jnp.sum(ShortConvMixer(cfg).apply({"params": mp}, u[None])[0]
                       * probe)

    def theirs(mp, u):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref._short_conv(mp, u) * probe)
    # a program each, not one a primitive of an eager backward
    got = jax.jit(jax.grad(ours, argnums=(0, 1)))(mp, u)
    exp = jax.jit(jax.grad(theirs, argnums=(0, 1)))(mp, u)
    for (path, g), e in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(exp)):
        scale = float(jnp.abs(e).max()) + 1e-6
        np.testing.assert_allclose(g / scale, e / scale, atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------------ the parameters

def _published():
    with open(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                           "lfm2-24b-a2b-10l.json")) as f:
        return json.load(f)


def test_count_params_is_the_tree(cfg, params):
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == count_params(cfg)


@pytest.mark.parametrize("layers,billions", [(40, 23.84), (10, 5.267)])
def test_count_params_at_the_published_and_the_cut_size(layers, billions):
    """Shapes only, no arrays: the whole model's published "24B" and the
    benchmark configuration's ten layers, from the configuration's file."""
    sizes = {**_published(), "num_hidden_layers": layers,
             "layers_kept": list(range(layers))}
    c = GPTConfig(**ref.program_config(sizes), max_seq_len=128)
    assert round(count_params(c) / 1e9, 3 if layers == 10 else 2) == billions
    shapes = jax.eval_shape(
        lambda: GPTLogits(c).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == count_params(c)


def test_the_configuration_keeps_every_published_width():
    sizes = _published()
    period = ["conv", "conv", "full_attention", "conv"]
    assert sizes["layer_types"] == period * 10     # the published list, whole
    assert sizes["layers_kept"] == list(range(10))
    assert ref.layer_kinds(sizes) == (period * 3)[:10]
    for key, value in dict(
            hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
            intermediate_size=11776, num_experts=64,
            moe_intermediate_size=1536, num_experts_per_tok=4,
            conv_L_cache=3, vocab_size=65536, num_dense_layers=2).items():
        assert sizes[key] == value, key
    assert set(sizes["reduced"]) == {"num_hidden_layers",
                                     "max_position_embeddings"}


# ------------------------------------------------------------ the checkpoint

def test_the_name_map_round_trips(cfg, params):
    """A seeded tiny state dict under the published tensor names and shapes
    loads into the tree it was written from, name for name."""
    from deepspeed_tpu.checkpoint import hf
    sd = hf.lfm2_moe_state_dict(cfg, params)
    names = set()
    for pat in hf.LFM2_MOE_WEIGHT_NAMES:
        names |= {pat.format(i=i, e=e) for i in range(cfg.num_layers)
                  for e in range(cfg.num_experts)}
    assert set(sd) <= names
    assert sd["model.layers.0.conv.conv.weight"].shape == (
        cfg.hidden_size, 1, 3)
    assert sd["model.layers.0.conv.in_proj.weight"].shape == (
        3 * cfg.hidden_size, cfg.hidden_size)
    assert sd["model.layers.2.self_attn.q_proj.weight"].shape == (
        cfg.num_heads * cfg.head_dim, cfg.hidden_size)
    assert sd["model.layers.3.feed_forward.experts.7.w1.weight"].shape == (
        cfg.expert_dim, cfg.hidden_size)
    assert "model.layers.2.conv.in_proj.weight" not in sd
    assert "model.layers.1.feed_forward.gate.weight" not in sd
    back = hf._lfm2_moe_tree(sd, cfg)
    flat = dict(jax.tree_util.tree_leaves_with_path(back))
    for path, a in jax.tree_util.tree_leaves_with_path(params):
        np.testing.assert_array_equal(flat[path], a,
                                      err_msg=jax.tree_util.keystr(path))
    got = hf.lfm2_moe_config({**SIZES, "max_position_embeddings": 256})
    assert dataclasses.replace(got, dtype=cfg.dtype) == cfg


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("tie_word_embeddings", False), ("num_experts", 0),
    ("layer_types", ["conv", "mamba"]),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"})])
def test_the_config_reader_refuses_by_key(key, value):
    from deepspeed_tpu.checkpoint import hf
    with pytest.raises(NotImplementedError, match=f"lfm2_moe: {key}="):
        hf.lfm2_moe_config({**SIZES, key: value})
