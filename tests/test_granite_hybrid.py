"""Scan layers (``GPTConfig.layer_types``: Mamba-2 mixers beside attention
layers) against the plain reference's left-to-right recurrence
(``benchmark/reference/_granite_hybrid.py``), at tiny sizes in float32: the
three operations, the model, the planted faults, the gradient and the
checkpoint name map (the serving engine's paths are in
``test_granite_hybrid_engine.py``: a file runs on one worker).

Tolerances: everything here is float32 on the CPU, so a difference is
summation order (the chunked form against the recurrence): 2e-4 absolute on
values of order 1, and a planted fault must read at least five times that."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from granite_tiny import (SIZES, TOL, cfg, faults, params, ref,  # noqa: F401
                          seqs, want)

from deepspeed_tpu import ops
from deepspeed_tpu.ops.ssm_scan import (pack_state, state_update_supported,
                                        unpack_state)
from deepspeed_tpu.models.gpt import (GPT, GPTConfig, GPTLogits, Mamba2Mixer,
                                      count_params)


# ------------------------------------------------------------ the operations

@pytest.fixture(scope="module")
def scan_rows():
    rng = np.random.default_rng(5)
    T, h, p, g, n = 37, 4, 8, 2, 16
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(x=f(T, h, p), dt=jax.nn.softplus(f(T, h) - 1.0),
                A=-jnp.exp(f(h)), B=f(T, g, n), C=f(T, g, n), D=f(h))


@pytest.mark.parametrize("chunk", [8, 16, 37, 64, 5])
def test_chunked_scan_is_the_recurrence(scan_rows, chunk):
    """Chunk sizes that divide the length (37), that do not (a padded last
    chunk) and that exceed it."""
    r = scan_rows
    want = ref._recurrence(r["x"], r["dt"], r["A"], r["B"], r["C"], r["D"])
    y, _ = ops.ssm_chunk_scan(
        r["x"][None], r["dt"][None], r["A"], r["B"][None], r["C"][None],
        r["D"], jnp.zeros((1, 4, 8, 16)), chunk=chunk)
    np.testing.assert_allclose(y[0], want, atol=TOL)


@pytest.mark.parametrize("cuts", [(20,), (8, 9, 30), (1, 36)])
def test_scan_fed_in_pieces_carries_its_state(scan_rows, cuts):
    """A sequence fed piece by piece, each from the state the last left
    (pieces of one row through the recurrence op), equals one pass."""
    r = scan_rows
    want = ref._recurrence(r["x"], r["dt"], r["A"], r["B"], r["C"], r["D"])
    state = jnp.zeros((1, 4, 8, 16))
    out = []
    for a, b in zip((0,) + cuts, cuts + (37,)):
        part = [r[k][None, a:b] for k in ("x", "dt", "B", "C")]
        if b - a == 1:         # (the op works in place in a packed pool)
            y, pool = ops.ssm_state_update(
                part[0][:, 0], part[1][:, 0], r["A"], part[2][:, 0],
                part[3][:, 0], r["D"], pack_state(state)[None])
            y, state = y[:, None], unpack_state(pool[0], 8)
        else:
            y, state = ops.ssm_chunk_scan(part[0], part[1], r["A"], part[2],
                                          part[3], r["D"], state, chunk=8)
        out.append(y[0])
    np.testing.assert_allclose(jnp.concatenate(out), want, atol=TOL)


def test_ragged_segments_scan_from_their_own_states(scan_rows):
    """Token-major rows: two segments of unequal length among padding, each
    from its own initial state; rows of no segment read zero."""
    r = scan_rows
    N, rng = 64, np.random.default_rng(6)
    s0 = jnp.asarray(rng.normal(size=(2, 4, 8, 16)), jnp.float32)
    put = lambda a: jnp.zeros((N,) + a.shape[1:]).at[3:40].set(a).at[  # noqa: E731
        45:57].set(a[:12])
    seg = (jnp.asarray([3, 45]), jnp.asarray([37, 12]))
    y, s1 = ops.ssm_chunk_scan(put(r["x"]), put(r["dt"]), r["A"],
                               put(r["B"]), put(r["C"]), r["D"], s0, seg,
                               chunk=16, max_len=40)
    for g, (a, n) in enumerate(((3, 37), (45, 12))):
        part = [r[k][None, :n] for k in ("x", "dt", "B", "C")]
        yd, sd = ops.ssm_chunk_scan(part[0], part[1], r["A"], part[2],
                                    part[3], r["D"], s0[g:g + 1], chunk=16)
        np.testing.assert_allclose(y[a:a + n], yd[0], atol=TOL)
        np.testing.assert_allclose(s1[g], sd[0], atol=TOL)
    assert not np.asarray(y[:3]).any() and not np.asarray(y[57:]).any()


@pytest.mark.parametrize("heads,head_dim,groups", [(4, 64, 1), (8, 16, 1),
                                                   (4, 16, 2)])
def test_state_update_in_the_packed_pool(heads, head_dim, groups):
    """The one-row recurrence in place in one layer of a packed pool (heads
    side by side on the lanes where their width divides 128): the XLA form
    against the arithmetic written out, the kernel (interpreted) against
    the XLA form; a fresh slot starts from zero, an inactive one keeps its
    state, the other layer is not touched."""
    rng = np.random.default_rng(8)
    S, n = 3, 16
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    x, dt = f(S, heads, head_dim), jax.nn.softplus(f(S, heads))
    A, D = -jnp.exp(f(heads)), f(heads)
    B, C = f(S, groups, n), f(S, groups, n)
    state = f(2, S, heads, head_dim, n)
    pool = pack_state(state)
    np.testing.assert_array_equal(unpack_state(pool, head_dim), state)
    active = jnp.asarray([True, False, True])
    fresh = jnp.asarray([False, False, True])
    old = jnp.where(fresh[:, None, None, None], 0.0, state[1])
    Bh = jnp.repeat(B, heads // groups, 1)
    Ch = jnp.repeat(C, heads // groups, 1)
    new = (old * jnp.exp(dt * A)[..., None, None]
           + (x * dt[..., None])[..., None] * Bh[:, :, None, :])
    want_y = jnp.einsum("shpn,shn->shp", new, Ch) + D[:, None] * x
    want = jnp.where(active[:, None, None, None], new, old)
    forms = ["xla"] + (["pallas"] if state_update_supported(
        x, dt, A, B, C, D, pool) else [])
    assert (forms == ["xla", "pallas"]) == (groups == 1
                                            and pool.shape[-1] == 128)
    for impl in forms:
        y, out = ops.ssm_state_update(x, dt, A, B, C, D, pool, 1, active,
                                      fresh, impl=impl)
        np.testing.assert_allclose(y, want_y, atol=1e-5, err_msg=impl)
        np.testing.assert_allclose(unpack_state(out[1], head_dim), want,
                                   atol=1e-6, err_msg=impl)
        np.testing.assert_array_equal(out[0], pool[0])


@pytest.mark.parametrize("cut", [1, 2, 20, 36])
def test_conv_carries_its_tail(cut):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(1, 37, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(12, 4)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(12,)), jnp.float32)
    want = ref._conv(x[0], w, b)
    zero = jnp.zeros((1, 3, 12))
    whole, tail = ops.causal_conv1d(x, w, b, zero)
    np.testing.assert_allclose(whole[0], want, atol=1e-6)
    first, t1 = ops.causal_conv1d(x[:, :cut], w, b, zero)
    rest, t2 = ops.causal_conv1d(x[:, cut:], w, b, t1)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1)[0], want,
                               atol=1e-6)
    np.testing.assert_array_equal(t2, tail)
    # a padded layout: rows behind the count leave the tail alone
    _, t3 = ops.causal_conv1d(x, w, b, zero, jnp.asarray([cut]))
    np.testing.assert_array_equal(t3, t1)


# ------------------------------------------------------------------ the model

def test_the_model_is_the_reference(cfg, params, seqs, want):
    forward = jax.jit(GPTLogits(cfg).apply)     # a program a length
    for s, w in zip(seqs, want):
        got = forward({"params": params}, s[None])[0]
        np.testing.assert_allclose(got, w, atol=TOL)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == count_params(cfg)


def test_only_attention_layers_have_an_attention_geometry(cfg):
    assert cfg.scan_layers == (0, 2, 3) and cfg.attention_layers == (1,)
    assert cfg.for_layer(1) is cfg
    with pytest.raises(ValueError, match="scan layer"):
        cfg.for_layer(0)
    with pytest.raises(ValueError, match="layer_types names"):
        dataclasses.replace(cfg, num_layers=5).is_scan_layer(0)
    plain = GPTConfig.tiny()
    assert not plain.is_scan_layer(0) and plain.attention_layers == (0, 1)


@pytest.mark.parametrize("fault", faults.FAULTS + (faults.CONTROL,))
def test_a_planted_fault_reads_as_a_fault(params, seqs, want, fault):
    """Each planted fault moves the reference's own logits by far more than
    the tolerance the program is held to: the comparison would catch it."""
    sizes = SIZES
    if fault == "softmax_scale_one_eighth":     # here 1/8 IS the scale
        sizes = {**SIZES, "attention_multiplier": 1 / 64}
        base = np.asarray(ref.logits(params, seqs[0], sizes))
    else:
        base = want[0]
    with faults.planted(fault, params, sizes) as (bad_params, bad_sizes):
        bad = np.asarray(ref.logits(bad_params, seqs[0], bad_sizes))
    # (a state rounded to bf16 is a precision, not a mistake: it moves the
    # logits, by less than any fault does: 4e-4 of 0.2 here)
    least = 1e-5 if fault == "state_rounded_to_bf16" else 5 * TOL
    assert np.abs(bad - base).max() > least, fault
    again = np.asarray(ref.logits(params, seqs[0], sizes))
    np.testing.assert_array_equal(again, base)       # and is taken out again


def test_gradient_through_the_mixer(cfg, params):
    """``jax.grad`` through the chunked scan equals the gradient of the
    reference's recurrence, for the input and every weight of a mixer."""
    mp = params["backbone"]["block_0"]["Mamba2Mixer_0"]
    u = jax.random.normal(jax.random.PRNGKey(9), (21, cfg.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(10), (21, cfg.hidden_size))

    def ours(mp, u):
        return jnp.sum(Mamba2Mixer(cfg).apply({"params": mp}, u[None])[0]
                       * probe)

    def theirs(mp, u):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref._mamba(
                {**mp, "conv_b": mp.get("conv_b")}, u, heads=cfg.ssm_heads,
                head_dim=cfg.ssm_head_dim, groups=cfg.ssm_groups,
                state=cfg.ssm_state, eps=cfg.norm_eps) * probe)
    # a program each, not one a primitive of an eager backward
    got = jax.jit(jax.grad(ours, argnums=(0, 1)))(mp, u)
    exp = jax.jit(jax.grad(theirs, argnums=(0, 1)))(mp, u)
    for (path, g), e in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(exp)):
        scale = float(jnp.abs(e).max()) + 1e-6
        np.testing.assert_allclose(g / scale, e / scale, atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_loss_differentiates_through_every_layer(cfg, params):
    loss = lambda p: GPT(cfg).apply(  # noqa: E731
        {"params": p}, {"input_ids": jnp.arange(8)[None] % 128},
        deterministic=True)
    g = jax.jit(jax.grad(loss))(params)      # one program, not one an op
    assert all(np.isfinite(a).all() for a in jax.tree_util.tree_leaves(g))
    for i in cfg.scan_layers:
        assert float(jnp.abs(g["backbone"][f"block_{i}"]["Mamba2Mixer_0"][
            "A_log"]).max()) > 0


# ------------------------------------------------------------ the checkpoint

def test_the_name_map_round_trips(cfg, params):
    """A seeded tiny state dict under the published tensor names and shapes
    loads into the tree it was written from, name for name."""
    from deepspeed_tpu.checkpoint import hf
    sd = hf.granite_hybrid_state_dict(cfg, params)
    names = set()
    for pat in hf.GRANITE_HYBRID_WEIGHT_NAMES:
        names |= {pat.format(i=i) for i in range(cfg.num_layers)}
    assert set(sd) <= names
    assert sd["model.layers.0.mamba.conv1d.weight"].shape == (
        cfg.ssm_conv_dim, 1, cfg.ssm_conv)
    assert sd["model.layers.0.shared_mlp.input_linear.weight"].shape == (
        2 * cfg.mlp_dim, cfg.hidden_size)
    assert sd["model.layers.1.self_attn.q_proj.weight"].shape == (
        cfg.num_heads * cfg.head_dim, cfg.hidden_size)
    assert "model.layers.1.mamba.in_proj.weight" not in sd
    back = hf._granite_hybrid_tree(sd, cfg)
    flat = dict(jax.tree_util.tree_leaves_with_path(back))
    for path, a in jax.tree_util.tree_leaves_with_path(params):
        np.testing.assert_array_equal(flat[path], a,
                                      err_msg=jax.tree_util.keystr(path))
    got = hf.granite_hybrid_config({**SIZES, "max_position_embeddings": 256})
    assert dataclasses.replace(got, dtype=cfg.dtype) == cfg
