"""The Pallas grouped GEMM (``ops/grouped_gemm.py``) in interpret mode on the
CPU against ``lax.ragged_dot`` on the live rows: groups without rows, one
group holding every row, groups that cross a row tile, a tail behind the
last group with NaN planted in it, a buffer no row of which is anybody's,
and what a step a GROUP reaches (its row tiles start at its own first row):
a group of more than two tiles, one of exactly a tile's rows from the middle
of a sublane group, a run of one-row groups that share one, groups without
rows between full ones, the buffer's last rows holding several groups' first
rows and a tail so that their tiles move up from the buffer's end; both
forms (plain, and the gated first half against its two-product form), both
dtypes, and every row tile the shape rule can choose.  Widths scaled down, N
kept at 11 x 128 (its only column tiles are 128 and all of it).  Then the
expert layer's function around it (``moe/layer.py:_expert_ffn_ragged``): no
tail row reaches its result."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import ops
from deepspeed_tpu.moe.layer import _expert_ffn_ragged
from deepspeed_tpu.ops.registry import dispatch_log, reset_dispatch_log

gg = sys.modules["deepspeed_tpu.ops.grouped_gemm"]

N = 11 * 128


def _sizes(kind, A, G, rng, tm):
    out = np.zeros(G, int)
    if kind == "long":              # more than two tiles, from mid-tile on
        out[0], out[2] = 5, 2 * tm + 9
        out[G - 1] = A - out.sum()
        return out
    if kind == "exact":             # a tile's rows exactly, twice, from row 7
        out[1], out[2], out[3] = 7, tm, tm
        out[5] = A - out.sum()
        return out
    if kind == "ones":              # nine one-row groups in one sublane group
        out[0], out[1:10], out[10] = 3, 1, tm + 2
        return out
    if kind == "gaps":              # full tiles, groups without rows between
        out[::3] = tm
        out[::3][A // tm:] = 0
        out[G - 1] += A - out.sum()
        return out
    if kind == "overrun":
        # the buffer's last tile of rows: the end of a group that began
        # before it, a one-row group, the last group and three rows that
        # are nobody's, so the last groups' tiles move up from the end
        out[G - 1] = max(tm // 4 - 4, 1)
        out[G - 2] = 1
        out[G - 4] = 3 * tm // 4 + 4
        out[0] = A - 3 - out.sum()
        return out
    if kind == "one_group":
        out = np.zeros(G, int)
        out[G // 2] = A
        return out
    if kind == "zeros":             # every other group empty, the rest full
        out = np.zeros(G, int)
        live = np.arange(1, G, 2)
        out[live] = rng.multinomial(A, np.ones(len(live)) / len(live))
        return out
    if kind == "crossing":          # uneven, every group has rows
        return rng.multinomial(A - G, np.ones(G) / G) + 1
    if kind == "empty":             # no row chose an expert held here
        return np.zeros(G, int)
    assert kind == "tail"           # a third of the buffer belongs to nobody
    return rng.multinomial(2 * A // 3 - 5, np.ones(G) / G)


CASES = [
    # id, A, G, K, sizes, gated, dtype, the row tile the rule must choose
    ("decode64-zeros-plain", 64, 32, 256, "zeros", False, "float32", 8),
    ("decode64-tail-gated", 64, 32, 256, "tail", True, "bfloat16", 16),
    ("decode64-empty-gated", 64, 32, 256, "empty", True, "bfloat16", 16),
    ("decode288-crossing-gated", 288, 64, 256, "crossing", True, "bfloat16", 16),
    ("decode288-tail-plain", 288, 64, 256, "tail", False, "bfloat16", 16),
    ("decode288-one_group-gated", 288, 64, 128, "one_group", True, "float32", 8),
    ("rows288-groups16-crossing-plain", 288, 16, 128, "crossing", False, "bfloat16", 32),
    ("rows288-groups16-tail-gated", 288, 16, 128, "tail", True, "float32", 32),
    ("mixed4096-groups64-zeros-gated", 4096, 64, 128, "zeros", True, "bfloat16", 64),
    ("mixed4096-groups32-tail-gated", 4096, 32, 128, "tail", True, "bfloat16", 128),
    ("mixed4096-groups32-crossing-plain", 4096, 32, 128, "crossing", False, "float32", 128),
    ("mixed4096-one_group-plain", 4096, 32, 128, "one_group", False, "bfloat16", 128),
    # what a step a group reaches (PR 51)
    ("rows288-groups16-long-gated", 288, 16, 128, "long", True, "bfloat16", 32),
    ("rows288-groups16-long-plain", 288, 16, 128, "long", False, "float32", 32),
    ("mixed4096-groups32-exact-gated", 4096, 32, 128, "exact", True, "bfloat16", 128),
    ("rows288-groups16-exact-plain", 288, 16, 128, "exact", False, "float32", 32),
    ("decode288-ones-gated", 288, 64, 256, "ones", True, "bfloat16", 16),
    ("mixed4096-groups64-ones-plain", 4096, 64, 128, "ones", False, "float32", 64),
    ("mixed4096-groups32-gaps-gated", 4096, 32, 128, "gaps", True, "bfloat16", 128),
    ("decode64-gaps-plain", 64, 32, 256, "gaps", False, "float32", 8),
    ("mixed4096-groups32-overrun-gated", 4096, 32, 128, "overrun", True, "bfloat16", 128),
    ("mixed4096-groups64-overrun-plain", 4096, 64, 128, "overrun", False, "float32", 64),
    ("rows288-groups16-overrun-gated", 288, 16, 128, "overrun", True, "bfloat16", 32),
    ("decode64-overrun-plain", 64, 32, 256, "overrun", False, "bfloat16", 16),
    ("decode64-empty-plain", 64, 32, 256, "empty", False, "float32", 8),
    ("mixed4096-groups32-empty-gated", 4096, 32, 128, "empty", True, "bfloat16", 128),
]
TAILS = ("tail", "empty", "ones", "overrun")


@pytest.mark.parametrize("A,G,K,kind,gated,dtype,tm",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_kernel_matches_ragged_dot_on_the_live_rows(A, G, K, kind, gated,
                                                    dtype, tm):
    dtype = jnp.dtype(dtype)
    assert gg._row_tile(A, G, dtype.itemsize) == tm
    rng = np.random.default_rng(A + G)
    sizes = _sizes(kind, A, G, rng, tm)
    live = int(sizes.sum())
    assert (sizes >= 0).all() and (live < A) == (kind in TAILS)
    ks = jax.random.split(jax.random.PRNGKey(G), 3)
    x = jax.random.normal(ks[0], (A, K), jnp.float32).astype(dtype)
    x = x.at[live:].set(jnp.nan)        # whoever reads the tail shows it
    w = (jax.random.normal(ks[1], (G, K, N)) * K ** -0.5).astype(dtype)
    gate = ((jax.random.normal(ks[2], (G, K, N)) * K ** -0.5).astype(dtype)
            if gated else None)
    gs = jnp.asarray(sizes, jnp.int32)
    reset_dispatch_log()
    got = ops.grouped_gemm(x, w, gs, gate, impl="pallas")
    assert [(d["op"], d["impl"]) for d in dispatch_log()] == [
        ("grouped_gemm", "pallas")]
    assert got.shape == (A, N) and got.dtype == dtype
    got = np.asarray(got[:live], np.float32)
    # the two-product form on the rows' float32 values, the tail zeroed
    f32 = lambda a: a.astype(jnp.float32)
    clean = f32(x).at[live:].set(0)
    want = jax.lax.ragged_dot(clean, f32(w), gs)
    if gated:
        want = jax.nn.silu(jax.lax.ragged_dot(clean, f32(gate), gs)) * want
    want = np.asarray(want[:live])
    assert np.isfinite(got).all()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=5e-5)
    else:                               # one rounding to bf16, no more
        np.testing.assert_allclose(got, want, rtol=2 ** -8, atol=1e-6)
        # and it is the registry's other implementation to bf16's step
        xla = np.asarray(ops.grouped_gemm(x.at[live:].set(0), w, gs, gate,
                                          impl="xla")[:live], np.float32)
        np.testing.assert_allclose(got, xla, rtol=2 ** -5, atol=2 ** -7)


def test_column_tiles_divide_the_width_and_fit_twice():
    it = 2
    for K, Nn, operands in [(2048, 1408, 2), (1408, 2048, 1),
                            (3072, 3072, 2), (3072, 3072, 1),
                            (5120, 1536, 2), (1536, 5120, 1),
                            (4096, 14336, 2)]:
        tn = gg._col_tile(K, Nn, it, operands)
        assert Nn % tn == 0 and tn % 128 == 0
        assert 2 * operands * K * tn * it <= gg._PANEL_BYTES
    assert gg._col_tile(2048, 1408, 2, 2) == 1408      # all of 11 x 128
    assert gg._col_tile(32, 24, 4, 2) == 24            # no 128 in it: whole


@pytest.mark.parametrize("share", [True, False], ids=["share", "whole"])
def test_no_tail_row_reaches_the_expert_layers_result(share):
    """Through ``_expert_ffn_ragged`` with the kernel forced: a share drops
    the assignments to experts it does not hold and those of rows that are
    not live, whose tokens here are NaN, so the sorted buffer's tail is NaN
    going in and whatever the kernel left coming out; the result is the
    ``lax.ragged_dot`` path's, finite, zero on the dead rows."""
    S, H, M, k, routed = 24, 128, 256, 4, 16
    held, offset = (4, 4) if share else (routed, 0)
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    tokens = jax.random.normal(ks[0], (S, H), jnp.float32)
    live = None
    if share:
        live = jnp.arange(S) % 5 != 0
        tokens = jnp.where(live[:, None], tokens, jnp.nan)
    idx = jnp.stack([jax.random.permutation(kk, routed)[:k]
                     for kk in jax.random.split(ks[1], S)])
    wts = jax.random.uniform(ks[2], (S, k))
    wi = jax.random.normal(ks[3], (held, H, M)) * H ** -0.5
    wg = jax.random.normal(ks[4], (held, H, M)) * H ** -0.5
    wo = jax.random.normal(ks[5], (held, M, H)) * M ** -0.5
    kw = dict(expert_offset=offset, num_experts=routed, live=live,
              with_stats=True)
    reset_dispatch_log()
    got, stats = jax.jit(lambda t: _expert_ffn_ragged(
        t, idx, wts, wi, wo, wg, impl="pallas", **kw))(tokens)
    assert {(d["op"], d["impl"]) for d in dispatch_log()} == {
        ("grouped_gemm", "pallas")}
    clean = tokens if live is None else jnp.where(live[:, None], tokens, 0)
    want, wstats = _expert_ffn_ragged(clean, idx, wts, wi, wo, wg, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(wstats))
    if share:
        assert int(stats[0]) < S * k                    # there was a tail
        assert not np.asarray(got)[~np.asarray(live)].any()
    # the GELU form has no kernel: it takes lax.ragged_dot whatever is asked
    reset_dispatch_log()
    _expert_ffn_ragged(clean, idx, wts, wi, wo, None, impl="pallas", **kw)
    assert {(d["op"], d["impl"]) for d in dispatch_log()} == {
        ("grouped_gemm", "xla")}


def _dense_expert_ffn(tokens, expert_idx, weights, wi, wo, wg=None, *,
                      expert_offset=0, num_experts=None, live=None,
                      with_stats=False, impl="xla"):
    """What ``_expert_ffn_ragged`` computes, with no sort and no grouped
    product: every held expert on every token, weighted by the routing."""
    assert live is None and not with_stats
    E = wi.shape[0]
    h = jax.nn.silu(jnp.einsum("sh,ehm->esm", tokens, wg)) * jnp.einsum(
        "sh,ehm->esm", tokens, wi)
    y = jnp.einsum("esm,emh->esh", h, wo)
    local = expert_idx - expert_offset                         # [S, k]
    hot = (local[..., None] == jnp.arange(E)) * weights[..., None]
    return jnp.einsum("se,esh->sh", hot.sum(1).astype(y.dtype), y)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_what_is_differentiated_keeps_ragged_dot_and_its_gradients(
        router, monkeypatch):
    """The flax module's dropless routes ask for ``lax.ragged_dot`` by name
    (a ``pallas_call`` has no transpose), on a TPU too; their gradients are
    those of the layer written densely."""
    from deepspeed_tpu.moe import layer
    from deepspeed_tpu.ops import registry
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)  # would be pallas
    extra = (dict(router="sigmoid", route_scale=2.0, shared_dim=24,
                  experts_held=4, expert_offset=2) if router == "sigmoid"
             else {})
    mod = layer.MoE(hidden_size=128, num_experts=8, k=2, mlp_dim=256,
                    gated=True, dropless=True, **extra)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 128))
    params = mod.init(jax.random.PRNGKey(1), x, deterministic=True)

    def loss(p):
        out, aux = mod.apply(p, x, deterministic=True)
        return jnp.sum(out ** 2) + aux
    reset_dispatch_log()
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert "ragged_dot" in text and "pallas_call" not in text
    assert {(d["op"], d["impl"], d["reason"]) for d in dispatch_log()} == {
        ("grouped_gemm", "xla", "forced")}
    got = jax.grad(loss)(params)
    monkeypatch.setattr(layer, "_expert_ffn_ragged", _dense_expert_ffn)
    want = jax.grad(loss)(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(a).max()) > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, err_msg=str(path))
