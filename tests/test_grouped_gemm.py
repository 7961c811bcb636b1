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
tail row reaches its result; its positions come from counts and are the
stable sort's; it is the layer written densely, whole and as a share, with
dead rows, one expert taking everything, ``k = 1``; in bfloat16 it is no
further from float32 than the scatter-add it replaced (PR 56); and its
lowered text holds one sort, of the ids, and no scatter."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import ops
from deepspeed_tpu.moe.layer import _expert_ffn_ragged, _positions_by_count
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
    """What ``_expert_ffn_ragged`` computes, with no permutation and no
    grouped product: every held expert on every token, weighted by the
    routing; a row ``live`` masks out counts for nothing and gives 0."""
    E = wi.shape[0]
    local = expert_idx - expert_offset                         # [S, k]
    hot = local[..., None] == jnp.arange(E)                    # [S, k, E]
    if live is not None:
        tokens = jnp.where(live[:, None], tokens, 0)
        hot = hot & live[:, None, None]
    h = jax.nn.silu(jnp.einsum("sh,ehm->esm", tokens, wg)) * jnp.einsum(
        "sh,ehm->esm", tokens, wi)
    y = jnp.einsum("esm,emh->esh", h, wo)
    out = jnp.einsum("se,esh->sh", (hot * weights[..., None]).sum(1).astype(
        y.dtype), y)
    if not with_stats:
        return out
    n_live = expert_idx.size if live is None else live.sum() * local.shape[1]
    return out, jnp.stack([hot.sum(), n_live, hot.any((0, 1)).sum()]).astype(
        jnp.int32)


def _scatter_add_expert_ffn(tokens, expert_idx, weights, wi, wo, wg, *,
                            expert_offset=0, live=None):
    """The layer as it was until PR 56: ``argsort``, the rows gathered, the
    products weighted in the rows' dtype and scatter-added into an
    accumulator of that dtype behind a mask pass over the tail."""
    S, k = expert_idx.shape
    E = wi.shape[0]
    local = expert_idx.reshape(-1) - expert_offset
    keep = (local >= 0) & (local < E)
    if live is not None:
        keep = keep & jnp.repeat(live, k)
    flat_e = jnp.where(keep, local, E)
    order = jnp.argsort(flat_e)
    tok_rows = jnp.repeat(jnp.arange(S), k)[order]
    sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(1, mode="drop")
    o = ops.grouped_gemm(ops.grouped_gemm(tokens[tok_rows], wi, sizes, wg,
                                          impl="xla"), wo, sizes, impl="xla")
    done = jnp.arange(S * k) < jnp.sum(sizes)
    o = jnp.where(done[:, None], o, 0)
    w = weights.reshape(-1)[order].astype(o.dtype)
    return jnp.zeros_like(tokens).at[jnp.where(done, tok_rows, S)].add(
        o * w[:, None], mode="drop")


@pytest.mark.parametrize("A,num,kind", [
    (64, 33, "uniform"), (288, 65, "uniform"), (4096, 33, "share"),
    (8192, 65, "uniform"), (100, 5, "uniform"), (129, 3, "one"),
    (256, 17, "sentinel"), (1, 2, "one")])
def test_positions_by_count_are_the_stable_sorts(A, num, kind):
    """``dest`` is the inverse of ``argsort(ids, stable)`` and ``sizes`` the
    count of each id: at the cells' buffer lengths, at lengths no block of
    128 divides, with most entries the sentinel (a share), all of them, and
    all entries one id."""
    rng = np.random.default_rng(A + num)
    ids = {"uniform": lambda: rng.integers(0, num, A),
           "share": lambda: np.where(rng.random(A) < 0.125,
                                     rng.integers(0, num - 1, A), num - 1),
           "sentinel": lambda: np.full(A, num - 1),
           "one": lambda: np.full(A, num // 2)}[kind]().astype(np.int32)
    dest, sizes = jax.jit(_positions_by_count, static_argnums=1)(
        jnp.asarray(ids), num)
    assert dest.dtype == jnp.int32 and sizes.dtype == jnp.int32
    want = np.empty(A, int)
    want[np.argsort(ids, kind="stable")] = np.arange(A)
    np.testing.assert_array_equal(np.asarray(dest), want)
    np.testing.assert_array_equal(np.asarray(sizes),
                                  np.bincount(ids, minlength=num))


def _layer_case(kind, dtype=jnp.float32):
    """tokens, routing, weights and keywords of one case of the expert
    layer; dead rows' tokens are NaN, so whoever reads one shows it."""
    S, H, M, k, routed = 24, 128, 256, 4, 16
    held, offset, live = routed, 0, None
    if kind == "k1":
        k = 1
    if kind in ("share", "dead_rows", "no_live_row", "ragged_tile"):
        held, offset = 4, 4
    if kind == "dead_rows":
        live = jnp.arange(S) % 5 != 0
    if kind == "no_live_row":
        live = jnp.zeros((S,), bool)
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    tokens = jax.random.normal(ks[0], (S, H), jnp.float32)
    if live is not None:
        tokens = jnp.where(live[:, None], tokens, jnp.nan)
    idx = jnp.stack([jax.random.permutation(kk, routed)[:k]
                     for kk in jax.random.split(ks[1], S)])
    if kind == "one_expert":
        idx = jnp.full((S, k), 5)
    if kind == "ragged_tile":
        # 13 assignments held here, 3 + 9 + 1 + 0: no multiple of a row
        # tile (8 rows in float32, 16 in bfloat16) and a group without rows
        idx = jnp.full((S, k), 0).at[:3, 0].set(4).at[3:12, 1].set(5).at[
            12, 2].set(6)
    wts = jax.random.uniform(ks[2], (S, k))
    wi = jax.random.normal(ks[3], (held, H, M)) * H ** -0.5
    wg = jax.random.normal(ks[4], (held, H, M)) * H ** -0.5
    wo = jax.random.normal(ks[5], (held, M, H)) * M ** -0.5
    args = (tokens.astype(dtype), idx, wts) + tuple(
        w.astype(dtype) for w in (wi, wo, wg))
    return args, dict(expert_offset=offset, num_experts=routed, live=live)


LAYER_KINDS = ["whole", "share", "dead_rows", "no_live_row", "one_expert",
               "k1", "ragged_tile"]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kind", LAYER_KINDS)
def test_expert_layer_is_the_dense_layer(kind, impl):
    """``_expert_ffn_ragged`` against the layer written densely: finite,
    exactly 0 on the rows that are not live, the same counters, and the
    positions and group sizes it works from are the stable sort's."""
    args, kw = _layer_case(kind)
    idx, live = args[1], kw["live"]
    got, stats = jax.jit(lambda *a: _expert_ffn_ragged(
        *a, impl=impl, with_stats=True, **kw))(*args)
    want, wstats = _dense_expert_ffn(*args, with_stats=True, **kw)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), atol=3e-5)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(wstats))
    if live is not None:
        dead = ~np.asarray(live)
        assert not got[dead].any() and not np.signbit(got[dead]).any()
    if kind == "no_live_row":
        assert not got.any() and int(stats[0]) == 0
    if kind == "ragged_tile":
        assert int(stats[0]) == 13 and int(stats[2]) == 3
    # what the layer permutes by
    E = args[3].shape[0]
    local = np.asarray(idx).reshape(-1) - kw["expert_offset"]
    keep = (local >= 0) & (local < E)
    if live is not None:
        keep &= np.repeat(np.asarray(live), idx.shape[1])
    flat_e = np.where(keep, local, E).astype(np.int32)
    dest, sizes = _positions_by_count(jnp.asarray(flat_e), E + 1)
    order = np.argsort(flat_e, kind="stable")
    np.testing.assert_array_equal(np.asarray(dest)[order],
                                  np.arange(flat_e.size))
    np.testing.assert_array_equal(np.asarray(sizes)[:E],
                                  np.bincount(flat_e, minlength=E + 1)[:E])
    assert int(stats[0]) == keep.sum()


@pytest.mark.parametrize("kind", ["whole", "share", "dead_rows", "k1"])
def test_bf16_combine_is_no_further_from_float32_than_the_scatter_add(kind):
    """Products and their sum over ``k`` are float32 with one rounding; the
    scatter-add rounded each weighted product and the accumulator after each
    add.  Against the float32 dense layer on the same bfloat16 values the
    gather's error is at most the scatter-add's (the grouped products are
    the same bits: same rows in the same order)."""
    args, kw = _layer_case(kind, jnp.bfloat16)
    got = np.asarray(_expert_ffn_ragged(*args, **kw), np.float32)
    kw.pop("num_experts")
    old = np.asarray(_scatter_add_expert_ffn(*args, **kw), np.float32)
    f32 = tuple(a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
                for a in args)
    want = np.asarray(_dense_expert_ffn(*f32, **kw))
    rms = lambda d: float(np.sqrt((d ** 2).mean()))
    assert rms(got - want) <= rms(old - want)


@pytest.mark.parametrize("cell,S,k,routed,held,offset,H,M", [
    ("lfm2-mixed", 2048, 4, 64, 64, 0, 2048, 1536),
    ("dots3-mixed", 1024, 8, 256, 32, 32, 5120, 1536),
    ("moonlight-decode", 48, 6, 64, 64, 0, 2048, 1408)])
def test_step_programs_expert_fn_has_one_sort_and_no_row_scatter(
        cell, S, k, routed, held, offset, H, M):
    """The lowered text of the serving step programs' ``_experts_fn`` at a
    cell's shape (PR 56): no ``scatter`` at all (the combine is a gather,
    the group sizes are counted and not scatter-added), and ONE ``sort``,
    over the int32 ids (``order``; step 0 found it cheaper than the int32
    scatter that would invert ``dest``)."""
    import re
    import types
    from deepspeed_tpu.inference.v2.model import _experts_fn
    cfg = types.SimpleNamespace(expert_offset=offset, num_experts=routed)
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    text = _experts_fn(cfg, True).lower(
        sds((S, H), bf), sds((S, k), jnp.int32), sds((S, k), jnp.float32),
        sds((held, H, M), bf), sds((held, M, H), bf), sds((held, H, M), bf),
        live=sds((S,), jnp.bool_)).as_text()
    assert '"stablehlo.scatter"(' not in text
    sorts = re.findall(r'"stablehlo\.sort"\(.*?\) -> \((.*?)\)\n', text,
                       flags=re.S)
    assert sorts == [f"tensor<{S * k}xi32>, tensor<{S * k}xi32>"], sorts
    # the rows out in one gather, the products back in one a choice
    assert text.count('"stablehlo.gather"(') == 1 + k


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
