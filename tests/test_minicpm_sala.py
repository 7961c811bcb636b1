"""MiniCPM-SALA's two mechanisms (``GPTConfig.layer_types`` "lightning":
a matrix state a head under a fixed decay; ``block_topk``: attention layers
that select blocks of keys a KV head from pooled keys) against the plain
reference (``benchmark/reference/_minicpm_sala.py``), at tiny sizes in
float32: the operations, the model, the planted faults and the checkpoint's
name map (the serving engine's paths are in ``test_minicpm_sala_engine.py``:
a file runs on one worker).

Tolerances: everything here is float32 on the CPU, so a difference is
summation order: 2e-4 absolute on values of order 1, and a planted fault
must read at least a hundred times that."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sala_tiny import (SIZES, TOL, cfg, config, faults, params,  # noqa: F401
                       ref, seqs, want)

from deepspeed_tpu import ops
from deepspeed_tpu.models.gpt import (GPTLogits, count_params,
                                      lightning_decay)
from deepspeed_tpu.ops import block_select
from deepspeed_tpu.ops.block_select import BlockGeometry
from deepspeed_tpu.ops.ssm_scan import (pack_state, state_update_supported,
                                        unpack_state)

GEO = BlockGeometry(kernel=4, stride=2, block=8, topk=4, window=16, init=1,
                    dense_len=32)


# ------------------------------------------------------------ the recurrence

@pytest.fixture(scope="module")
def rows():
    """37 rows of 8 heads of 16 over a state of 16, a key and a query a
    head (8 groups), as a lightning layer hands them to the scan."""
    rng = np.random.default_rng(5)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return f(37, 8, 16), f(37, 8, 16), f(37, 8, 16)        # q, k, v


@pytest.mark.parametrize("swapped", [False, True])
@pytest.mark.parametrize("chunk", [8, 16, 37, 64, 5])
def test_chunked_scan_is_the_recurrence_at_a_group_a_head(rows, chunk,
                                                          swapped):
    """``ops.ssm_chunk_scan`` at ``dt = 1``, a fixed decay and as many
    groups as heads is the reference's left-to-right recurrence, at chunks
    that divide the rows and chunks that do not, with the state in either
    orientation."""
    q, k, v = rows
    lam = ref._decay(8)
    want = ref._recurrence(q, k, v, lam)
    state0 = jnp.zeros((1, 8, 16, 16), jnp.float32)
    y, s1 = ops.ssm_chunk_scan(
        v[None], jnp.ones((1, 37, 8)), jnp.asarray(lightning_decay(8)),
        k[None], q[None], jnp.zeros(8), state0, chunk=chunk, swapped=swapped)
    np.testing.assert_allclose(y[0], want, atol=TOL)
    # the state it leaves: sum over rows of decay^(T-1-t) v_t k_t^T
    t = jnp.arange(37)
    w = lam[None, :] ** (36 - t)[:, None]
    final = jnp.einsum("th,thp,thn->hpn", w, v, k)
    got = jnp.swapaxes(s1[0], -1, -2) if swapped else s1[0]
    np.testing.assert_allclose(got, final, atol=TOL)


def test_the_decay_is_the_alibi_slope_rule():
    lam = np.exp(lightning_decay(32))
    np.testing.assert_allclose(lam, np.asarray(ref._decay(32)), rtol=1e-6)
    assert lam[0] == pytest.approx(np.exp(-2 ** -0.25), rel=1e-6)
    assert lam[-1] == pytest.approx(np.exp(-2 ** -8.0), rel=1e-6)
    assert np.all(np.diff(lam) > 0)


@pytest.mark.parametrize("heads,slots", [(8, 3), (16, 2), (32, 1)])
def test_state_update_with_a_column_a_head(heads, slots):
    """The one-row recurrence in a packed pool whose heads are the lanes'
    width and each have their own ``B`` and ``C`` (lightning attention's
    geometry): the XLA form against the arithmetic written out, the kernel
    (interpreted) against the XLA form; a fresh slot starts from zero, an
    inactive one keeps its state, the other layer is not touched."""
    rng = np.random.default_rng(8)
    S, p, n = slots, 128, 128
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    x, dt = f(S, heads, p), jnp.ones((S, heads))
    A, D = jnp.asarray(lightning_decay(heads)), jnp.zeros(heads)
    B, C = f(S, heads, n), f(S, heads, n)
    state = f(2, S, heads, p, n)
    pool = pack_state(state)
    assert pool.shape == (2, S, heads, n, p)
    np.testing.assert_array_equal(unpack_state(pool, p), state)
    active = jnp.asarray([True, False, True][:S])
    fresh = jnp.asarray([False, False, True][:S])
    old = jnp.where(fresh[:, None, None, None], 0.0, state[1])
    new = (old * jnp.exp(A)[None, :, None, None]
           + x[..., None] * B[:, :, None, :])
    want_y = jnp.einsum("shpn,shn->shp", new, C)
    want = jnp.where(active[:, None, None, None], new, old)
    assert state_update_supported(x, dt, A, B, C, D, pool)
    for impl in ("xla", "pallas"):
        y, out = ops.ssm_state_update(x, dt, A, B, C, D, pool, 1, active,
                                      fresh, impl=impl)
        np.testing.assert_allclose(y, want_y, atol=1e-4, err_msg=impl)
        np.testing.assert_allclose(unpack_state(out[1], p), want, atol=1e-5,
                                   err_msg=impl)
        np.testing.assert_array_equal(out[0], pool[0])


# ------------------------------------------------------------- the selection

@pytest.fixture(scope="module")
def qk():
    rng = np.random.default_rng(9)
    T, nkv, g, d = 90, 2, 2, 16
    q = jnp.asarray(rng.normal(size=(T, nkv, g, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, nkv, d)), jnp.float32)
    return q * 2.0, k


def ref_keep(q, k, geo=GEO):
    """The reference's choice of blocks: bool [T, nkv, NB]."""
    T, nkv = k.shape[:2]
    sp = dict(kernel_size=geo.kernel, kernel_stride=geo.stride,
              block_size=geo.block, topk=geo.topk, window_size=geo.window,
              init_blocks=geo.init, dense_len=geo.dense_len)
    pos = jnp.arange(T)
    return jnp.stack([ref._select_blocks(
        q[:, n].transpose(1, 0, 2),
        ref._pooled(k[:, n], geo.kernel, geo.stride), pos, T, sp,
        q.shape[-1] ** -0.5) for n in range(nkv)], axis=1)


def test_pooled_keys_are_the_means_of_complete_spans(qk):
    _, k = qk
    got = block_select.pooled_keys(k, GEO)
    assert got.shape == (45, 2, 16)
    for n in range(2):
        want = ref._pooled(k[:, n], 4, 2)                # 44 complete spans
        np.testing.assert_allclose(got[:44, n], want, atol=1e-6)


def test_block_scores_forced_blocks_and_the_choice(qk):
    """The program's block scores, marks and exact top-k against the
    reference's full sort, row for row and KV head for KV head: block 0 and
    the two blocks that end at the row's own are always in, four in all."""
    q, k = qk
    T = k.shape[0]
    pos = jnp.arange(T)
    kbar = block_select.pooled_keys(jnp.pad(k, ((0, 6), (0, 0), (0, 0))),
                                    GEO)
    scores = ops.block_scores(q, kbar[None], pos, geo=GEO, scale=0.25)
    marked = block_select.mark_blocks(scores, pos, GEO)
    keep = np.asarray(block_select.kept_blocks(marked, GEO.topk))
    want = np.asarray(ref_keep(q, k))
    np.testing.assert_array_equal(keep, want)
    sel = np.arange(T) >= 32
    own = np.arange(T) // 8
    assert (keep.sum(-1)[sel] == 4).all()
    for t in np.flatnonzero(sel):
        assert keep[t, :, 0].all() and keep[t, :, own[t]].all() \
            and keep[t, :, own[t] - 1].all()
    # the two KV heads choose differently somewhere
    assert (keep[sel][:, 0] != keep[sel][:, 1]).any()
    # threshold_mask and index_select are the same choice
    flat = jnp.pad(marked.reshape(T * 2, -1), ((0, 0), (0, 128 - 12)),
                   constant_values=-jnp.inf)
    picked = np.asarray(ops.index_select(flat, 4))
    listed = np.zeros((T * 2, 128), bool)
    np.put_along_axis(listed, picked, True, axis=1)
    listed &= np.asarray(flat) > -np.inf
    np.testing.assert_array_equal(listed[:, :12].reshape(T, 2, 12)[sel],
                                  keep[sel])


def test_ties_go_to_the_lower_block():
    """Equal scores: the lower block wins, as a stable full sort would."""
    scores = jnp.zeros((1, 1, 10)).at[0, 0, 5].set(0.5)
    pos = jnp.asarray([79])                        # own block 9
    keep = np.asarray(block_select.kept_blocks(
        block_select.mark_blocks(scores, pos, GEO), GEO.topk))[0, 0]
    # forced 0, 8, 9; the best other is 5; were it not there, block 1
    np.testing.assert_array_equal(np.flatnonzero(keep), [0, 5, 8, 9])
    keep = np.asarray(block_select.kept_blocks(block_select.mark_blocks(
        jnp.zeros((1, 1, 10)), pos, GEO), GEO.topk))[0, 0]
    np.testing.assert_array_equal(np.flatnonzero(keep), [0, 1, 8, 9])


@pytest.mark.parametrize("context", [31, 32, 33])
def test_dense_len_is_a_context_not_a_position(qk, context):
    """A row whose context (position + 1) is exactly ``dense_len`` still
    reads every key; one more and it selects."""
    q, k = qk
    t = context - 1
    mask = np.asarray(block_select.dense_key_mask(
        q[None, :context], k[None, :context], jnp.arange(context)[None],
        geo=GEO, scale=0.25))[0]                          # [nkv, T, S]
    assert (mask[:, t].sum(-1) == context).all() == (context <= 32)
    if context > 32:        # 3 whole blocks and the own block's head
        assert (mask[:, t].sum(-1) == 3 * 8 + t % 8 + 1).all()


def test_geometry_refuses_what_the_rule_cannot_mean():
    with pytest.raises(ValueError, match="multiples"):
        GEO._replace(kernel=5).check()
    with pytest.raises(ValueError, match="outnumber"):
        GEO._replace(topk=2).check()
    with pytest.raises(ValueError, match="dense_len"):
        GEO._replace(dense_len=16).check()


def test_block_attention_over_the_paged_pool(qk):
    """One row a slot over its kept blocks, each kv head a sequence of its
    own to the paged decode op over pages of one block, against attention
    under the dense mask; the pooled keys a step completes, from pages,
    against the dense pooling."""
    q, k = qk
    rng = np.random.default_rng(3)
    T, nkv, g, d = q.shape
    v = jnp.asarray(rng.normal(size=(T, nkv, d)), jnp.float32)
    bs, pages = 16, 8
    table = jnp.asarray([[5, 2, 7, 0, 3, 6]], jnp.int32)

    def paged(a):
        pool = jnp.zeros((pages, nkv, bs, d), jnp.float32)
        a = jnp.pad(a, ((0, 96 - T), (0, 0), (0, 0))).reshape(6, bs, nkv, d)
        return pool.at[table[0]].set(jnp.moveaxis(a, 2, 1))
    k_pages, v_pages = paged(k), paged(v)
    t = 77
    pos = jnp.asarray([t])
    keep = ref_keep(q, k)[t]                               # [nkv, NB]
    blocks = jnp.stack([jnp.flatnonzero(keep[n], size=4) for n in range(2)])
    kept, lens = block_select.kept_block_table(
        table, blocks[None], pos, jnp.asarray([True]), bs, GEO)
    np.testing.assert_array_equal(lens, [3 * 8 + t % 8 + 1] * 2)
    got = ops.paged_attention(
        q[t].reshape(2, 1, g, d), block_select.block_pages(k_pages, GEO),
        block_select.block_pages(v_pages, GEO), kept, lens, scale=0.25,
        impl="xla").reshape(nkv, g, d)
    mask = jnp.repeat(keep, 8, axis=-1)[:, :T] & (jnp.arange(T) <= t)
    want = block_select.masked_attention(
        q[t][None, None], k[None], v[None], mask[None, :, None], 0.25)[0, 0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    # upkeep: every row's completed pooled key, written and gathered back
    rows_pos = jnp.arange(T)
    mine = jnp.broadcast_to(table, (T, 6))
    new, j, done = block_select.completed_pooled_keys(k_pages, mine,
                                                      rows_pos, GEO)
    assert int(done.sum()) == 44 and not bool(done[:3].any())
    kp = block_select.write_pooled_keys(
        jnp.zeros((pages, bs // 2, nkv, d)), new, j, done, mine)
    back = block_select.slot_pooled_keys(kp, table)[0]
    np.testing.assert_allclose(back[:44], block_select.pooled_keys(
        k, GEO)[:44], atol=1e-6)


def test_prefill_kernel_takes_a_selection_a_kv_head(qk):
    """The masked prefill kernel (interpreted) with bits a KV head against
    its XLA form: two slots' chunks in one flat batch, the second head's
    bits unlike the first's."""
    from deepspeed_tpu.ops.sparse_index import _pack_rows
    rng = np.random.default_rng(4)
    nkv, g, d, bs, MB = 2, 2, 128, 128, 2
    N, S = 64, 2
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q = f(N, nkv, g, d)
    k_pages, v_pages = f(5, nkv, bs, d), f(5, nkv, bs, d)
    table = jnp.asarray([[1, 3], [4, 0]], jnp.int32)
    kv_len = jnp.asarray([200, 90], jnp.int32)
    counts = jnp.asarray([40, 24], jnp.int32)
    first = jnp.asarray([0, 40], jnp.int32)
    keep = jnp.asarray(rng.random((nkv, N, MB * bs)) < 0.5)
    bits = jnp.stack([_pack_rows(keep[n]) for n in range(nkv)])
    args = (q, k_pages, v_pages, table, kv_len, kv_len - counts, counts,
            first)
    want = ops.ragged_prefill_attention(*args, max_q=64, sel_mask=bits,
                                        impl="xla")
    got = ops.ragged_prefill_attention(*args, max_q=64, sel_mask=bits,
                                       impl="pallas")
    np.testing.assert_allclose(got, want, atol=2e-5)
    one = ops.ragged_prefill_attention(*args, max_q=64, sel_mask=bits[0],
                                       impl="xla")
    assert float(jnp.abs(one[:, 1] - want[:, 1]).max()) > 1e-2
    np.testing.assert_allclose(one[:, 0], want[:, 0], atol=1e-6)


# ------------------------------------------------------------------ the model

def test_the_model_is_the_reference(cfg, params, seqs, want):
    """The flax model (the chunked scan; the selection in its dense form)
    on whole sequences against the reference, past ``dense_len``."""
    lm = GPTLogits(cfg)
    for s, w in zip(seqs, want):
        got = lm.apply({"params": params}, s[None])[0]
        np.testing.assert_allclose(got, w, atol=TOL)


def test_layers_and_parameters(cfg, params):
    assert cfg.layer_types == ("attention", "lightning", "lightning",
                               "attention", "lightning", "attention")
    assert cfg.scan_layers == (1, 2, 4) and cfg.state_layers == (1, 2, 4)
    assert [cfg.rope_for_layer(i) for i in range(6)] == [
        False, True, True, False, True, False]
    assert count_params(cfg) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    with pytest.raises(ValueError, match="lightning-attention layer"):
        cfg.for_layer(1)


def test_published_widths_count_3_93_billion():
    import json
    import os
    from sala_tiny import ROOT
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala-12l.json")) as f:
        published = json.load(f)
    c = config(published, max_seq_len=66048)
    assert count_params(c) == 3_929_972_864
    assert c.layer_types.count("attention") == 3
    assert c.layer_types.count("lightning") == 9
    assert c.block_geometry == BlockGeometry(32, 16, 64, 64, 2048, 1, 8192)
    assert c.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert c.logits_divisor == 16.0 and c.embed_scale == 12.0


@pytest.mark.parametrize("fault", faults.FAULTS + (faults.CONTROL,))
def test_a_planted_fault_reads_as_a_fault(params, seqs, want, fault):
    """Every planted fault moves the tiny model's logits a hundred times
    the tolerance and more (a state in bf16: ten times)."""
    with faults.planted(fault, params, SIZES) as (bad_params, bad_sizes):
        got = np.asarray(ref.logits(bad_params, seqs[0], bad_sizes))
    gap = float(np.abs(got - want[0]).max())
    assert gap > (10 if fault == "state_rounded_to_bf16" else 100) * TOL, gap
    again = np.asarray(ref.logits(params, seqs[0], SIZES))
    np.testing.assert_array_equal(again, want[0])      # and it is taken out


def test_gradient_through_a_lightning_layer(cfg, params):
    """The chunked scan differentiates (a static ``lax.scan``)."""
    from deepspeed_tpu.models.gpt import LightningMixer
    c = dataclasses.replace(cfg, ssm_chunk=8)
    mp = params["backbone"]["block_1"]["LightningMixer_0"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 20, 64)),
                    jnp.float32)
    pos = jnp.arange(20)[None]

    def loss(mp, x):
        return jnp.sum(LightningMixer(c).apply({"params": mp}, x, pos) ** 2)
    g = jax.grad(loss)(mp, x)
    assert all(float(jnp.abs(a).max()) > 0
               for a in jax.tree_util.tree_leaves(g))


# ------------------------------------------------------------ the checkpoint

def test_the_name_map_round_trips(cfg, params):
    """A seeded tiny state dict under the published tensor names and shapes
    loads into the tree it was written from, name for name; the config
    reader makes the reference's settings of the same file."""
    from deepspeed_tpu.checkpoint import hf
    sd = hf.minicpm_sala_state_dict(cfg, params)
    names = set()
    for pat in hf.MINICPM_SALA_WEIGHT_NAMES:
        names |= {pat.format(i=i) for i in range(cfg.num_layers)}
    assert set(sd) <= names
    inner = cfg.ssm_inner
    assert sd["model.layers.1.self_attn.q_proj.weight"].shape == (
        inner, cfg.hidden_size)
    assert sd["model.layers.1.self_attn.o_norm.weight"].shape == (128,)
    assert sd["model.layers.0.self_attn.k_proj.weight"].shape == (
        2 * 128, cfg.hidden_size)
    assert "model.layers.0.self_attn.o_norm.weight" not in sd
    assert sd["lm_head.weight"].shape == (cfg.vocab_size, cfg.hidden_size)
    back = hf._minicpm_sala_tree(sd, cfg)
    flat = dict(jax.tree_util.tree_leaves_with_path(back))
    for path, a in jax.tree_util.tree_leaves_with_path(params):
        np.testing.assert_array_equal(flat[path], a,
                                      err_msg=jax.tree_util.keystr(path))
    got = hf.minicpm_sala_config({**SIZES, "max_position_embeddings": 256})
    assert dataclasses.replace(got, dtype=cfg.dtype) == cfg


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("lightning_scale", "1/d"),
    ("tie_word_embeddings", True), ("attn_use_output_gate", False),
    ("mixer_types", ["minicpm4", "mamba2"] * 3),
    ("sparse_config", {"pool": "max"})])
def test_the_config_reader_refuses_what_it_does_not_know(key, value):
    from deepspeed_tpu.checkpoint import hf
    with pytest.raises(NotImplementedError, match="minicpm_sala"):
        hf.minicpm_sala_config({**SIZES, key: value})


def test_the_config_reader_fills_only_what_the_config_leaves_out():
    from deepspeed_tpu.checkpoint import hf
    published = {k: v for k, v in SIZES.items()
                 if k not in ("sparse_config", "layers_kept", "published")}
    got = hf.minicpm_sala_config(published)
    assert (got.block_topk, got.block_size, got.block_dense_len) == (
        64, 64, 8192)
    assert got.residual_scale == pytest.approx(1.4 / 6 ** 0.5)
    given = hf.minicpm_sala_config(
        {**published, "sparse_config": {"topk": 48}})
    assert (given.block_topk, given.block_window) == (48, 2048)
