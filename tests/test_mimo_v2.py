"""MiMo-V2-Flash's new mechanisms below the serving engine, at tiny sizes in
float32 on the CPU: the paged attention ops (both kernels interpreted and
both fallbacks) against a dense softmax with a sink, at unequal K/V widths,
kv-major pages, group sizes 16 and 8, a window of one page and work items
that start past page 0; the KV append at unequal widths; the flax model
against the plain reference (``benchmark/reference/_mimo_v2.py``) on seeded
weights, each planted fault visibly off it; the expert shares adding up to
the uncut layer; the checkpoint config against the catalog's row.

Tolerance: float32 on the CPU, so a difference is summation order (the
online softmax a page at a time against the dense one, the grouped experts
against the dense form): 2e-4 absolute on values of order 1."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from mimo_tiny import (SIZES, TOL, cfg, faults, make_cfg,  # noqa: F401
                       make_params, params, ref, seqs, want)

from deepspeed_tpu import ops
from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits, count_params
from deepspeed_tpu.ops import kv_append
from deepspeed_tpu.ops.paged_attention import (ragged_prefill_supported,
                                               supported as decode_supported)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BS = 128            # a page, and the window: a window of ONE page


def dense_sink_attention(q, k, v, sink, window, q_pos):
    """``q [R, heads, d]`` at positions ``q_pos [R]`` over one sequence's
    ``k [T, nkv, d]``, ``v [T, nkv, dv]``: float64 softmax with the sink in
    the denominator, written out."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    heads, nkv = q.shape[1], k.shape[1]
    g = heads // nkv
    out = np.zeros((q.shape[0], heads, v.shape[-1]))
    for r, t in enumerate(q_pos):
        lo = 0 if window is None else max(0, t - window + 1)
        for h in range(heads):
            s = (k[lo:t + 1, h // g] @ q[r, h]) * q.shape[-1] ** -0.5
            b = -np.inf if sink is None else float(sink[h])
            m = max(s.max(), b)
            e = np.exp(s - m)
            out[r, h] = (e / (np.exp(b - m) + e.sum())) @ v[lo:t + 1, h // g]
    return out


def paged(rng, lens, nkv, hd, vd, kv_major=True):
    """Pages of sequences ``lens`` in a shuffled pool: (k_pages, v_pages,
    block_table, per-sequence dense k and v)."""
    mb = max(-(-n // BS) for n in lens)
    nb = len(lens) * mb + 3
    order = rng.permutation(nb)
    kp = np.zeros((nb, nkv, BS, hd), np.float32)
    vp = np.zeros((nb, nkv, BS, vd), np.float32)
    bt = np.zeros((len(lens), mb), np.int32)
    dense = []
    for s, n in enumerate(lens):
        k = rng.normal(size=(n, nkv, hd)).astype(np.float32)
        v = rng.normal(size=(n, nkv, vd)).astype(np.float32)
        dense.append((k, v))
        for p in range(-(-n // BS)):
            page = order[s * mb + p]
            bt[s, p] = page
            rows = slice(p * BS, min(n, (p + 1) * BS))
            kp[page, :, :rows.stop - rows.start] = k[rows].transpose(1, 0, 2)
            vp[page, :, :rows.stop - rows.start] = v[rows].transpose(1, 0, 2)
    if kv_major:
        kp, vp = kp.transpose(0, 1, 3, 2), vp.transpose(0, 1, 3, 2)
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), dense


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("nkv,g,window,with_sink", [
    (1, 16, None, False),       # a full layer's group of 16, no sink
    (2, 8, BS, True),           # a window layer's group of 8, its sink
    (2, 8, None, True)])        # a sink on a layer that reads every key
def test_paged_decode_with_a_sink_and_a_narrower_value(impl, nkv, g, window,
                                                       with_sink):
    rng = np.random.default_rng(5)
    hd, vd, heads = 24, 16, nkv * g
    lens = [300, 0, 129, 5]          # past the window, empty, one key over
    kp, vp, bt, dense = paged(rng, lens, nkv, hd, vd)
    q = rng.normal(size=(len(lens), heads, hd)).astype(np.float32)
    sink = (rng.normal(size=heads) + 1.0).astype(np.float32) \
        if with_sink else None
    got = ops.paged_attention(
        jnp.asarray(q).reshape(len(lens), nkv, g, hd), kp, vp, bt,
        jnp.asarray(lens, jnp.int32), window=window, kv_major=True,
        impl=impl, sink=None if sink is None else jnp.asarray(sink))
    got = np.asarray(got).reshape(len(lens), heads, vd)
    for s, n in enumerate(lens):
        if n == 0:
            assert not got[s].any()
            continue
        k, v = dense[s]
        w = dense_sink_attention(q[s:s + 1], k, v, sink, window, [n - 1])
        np.testing.assert_allclose(got[s], w[0], atol=TOL)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("nkv,g,window,with_sink", [
    (1, 16, None, False), (2, 8, BS, True)])
def test_ragged_prefill_with_a_sink_past_page_0(impl, nkv, g, window,
                                                with_sink):
    """Chunks of 32 rows at contexts of 0, 260 and 130: with a window of one
    page the second and third slots' items start at pages 1-2, past page 0;
    one slot is empty."""
    rng = np.random.default_rng(7)
    hd, vd, heads = 24, 16, nkv * g
    starts, counts = [0, 260, 0, 130], [20, 32, 0, 7]
    lens = [a + c for a, c in zip(starts, counts)]
    kp, vp, bt, dense = paged(rng, lens, nkv, hd, vd)
    N = 64
    q = rng.normal(size=(N, heads, hd)).astype(np.float32)
    row_starts = np.cumsum([0] + counts[:-1]).astype(np.int32)
    sink = (rng.normal(size=heads) + 1.0).astype(np.float32) \
        if with_sink else None
    got = ops.ragged_prefill_attention(
        jnp.asarray(q).reshape(N, nkv, g, hd), kp, vp, bt,
        jnp.asarray(lens, jnp.int32), jnp.asarray(starts, jnp.int32),
        jnp.asarray(counts, jnp.int32), jnp.asarray(row_starts), max_q=32,
        window=window, kv_major=True, impl=impl,
        sink=None if sink is None else jnp.asarray(sink))
    got = np.asarray(got).reshape(N, heads, vd)
    for s, (a, c) in enumerate(zip(starts, counts)):
        if c:
            k, v = dense[s]
            rows = slice(row_starts[s], row_starts[s] + c)
            w = dense_sink_attention(q[rows], k, v, sink, window,
                                     range(a, a + c))
            np.testing.assert_allclose(got[rows], w, atol=TOL)


def test_the_ops_gates_take_unequal_widths_and_refuse_what_is_not_built():
    kp = jnp.zeros((4, 2, 24, BS))
    vp = jnp.zeros((4, 2, 16, BS))
    q = jnp.zeros((3, 2, 8, 24))
    bt, lens = jnp.zeros((3, 2), jnp.int32), jnp.zeros((3,), jnp.int32)
    sink = jnp.zeros((16,))
    ok = dict(kv_major=True, sink=sink)
    assert decode_supported(q, kp, vp, bt, lens, **ok)
    assert decode_supported(q, kp, vp, bt, lens, kv_major=True)
    # the value pool has the key pool's shape but for its head width
    assert not decode_supported(q, kp, jnp.zeros((4, 1, 16, BS)), bt, lens, **ok)
    assert not decode_supported(q, kp, jnp.zeros((4, 2, 12, BS)), bt, lens, **ok)
    assert not decode_supported(q, kp, vp, bt, lens, kv_major=True,
                            sink=jnp.zeros((8,)))      # one logit a head
    # latent pages carry no sink
    lat = jnp.zeros((4, 1, 16, 256))
    assert not decode_supported(jnp.zeros((3, 1, 16, 256)), lat, None, bt, lens,
                            v_dim=128, sink=sink)
    assert ragged_prefill_supported(q, kp, vp, bt, lens, lens, lens,
                                       lens, **ok)


@pytest.mark.parametrize("kv_major", [True, False])
def test_kv_append_at_unequal_widths_matches_the_xla_form(kv_major):
    """K rows 256 wide beside V rows 128 wide in ONE kernel call, bit for
    bit the XLA form's pools (standard pages need whole lane tiles, so the
    widths here are 256 and 128; kv-major pages take 24 and 16 too)."""
    rng = np.random.default_rng(1)
    nkv, hk, hv = (2, 256, 128)
    S, MB, bs, N = 3, 2, 128, 48
    shape = (lambda w: (8, nkv, w, bs)) if kv_major \
        else (lambda w: (8, nkv, bs, w))
    pools = tuple(jnp.asarray(rng.normal(size=shape(w)), jnp.float32)
                  for w in (hk, hv))
    new = tuple(jnp.asarray(rng.normal(size=(N, nkv, w)), jnp.float32)
                for w in (hk, hv))
    bt = jnp.asarray([[1, 4], [6, 2], [0, 3]], jnp.int32)
    # slot 0: 30 rows from 100 (crosses the page edge); slot 2: 18 from 7
    slot = np.r_[np.zeros(30), np.full(18, 2)].astype(np.int32)
    pos = np.r_[np.arange(100, 130), np.arange(7, 25)].astype(np.int32)
    plan = kv_append.append_plan(bt, jnp.asarray(slot), jnp.asarray(pos),
                                 bs, 32, kv_major)
    assert kv_append.supported(pools, new, plan, 0, kv_major=kv_major)
    want = kv_append.xla_paged_kv_append(pools, new, plan, 0,
                                         kv_major=kv_major)
    got = kv_append.pallas_paged_kv_append(pools, new, plan, jnp.int32(0),
                                           kv_major=kv_major, interpret=True)
    for g, w, old in zip(got, want, pools):
        assert np.array_equal(np.asarray(g), np.asarray(w))
        assert not np.array_equal(np.asarray(g), np.asarray(old))


# ------------------------------------------------------- model and reference

def test_the_flax_model_is_the_reference(cfg, params, seqs, want):
    lm = GPTLogits(cfg)
    for s, w in zip(seqs, want):
        got = np.asarray(lm.apply({"params": params}, s[None]))[0]
        np.testing.assert_allclose(got, w, atol=TOL)
    a0 = params["backbone"]["block_0"]["Attention_0"]
    a1 = params["backbone"]["block_1"]["Attention_0"]
    assert "sink" not in a0 and a1["sink"].shape == (8,)
    assert a1["sink"].dtype == jnp.float32
    assert a0["wk"].shape == (64, 2, 24) and a1["wk"].shape == (64, 4, 24)
    assert a0["wv"].shape == (64, 2, 16) and a1["wo"].shape == (8, 16, 64)
    assert count_params(cfg) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("fault", list(faults.FAULTS) + [faults.CONTROL])
def test_each_planted_fault_moves_the_reference(fault, params, seqs, want):
    """The reference with one thing wrong reads visibly off the healthy
    one: what the comparison on the chip has to tell apart exists."""
    sizes = SIZES
    if fault.startswith("rotated_columns"):     # 12 of 24, not 8
        assert ref.rotated_columns({**SIZES, "partial_rotary_factor": 0.5}) \
            == 12 != ref.rotated_columns(SIZES) == 8
    with faults.planted(fault, params, sizes) as (bp, bs):
        bad = np.asarray(ref.logits(bp, seqs[0], bs))
    assert np.abs(bad - want[0]).max() > 20 * TOL, fault
    again = np.asarray(ref.logits(params, seqs[0], SIZES))
    np.testing.assert_array_equal(again, want[0])      # and is taken out


def test_the_sixteen_shares_add_up_to_the_uncut_layer(seqs):
    """An expert layer's routed part with every expert held is the sum of
    sixteen chips' parts, each holding one expert of the router's sixteen:
    in the reference (its ``layer(parts="routed")``) and in the program
    (the flax model's expert layer at ``experts_held`` 1)."""
    whole = {**SIZES, "n_routed_experts": 16, "router_width": 16,
             "expert_offset": 0, "num_hidden_layers": 2,
             "hybrid_layer_pattern": [0, 1], "moe_layer_freq": [0, 1]}
    cfg = make_cfg(whole)
    params = make_params(cfg, seed=5)
    p = ref.tree(params)
    x = ref.layer(p["layers"][0], ref.embed(p["embed"], jnp.asarray(seqs[1])),
                  **ref._layer_args(whole, False, False))
    kw = ref._layer_args(whole, True, True)
    lp = p["layers"][1]
    uncut = np.asarray(ref.layer(lp, x, parts="routed", **kw))
    total = np.zeros_like(uncut)
    for e in range(16):
        share = {**lp, **{n: lp[n][e:e + 1]
                          for n in ("e_gate", "e_up", "e_down")}}
        total += np.asarray(ref.layer(share, x, offset=e, parts="routed",
                                      **kw))
    np.testing.assert_allclose(total, uncut, atol=TOL)
    assert np.abs(uncut).max() > 0.05
    # the program, one share: its logits are the reference's at that share
    one = {**whole, "n_routed_experts": 1, "expert_offset": 5}
    c1 = make_cfg(one)
    m = params["backbone"]["block_1"]["moe"]
    held = jax.tree_util.tree_map(lambda a: a, params)
    held["backbone"] = {**params["backbone"], "block_1": {
        **params["backbone"]["block_1"], "moe": {
            **m, **{n: m[n][5:6] for n in ("wge", "wi", "wo")}}}}
    got = np.asarray(GPTLogits(c1).apply({"params": held}, seqs[1][None]))[0]
    np.testing.assert_allclose(
        got, np.asarray(ref.logits(held, seqs[1], one)), atol=TOL)


# --------------------------------------------------------------- the config

def test_the_checkpoint_config_is_the_references_and_counts_309_b():
    from deepspeed_tpu.checkpoint.hf import mimo_v2_flash_config
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    c = mimo_v2_flash_config(row["config"])
    assert c == GPTConfig(**ref.program_config(row["config"]),
                          max_seq_len=c.max_seq_len, dtype=jnp.bfloat16)
    assert round(count_params(c) / 1e9, 1) == 308.8
    assert c.for_layer(0).kv_heads == 4 and c.for_layer(1).kv_heads == 8
    assert c.for_layer(0).rope_theta == 5e6 and c.for_layer(1).rope_theta == 1e4
    assert not c.for_layer(0).attn_sink and c.for_layer(1).attn_sink
    assert (c.head_dim, c.value_dim, c.attn_value_scale) == (192, 128, 0.707)
    assert [i for i in range(12) if c.window_for_layer(i) is None] == [0, 5,
                                                                       11]
    assert not c.is_moe_layer(0) and c.is_moe_layer(1)
    from deepspeed_tpu.models.gpt import rotary_dim
    assert rotary_dim(192, c.rope_pct) == 64
    cut = mimo_v2_flash_config(
        {**row["config"], "num_hidden_layers": 7, "vocab_size": 19072},
        experts_held=16, expert_offset=64)
    assert count_params(cut) == 3_429_955_392          # 3.43 B, 6.86 GB bf16
    for key, bad in (("n_group", 2), ("scoring_func", "softmax"),
                     ("n_shared_experts", 1), ("attention_bias", True)):
        with pytest.raises(NotImplementedError, match=key):
            mimo_v2_flash_config({**row["config"], key: bad})
    with pytest.raises(NotImplementedError, match="full layers alone"):
        mimo_v2_flash_config({**row["config"],
                              "add_swa_attention_sink_bias": False,
                              "add_full_attention_sink_bias": True})


def test_loading_weights_says_why_it_cannot(tmp_path):
    from deepspeed_tpu.checkpoint.hf import load_hf_checkpoint
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    (tmp_path / "config.json").write_text(json.dumps(
        {**row["config"], "num_hidden_layers": 2}))
    with pytest.raises(NotImplementedError, match="tensor names"):
        load_hf_checkpoint(str(tmp_path))


def test_a_layer_view_says_whether_the_layer_has_a_sink():
    c = make_cfg()
    assert [bool(c.for_layer(i).attn_sink) for i in range(4)] == [
        False, True, False, True]
    both = dataclasses.replace(c, attn_sink="all")
    assert all(both.for_layer(i).attn_sink for i in range(4))
    with pytest.raises(ValueError, match="attn_sink"):
        dataclasses.replace(c, attn_sink="some").for_layer(0)
    with pytest.raises(ValueError, match="window_attn may set"):
        dataclasses.replace(c, window_attn=(("hidden_size", 8),)).for_layer(1)
