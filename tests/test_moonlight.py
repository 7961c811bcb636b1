"""deepseek_v3 with latent attention (Moonlight) on the normal path, at tiny
sizes on the CPU: the flax module and the v2 engine's latent page pool
against the benchmark's plain float32 reference
(``benchmark/reference/_deepseek_mla.py``, which imports nothing from the
program and is NOT absorbed), the two paged kernels in their latent form,
planted faults that must show, the published tensor names, and what
start-up refuses beside a latent pool."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import v2_engine

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2 import model as v2model
from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits, count_params
from deepspeed_tpu.parallel.metadata import unbox

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))
import _deepseek_mla as ref  # noqa: E402  (the benchmark's plain reference)
import _mla_faults  # noqa: E402

V = 96


def sizes(**over):
    """A tiny deepseek_v3 configuration file: one dense layer, two expert
    layers; the latent 128 wide so that a page row (128 + 8 -> 256) has pad
    columns as the published 512 + 64 -> 640 has."""
    out = dict(
        model_type="deepseek_v3", hidden_act="silu", hidden_size=32,
        intermediate_size=64, moe_intermediate_size=24,
        num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, kv_lora_rank=128,
        q_lora_rank=None, num_hidden_layers=3, first_k_dense_replace=1,
        moe_layer_freq=1, n_routed_experts=8, num_experts_per_tok=3,
        n_shared_experts=2, n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.446, scoring_func="sigmoid",
        rms_norm_eps=1e-5, rope_theta=50000, attention_bias=False,
        ep_size=1, tie_word_embeddings=False, vocab_size=V,
        num_nextn_predict_layers=0, max_position_embeddings=8192)
    out.update(over)
    return out


def model(sz, max_seq_len=512, seed=0, **cfg_over):
    cfg = GPTConfig(**{**ref.program_config(sz), **cfg_over},
                    max_seq_len=max_seq_len)
    params = unbox(GPTLogits(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # gains and the selection bias away from one / zero and the weights
    # large enough for attention to be sharp, so that a norm, a scale or a
    # bias left out would show
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 200))

    def shake(path, a):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "kv_norm" in name:
            return 1.0 + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "expert_bias" in name:
            return 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a * 8.0
    return cfg, jax.tree_util.tree_map_with_path(shake, params)


def engine(cfg, params, top=None, build=v2_engine, **sm):
    """``build=InferenceEngineV2``: a private engine, for a case that reads
    what its own traces log."""
    manager = {"max_tracked_sequences": 4, "max_ragged_sequence_count": 4,
               "max_ragged_batch_size": 128, "max_q_per_seq": 32,
               "kv_block_size": 16, "num_kv_blocks": 64, **sm}
    return build(cfg, {"dtype": "float32", **(top or {}),
                       "state_manager": manager}, params=params)


# ---------------------------------------------------- the model, both views

def test_flax_logits_match_the_reference():
    """Every expert is held, so this is the share test too: the module's
    layer is the reference's uncut layer."""
    sz = sizes()
    cfg, params = model(sz)
    assert cfg.mla and cfg.latent_dim == 136 and cfg.latent_page_dim == 256
    ids = np.random.default_rng(0).integers(0, V, size=40)
    got = jax.jit(GPTLogits(cfg).apply)(         # one program, not one an op
        {"params": params}, jnp.asarray(ids)[None])[0]
    want = ref.logits(params, ids, sz)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert n == count_params(cfg)


def _serve(eng, seqs, chunk, tail):
    """Prompts in ``chunk`` rows a step, the last ``tail`` rows one at a
    time (so short sequences ride the long ones' mixed steps as one-row
    slots): per sequence (logits of each step's last row, the rows)."""
    got, rows, pos = [[] for _ in seqs], [[] for _ in seqs], [0] * len(seqs)
    while any(pos[i] < len(s) for i, s in enumerate(seqs)):
        uids, toks = [], []
        for i, s in enumerate(seqs):
            if pos[i] >= len(s):
                continue
            left = len(s) - tail - pos[i]
            n = min(chunk, left) if left > 0 else 1
            uids.append(i + 1)
            toks.append(s[pos[i]:pos[i] + n])
            pos[i] += n
            rows[i].append(pos[i] - 1)
        for u, o in zip(uids, eng.put(uids, toks)):
            got[u - 1].append(o)
    return got, rows


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_engine_prefill_in_chunks_then_decode_through_the_latent_cache(impl):
    """Mixed steps (chunks of 32 rows beside one-row riders), then one token
    at a time, across page boundaries: every step's logits are the
    reference's full forward at that row.  ``pallas``: both kernels in their
    latent form, interpreted."""
    sz = sizes()
    cfg, params = model(sz, attn_impl=impl)
    eng = engine(cfg, params)
    assert eng.cache.v is None and eng.cache.k.shape == (3, 64, 1, 16, 256)
    assert eng.kv_bytes_per_token() == 3 * 256 * 4 and eng.kv_window is None
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, V, size=n) for n in (150, 23, 70)]
    got, rows = _serve(eng, seqs, chunk=32, tail=6)
    for s, g, r in zip(seqs, got, rows):
        want = np.asarray(ref.logits(params, s, sz, rows=r))
        np.testing.assert_allclose(np.stack(g), want, atol=3e-5)
    assert eng.telemetry.value("kv_bytes_per_token") == 3 * 256 * 4


def test_generate_matches_the_references_greedy_tokens():
    """``generate``: admission, SplitFuse chunks, fused decode bursts and a
    queue longer than the slots over the latent pool; token for token the
    reference's greedy continuation."""
    sz = sizes()
    cfg, params = model(sz)
    eng = engine(cfg, params, max_tracked_sequences=3,
                 max_ragged_sequence_count=3)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, V, size=n).astype(np.int32)
               for n in (40, 5, 19, 33, 11)]
    outs = eng.generate(prompts, max_new_tokens=20)
    for p, out in zip(prompts, outs):
        rows = list(range(len(p) - 1, len(p) + 19))
        want = np.asarray(ref.logits(params, np.concatenate([p, out]), sz,
                                     rows=rows))
        assert list(out) == want.argmax(-1).tolist()
    assert eng.state.allocator.free_blocks == 64
    tel = eng.telemetry
    assert tel.value("serving_dispatches_total", kind="burst") > 0
    assert (tel.value("moe_local_assignments_total")
            == tel.value("moe_assignments_total") > 0)    # every expert held


def test_absorbed_decode_is_the_expanded_form_on_the_same_cache():
    """One layer's decode attention read back from the engine's own pages:
    absorbed (what the layer body ``_layer`` does in every kind of step,
    here a decode step's: ``q_nope Wkvb_k^T`` against the latent row, ``s
    c`` carried through ``Wkvb_v``) against keys and values expanded from
    those pages a head."""
    sz = sizes()
    cfg, params = model(sz)
    eng = engine(cfg, params)
    ids = np.random.default_rng(3).integers(0, V, size=45)
    eng.put([1], [ids[:32]])
    eng.put([1], [ids[32:]])
    seq = eng.state.get(1)
    ap = params["backbone"]["block_1"]["Attention_0"]
    NB = eng.cache.k.shape[1]
    pages = eng.cache.k[1][np.asarray(seq.blocks)]          # [P, 1, bs, 256]
    rows = pages.reshape(-1, 256)[:45]
    c, k_pe = rows[:, :128], rows[:, 128:136]
    assert float(jnp.max(jnp.abs(rows[:, 136:]))) == 0.0    # the pad
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 32))
    q, *_ = v2model._mla_qkv(ap, h, jnp.asarray([44]), cfg)  # [1, 4, 256]
    table = jnp.asarray(seq.blocks, jnp.int32)[None] + NB
    flat = eng.cache.k.reshape((-1,) + eng.cache.k.shape[2:])
    from deepspeed_tpu import ops
    o_lat = ops.paged_attention(q.reshape(1, 1, 4, 256), flat, None, table,
                                jnp.asarray([45]), scale=24 ** -0.5,
                                v_dim=128, impl="xla")
    nope = 16
    got = jnp.einsum("nr,rnd->nd", o_lat[0, 0], ap["wkv_b"][..., nope:])
    # expanded, from the same rows
    kv = jnp.einsum("sr,rnd->snd", c, ap["wkv_b"])
    q_nope, q_pe = __import__("deepspeed_tpu.models.gpt", fromlist=["x"]) \
        .mla_query(ap["wq"], h, jnp.asarray([44]), cfg)
    s = (jnp.einsum("nd,snd->ns", q_nope[0], kv[..., :nope])
         + jnp.einsum("nd,sd->ns", q_pe[0], k_pe)) * 24 ** -0.5
    want = jnp.einsum("ns,snd->nd", jax.nn.softmax(s, -1), kv[..., nope:])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------ the kernels

@pytest.mark.parametrize("window", [None, 40])
def test_latent_kernels_at_576_over_512_match_the_xla_fallbacks(window):
    """Both Pallas kernels, interpreted, at the published page row (512 + 64
    padded to 640, value = its leading 512) with 16 query heads in one
    group, against the XLA fallbacks; dead pages are never read."""
    from deepspeed_tpu.ops.paged_attention import (
        _prefill_chunk, pallas_paged_attention, pallas_ragged_prefill,
        ragged_prefill_supported, supported, xla_paged_attention,
        xla_ragged_prefill)
    rng = np.random.default_rng(0)
    S, MB, bs, g, kd, vd = 4, 6, 8, 16, 640, 512
    NB = S * MB
    k = jnp.asarray(rng.standard_normal((NB, 1, bs, kd)),
                    jnp.float32).at[..., 576:].set(0)
    q = jnp.asarray(rng.standard_normal((S, 1, g, kd)),
                    jnp.float32).at[..., 576:].set(0)
    bt = jnp.asarray(rng.permutation(NB).reshape(S, MB), jnp.int32)
    lens = jnp.asarray([0, 5, 17, 48], jnp.int32)
    live = np.zeros(NB, bool)
    for s, n in enumerate(np.asarray(lens)):
        live[np.asarray(bt)[s, :-(-int(n) // bs)]] = True
    poisoned = jnp.where(jnp.asarray(~live)[:, None, None, None], jnp.nan, k)
    kw = dict(scale=192 ** -0.5, v_dim=vd, window=window)
    want = xla_paged_attention(q, k, None, bt, lens, **kw)
    got = pallas_paged_attention(q, poisoned, None, bt, lens, interpret=True,
                                 **kw)
    assert got.shape == (S, 1, g, vd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # token-major rows: the slots' spans one after the other (5, 16 and 9
    # rows; slot 0 holds none), then three rows no slot owns
    Q = 16
    counts = jnp.asarray([0, 5, 16, 9], jnp.int32)
    first = jnp.asarray([0, 0, 5, 21], jnp.int32)
    qq = jnp.asarray(rng.standard_normal((33, 1, g, kd)),
                     jnp.float32).at[..., 576:].set(0)
    want = xla_ragged_prefill(qq, k, None, bt, lens, lens - counts, counts,
                              first, max_q=Q, **kw)
    got = pallas_ragged_prefill(qq, poisoned, None, bt, lens, lens - counts,
                                counts, first, max_q=Q, interpret=True, **kw)
    assert got.shape == (33, 1, g, vd)
    np.testing.assert_allclose(
        np.asarray(got)[:30], np.asarray(want)[:30], atol=2e-5)
    # the registry's predicate: latent pages are Pallas's when the value is
    # whole lane tiles; a GQA call is what it was
    page = jax.ShapeDtypeStruct((NB, 1, 128, kd), jnp.bfloat16)
    args = (jax.ShapeDtypeStruct((S, 1, g, kd), jnp.bfloat16), page, None,
            bt, lens)
    assert supported(*args, v_dim=512) and not supported(*args, v_dim=500)
    assert not supported(*args) and not supported(*args[:2], page, bt, lens,
                                                  v_dim=512)
    assert ragged_prefill_supported(
        jax.ShapeDtypeStruct((S * 128, 1, g, kd), jnp.bfloat16), page, None,
        bt, lens, lens, lens, lens, max_q=128, v_dim=512)
    # 16 heads on a 512-wide value take a chunk of 32 rows; GQA keeps 128
    assert _prefill_chunk(1024, 16, 512) == 32
    assert _prefill_chunk(1024, 4, 128) == _prefill_chunk(1024, 6, 128) == 128


# ------------------------------------------------------------ planted faults

@pytest.mark.parametrize("fault", _mla_faults.FAULTS + (_mla_faults.CONTROL,))
def test_planted_faults_fail_the_comparison(fault):
    """The engine's logits against a reference with ONE thing wrong
    (``benchmark/reference/_mla_faults.py``; the last case is the control,
    its weights rounded to fp8) are far outside the 3e-5 the healthy
    comparison holds: the tests above would fail on each."""
    sz = sizes()
    cfg, params = model(sz)
    eng = engine(cfg, params)
    ids = np.random.default_rng(5).integers(0, V, size=50)
    eng.put([1], [ids[:32]])
    got = np.stack([eng.put([1], [ids[32:48]])[0],
                    eng.put([1], [ids[48:49]])[0]])
    healthy = np.asarray(ref.logits(params, ids[:49], sz, rows=[47, 48]))
    np.testing.assert_allclose(got, healthy, atol=3e-5)
    with _mla_faults.planted(fault, params, sz) as (bad_params, bad_sz):
        bad = np.asarray(ref.logits(bad_params, ids[:49], bad_sz,
                                    rows=[47, 48]))
    assert float(np.max(np.abs(got - bad))) > 1e-2, fault
    again = np.asarray(ref.logits(params, ids[:49], sz, rows=[47, 48]))
    np.testing.assert_array_equal(again, healthy)      # the fault is out


# ------------------------------------------------- what start-up refuses

DENSE = GPTConfig.llama(num_layers=2, hidden=32, heads=4, vocab_size=64,
                        max_seq_len=64, dtype=None)


@pytest.mark.parametrize("what,kw", [
    ("prefix", {"sm": {"prefix_cache": True}}),
    ("kv_quant", {"sm": {"kv_quant": "int8"}}),
    ("tp", {"top": {"tensor_parallel": {"tp_size": 2}}}),
    ("speculative", {"draft": True}),
    ("LoRA", {"top": {"adapters": {"enabled": True}}}),
])
def test_start_up_refuses_what_a_latent_pool_is_not_built_with(what, kw,
                                                               devices):
    cfg, _ = model(sizes(n_routed_experts=0, first_k_dense_replace=3)
                   if what == "tp" else sizes())
    config = {"dtype": "float32", **kw.get("top", {}),
              "state_manager": {"max_tracked_sequences": 4,
                                "kv_block_size": 16, **kw.get("sm", {})}}
    with pytest.raises(NotImplementedError, match="latent") as err:
        InferenceEngineV2(cfg, config,
                          draft_model=DENSE if kw.get("draft") else None)
    assert what in str(err.value)


# ------------------------------------------------- the published config

def _published():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "moonlight-16b-a3b-7l.json")) as f:
        cut = json.load(f)
    return cut, {**cut, "num_hidden_layers":
                 cut["published"]["num_hidden_layers"]}


def test_hf_deepseek_v3_config_counts_the_published_model():
    from deepspeed_tpu.checkpoint.hf import deepseek_v3_config
    cut, hf = _published()
    cfg = deepseek_v3_config(hf, max_seq_len=8192)
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.kv_lora_rank,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.mlp_dim,
            cfg.expert_dim, cfg.moe_shared_dim, cfg.num_experts, cfg.moe_k,
            cfg.vocab_size) == (2048, 16, 192, 512, 64, 128, 11264, 1408,
                                2816, 64, 6, 163840)
    assert (cfg.latent_dim, cfg.latent_page_dim) == (576, 640)
    assert [cfg.is_moe_layer(i) for i in range(3)] == [False, True, True]
    assert cfg.moe_route_scale == 2.446 and cfg.rope_theta == 50000.0
    assert 15.9e9 < count_params(cfg) < 16.0e9          # "16B-A3B"
    small = GPTConfig(**ref.program_config(cut), max_seq_len=7680)
    assert dataclasses.replace(cfg, num_layers=7, max_seq_len=7680,
                               dtype=small.dtype) == small
    assert 4.25e9 < count_params(small) < 4.28e9        # 8.53 GB in bf16


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("num_nextn_predict_layers", 1),
    ("rope_scaling", {"type": "yarn", "factor": 40})])
def test_hf_deepseek_v3_config_refuses_what_is_not_built(key, value):
    from deepspeed_tpu.checkpoint.hf import deepseek_v3_config
    with pytest.raises(NotImplementedError, match=key):
        deepseek_v3_config({**_published()[1], key: value})


def test_published_tensor_names_round_trip_to_the_references_logits():
    """A tiny random state dict under the published names, in torch's
    [out, in] layout with the rotary columns paired as published
    (interleaved): the loader's tree gives the reference's logits for the
    model those tensors are, and every published name is read."""
    from deepspeed_tpu.checkpoint.hf import (DEEPSEEK_V3_WEIGHT_NAMES,
                                             _deepseek_v3_tree,
                                             deepseek_v3_config)
    sz = sizes()
    cfg, params = model(sz)
    assert dataclasses.replace(
        deepseek_v3_config(sz, max_seq_len=512), dtype=cfg.dtype) == cfg
    nope, rot, rank = 16, 8, 128
    # halves -> interleaved: the inverse of the loader's permutation
    inv = np.argsort(np.concatenate([np.arange(0, rot, 2),
                                     np.arange(1, rot, 2)]))
    bb = params["backbone"]
    sd, read = {}, set()

    def put(name, a):
        sd[name] = np.asarray(a)
    put("model.embed_tokens.weight", bb["wte"])
    put("model.norm.weight", bb["final_norm"]["scale"])
    put("lm_head.weight", params["lm_head"].T)
    for i in range(3):
        blk, p = bb[f"block_{i}"], f"model.layers.{i}."
        a = blk["Attention_0"]
        put(p + "input_layernorm.weight", blk["Norm_0"]["scale"])
        put(p + "post_attention_layernorm.weight", blk["Norm_1"]["scale"])
        wq = np.asarray(a["wq"])
        wq = np.concatenate([wq[..., :nope], wq[..., nope:][..., inv]], -1)
        put(p + "self_attn.q_proj.weight", wq.reshape(32, -1).T)
        wa = np.asarray(a["wkv_a"])
        put(p + "self_attn.kv_a_proj_with_mqa.weight", np.concatenate(
            [wa[:, :rank], wa[:, rank:][:, inv]], -1).T)
        put(p + "self_attn.kv_a_layernorm.weight", a["kv_norm"])
        put(p + "self_attn.kv_b_proj.weight",
            np.asarray(a["wkv_b"]).reshape(rank, -1).T)
        put(p + "self_attn.o_proj.weight",
            np.asarray(a["wo"]).reshape(-1, 32).T)
        if "moe" in blk:
            m = blk["moe"]
            put(p + "mlp.gate.weight", m["gate"].T)
            put(p + "mlp.gate.e_score_correction_bias", m["expert_bias"])
            for e in range(8):
                for ours, theirs in (("wge", "gate_proj"), ("wi", "up_proj"),
                                     ("wo", "down_proj")):
                    put(f"{p}mlp.experts.{e}.{theirs}.weight", m[ours][e].T)
            for ours, theirs in (("shared_wg", "gate_proj"),
                                 ("shared_wi", "up_proj"),
                                 ("shared_wo", "down_proj")):
                put(f"{p}mlp.shared_experts.{theirs}.weight", m[ours].T)
        else:
            for ours, theirs in (("wg", "gate_proj"), ("wi", "up_proj"),
                                 ("wo", "down_proj")):
                put(f"{p}mlp.{theirs}.weight", blk["MLP_0"][ours].T)

    class Reader:
        def get(self, name):
            read.add(name)
            return sd[name]
    tree = _deepseek_v3_tree(Reader(), cfg)
    assert read == set(sd)
    # the table names every tensor the loader read, and no other
    import re
    patterns = [re.compile(re.escape(n).replace(r"\{i\}", r"\d+")
                           .replace(r"\{e\}", r"\d+") + "$")
                for n in DEEPSEEK_V3_WEIGHT_NAMES]
    assert all(any(p.match(n) for p in patterns) for n in sd)
    assert all(any(p.match(n) for n in sd) for p in patterns)
    ids = np.random.default_rng(6).integers(0, V, size=20)
    np.testing.assert_allclose(
        np.asarray(ref.logits(tree, ids, sz)),
        np.asarray(ref.logits(params, ids, sz)), atol=1e-6)
    # the permutation is rotate-half of the de-interleaved vector: rotating
    # neighbours as published, then permuting, is permuting, then rotating
    # halves
    x = np.random.default_rng(7).standard_normal((5, rot)).astype(np.float32)
    pos = jnp.arange(5)
    perm = np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2)])
    freq = 50000.0 ** (-np.arange(rot // 2) / (rot // 2))
    ang = np.arange(5)[:, None] * freq
    pairs = x.reshape(5, rot // 2, 2)
    inter = np.stack([pairs[..., 0] * np.cos(ang) - pairs[..., 1] * np.sin(ang),
                      pairs[..., 1] * np.cos(ang) + pairs[..., 0] * np.sin(ang)],
                     -1).reshape(5, rot)
    np.testing.assert_allclose(
        np.asarray(ref._rope(jnp.asarray(x[:, perm]), pos, 50000.0)),
        inter[:, perm], atol=1e-5)
