"""dots3_note (dots3-note-prev) on the normal path, at tiny sizes on the CPU:
the flax module and the v2 engine (a latent pool a page group, an index-key
pool, index scores -> exact top-k -> attention over the selected rows on the
full layers; the two paged kernels in their latent form under a window on the
sliding ones) against the benchmark's plain float32 reference
(``benchmark/reference/_dots3_note.py``, which imports nothing from the
program, is NOT absorbed and selects by a mask over dense scores); the
selected index SETS; the eight shares; planted faults that must show; what
start-up refuses; the published tensor names."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import v2_engine

from deepspeed_tpu import ops
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2 import model as v2model
from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits, count_params
from deepspeed_tpu.parallel.metadata import unbox

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))
import _dots3_faults  # noqa: E402
import _dots3_note as ref  # noqa: E402  (the benchmark's plain reference)

V = 96
TOPK, WINDOW = 24, 9


def sizes(**over):
    """A tiny dots3_note configuration file: the published five-layer cut's
    pattern (dense full, full, sliding x 3), two latent geometries, an
    indexer whose top-k (24) binds from the 25th position on, a window (9)
    that wraps its ring of 16-token pages many times in 100 positions."""
    out = dict(
        model_type="dots3_note", hidden_act="silu", hidden_size=32,
        intermediate_size=64, moe_intermediate_size=24,
        num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, kv_lora_rank=128,
        q_lora_rank=40, index_topk=TOPK, index_n_heads=8,
        index_head_dim=128, swa_num_attention_heads=2,
        swa_num_key_value_heads=2, swa_qk_nope_head_dim=24,
        swa_qk_rope_head_dim=8, swa_v_head_dim=12, swa_kv_lora_rank=56,
        swa_q_lora_rank=40, swa_rope_theta=50000, sliding_window_size=WINDOW,
        attention_gate_type="headwise", swa_attention_gate_type="headwise",
        apply_mla_qkv_lora_rescale=True, num_hidden_layers=5,
        layer_types=["full_attention", "full_attention"]
        + ["sliding_attention"] * 3, first_k_dense_replace=1,
        moe_layer_freq=1, n_routed_experts=4, router_width=16,
        expert_offset=4, num_experts_per_tok=3, n_shared_experts=1,
        norm_topk_prob=True, routed_scaling_factor=1, topk_method="noaux_tc",
        scoring_func="sigmoid", rms_norm_eps=1e-5, rope_theta=80000000,
        rope_scaling=None, attention_bias=False, tie_word_embeddings=False,
        vocab_size=V, max_position_embeddings=32768)
    out.update(over)
    return out


_PARAMS = {}    # weights by sizes: a tree is made once a module run


def model(sz, max_seq_len=512, seed=0, **cfg_over):
    cfg = GPTConfig(**{**ref.program_config(sz), **cfg_over},
                    max_seq_len=max_seq_len)
    key = json.dumps(sz, sort_keys=True) + str(seed)
    if key in _PARAMS:
        return cfg, _PARAMS[key]
    params = unbox(jax.jit(GPTLogits(cfg).init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # gains and biases away from one / zero and the weights large enough
    # for attention and the index scores to be sharp, so that a norm, a
    # scale, a gate or a bias left out would show
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 400))

    def shake(path, a):
        name = jax.tree_util.keystr(path)
        if "norm_bias" in name or "expert_bias" in name:
            return 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim == 1:
            return 1.0 + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a * 8.0
    _PARAMS[key] = jax.tree_util.tree_map_with_path(shake, params)
    return cfg, _PARAMS[key]


def engine(cfg, params, top=None, build=v2_engine, **sm):
    """``build=InferenceEngineV2``: a private engine, for a case that reads
    what its own traces log."""
    manager = {"max_tracked_sequences": 4, "max_ragged_sequence_count": 4,
               "max_ragged_batch_size": 128, "max_q_per_seq": 32,
               "kv_block_size": 16, "num_kv_blocks": 64, **sm}
    return build(cfg, {"dtype": "float32", **(top or {}),
                       "state_manager": manager}, params=params)


def two_layers(**over):
    """The dense full layer and one sliding expert layer (published indices
    0 and 2): every mechanism once, for the tests that need no more."""
    return sizes(**{"num_hidden_layers": 2, "layers_kept": [0, 2], **over})


# ---------------------------------------------------- the model, both views

def test_config_has_a_geometry_a_kind_of_layer():
    cfg, params = model(sizes())
    full, slide = cfg.for_layer(0), cfg.for_layer(2)
    assert (full.num_heads, full.head_dim, full.kv_lora_rank,
            full.latent_page_dim, full.index_topk) == (4, 24, 128, 256, TOPK)
    assert (slide.num_heads, slide.head_dim, slide.kv_lora_rank,
            slide.latent_page_dim, slide.index_topk,
            slide.rope_theta) == (2, 32, 56, 128, 0, 50000.0)
    assert cfg.for_layer(1) is full and cfg.for_layer(4) is slide
    assert [cfg.window_for_layer(i) for i in range(5)] == [
        None, None, WINDOW, WINDOW, WINDOW]
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert n == count_params(cfg)
    with pytest.raises(ValueError, match="window_attn"):
        dataclasses.replace(cfg, window_attn=(("hidden_size", 8),)
                            ).for_layer(2)


def test_flax_logits_match_the_reference():
    """The uncached module (expanded, the selection a mask) is the
    reference."""
    sz = sizes()
    cfg, params = model(sz)
    ids = np.random.default_rng(0).integers(0, V, size=70)
    got = jax.jit(GPTLogits(cfg).apply)(         # one program, not one an op
        {"params": params}, jnp.asarray(ids)[None])[0]
    want = ref.logits(params, ids, sz)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


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
def test_engine_prefill_in_chunks_then_decode_through_the_pools(impl):
    """Mixed steps (chunks of 32 rows beside one-row riders), then one token
    at a time: the selection binds in prefill (from the first chunk's 25th
    row) and in decode, the window wraps its ring, and every step's logits
    are the reference's full forward at that row, to 1e-4 in float32.
    ``pallas``: the two paged kernels and the index-score kernel,
    interpreted."""
    sz = sizes()
    cfg, params = model(sz, attn_impl=impl)
    eng = engine(cfg, params)
    assert eng.cache.kw is not None and eng.cache.ki is not None
    assert eng.cache.k.shape[-1] == 256 and eng.cache.kw.shape[-1] == 128
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, V, size=n) for n in (100, 41, 7)]
    got, rows = _serve(eng, seqs, chunk=32, tail=6)
    for s, g, r in zip(seqs, got, rows):
        want = np.asarray(ref.logits(params, s, sz, rows=r))
        np.testing.assert_allclose(np.stack(g), want, atol=1e-4)
    # pages behind the window went back to their group
    assert eng.state.w_released_total > 0


def test_put_runs_a_long_prompt_as_chunks():
    """``put()`` with more tokens than a forward takes (the benchmark's
    comparison: 6,144 and 4,608 there) feeds them as chunks
    (``put_chunked``) and returns each sequence's last row."""
    sz = two_layers()
    cfg, params = model(sz)
    eng = engine(cfg, params)
    rng = np.random.default_rng(2)
    seqs = [rng.integers(0, V, size=n) for n in (150, 90)]
    out = eng.put([1, 2], seqs)
    for s, o in zip(seqs, out):
        want = np.asarray(ref.logits(params, s, sz, rows=[len(s) - 1]))[0]
        np.testing.assert_allclose(o, want, atol=1e-4)
    nxt = eng.put([1, 2], [np.asarray([3]), np.asarray([5])])
    want = np.asarray(ref.logits(params, np.append(seqs[0], 3), sz,
                                 rows=[150]))[0]
    np.testing.assert_allclose(nxt[0], want, atol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_programs_selected_sets_are_the_references(impl):
    """Layer 0's selection as the program makes it (its index queries, the
    index keys the ENGINE wrote into the index-key pool, op ``index_scores``
    -> op ``index_select``) against the reference's mask, row for row: the
    same SETS, for a prompt's rows and past them."""
    sz = two_layers()
    cfg, params = model(sz, attn_impl=impl)
    eng = engine(cfg, params)
    ids = np.random.default_rng(3).integers(0, V, size=90)
    _serve(eng, [ids], chunk=32, tail=4)
    want = np.asarray(ref.selections(params, ids, sz)[0])       # [T, T]
    lc = cfg.for_layer(0)
    blk = params["backbone"]["block_0"]
    pos = jnp.arange(len(ids), dtype=jnp.int32)
    h = v2model._norm(blk["Norm_0"], params["backbone"]["wte"][ids], cfg)
    *_, cq = v2model._mla_qkv(blk["Attention_0"], h, pos, lc)
    qi, wi, _ = v2model._index_rows(blk["Attention_0"], h, cq, pos, lc)
    seq = eng.state.get(1)
    table = jnp.asarray(np.asarray(seq.blocks, np.int32)[None])
    scores = ops.index_scores(
        qi, wi, eng.cache.ki.reshape((-1,) + eng.cache.ki.shape[2:]), table,
        jnp.zeros(len(ids), jnp.int32), pos, max_rows=len(ids), impl=impl)
    picked = np.asarray(ops.index_select(scores, TOPK))
    for t in range(len(ids)):
        mine = set(picked[t, :min(TOPK, t + 1)].tolist())
        assert mine == set(np.flatnonzero(want[t]).tolist()), t
    assert want[-1].sum() == TOPK and want[5].sum() == 6


def test_tokens_are_the_same_whichever_way_a_chunk_reads_its_keys(monkeypatch):
    """``ops.sparse_index.MASKED_REACH`` forced to 0 (every prompt chunk
    gathers its rows, as before PR 43) and to the table's width (every chunk
    takes the masked prefill kernel): the same tokens, the gathered run's
    logits still the reference's, and the counter and the spans say which
    way each mixed step went, by the program's own rule."""
    from deepspeed_tpu.ops import sparse_index
    sz = two_layers()
    cfg, params = model(sz)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, V, size=n) for n in (100, 41)]
    tokens, masked = {}, {}
    for reach in (0, 512):
        monkeypatch.setattr(sparse_index, "MASKED_REACH", reach)
        # a private engine: the patched reach is read when a step traces
        eng = InferenceEngineV2(
            cfg, {"dtype": "float32", "generation": {"do_sample": False},
                  "state_manager": {
                      "max_tracked_sequences": 4,
                      "max_ragged_sequence_count": 4,
                      "max_ragged_batch_size": 128, "max_q_per_seq": 32,
                      "kv_block_size": 16, "num_kv_blocks": 64}},
            params=params)
        tokens[reach] = [np.asarray(t) for t in eng.generate(
            prompts, max_new_tokens=6)]
        spans = [ev["args"] for ev in eng.telemetry.tracer.events
                 if ev["name"] == "mixed_dispatch"]
        assert [a["sel_reach"] for a in spans][:2] == [32, 64]
        masked[reach] = (
            eng.telemetry.value("serving_selected_masked_steps_total"),
            spans[-1]["sel_masked_steps"], len(spans))
        if not reach:
            got, rows = _serve(eng, [prompts[0]], chunk=32, tail=2)
            np.testing.assert_allclose(
                np.stack(got[0]), np.asarray(ref.logits(
                    params, prompts[0], sz, rows=rows[0])), atol=1e-4)
    for a, b in zip(tokens[0], tokens[512]):
        np.testing.assert_array_equal(a, b)
    assert masked[0][:2] == (0, 0)
    assert masked[512][0] == masked[512][1] == masked[512][2] > 0


def _primitives(jaxpr):
    """The names of every primitive in ``jaxpr``, its sub-programs'
    (branches, loop bodies, kernels) among them."""
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out |= _primitives(sub)
    return out


def test_only_the_branch_that_gathers_sorts():
    """A mixed step's selecting layer (PR 54): the branch that takes the
    masked prefill kernel gets its bits from ``threshold_mask`` and holds no
    sort; the list is sorted inside the branch that gathers it (and, outside
    the two, for the one-row slots, which gather)."""
    lc = model(two_layers())[0].for_layer(0)
    S, MB, bs, N, Q = 4, 8, 16, 64, 32
    nI, dI = lc.index_n_heads, lc.index_head_dim
    f32, i32 = jnp.float32, jnp.int32       # (256: the latent row, padded)
    pages = v2model._LayerPages(
        k=jnp.zeros((S * MB, 1, bs, 256), f32), v=None,
        ki=jnp.zeros((S * MB, 1, bs, dI), f32),
        table=jnp.zeros((S, MB), i32), k_scale=None, v_scale=None)
    rows = v2model._MixedRows(jnp.zeros((N,), i32), jnp.zeros((S,), i32),
                              jnp.zeros((S,), i32), jnp.zeros((S,), i32))
    jaxpr = jax.make_jaxpr(lambda q, qi, wi, pages, slot, pos, rows: (
        v2model._selected_attention(q, qi, wi, pages, slot, pos, lc,
                                    block_size=bs, max_rows=Q, rows=rows)))(
        jnp.zeros((N, lc.num_heads, 256), f32), jnp.zeros((N, nI, dI), f32),
        jnp.zeros((N, nI), f32), pages, jnp.zeros((N,), i32),
        jnp.zeros((N,), i32), rows).jaxpr
    sorts = {"sort", "top_k", "argsort"}
    outside = {e.primitive.name for e in jaxpr.eqns}
    assert "top_k" in outside                   # the one-row slots' lists
    (cond,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    listed, masked = (_primitives(b.jaxpr) for b in cond.params["branches"])
    assert not masked & sorts and "scan" in masked       # the counted passes
    assert "top_k" in listed and "gather" in listed


# sha256[:16] of the lowered mixed and decode step programs of a dense GQA
# model (Mistral's shape) and of a latent-attention MoE (Moonlight's), at tiny
# widths on ``conftest.lower_serving_steps``, taken on the parent of PR 54
# (2904d51): a configuration without ``index_topk`` never reaches
# ``_selected_attention``, so its programs lower to the text they lowered to.
# A later PR that changes what those programs ARE takes the hashes anew, on
# its own parent, and says so.  PR 56 took the latent pair anew: that model
# has expert layers, which permute by counts and combine by a gather since
# (``moe/layer.py:_expert_ffn_ragged``); the dense pair is 2904d51's still.
UNSELECTING_PROGRAMS = {
    ("gqa", "ragged_forward_sampled"): "aeec98ca9fc5a3f8",
    ("gqa", "ragged_decode_sampled"): "ce0f75f292b78f91",
    ("latent", "ragged_forward_sampled"): "7285e4f606f9c8a8",
    ("latent", "ragged_decode_sampled"): "191098ea5d7a97ec",
}


def unselecting_config(kind):
    if kind == "gqa":
        return dataclasses.replace(
            GPTConfig.llama(num_layers=2, hidden=64, heads=4, num_kv_heads=2,
                            vocab_size=128, max_seq_len=256, dtype=None),
            dtype=jnp.float32)
    return GPTConfig(
        num_layers=2, hidden_size=64, num_heads=4, head_dim=24,
        kv_lora_rank=128, qk_rope_head_dim=8, v_head_dim=16, use_rope=True,
        use_rmsnorm=True, gated_mlp=True, tie_embeddings=False,
        vocab_size=128, max_seq_len=256, mlp_dim_override=128, num_experts=4,
        moe_k=2, moe_dropless=True, moe_router="sigmoid",
        moe_router_bias=True, moe_shared_dim=32, moe_expert_dim=32,
        moe_dense_layers=1, dtype=jnp.float32)


def unselecting_hashes(kind):
    import hashlib

    from conftest import lower_serving_steps
    lowered = lower_serving_steps(
        unselecting_config(kind), jnp.float32, slots=4, tokens=64, max_q=16,
        table_width=8, block_size=16, num_pages=32, steps=4)[2]
    return {(kind, name): hashlib.sha256(
        lowered[name].as_text().encode()).hexdigest()[:16]
        for name in ("ragged_forward_sampled", "ragged_decode_sampled")}


@pytest.mark.parametrize("kind", ["gqa", "latent"])
def test_a_model_that_does_not_select_lowers_as_on_the_parent(kind):
    got = unselecting_hashes(kind)
    assert got == {k: v for k, v in UNSELECTING_PROGRAMS.items()
                   if k[0] == kind}


def test_exact_selection_breaks_ties_toward_the_lower_position():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 3.0, 0.5, 3.0]])
    assert sorted(np.asarray(ops.index_select(scores, 3))[0]) == [1, 2, 3]
    seen = jnp.ones((1, 6), bool)
    assert np.flatnonzero(np.asarray(ref.select(scores, seen, 3))[0]
                          ).tolist() == [1, 2, 3]


def test_selection_sorts_only_the_width_its_rows_can_reach():
    """``index_select(width=)`` sorts the narrowest power-of-two share of
    the columns that holds every finite score: the same lists as the whole
    sort, whichever branch runs."""
    rng = np.random.default_rng(7)
    scores = rng.normal(size=(5, 512)).astype(np.float32)
    for reach in (40, 64, 65, 200, 512):
        masked = jnp.asarray(np.where(np.arange(512) < reach, scores,
                                      -np.inf))
        whole = np.asarray(ops.index_select(masked, 32))
        part = np.asarray(jax.jit(lambda s, w: ops.index_select(
            s, 32, width=w))(masked, jnp.int32(reach)))
        np.testing.assert_array_equal(part, whole)


def test_one_table_width_for_every_step_program():
    """A model that selects its keys takes the table's whole width in every
    step: one mixed program a token width, whatever the contexts."""
    sz = two_layers()
    cfg, params = model(sz)
    eng = engine(cfg, params, max_q_per_seq=64)
    ids = np.random.default_rng(8).integers(0, V, size=150)
    got, rows = _serve(eng, [ids], chunk=64, tail=2)
    want = np.asarray(ref.logits(params, ids, sz, rows=rows[0]))
    np.testing.assert_allclose(np.stack(got[0]), want, atol=1e-4)
    # (engines of one configuration share their compiled steps: this one's
    # are those with its chunk of 64)
    assert {key[2] for key in eng._steps
            if key[0] == "mixed" and key[1] == 64} == {32}


def test_counters_say_what_was_scored_kept_and_what_dense_would_read():
    sz = sizes(num_hidden_layers=3, layers_kept=[0, 1, 2])
    cfg, params = model(sz)
    eng = engine(cfg, params)
    eng.generate([np.arange(60, dtype=np.int32) % V], max_new_tokens=9)
    tel = eng.telemetry
    causal = tel.value("serving_global_pairs_total")
    kept = tel.value("serving_selected_pairs_total")
    scored = tel.value("serving_index_pairs_total")
    # two full layers; rows 0..67: the prompt, then a burst of eight (the
    # last generated token is never fed)
    assert causal == 2 * sum(t + 1 for t in range(68))
    assert kept == 2 * sum(min(t + 1, TOPK) for t in range(68))
    assert scored == causal and kept < causal
    note = tel.counter_note(eng.state)
    assert (note["index_pairs"], note["sel_pairs"], note["global_pairs"]) \
        == (scored, kept, causal)
    assert note["index_bytes_per_token"] == 2 * 128 * 4
    assert note["kv_bytes_per_token_global"] == 2 * 256 * 4
    assert note["kv_bytes_per_token_window"] == 1 * 128 * 4
    assert eng.kv_bytes_per_token() == sum(
        note[k] for k in ("index_bytes_per_token",
                          "kv_bytes_per_token_global",
                          "kv_bytes_per_token_window"))


def test_a_table_no_wider_than_the_selection_takes_the_dense_kernels():
    """A model whose contexts cannot outgrow ``index_topk`` (``max_seq_len``
    64) reads every key through the existing kernels and scores nothing;
    the index keys are written all the same."""
    from deepspeed_tpu.ops.registry import dispatch_log, reset_dispatch_log
    sz = two_layers(index_topk=64)
    cfg, params = model(sz, max_seq_len=64)
    # a private engine: the log is what its own traces wrote
    eng = engine(cfg, params, build=InferenceEngineV2)
    ids = np.random.default_rng(4).integers(0, V, size=40)
    reset_dispatch_log()
    got, rows = _serve(eng, [ids], chunk=32, tail=0)       # 3 pages of 16
    assert "index_scores" not in {d["op"] for d in dispatch_log()}
    want = np.asarray(ref.logits(params, ids, sz, rows=rows[0]))
    np.testing.assert_allclose(np.stack(got[0]), want, atol=1e-4)
    assert float(jnp.abs(eng.cache.ki).sum()) > 0
    assert eng.telemetry.value("serving_index_pairs_total") == 0


# ------------------------------------------------------------- the share

def test_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test, on the reference the cell is compared with:
    the routed parts of eight shares of two experts, with the shared expert
    counted once, are the uncut layer's feed-forward."""
    sz = two_layers(n_routed_experts=16, expert_offset=0)
    cfg, params = model(sz)
    lp = ref.tree(params)["layers"][1]
    kw = dict(eps=1e-5, g=ref.geometry(sz, "sliding"), k=3, norm_topk=True,
              scale=1.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (21, 32))
    whole = ref.layer(lp, x, parts="routed", **kw)
    total = 0.0
    for c in range(8):
        mine = {k: (v[2 * c:2 * c + 2] if k.startswith("e_") else v)
                for k, v in lp.items()}
        total = total + ref.layer(mine, x, parts="routed", offset=2 * c,
                                  **kw)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)
    h = ref._attention_half(lp, x, 1e-5, kw["g"])
    f = whole + ref.layer(lp, x, parts="shared", **kw)
    np.testing.assert_allclose(np.asarray(h + f),
                               np.asarray(ref.layer(lp, x, **kw)), atol=2e-5)
    # and the program's layer with a share of two is the reference's
    sz2 = two_layers(n_routed_experts=2, expert_offset=6)
    cfg2, params2 = model(sz2)
    ids = np.random.default_rng(6).integers(0, V, size=30)
    got = jax.jit(GPTLogits(cfg2).apply)(
        {"params": params2}, jnp.asarray(ids)[None])
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(ref.logits(params2, ids, sz2)),
                               atol=1e-4)


# ------------------------------------------------------------ planted faults

@pytest.mark.parametrize("fault",
                         _dots3_faults.FAULTS + (_dots3_faults.CONTROL,))
def test_planted_faults_fail_the_comparison(fault):
    """The engine's logits against a reference with ONE thing wrong
    (``benchmark/reference/_dots3_faults.py``; the last case is the control,
    its weights rounded to fp8) are far outside the 1e-4 the healthy
    comparison holds: the tests above would fail on each."""
    sz = two_layers()
    cfg, params = model(sz)
    eng = engine(cfg, params)
    ids = np.random.default_rng(5).integers(0, V, size=60)
    eng.put([1], [ids[:32]])
    got = np.stack([eng.put([1], [ids[32:58]])[0],
                    eng.put([1], [ids[58:59]])[0]])
    healthy = np.asarray(ref.logits(params, ids[:59], sz, rows=[57, 58]))
    np.testing.assert_allclose(got, healthy, atol=1e-4)
    with _dots3_faults.planted(fault, params, sz) as (bad_params, bad_sz):
        bad = np.asarray(ref.logits(bad_params, ids[:59], bad_sz,
                                    rows=[57, 58]))
    assert float(np.max(np.abs(got - bad))) > 1e-2, fault
    again = np.asarray(ref.logits(params, ids[:59], sz, rows=[57, 58]))
    np.testing.assert_array_equal(again, healthy)      # the fault is out


# ------------------------------------------------- what start-up refuses

DENSE = GPTConfig.llama(num_layers=2, hidden=32, heads=4, vocab_size=64,
                        max_seq_len=64, dtype=None)


@pytest.mark.parametrize("what,why,kw", [
    ("prefix", "index keys", {"sm": {"prefix_cache": True}}),
    ("kv_quant", "selection", {"sm": {"kv_quant": "int8"}}),
    ("tp", "one selection", {"top": {"tensor_parallel": {"tp_size": 2}}}),
    ("speculative", "own selection", {"draft": True}),
    ("LoRA", "latent form", {"top": {"adapters": {"enabled": True}}}),
    ("index_topk", "holds no index keys", {"layers": [0, 1]}),
    ("index_topk", "nothing to select", {"layers": [2, 3]}),
])
def test_start_up_refuses_what_this_model_cannot_sit_beside(what, why, kw,
                                                            devices):
    """Each refusal names what it refuses and the reason."""
    cfg, params = model(two_layers(n_routed_experts=0, router_width=0,
                                   first_k_dense_replace=5)
                        if what == "tp" else
                        two_layers(layers_kept=kw.get("layers", [0, 2])))
    config = {"dtype": "float32", **kw.get("top", {}),
              "state_manager": {"max_tracked_sequences": 4,
                                "kv_block_size": 16, **kw.get("sm", {})}}
    with pytest.raises(NotImplementedError, match="latent") as err:
        InferenceEngineV2(cfg, config, params=params,
                          draft_model=DENSE if kw.get("draft") else None)
    assert what in str(err.value) and why in str(err.value)


def test_the_flax_module_refuses_what_it_does_not_build():
    cfg, params = model(sizes())
    lm = GPTLogits(dataclasses.replace(cfg, attn_gate=True))
    with pytest.raises(NotImplementedError, match="attn_gate_headwise"):
        lm.apply({"params": params}, jnp.zeros((1, 4), jnp.int32))
    lm = GPTLogits(dataclasses.replace(cfg, q_lora_rank=0, window_attn=()))
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


# ------------------------------------------------- the published config

def _published():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "dots3-note-prev-5l-ep8.json")) as f:
        cut = json.load(f)
    return cut, {**cut, **{k: cut["published"][k] for k in (
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings")}}


def test_hf_dots3_note_config_counts_the_published_model():
    from deepspeed_tpu.checkpoint.hf import dots3_note_config
    cut, hf = _published()
    cfg = dots3_note_config(hf, max_seq_len=32768)
    full, slide = cfg.for_layer(0), cfg.for_layer(2)
    assert (full.num_heads, full.head_dim, full.v_head_dim, full.kv_lora_rank,
            full.q_lora_rank, full.latent_page_dim, full.index_topk,
            full.rope_theta) == (128, 192, 128, 512, 1024, 640, 2048, 8e7)
    assert (slide.num_heads, slide.head_dim, slide.v_head_dim,
            slide.kv_lora_rank, slide.q_lora_rank, slide.latent_page_dim,
            slide.index_topk, slide.rope_theta) == (
                64, 256, 128, 1024, 1024, 1152, 0, 50000.0)
    assert sum(cfg.window_for_layer(i) == 513 for i in range(46)) == 33
    assert 279e9 < count_params(cfg) < 280e9    # the language model alone
    small = GPTConfig(**ref.program_config(cut), max_seq_len=32768)
    assert dataclasses.replace(
        cfg, num_layers=5, vocab_size=19008, experts_held=32,
        expert_offset=32, local_attn_layers=(2, 3, 4),
        dtype=small.dtype) == small
    assert 4.08e9 < count_params(small) < 4.09e9        # 8.17 GB in bf16


def test_hf_configs_accept_a_query_latent_and_refuse_another_gate():
    from deepspeed_tpu.checkpoint.hf import (deepseek_v3_config,
                                             dots3_note_config)
    _, hf = _published()
    assert deepseek_v3_config(hf).q_lora_rank == 1024
    with pytest.raises(NotImplementedError, match="attention_gate_type"):
        dots3_note_config({**hf, "attention_gate_type": "elementwise"})


def test_published_tensor_names_round_trip_to_the_references_logits():
    """A tiny random state dict under the published names, in torch's
    [out, in] layout with the rotary columns of the attention paired as
    published (interleaved; the indexer's are by halves already): the
    loader's tree gives the reference's logits for the model those tensors
    are, and every name of the table is read."""
    import re
    from deepspeed_tpu.checkpoint.hf import (DOTS3_NOTE_WEIGHT_NAMES,
                                             _deepseek_v3_tree,
                                             dots3_note_config)
    sz = two_layers(n_routed_experts=16, router_width=16, expert_offset=0,
                    layer_types=["full_attention", "sliding_attention"],
                    layers_kept=None)
    cfg, params = model(sz)
    assert dataclasses.replace(
        dots3_note_config(sz, max_seq_len=512), dtype=cfg.dtype) == cfg
    rot = 8
    # halves -> interleaved: the inverse of the loader's permutation
    inv = np.argsort(np.concatenate([np.arange(0, rot, 2),
                                     np.arange(1, rot, 2)]))
    bb = params["backbone"]
    sd, read = {}, set()

    def put(name, a):
        sd[name] = np.asarray(a)

    def t2(a):                  # [in, ...] -> torch's [out, in]
        a = np.asarray(a)
        return a.reshape(a.shape[0], -1).T
    put("model.embed_tokens.weight", bb["wte"])
    put("model.norm.weight", bb["final_norm"]["scale"])
    put("lm_head.weight", params["lm_head"].T)
    for i in range(2):
        blk, p = bb[f"block_{i}"], f"model.layers.{i}."
        a, lc = blk["Attention_0"], cfg.for_layer(i)
        nope, rank = lc.head_dim - rot, lc.kv_lora_rank
        put(p + "input_layernorm.weight", blk["Norm_0"]["scale"])
        put(p + "post_attention_layernorm.weight", blk["Norm_1"]["scale"])
        put(p + "self_attn.q_a_proj.weight", t2(a["wq_a"]))
        put(p + "self_attn.q_a_layernorm.weight", a["q_norm"])
        wq = np.asarray(a["wq_b"])
        put(p + "self_attn.q_b_proj.weight", t2(np.concatenate(
            [wq[..., :nope], wq[..., nope:][..., inv]], -1)))
        wa = np.asarray(a["wkv_a"])
        put(p + "self_attn.kv_a_proj_with_mqa.weight", np.concatenate(
            [wa[:, :rank], wa[:, rank:][:, inv]], -1).T)
        put(p + "self_attn.kv_a_layernorm.weight", a["kv_norm"])
        put(p + "self_attn.kv_b_proj.weight", t2(a["wkv_b"]))
        put(p + "self_attn.o_proj.weight",
            np.asarray(a["wo"]).reshape(-1, 32).T)
        put(p + "self_attn.g_proj.weight", t2(a["wgate"]))
        if "wq_idx" in a:
            x = p + "self_attn.indexer."
            put(x + "wq_b.weight", t2(a["wq_idx"]))
            put(x + "wk.weight", t2(a["wk_idx"]))
            put(x + "k_norm.weight", a["k_idx_norm_scale"])
            put(x + "k_norm.bias", a["k_idx_norm_bias"])
            put(x + "weights_proj.weight", t2(a["ww_idx"]))
        if "moe" in blk:
            m = blk["moe"]
            put(p + "mlp.gate.weight", m["gate"].T)
            put(p + "mlp.gate.e_score_correction_bias", m["expert_bias"])
            for e in range(16):
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
    patterns = [re.compile(re.escape(n).replace(r"\{i\}", r"\d+")
                           .replace(r"\{e\}", r"\d+") + "$")
                for n in DOTS3_NOTE_WEIGHT_NAMES]
    assert all(any(p.match(n) for p in patterns) for n in sd)
    assert all(any(p.match(n) for n in sd) for p in patterns)
    ids = np.random.default_rng(6).integers(0, V, size=40)
    np.testing.assert_allclose(
        np.asarray(ref.logits(tree, ids, sz)),
        np.asarray(ref.logits(params, ids, sz)), atol=1e-5)


# ------------------------------------- what the benchmark's readers take

@pytest.fixture(scope="module")
def dispatch_spans():
    """The dispatch spans of a short ``generate()`` (two prompts, so that a
    mixed step carries a riding decode row), from the tracer's buffer."""
    sz = two_layers()
    cfg, params = model(sz)
    eng = engine(cfg, params, top={"generation": {"do_sample": False}})
    rng = np.random.default_rng(9)
    eng.generate([rng.integers(0, V, size=n) for n in (70, 20)],
                 max_new_tokens=9)
    return [ev for ev in eng.telemetry.tracer.events
            if ev["name"].endswith("_dispatch")]


@pytest.mark.parametrize("span,arg", [
    (kind, arg) for kind in ("mixed_dispatch", "burst_dispatch")
    for arg in ("index_pairs", "sel_pairs", "global_pairs",
                "sel_masked_steps", "index_pairs_step", "sel_pairs_step",
                "kv_bytes_per_token_global", "kv_bytes_per_token_window",
                "index_bytes_per_token", "ctx_tokens_window")]
    + [("mixed_dispatch", arg) for arg in (
        "sel_pairs_one_row", "ctx_tokens_window_one_row", "qk_pairs_window",
        "one_row_slots", "sel_reach")])
def test_dispatch_span_carries(dispatch_spans, span, arg):
    """Every argument ``benchmark/readers/sparse.py`` and the ``.sparse``
    metrics take from a dispatch span, by name."""
    got = [ev for ev in dispatch_spans if ev["name"] == span]
    assert got, sorted({ev["name"] for ev in dispatch_spans})
    assert all(arg in ev["args"] for ev in got), (span, arg)
    values = [float(ev["args"][arg]) for ev in got]
    assert min(values) >= 0
    if arg in ("index_pairs", "sel_pairs", "global_pairs"):
        assert values == sorted(values) and values[-1] > 0  # running totals
