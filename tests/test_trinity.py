"""afmoe (Arcee Trinity) on the normal path, at tiny sizes on the CPU: the
flax module and the v2 engine against the benchmark's plain float32
reference (``benchmark/reference/_afmoe.py``, which imports nothing from the
program), the expert layer's share, the sigmoid router, the window page
group of the KV manager, and what start-up refuses."""

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
from deepspeed_tpu.inference.v2.ragged import DSStateManager
from deepspeed_tpu.models.gpt import GPTConfig, GPTLogits, count_params
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import sigmoid_topk
from deepspeed_tpu.parallel.metadata import unbox

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))
import _afmoe  # noqa: E402  (the benchmark's plain reference)

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def sizes(window=12, held=4, offset=4, router=16, **over):
    """A tiny afmoe configuration file: one dense layer and a period of
    expert layers (three window, one global), the published indices kept."""
    out = dict(
        model_type="afmoe", hidden_act="silu", hidden_size=32,
        intermediate_size=64, moe_intermediate_size=24,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_hidden_layers=5, num_dense_layers=1, layers_kept=[0, 8, 9, 10, 11],
        layer_types=PERIOD * 3, num_experts=held, router_width=router,
        expert_offset=offset, num_experts_per_tok=4, num_shared_experts=1,
        n_group=1, rms_norm_eps=1e-5, rope_theta=10000, route_norm=True,
        route_scale=2.448, score_func="sigmoid", mup_enabled=True,
        sliding_window=window, tie_word_embeddings=False, vocab_size=96)
    out.update(over)
    return out


def model(sz, max_seq_len=128, seed=0):
    cfg = GPTConfig(**_afmoe.program_config(sz), max_seq_len=max_seq_len)
    params = unbox(GPTLogits(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # gains and the selection bias away from their initial one / small
    # values, so that a norm or a bias left out would show
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 200))

    def shake(path, a):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "_norm" in name:
            return 1.0 + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "expert_bias" in name:
            return 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a
    return cfg, jax.tree_util.tree_map_with_path(shake, params)


def engine(cfg, params, build=v2_engine, **sm):
    """``build=InferenceEngineV2``: a private engine, for a case that reads
    what its own traces log."""
    manager = {"max_tracked_sequences": 4, "max_ragged_sequence_count": 4,
               "max_ragged_batch_size": 32, "max_q_per_seq": 8,
               "kv_block_size": 4, "num_kv_blocks": 64,
               "num_kv_window_blocks": 24, **sm}
    return build(cfg, {"dtype": "float32", "state_manager": manager},
                 params=params)


# ---------------------------------------------------- the model, both views

@pytest.mark.parametrize("held,offset,router", [(4, 4, 16), (16, 0, 16)],
                         ids=["share", "whole"])
def test_flax_logits_match_the_reference(held, offset, router):
    sz = sizes(held=held, offset=offset, router=router)
    cfg, params = model(sz)
    ids = np.random.default_rng(0).integers(0, 96, size=30)
    got = jax.jit(GPTLogits(cfg).apply)(         # one program, not one an op
        {"params": params}, jnp.asarray(ids)[None])[0]
    want = _afmoe.logits(params, ids, sz)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("window", [6, 12])
def test_engine_prefill_in_chunks_then_decode_across_released_pages(window):
    """Prompts go in 8 rows at a time, then one token at a time; contexts
    pass the tiny window, so the window group gives pages back and later
    rows are read across the released boundary.  Every step's logits are
    the reference's full forward at that row."""
    sz = sizes(window=window)
    cfg, params = model(sz)
    eng = engine(cfg, params)
    assert eng.kv_window == window
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, 96, size=n) for n in (50, 23)]
    got, rows, pos = [[], []], [[], []], [0, 0]
    while any(pos[i] < len(s) for i, s in enumerate(seqs)):
        uids, toks = [], []
        for i, s in enumerate(seqs):
            if pos[i] >= len(s):
                continue
            left = len(s) - 3 - pos[i]          # the last 3 rows decode
            n = min(8, left) if left > 0 else 1
            uids.append(i + 1)
            toks.append(s[pos[i]:pos[i] + n])
            pos[i] += n
            rows[i].append(pos[i] - 1)
        for u, o in zip(uids, eng.put(uids, toks)):
            got[u - 1].append(o)
    for i, s in enumerate(seqs):
        want = np.asarray(_afmoe.logits(params, s, sz, rows=rows[i]))
        np.testing.assert_allclose(np.stack(got[i]), want, atol=2e-5)
    long = eng.state.get(1)
    assert long.w_released > 0 and long.wblocks[0] == -1
    live = len(long.wblocks) - long.w_released
    assert live <= eng.state.window_ring(8)
    assert len(long.blocks) == -(-50 // 4)      # the global group keeps all


def test_generate_matches_the_references_greedy_tokens():
    """``generate``: admission, SplitFuse chunks, fused decode bursts and a
    queue longer than the slots, through both page groups; token for token
    the reference's greedy continuation (float32, no near-ties at this
    size)."""
    sz = sizes(window=12)
    cfg, params = model(sz)
    eng = engine(cfg, params, max_tracked_sequences=3,
                 max_ragged_sequence_count=3)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32)
               for n in (40, 5, 19, 33, 11)]
    outs = eng.generate(prompts, max_new_tokens=9)
    for p, out in zip(prompts, outs):
        # causal: one forward over prompt + answer scores every step
        rows = list(range(len(p) - 1, len(p) + 8))
        want = np.asarray(_afmoe.logits(
            params, np.concatenate([p, out]), sz, rows=rows))
        assert list(out) == want.argmax(-1).tolist()
    assert eng.state.wallocator.free_blocks == 24   # all given back
    assert eng.state.allocator.free_blocks == 64
    tel = eng.telemetry
    assert tel.value("moe_assignments_total") > 0
    share = (tel.value("moe_local_assignments_total")
             / tel.value("moe_assignments_total"))
    assert 0.1 < share < 0.45                        # 4 of 16 experts held
    assert tel.value("kv_pages_released_total", group="window") > 0


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_counters_follow_expert_layers_not_the_router(router):
    """Any model with expert layers gets the MoE counters, whichever router
    it has; one that holds every expert has every assignment local."""
    cfg = GPTConfig(num_layers=2, num_heads=2, head_dim=8, hidden_size=16,
                    vocab_size=64, max_seq_len=64, num_experts=4, moe_k=2,
                    moe_every=2, moe_dropless=True, moe_router=router,
                    gated_mlp=True, gate_act="silu", use_rmsnorm=True)
    params = unbox(GPTLogits(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    eng = v2_engine(cfg, {"dtype": "float32", "state_manager": {
        "max_tracked_sequences": 2, "max_ragged_batch_size": 16,
        "max_q_per_seq": 8, "kv_block_size": 4, "num_kv_blocks": 16}},
        params=params)
    eng.generate([np.arange(6, dtype=np.int32)], max_new_tokens=3)
    tel = eng.telemetry
    assert tel.value("moe_assignments_total") >= (6 + 2) * 2   # k of 2
    assert (tel.value("moe_local_assignments_total")
            == tel.value("moe_assignments_total"))
    assert 0 < tel.value("moe_experts_touched_total")


def test_step_programs_log_the_grouped_gemm():
    from deepspeed_tpu.ops.registry import dispatch_log, reset_dispatch_log
    cfg, params = model(sizes())
    reset_dispatch_log()
    # a private engine: the log is what its own traces wrote
    engine(cfg, params, build=InferenceEngineV2).put(
        [1], [np.arange(6, dtype=np.int32)])
    ops = {d["op"]: d["impl"] for d in dispatch_log()}
    assert ops.get("grouped_gemm") == "xla"


@pytest.mark.parametrize("family", ["trinity", "moonlight"])
def test_the_grouped_gemm_kernel_serves_the_same_tokens(family, monkeypatch):
    """The Pallas grouped GEMM, forced (it runs interpreted here) where
    ``inference/v2/model.py:_ffn`` leaves the choice to the registry: a
    prompt chunk each and three decode steps give the ``lax.ragged_dot``
    path's tokens and logits, and the dispatch log says which ran."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops.registry import dispatch_log, reset_dispatch_log
    if family == "trinity":
        cfg, params = model(sizes())
        make = engine
    else:
        import test_moonlight as moon
        cfg, params = moon.model(moon.sizes())
        make = moon.engine
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32) for n in (8, 5)]

    def serve():
        reset_dispatch_log()
        # private engines: the patch and the log are read when a step traces
        eng = make(cfg, params, build=InferenceEngineV2)
        steps = [np.stack(eng.put([1, 2], prompts))]
        for _ in range(3):
            nxt = steps[-1].argmax(-1).astype(np.int32)
            steps.append(np.stack(eng.put([1, 2], [nxt[:1], nxt[1:]])))
        took = {d["impl"] for d in dispatch_log()
                if d["op"] == "grouped_gemm"}
        return np.stack(steps), took
    want, took = serve()
    assert took == {"xla"}
    real = ops.grouped_gemm
    monkeypatch.setattr(
        ops, "grouped_gemm", lambda *a, impl=None, **kw: real(
            *a, impl="pallas" if impl is None else impl, **kw))
    got, took = serve()
    assert took == {"pallas"}
    assert (got.argmax(-1) == want.argmax(-1)).all()
    rel_rms = np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean())
    assert rel_rms < 2e-2, rel_rms


# ------------------------------------------------------------- the share

def _moe_module(sz, held, offset):
    return MoE(hidden_size=32, num_experts=16, k=4, mlp_dim=24, gated=True,
               dropless=True, router="sigmoid", route_scale=2.448,
               router_bias=True, shared_dim=24, experts_held=held,
               expert_offset=offset)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_eight_shares_and_the_shared_expert_once_add_up_to_the_layer(side):
    """The guide's share test: the routed parts that the shares give (here
    four shares of four experts, and eight of two), with what every chip
    computes alike, the shared expert, counted once, are the uncut layer."""
    sz = sizes(held=16, offset=0)
    cfg, params = model(sz)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 21, 32))
    blk = params["backbone"]["block_2"]
    if side == "program":
        whole = _moe_module(sz, None, 0)
        full, _ = whole.apply({"params": blk["moe"]}, x, deterministic=True)
        shared = (jax.nn.silu(x @ blk["moe"]["shared_wg"])
                  * (x @ blk["moe"]["shared_wi"])) @ blk["moe"]["shared_wo"]
        for n in (4, 8):
            held = 16 // n
            total = 0.0
            for c in range(n):
                lo = c * held
                mine = {k: (v[lo:lo + held] if k in ("wi", "wo", "wge")
                            else v) for k, v in blk["moe"].items()}
                part, _ = _moe_module(sz, held, lo).apply(
                    {"params": mine}, x, deterministic=True)
                total = total + (part - shared)
            np.testing.assert_allclose(np.asarray(total + shared),
                                       np.asarray(full), atol=2e-5)
    else:
        lp = _afmoe.tree(params)["layers"][2]
        kw = dict(eps=1e-5, theta=1e4, window=12, k=4, route_norm=True,
                  route_scale=2.448)
        xs = x[0]
        whole = _afmoe.layer(lp, xs, parts="routed", **kw)
        total = 0.0
        for c in range(8):
            mine = {k: (v[2 * c:2 * c + 2] if k.startswith("e_") else v)
                    for k, v in lp.items()}
            total = total + _afmoe.layer(mine, xs, parts="routed",
                                         offset=2 * c, **kw)
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                                   atol=2e-5)
        # and shared + routed, under the last norm, is the layer
        h = _afmoe._attention_half(lp, xs, 1e-5, 1e4, 12)
        f = whole + _afmoe.layer(lp, xs, parts="shared", **kw)
        np.testing.assert_allclose(
            np.asarray(h + _afmoe._rms(f, lp["n4"], 1e-5)),
            np.asarray(_afmoe.layer(lp, xs, **kw)), atol=2e-5)


# ------------------------------------------------------------- the router

@pytest.mark.parametrize("norm", [True, False])
def test_router_weights_come_from_the_scores_alone(norm):
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    idx, w = sigmoid_topk(logits, 4, None, norm, 2.448)
    s = jax.nn.sigmoid(logits)
    top = jnp.sort(s, -1)[:, -4:].sum(-1)
    if norm:
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.448, rtol=1e-5)
    else:
        np.testing.assert_allclose(np.asarray(w.sum(-1)),
                                   2.448 * np.asarray(top), rtol=1e-5)
    assert idx.dtype == jnp.int32 and idx.shape == (64, 4)


def test_router_bias_moves_the_selection_and_not_the_weights():
    logits = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    bias = jnp.zeros(16).at[3].set(5.0)           # expert 3 always chosen
    idx0, _ = sigmoid_topk(logits, 4, None, True, 1.0)
    idx, w = sigmoid_topk(logits, 4, bias, True, 1.0)
    assert bool(jnp.all(jnp.any(idx == 3, -1)))
    assert not bool(jnp.all(jnp.any(idx0 == 3, -1)))
    s = jax.nn.sigmoid(logits)
    chosen = jnp.take_along_axis(s, idx, -1)      # no bias in the weights
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(chosen / chosen.sum(-1, keepdims=True)),
        rtol=1e-5)


# ------------------------------------------------ the window page group

def manager(window=16, bs=4, nb=40, nbw=12, slots=4):
    return DSStateManager(slots, nb, bs, 128, window=window,
                          window_blocks=nbw)


def _advance(state, seq, n):
    state.ensure_blocks(seq, n)
    seq.seen_tokens += n


def test_window_group_holds_a_ring_whatever_the_length():
    state = manager()
    seq = state.create(1)
    ring = state.window_ring(8)                   # (16 + 8)/4 + 1 = 7
    assert ring == 7
    for _ in range(12):                           # 96 tokens, 8 at a time
        _advance(state, seq, 8)
        live = [b for b in seq.wblocks if b >= 0]
        assert len(live) <= ring
        assert len(live) == 12 - state.wallocator.free_blocks
    assert len(seq.blocks) == 24                  # the global group: all
    assert seq.w_released == len(seq.wblocks) - len(live)
    # what was given back is exactly what no query to come can see
    first_needed = (seq.seen_tokens - 16 + 1) // 4
    _advance(state, seq, 1)
    assert seq.w_released == first_needed
    assert state.w_released_total == seq.w_released


def test_window_pages_are_reused_by_later_sequences():
    state = manager(nbw=8)
    a = state.create(1)
    for _ in range(6):
        _advance(state, a, 4)                      # 24 tokens: 6 pages, 1 free
    first = [b for b in a.wblocks[:2]]
    assert first == [-1, -1] or a.w_released >= 1
    b = state.create(2)
    assert state.fits([(b, 8)])
    _advance(state, b, 8)
    assert set(b.wblocks) <= set(range(8))
    assert len(set(x for x in a.wblocks + b.wblocks if x >= 0)) == \
        8 - state.wallocator.free_blocks


def test_admission_counts_both_groups():
    state = manager(nb=40, nbw=6)
    a = state.create(1)
    _advance(state, a, 16)                         # 4 window pages of 6
    assert state.fits([(None, 8)])                 # 2 more fit
    assert not state.fits([(None, 12)])            # 3 do not
    assert not state.fits([(None, 8), (None, 4)])  # nor together
    tight = manager(nb=5, nbw=12)                  # the global group short
    b = tight.create(1)
    _advance(tight, b, 16)
    assert not tight.fits([(b, 8)]) and tight.fits([(b, 4)])
    # pages a step gives back first count as supply for that step
    _advance(state, a, 8)                          # seen 24: 6 pages held
    assert state.wallocator.free_blocks == 0
    assert state.fits([(a, 4)])                    # releases one, takes one


def test_preemption_by_recomputation_releases_both_groups():
    state = manager()
    seq = state.create(7)
    for _ in range(5):
        _advance(state, seq, 8)
    assert state.wallocator.free_blocks < 12
    state.flush(7)                                 # what preempt() calls
    assert state.wallocator.free_blocks == 12
    assert state.allocator.free_blocks == 40
    assert state.free_sequence_slots == 4


DENSE = GPTConfig.llama(num_layers=4, hidden=32, heads=4, vocab_size=64,
                        max_seq_len=64, dtype=None)


@pytest.mark.parametrize("cfg", [
    DENSE, dataclasses.replace(DENSE, sliding_window=8)],
    ids=["no-window", "every-layer-windowed"])
def test_a_model_whose_layers_are_all_alike_has_one_group(cfg):
    eng = engine(cfg, None)
    assert eng.kv_window is None and eng.state.wallocator is None
    assert eng._model_static == {}
    assert eng.cache.k.shape[0] == cfg.num_layers  # [L, NB, ...] as ever
    out = eng.put([1], [np.arange(6, dtype=np.int32)])
    assert out.shape == (1, 64) and eng.state.get(1).wblocks == []


MIXED = dataclasses.replace(DENSE, sliding_window=8, local_attn_layers=(0, 2))


@pytest.mark.parametrize("what,kw", [
    ("prefix_cache", {"sm": {"prefix_cache": True}}),
    ("kv_quant", {"sm": {"kv_quant": "int8"}}),
    ("tp mesh", {"top": {"tensor_parallel": {"tp_size": 2}}}),
    ("speculative decoding", {"draft": True}),
    ("LoRA adapter pages", {"top": {"adapters": {"enabled": True}}}),
])
def test_start_up_refuses_what_two_page_groups_are_not_built_with(
        what, kw, devices):
    config = {"dtype": "float32", **kw.get("top", {}),
              "state_manager": {"max_tracked_sequences": 4,
                                "kv_block_size": 4, **kw.get("sm", {})}}
    with pytest.raises(NotImplementedError, match="two\n? *page groups|"
                       "page group") as err:
        InferenceEngineV2(MIXED, config,
                          draft_model=DENSE if kw.get("draft") else None)
    assert what.split()[0] in str(err.value)


def test_a_window_pool_too_small_for_one_ring_is_refused():
    with pytest.raises(ValueError, match="ring"):
        engine(MIXED, None, num_kv_window_blocks=2)


# ------------------------------------------------- the published config

def test_hf_afmoe_config_counts_the_published_model():
    from deepspeed_tpu.checkpoint.hf import AFMOE_WEIGHT_NAMES, afmoe_config
    with open(os.path.join(REPO, "benchmark", "configs",
                           "trinity-large-preview-5l-ep8.json")) as f:
        cut = json.load(f)
    hf = {**cut, **cut["published"]}               # the uncut model
    cfg = afmoe_config(hf, max_seq_len=4096)
    kinds = [cfg.window_for_layer(i) for i in range(cfg.num_layers)]
    assert kinds.count(None) == 15 and kinds.count(4096) == 45
    assert all((w is None) == ((i + 1) % 4 == 0)
               for i, w in enumerate(kinds))
    assert [cfg.is_moe_layer(i) for i in range(8)] == [False] * 6 + [True] * 2
    assert not cfg.rope_for_layer(3) and cfg.rope_for_layer(2)
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.expert_dim, cfg.moe_shared_dim,
            cfg.num_experts, cfg.moe_k) == (3072, 48, 8, 128, 12288, 3072,
                                            3072, 256, 4)
    assert cfg.moe_route_scale == 2.448 and cfg.vocab_size == 200192
    assert 395e9 < count_params(cfg) < 405e9       # "400B-A13B"
    # the cut, as the benchmark's reference turns it into a GPTConfig,
    # holds the widths and an eighth of the experts and of the vocabulary
    small = GPTConfig(**_afmoe.program_config(cut), max_seq_len=12672)
    assert small.local_experts * 8 == cfg.num_experts
    assert small.vocab_size * 8 == cfg.vocab_size
    assert 4.2e9 < count_params(small) < 4.4e9     # 8.64 GB in bf16
    assert "model.layers.{i}.mlp.expert_bias" in AFMOE_WEIGHT_NAMES


def test_put_with_routes_agrees_with_the_references_routing():
    """Free routing in float32: the experts the engine's routers choose,
    prefill rows and decode rows, are the reference's, layer by layer."""
    sz = sizes(window=12)
    cfg, params = model(sz)
    eng = engine(cfg, params)
    ids = np.random.default_rng(3).integers(0, 96, size=15)
    _, (pre,) = eng.put([1], [ids[:8]], with_routes=True)
    _, (mid,) = eng.put([1], [ids[8:14]], with_routes=True)
    _, (dec,) = eng.put([1], [ids[14:]], with_routes=True)
    got = np.concatenate([pre, mid, dec], axis=1)        # [4 layers, 15, 4]
    want = _afmoe.routing(params, ids, sz)
    assert got.shape == (4, 15, 4)
    for layer, (chosen, margin) in enumerate(want):
        assert margin.shape == (15,) and float(margin.min()) > 1e-6
        np.testing.assert_array_equal(np.sort(got[layer], -1),
                                      np.sort(np.asarray(chosen), -1))
