"""MiMo-V2-Flash's mechanisms through the serving engine
(``InferenceEngineV2``: ``put()``, ``put_chunked``, ``generate()``) against
the plain reference's full forward (``benchmark/reference/_mimo_v2.py``), at
tiny sizes: prefill in chunks, one-row decode and fused bursts through a pool
a page group (the full layers' 2 kv heads beside the window layers' 4, keys 24
wide and values 16) over several turns of the window group's ring, with the
sink on the window layers; a window layer never holds more than its ring;
pages released inside decode bursts; the gauge's parts; what start-up
refuses.  Logits are compared wherever a path returns them; ``generate()``
returns tokens, which one reference pass over prompt and continuation
checks.

Tolerance: float32 on the CPU, so a difference is summation order: 2e-4
absolute on logits of order 1.  The interpreted Pallas kernels run in one
test (pages of 128, so a window of 128: the rehearsal preset's shape of the
timed configuration); the others take the XLA forms at pages of 16."""

import dataclasses

import numpy as np
import pytest
from mimo_tiny import (SIZES, STATE_MANAGER, TOL, cfg, engine,  # noqa: F401
                       make_cfg, make_params, params, ref, seqs, want)

from deepspeed_tpu.inference.v2 import InferenceEngineV2


@pytest.fixture(scope="module")
def steps():
    return {}                  # the engines' shared compiled step programs


def decode_rows(eng, uids, seqs, n_dec):
    """The runner's procedure: prompts through ``put()``, then ``n_dec``
    positions one at a time; each sequence's logits rows."""
    got = [[] for _ in seqs]

    def feed(toks):
        for i, row in enumerate(eng.put(uids, toks)):
            got[i].append(row)
        # a window layer never holds more than its ring
        ring = eng.state.window_ring(max(len(t) for t in toks))
        for uid in uids:
            seq = eng.state.get(uid)
            assert len(seq.wblocks) - seq.w_released <= ring
    feed([s[:len(s) - n_dec] for s in seqs])
    for j in range(n_dec):
        feed([s[len(s) - n_dec + j:len(s) - n_dec + j + 1] for s in seqs])
    return [np.stack(g) for g in got]


def test_prefill_in_chunks_then_decode_over_ring_turns(cfg, params, steps,
                                                       seqs, want):
    """A prompt of 75 rows at 32 a forward (``put_chunked``: the second and
    third chunks start past the 16-key window and past page 0) beside one of
    23; then 40 one-row steps: each window layer's ring turns twice more,
    pages behind the table released while the sequences decode."""
    eng = engine(cfg, params, steps)
    got = decode_rows(eng, [1, 2], seqs, 40)
    for g, w, s in zip(got, want, seqs):
        np.testing.assert_allclose(g, w[len(s) - 41:], atol=TOL)
    st = eng.state
    # 115 and 63 positions in pages of 16 behind a window of 16: all but
    # the last page or two of each sequence were given back
    assert st.w_allocated_total == 8 + 4
    assert st.w_released_total == 6 + 2
    assert st.w_released_decode_total == 0          # put() is no burst
    assert eng.cache.kw.shape == (1, 2 * 24, 4, 24, 16)   # 4 rings of 6
    assert eng.cache.vw.shape == (1, 2 * 24, 4, 16, 16)
    assert eng.cache.k.shape == (1, 2 * 64, 2, 24, 16)
    assert eng.cache.v.shape == (1, 2 * 64, 2, 16, 16)


def test_the_interpreted_kernels_through_the_engine(steps):
    """The Pallas kernels (interpreted) on both page groups: pages of 128
    and a window of 128, ONE page; a prompt of 300 rows fed 64 at a time, so
    that chunks start past the window and past page 0, then 90 one-row steps
    across the page edge at 384, the first page released behind the
    table."""
    sizes = {**SIZES, "sliding_window": 128, "max_position_embeddings": 512}
    c = make_cfg(sizes, max_seq_len=512, attn_impl="pallas")
    p = make_params(c)
    rng = np.random.default_rng(2)
    s = rng.integers(0, 128, size=390).astype(np.int32)
    eng = engine(c, p, None, kv_block_size=128, num_kv_blocks=16,
                 max_q_per_seq=64)
    assert eng.paged_impl == "pallas" and eng.state.block_size == 128
    got = decode_rows(eng, [1], [s], 90)[0]
    w = np.asarray(ref.logits(p, s, sizes, rows=list(range(299, 390))))
    np.testing.assert_allclose(got, w, atol=TOL)
    assert eng.state.w_released_total == 2
    from deepspeed_tpu.ops.registry import dispatch_log
    took = {(d["op"], d["impl"]) for d in dispatch_log()
            if d["op"] in ("paged_attention", "ragged_prefill_attention",
                           "paged_kv_append")}
    assert {("paged_attention", "pallas"),
            ("ragged_prefill_attention", "pallas")} <= took


def test_a_one_row_rider_beside_a_prompt_chunk(cfg, params, steps, seqs,
                                               want):
    """One mixed step holds a decoding sequence's single row (the paged
    decode kernel's) and another sequence's prompt chunk, on both groups."""
    eng = engine(cfg, params, steps)
    a, b = seqs[1], seqs[0][:30]
    eng.put([1], [a[:20]])
    rows = [eng.put([1, 2], [a[20:21], b[:20]]),
            eng.put([1, 2], [a[21:22], b[20:30]])]
    np.testing.assert_allclose(rows[0][0], want[1][20], atol=TOL)
    np.testing.assert_allclose(rows[1][0], want[1][21], atol=TOL)
    np.testing.assert_allclose(rows[1][1], want[0][29], atol=TOL)


def assert_greedy(params, prompt, out):
    ids = np.concatenate([prompt, out])
    rows = list(range(len(prompt) - 1, len(ids) - 1))
    best = np.asarray(ref.logits(params, ids, SIZES, rows=rows)).argmax(-1)
    np.testing.assert_array_equal(out, best)


@pytest.fixture(scope="module")
def generated(cfg, params, steps, seqs):
    prompts = [seqs[0][:40], seqs[1][:23]]
    eng = engine(cfg, params, steps)
    return eng, prompts, eng.generate(prompts, max_new_tokens=70)


def test_generate_releases_window_pages_inside_bursts(params, generated):
    """``generate()``: 70 tokens a request in fused bursts; with a window of
    one page of 16 every ring turns four times while its sequence only
    decodes, and what it gives back then is counted as released in
    decode."""
    eng, prompts, outs = generated
    for p, o in zip(prompts, outs):
        assert len(o) == 70
        assert_greedy(params, p, o)
    st, t = eng.state, eng.telemetry
    assert t.value("serving_dispatches_total", kind="burst") > 0
    assert st.w_released_decode_total >= 6
    assert st.w_released_decode_total <= st.w_released_total \
        < st.w_allocated_total
    assert t.value("kv_pages_released_in_decode_total", group="window") \
        == st.w_released_decode_total
    assert not st.tracked and st.wallocator.free_blocks == 24


def test_dispatch_spans_carry_the_groups_and_the_ring(generated):
    """What ``benchmark/readers/swa_rooflines.py``, ``swa_spans.py`` and
    ``span_counters.py`` take from the dispatch spans."""
    eng, _, _ = generated
    events = [ev for ev in eng.telemetry.tracer.events
              if ev["name"].endswith("_dispatch")]
    assert {ev["name"] for ev in events} >= {"mixed_dispatch",
                                             "burst_dispatch"}
    for ev in events:
        a = ev["args"]
        assert a["kv_bytes_per_token_global"] == 2 * 2 * 40 * 4
        assert a["kv_bytes_per_token_window"] == 2 * 4 * 40 * 4
        assert a["kv_bytes_per_token"] == 3 * 2 * 40 * 4 * 2
        assert 0 <= a["kvw_released_decode"] <= a["kvw_released"] \
            <= a["kvw_allocated"]
        assert a["ctx_tokens_window"] <= a["ctx_tokens"]
        if ev["name"] == "mixed_dispatch":
            assert a["qk_pairs_window"] <= a["qk_pairs"]
    assert events[-1]["args"]["kvw_released_decode"] > 0


def test_scopes_part_the_window_layers_attention_from_the_full(cfg, params):
    """``attn_window`` / ``attn_global`` round ``attn_kernel`` in both step
    programs of a model with two page groups (what
    ``benchmark/readers/swa_scope_time.py`` reads), and the decode
    program's kernels under both."""
    from lowering_hashes import record_programs
    eng = engine(cfg, params, None)
    seen = record_programs(eng)
    eng.put([1], [np.arange(40, dtype=np.int32)])
    eng.put([1], [np.array([3], np.int32)])
    kinds = {key if isinstance(key, str) else key[0] for key in seen}
    assert kinds == {"mixed", "decode"}
    for fn, args in seen.values():
        text = fn.lower(*args).as_text(debug_info=True)
        for scope in ("attn_window", "attn_global"):
            assert f"{scope}/" in text and "attn_kernel" in text, scope


def test_kv_bytes_per_token_from_each_groups_own_geometry(cfg, params, steps):
    eng = engine(cfg, params, steps)
    # float32: 2 full layers x 2 kv heads x (24 + 16), 2 window x 4 x 40
    assert eng.kv_bytes_by_group() == {"kv_bytes_per_token_global": 640,
                                       "kv_bytes_per_token_window": 1280}
    assert eng.kv_bytes_per_token() == 1920
    assert eng.kv_block_bytes() == 16 * 1920
    t = eng.telemetry
    assert t.value("kv_bytes_per_token") == 1920
    assert t.value("kv_bytes_per_token",
                   part="kv_bytes_per_token_window") == 1280
    # the sink stays float32 through the engine's cast
    bf16 = engine(cfg, params, None, dtype="bfloat16")
    a1 = bf16.params["backbone"]["block_1"]["Attention_0"]
    assert str(a1["sink"].dtype) == "float32"
    assert str(a1["wq"].dtype) == "bfloat16"


def test_a_model_whose_groups_are_alike_keeps_one_flat_pool(steps):
    """Window and full layers of ONE geometry and equal widths (Trinity's
    shape): ``create_grouped``'s flat array as ever, no ``kw`` / ``vw``."""
    sizes = {**SIZES, "swa_num_key_value_heads": 2, "v_head_dim": 24,
             "swa_v_head_dim": 24, "add_swa_attention_sink_bias": False}
    eng = engine(make_cfg(sizes), None, None)
    c = eng.cache
    assert c.kw is None and c.vw is None
    assert c.k.shape == c.v.shape == (1, 2 * 64 + 2 * 24, 2, 24, 16)
    assert eng.kv_bytes_by_group() == {}


@pytest.mark.parametrize("what,config,kw", [
    ("speculative decoding", {}, {"draft": True}),
    ("tp mesh", {"tensor_parallel": {"tp_size": 2}}, {}),
    ("LoRA adapter pages", {"adapters": {"enabled": True}}, {}),
    ("kv_quant", {"state_manager": {"kv_quant": "int8"}}, {}),
    ("prefix cache", {"state_manager": {"prefix_cache": True}},
     {"one_group": True})])
def test_start_up_refuses_what_is_not_built(cfg, what, config, kw):
    # (without experts: MoE serving refuses a tp mesh before these are
    # reached; one page group: a window group refuses the prefix cache
    # itself)
    over = dict(num_experts=0)
    if kw.get("one_group"):
        over.update(sliding_window=None, local_attn_layers=(),
                    window_attn=(), attn_sink="all")
    cfg = dataclasses.replace(cfg, **over)
    conf = {"dtype": "float32", **config,
            "state_manager": {**STATE_MANAGER,
                              **config.get("state_manager", {})}}
    extra = {"draft_model": cfg} if kw.get("draft") else {}
    with pytest.raises(NotImplementedError,
                       match=f"per-head sink.*value width.*{what}"):
        InferenceEngineV2(cfg, conf, **extra)


def test_a_window_group_refuses_the_prefix_cache(cfg):
    with pytest.raises(NotImplementedError, match="prefix_cache"):
        InferenceEngineV2(cfg, {"dtype": "float32", "state_manager": {
            **STATE_MANAGER, "prefix_cache": True}})
