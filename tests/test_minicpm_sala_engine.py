"""MiniCPM-SALA through the serving engine (``InferenceEngineV2``: ``put()``,
``put_chunked``, ``generate()``) against the plain reference's full forward
(``benchmark/reference/_minicpm_sala.py``), at tiny sizes in float32: the
lightning state carried across forwards, steps and bursts; the pooled keys
kept up across chunk, page and decode-step edges; rows under and over
``dense_len`` in one step; a slot's reuse, preemption by recompute, and what
start-up refuses.  Logits are compared wherever a path returns them;
``generate()`` returns tokens, which one reference pass over prompt and
continuation checks.

Tolerance: float32 on the CPU, so a difference is summation order (the
chunked scan and the paged forms against the recurrence and dense attention):
2e-4 absolute on logits of order 1.  A planted fault reads 0.1 and more
(``test_minicpm_sala.py``)."""

import dataclasses

import numpy as np
import pytest
from sala_tiny import (N_DEC, SIZES, STATE_MANAGER, TOL, cfg,  # noqa: F401
                       engine, params, ref, seqs, want)

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
    feed([s[:len(s) - n_dec] for s in seqs])
    for j in range(n_dec):
        feed([s[len(s) - n_dec + j:len(s) - n_dec + j + 1] for s in seqs])
    return [np.stack(g) for g in got]


def test_prefill_then_decode_through_the_cache(cfg, params, steps, seqs,
                                               want):
    """A prompt of 69 rows at 32 a forward (its second chunk crosses
    ``dense_len`` 32, so rows under and over it ride one step; pooled-key
    spans of 4 cross chunk edges at 32 and 64 and page edges every 16)
    beside one of 27, whose 8 decoded positions cross ``dense_len`` while
    decoding; then 8 one-row steps (a pooled key completes every second
    step)."""
    eng = engine(cfg, params, steps)
    got = decode_rows(eng, [1, 2], seqs, N_DEC)
    for g, w, s in zip(got, want, seqs):
        np.testing.assert_allclose(g, w[len(s) - N_DEC - 1:], atol=TOL)
    t = eng.telemetry
    assert t.value("serving_ssm_rows_total", path="chunk") == 3 * (69 + 27)
    assert t.value("serving_ssm_rows_total", path="step") == 3 * 2 * N_DEC
    assert t.value("ssm_state_bytes_per_slot") == 3 * 4 * 128 * 128 * 4
    c = eng.cache
    assert c.k.shape[0] == 3 and c.ssm.shape[:2] == (3, 4) and c.conv is None
    assert c.ki.shape == (3, 64, 8, 2, 128)


def test_the_kernels_forms_agree(cfg, params, steps, seqs, want):
    """The same through the Pallas forms, interpreted: the masked prefill
    kernel on a selection a KV head, the paged decode kernel, the state
    update with a column a head (pages of 128: the kernels' rule)."""
    eng = InferenceEngineV2(
        dataclasses.replace(cfg, attn_impl="pallas"),
        {"dtype": "float32", "state_manager": {
            **STATE_MANAGER, "kv_block_size": 128, "num_kv_blocks": 8}},
        params=params)
    got = decode_rows(eng, [1, 2], seqs, 2)
    for g, w, s in zip(got, want, seqs):
        np.testing.assert_allclose(g, w[len(s) - 3:], atol=TOL)


def test_a_one_row_rider_beside_a_prompt_chunk(cfg, params, steps, seqs,
                                               want):
    """One mixed step holds a decoding sequence's single row past
    ``dense_len`` (the recurrence; its kept blocks gathered) and another
    sequence's prompt chunk (the chunked scan; the prefill kernel)."""
    eng = engine(cfg, params, steps)
    a, b = seqs[1], seqs[0][:64]
    eng.put([1], [a[:20]])
    eng.put([1], [a[20:33]])
    rows = [eng.put([1, 2], [a[33:34], b[:32]]),
            eng.put([1, 2], [a[34:35], b[32:64]])]
    np.testing.assert_allclose(rows[0][0], want[1][33], atol=TOL)
    np.testing.assert_allclose(rows[1][0], want[1][34], atol=TOL)
    np.testing.assert_allclose(rows[1][1], want[0][63], atol=TOL)


def test_a_reused_slot_starts_from_zero(cfg, params, steps, seqs, want):
    """A slot (and its pages) handed to a new sequence: nothing of its last
    owner's state or pooled keys is read, though neither is ever
    cleared."""
    eng = engine(cfg, params, steps, max_tracked_sequences=1,
                 max_ragged_sequence_count=1)
    eng.put([1], [seqs[0][:32]])
    eng.put([1], [seqs[0][32:60]])
    assert np.abs(np.asarray(eng.cache.ssm)).max() > 0
    assert np.abs(np.asarray(eng.cache.ki, np.float32)).max() > 0
    eng.flush([1])
    got = decode_rows(eng, [2], seqs[1:], 3)[0]
    np.testing.assert_allclose(got, want[1][len(seqs[1]) - 4:], atol=TOL)


def assert_greedy(params, prompt, out):
    """``out`` is the reference's greedy continuation of ``prompt``: one
    reference pass over prompt and continuation, whose best token at each
    position must be the one that was generated next."""
    ids = np.concatenate([prompt, out])
    rows = list(range(len(prompt) - 1, len(ids) - 1))
    best = np.asarray(ref.logits(params, ids, SIZES, rows=rows)).argmax(-1)
    np.testing.assert_array_equal(out, best)


@pytest.fixture(scope="module")
def prompts(seqs):
    return [seqs[0][:50], seqs[1][:20]]


def test_generate_with_bursts_is_the_reference(cfg, params, steps, prompts):
    """``generate()``: SplitFuse mixing, the one-row route and fused decode
    bursts that carry the state, complete pooled keys and cross
    ``dense_len`` inside their loop (the second sequence at its 13th
    token)."""
    eng = engine(cfg, params, steps)
    outs = eng.generate(prompts, max_new_tokens=24)
    for p, o in zip(prompts, outs):
        assert len(o) == 24
        assert_greedy(params, p, o)
    t = eng.telemetry
    assert t.value("serving_dispatches_total", kind="burst") > 0
    # positions 0-31 of both sequences take the dense path on 3 layers; past
    # them 18 rows of the longer prompt and the one burst's 32 steps from
    # contexts 50 and 21 (it computes all its steps, 32 + 21 rows past 31)
    # keep 4 blocks a KV head
    assert t.value("serving_block_rows_total", path="dense") == 3 * 64
    sparse = t.value("serving_block_rows_total", path="sparse")
    assert sparse == 3 * (18 + 32 + 21)
    assert t.value("serving_block_kept_blocks_total") == 4 * sparse


@pytest.fixture(scope="module")
def dispatch_events(cfg, params, steps, prompts):
    eng = engine(cfg, params, steps)
    eng.generate(prompts, max_new_tokens=24)
    return eng, [ev for ev in eng.telemetry.tracer.events
                 if ev["name"].endswith("_dispatch")]


@pytest.mark.parametrize("arg", [
    "ssm_chunk_rows", "ssm_step_rows", "ssm_state_bytes_per_slot",
    "blk_dense_rows", "blk_sparse_rows", "blk_kept_blocks", "sel_pairs",
    "global_pairs", "index_pairs", "index_bytes_per_token",
    "kv_bytes_per_token", "blk_pairs_step", "blk_pairs_one_row",
    "blk_pooled_pairs", "blk_ctx_chunk", "blk_pooled_chunk"])
def test_dispatch_spans_carry_the_counts(dispatch_events, arg):
    """What ``benchmark/readers/sala.py``, ``ssm_spans.py``,
    ``span_counters.py`` and ``sparse.py`` take from the dispatch spans:
    running totals that only grow, a slot's and a token's bytes, and each
    dispatch's own needs."""
    eng, events = dispatch_events
    assert {"mixed_dispatch", "burst_dispatch"} <= {ev["name"]
                                                    for ev in events}
    seen = [ev["args"][arg] for ev in events]
    if arg == "ssm_state_bytes_per_slot":
        assert set(seen) == {3 * 4 * 128 * 128 * 4}
    elif arg == "index_bytes_per_token":    # 3 layers x 2 heads x 128 x 4 B
        assert set(seen) == {3 * 2 * 128 * 4 // 2}      # one every 2 tokens
    elif arg == "kv_bytes_per_token":
        assert set(seen) == {3 * 2 * 2 * 128 * 4}
    elif arg.startswith("blk_p") or arg.startswith("blk_ctx"):
        assert max(seen) > 0 and min(seen) >= 0
    else:
        assert seen == sorted(seen) and seen[-1] > 0
    if arg == "sel_pairs":
        last = events[-1]["args"]
        assert last["sel_pairs"] < last["global_pairs"]


def test_a_preempted_sequence_is_recomputed(cfg, params, steps, prompts):
    """A pool that holds one of two requests at a time: one is preempted
    mid-generation, gives up its pages and its state slot, and is recomputed
    from its prompt (position 0 starts from zero; its pooled keys are
    written again)."""
    eng = engine(cfg, params, steps, num_kv_blocks=6)
    outs = eng.generate(prompts, max_new_tokens=24)
    assert sum(eng.preempt_stats.values()) > 0
    for p, o in zip(prompts, outs):
        assert_greedy(params, p, o)
    assert not eng.state.tracked and eng.state.free_sequence_slots == 4


@pytest.mark.parametrize("what,config,kw", [
    ("prefix cache", {"state_manager": {"prefix_cache": True}}, {}),
    ("speculative decoding", {}, {"draft": True}),
    ("tp mesh", {"tensor_parallel": {"tp_size": 2}}, {}),
    ("LoRA adapter pages", {"adapters": {"enabled": True}}, {}),
    ("kv_quant", {"state_manager": {"kv_quant": "int8"}}, {})])
@pytest.mark.parametrize("kind", ["selection", "state"])
def test_start_up_refuses_what_is_not_built(cfg, kind, what, config, kw):
    """Both new mechanisms refuse, each for its own reason: a model of
    selecting layers alone, and one of lightning layers beside plain
    attention."""
    types = cfg.layer_types
    if kind == "selection":
        model = dataclasses.replace(cfg, layer_types=(), rope_layers="none")
        match = f"selection by blocks.*{what}"
    else:
        model = dataclasses.replace(cfg, block_topk=0)
        match = f"scan layers.*{what}"
    assert "lightning" in types
    conf = {"dtype": "float32", **config,
            "state_manager": {**STATE_MANAGER,
                              **config.get("state_manager", {})}}
    extra = {"draft_model": model} if kw.get("draft") else {}
    with pytest.raises(NotImplementedError, match=match):
        InferenceEngineV2(model, conf, **extra)


def test_pages_must_hold_whole_blocks(cfg, params):
    bad = dataclasses.replace(cfg, block_size=24, block_window=48,
                              block_dense_len=96)
    with pytest.raises(NotImplementedError, match="whole.*blocks"):
        engine(bad, None, None)
