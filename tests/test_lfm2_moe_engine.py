"""Short-conv layers, RoPE attention and experts through the serving engine
(``InferenceEngineV2``: ``put()``, ``put_chunked``, ``generate()``) against
the plain reference's full forward (``benchmark/reference/_lfm2_moe.py``),
at tiny sizes in float32: the conv tail carried across forwards, steps and
bursts, a slot's reuse, preemption by recompute, and what start-up refuses.
Logits are compared wherever a path returns them; ``generate()`` returns
tokens, which one reference pass over prompt and continuation checks.

Tolerance: float32 on the CPU, so a difference is summation order (the paged
kernels' XLA form and the grouped experts against dense attention and the
dense mask): 2e-4 absolute on logits of order 0.1 to 1."""

import numpy as np
import pytest
from lfm2_tiny import (SIZES, STATE_MANAGER, TOL, cfg, engine,  # noqa: F401
                       params, ref, seqs, want)

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
    """A prompt longer than two forwards (69 rows at 32 a forward: the tail
    crosses two forward boundaries, through ``put_chunked``), beside a
    shorter one in the same mixed step; then six one-row steps."""
    eng = engine(cfg, params, steps)
    got = decode_rows(eng, [1, 2], seqs, 6)
    for g, w, s in zip(got, want, seqs):
        np.testing.assert_allclose(g, w[len(s) - 7:], atol=TOL)
    t = eng.telemetry
    assert t.value("serving_conv_rows_total", path="chunk") == 5 * (69 + 17)
    assert t.value("serving_conv_rows_total", path="step") == 5 * 12
    # a conv tail and nothing else: 5 layers x 2 rows x 32 channels, float32
    assert t.value("conv_state_bytes_per_slot") == 5 * 2 * 32 * 4
    assert not t.value("ssm_state_bytes_per_slot")     # no scan, no name
    assert eng.cache.ssm is None and eng.cache.conv.shape == (5, 4, 2 * 32)
    # the KV pool belongs to the one attention layer
    assert eng.cache.k.shape[0] == 1
    assert eng.kv_bytes_per_token() == 2 * 2 * 8 * 4


def test_a_two_row_prompt_ends_a_row_behind_a_boundary(cfg, params, steps,
                                                       seqs, want):
    """The benchmark's comparison shape: a prompt two rows longer than two
    forwards, whose last chunk reads its first tap from the tail, beside a
    two-row prompt from a fresh slot."""
    eng = engine(cfg, params, steps)
    a, b = seqs[0][:66], seqs[1][:2]
    rows = eng.put([1, 2], [a, b])
    np.testing.assert_allclose(rows[0], want[0][65], atol=TOL)
    np.testing.assert_allclose(rows[1], want[1][1], atol=TOL)
    rows = eng.put([1, 2], [seqs[0][66:67], seqs[1][2:3]])
    np.testing.assert_allclose(rows[0], want[0][66], atol=TOL)
    np.testing.assert_allclose(rows[1], want[1][2], atol=TOL)


def test_a_one_row_rider_beside_a_prompt_chunk(cfg, params, steps, seqs,
                                               want):
    """One mixed step holds a decoding sequence's single row (its tail
    shifts) and another sequence's prompt chunk (convolved from its tail)."""
    eng = engine(cfg, params, steps)
    a, b = seqs[1], seqs[0][:30]
    eng.put([1], [a[:10]])
    rows = [eng.put([1, 2], [a[10:11], b[:20]]),
            eng.put([1, 2], [a[11:12], b[20:30]])]
    np.testing.assert_allclose(rows[0][0], want[1][10], atol=TOL)
    np.testing.assert_allclose(rows[1][0], want[1][11], atol=TOL)
    np.testing.assert_allclose(rows[1][1], want[0][29], atol=TOL)


def test_a_prompt_fed_as_three_chunks_beside_a_rider(cfg, params, steps,
                                                     seqs, want):
    """``put_chunked``'s own boundaries (20 rows a piece, not the
    forward's), a decoding rider in every one of the three steps."""
    eng = engine(cfg, params, steps)
    a, b = seqs[1], seqs[0][:60]
    eng.put([1], [a[:10]])
    for j, (lo, hi) in enumerate(((0, 20), (20, 40), (40, 60))):
        rows = eng.put([1, 2], [a[10 + j:11 + j], b[lo:hi]])
        np.testing.assert_allclose(rows[0], want[1][10 + j], atol=TOL)
        np.testing.assert_allclose(rows[1], want[0][hi - 1], atol=TOL)


def test_a_reused_slot_starts_from_zero(cfg, params, steps, seqs, want):
    """A slot handed to a new sequence: nothing of its last owner's tail is
    read, though it is never cleared."""
    eng = engine(cfg, params, steps, max_tracked_sequences=1,
                 max_ragged_sequence_count=1)
    eng.put([1], [seqs[0][:40]])
    assert np.abs(np.asarray(eng.cache.conv)).max() > 0
    eng.flush([1])
    got = decode_rows(eng, [2], [seqs[1][:8]], 7)[0]     # rows 0 .. 7
    np.testing.assert_allclose(got, want[1][:8], atol=TOL)


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
    return [seqs[0][:40], seqs[1]]


def test_generate_with_bursts_is_the_reference(cfg, params, steps, prompts):
    """``generate()``: SplitFuse mixing, the one-row route and fused decode
    bursts that carry the tail through their loop."""
    eng = engine(cfg, params, steps)
    outs = eng.generate(prompts, max_new_tokens=20)
    for p, o in zip(prompts, outs):
        assert len(o) == 20
        assert_greedy(params, p, o)
    assert eng.telemetry.value("serving_dispatches_total", kind="burst") > 0
    eng._fold_moe_stats(wait=True)
    # 4 expert layers x 2 experts a row, every scheduled row
    assert eng.telemetry.value("moe_assignments_total") == 8 * (
        eng.telemetry.value("serving_tokens_total", phase="prefill")
        + eng.telemetry.value("serving_tokens_total", phase="decode"))


@pytest.fixture(scope="module")
def dispatch_events(cfg, params, steps, prompts):
    """The ``*_dispatch`` events of one ``generate()`` call, as the
    program's own span buffer holds them."""
    eng = engine(cfg, params, steps)
    eng.generate(prompts, max_new_tokens=20)
    return eng, [ev for ev in eng.telemetry.tracer.events
                 if ev["name"].endswith("_dispatch")]


@pytest.mark.parametrize("arg", ["conv_chunk_rows", "conv_step_rows",
                                 "conv_slots", "conv_state_bytes_per_slot",
                                 "kv_bytes_per_token"])
def test_dispatch_spans_carry_the_conv_totals(dispatch_events, arg):
    """What ``benchmark/readers/conv_spans.py`` and ``conv_rooflines.py``
    take from the dispatch spans of a model with conv layers: running
    totals that only grow, under the conv's names and none of a scan's."""
    eng, events = dispatch_events
    seen = [ev["args"][arg] for ev in events]
    assert seen == sorted(seen) and seen[-1] > 0
    assert not any(k.startswith("ssm_") for ev in events for k in ev["args"])


def test_a_preempted_sequence_is_recomputed(cfg, params, steps, prompts):
    """A pool that holds one of two requests at a time: one is preempted
    mid-generation, gives up its pages and its tail's slot, and is
    recomputed from its prompt (position 0 starts from zero)."""
    eng = engine(cfg, params, steps, num_kv_blocks=5, kv_block_size=16)
    outs = eng.generate(prompts, max_new_tokens=20)
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
def test_start_up_refuses_what_is_not_built(cfg, what, config, kw):
    import dataclasses
    # (without experts: MoE serving refuses a tp mesh before the state
    # layers' refusals are reached)
    cfg = dataclasses.replace(cfg, num_experts=0)
    conf = {"dtype": "float32", **config,
            "state_manager": {**STATE_MANAGER,
                              **config.get("state_manager", {})}}
    extra = {"draft_model": cfg} if kw.get("draft") else {}
    with pytest.raises(NotImplementedError,
                       match=f"conv layers.*conv tail.*{what}"):
        InferenceEngineV2(cfg, conf, **extra)


def test_start_up_refuses_window_groups_beside_the_tails(cfg):
    import dataclasses
    windowed = dataclasses.replace(cfg, sliding_window=16,
                                   local_attn_layers=(2,),
                                   layer_types=("conv", "conv", "attention",
                                                "conv", "attention", "conv"))
    with pytest.raises(NotImplementedError,
                       match="conv layers.*window page groups"):
        InferenceEngineV2(windowed, {"dtype": "float32",
                                     "state_manager": STATE_MANAGER})
