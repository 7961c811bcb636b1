"""Scan layers through the serving engine (``InferenceEngineV2``: ``put()``,
``put_chunked``, ``generate()``) against the plain reference's full forward
(``benchmark/reference/_granite_hybrid.py``), at tiny sizes in float32: the
state and the conv tail carried across forwards, steps and bursts, a slot's
reuse, preemption by recompute, and what start-up refuses.  Logits are
compared wherever a path returns them; ``generate()`` returns tokens, which
one reference pass over prompt and continuation checks.

Tolerance: float32 on the CPU, so a difference is summation order (the
chunked form and the paged kernels' XLA form against the recurrence and
dense attention): 2e-4 absolute on logits of order 0.1 to 1."""

import numpy as np
import pytest
from granite_tiny import (SIZES, STATE_MANAGER, TOL, cfg, engine,  # noqa: F401
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
    """A prompt longer than a forward (69 rows at 32 a forward: the state
    and the conv tail cross two forward boundaries, through
    ``put_chunked``), beside a shorter one in the same mixed step, neither a
    multiple of the scan's chunk of 8; then six one-row steps."""
    eng = engine(cfg, params, steps)
    got = decode_rows(eng, [1, 2], seqs, 6)
    for g, w, s in zip(got, want, seqs):
        np.testing.assert_allclose(g, w[len(s) - 7:], atol=TOL)
    t = eng.telemetry
    assert t.value("serving_ssm_rows_total", path="chunk") == 3 * (69 + 17)
    assert t.value("serving_ssm_rows_total", path="step") == 3 * 12
    assert t.value("ssm_state_bytes_per_slot") == 3 * (
        4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert eng.cache.k.shape[0] == 1 and eng.cache.ssm.shape[:2] == (3, 4)
    assert eng.cache.conv.shape == (3, 4, 3 * 128)


def test_a_one_row_rider_beside_a_prompt_chunk(cfg, params, steps, seqs,
                                               want):
    """One mixed step holds a decoding sequence's single row (the
    recurrence) and another sequence's prompt chunk (the chunked scan)."""
    eng = engine(cfg, params, steps)
    a, b = seqs[1], seqs[0][:30]
    eng.put([1], [a[:10]])
    rows = [eng.put([1, 2], [a[10:11], b[:20]]),
            eng.put([1, 2], [a[11:12], b[20:30]])]
    np.testing.assert_allclose(rows[0][0], want[1][10], atol=TOL)
    np.testing.assert_allclose(rows[1][0], want[1][11], atol=TOL)
    np.testing.assert_allclose(rows[1][1], want[0][29], atol=TOL)


def test_a_reused_slot_starts_from_zero(cfg, params, steps, seqs, want):
    """A slot handed to a new sequence: nothing of its last owner's state
    or conv tail is read, though neither is ever cleared."""
    eng = engine(cfg, params, steps, max_tracked_sequences=1,
                 max_ragged_sequence_count=1)
    eng.put([1], [seqs[0][:40]])
    assert np.abs(np.asarray(eng.cache.ssm)).max() > 0
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
    return [seqs[0][:40], seqs[1]]


def test_generate_with_bursts_is_the_reference(cfg, params, steps, prompts):
    """``generate()``: SplitFuse mixing, the one-row route and fused decode
    bursts that carry the state through their loop."""
    eng = engine(cfg, params, steps)
    outs = eng.generate(prompts, max_new_tokens=20)
    for p, o in zip(prompts, outs):
        assert len(o) == 20
        assert_greedy(params, p, o)
    assert eng.telemetry.value("serving_dispatches_total", kind="burst") > 0


@pytest.fixture(scope="module")
def dispatch_events(cfg, params, steps, prompts):
    """The ``*_dispatch`` events of one ``generate()`` call, as the
    program's own span buffer holds them."""
    eng = engine(cfg, params, steps)
    eng.generate(prompts, max_new_tokens=20)
    return eng, [ev for ev in eng.telemetry.tracer.events
                 if ev["name"].endswith("_dispatch")]


@pytest.mark.parametrize("arg", ["ssm_chunk_rows", "ssm_step_rows",
                                 "ssm_slots", "ssm_state_bytes_per_slot"])
def test_dispatch_spans_carry_the_scan_totals(dispatch_events, arg):
    """What ``benchmark/readers/ssm_spans.py`` and ``ssm_rooflines.py``
    take from the dispatch spans of a model with scan layers: running
    totals that only grow, the state slots in use, a slot's bytes."""
    eng, events = dispatch_events
    kinds = {ev["name"] for ev in events}
    assert {"mixed_dispatch", "burst_dispatch"} <= kinds
    seen = [ev["args"][arg] for ev in events]
    if arg.endswith("_rows"):
        assert seen == sorted(seen) and seen[-1] > 0
        path = arg[len("ssm_"):-len("_rows")]
        assert seen[-1] <= eng.telemetry.value("serving_ssm_rows_total",
                                               path=path)
    elif arg == "ssm_slots":
        assert 1 <= max(seen) <= 2 and eng.telemetry.value(
            "ssm_state_slots_in_use") in seen
    else:
        assert set(seen) == {eng.telemetry.value("ssm_state_bytes_per_slot")}


def test_a_preempted_sequence_is_recomputed(cfg, params, steps, prompts):
    """A pool that holds one of two requests at a time: one is preempted
    mid-generation, gives up its pages and its state slot, and is recomputed
    from its prompt (position 0 starts from zero)."""
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
    conf = {"dtype": "float32", **config,
            "state_manager": {**STATE_MANAGER,
                              **config.get("state_manager", {})}}
    extra = {"draft_model": cfg} if kw.get("draft") else {}
    with pytest.raises(NotImplementedError, match=f"scan layers.*{what}"):
        InferenceEngineV2(cfg, conf, **extra)


