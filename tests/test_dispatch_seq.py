"""A dispatch says which one it is (PR 55): every step-program dispatch of a
serving engine carries ``seq`` (its number since the engine was built) and
``program`` (the jitted callable's name), the MoE totals say through which
dispatch they hold (``moe_seq``), a materialize up to which dispatch it
fetched (``through_seq``) and how far ahead the host was (``in_flight``), and
the train step's ``ds.dispatch`` names its program.  All on the CPU: names,
numbers and arithmetic, never a time."""

import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, os.path.join(BENCH, "readers")):
    if p not in sys.path:
        sys.path.insert(0, p)

import xmeta  # noqa: E402
import xtrace  # noqa: E402

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import GPTConfig  # noqa: E402

DISPATCH = ("ds.mixed_dispatch", "ds.decode_dispatch", "ds.burst_dispatch")
PROGRAM = {"ds.mixed_dispatch": "ragged_forward_sampled",
           "ds.decode_dispatch": "ragged_decode_sampled",
           "ds.burst_dispatch": "ragged_decode_burst"}


def _serve(tmp, enabled, **model):
    """Two ``generate`` calls of a tiny engine, the second under a profiler
    session: mixed steps, fused bursts and (the sixth prompt ends so near
    ``max_seq_len`` that no burst fits) single decodes.  Every MoE counter
    vector the telemetry is handed is kept beside its ``seq``."""
    cfg = GPTConfig.tiny(vocab_size=97, max_seq_len=64, **model)
    eng = InferenceEngineV2(cfg, config={
        "dtype": "fp32", "telemetry": {"enabled": enabled},
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_batch_size": 64,
                          "kv_block_size": 8, "max_q_per_seq": 16}}, seed=0)
    vectors = []
    fold = eng.telemetry.moe_stats

    def keep(vec, seq):
        vectors.append((seq, np.asarray(vec).astype(np.int64)))
        fold(vec, seq)
    eng.telemetry.moe_stats = keep
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, (9 + 5 * i,)).astype(np.int32)
               for i in range(5)]
    prompts.append(rng.integers(0, 97, (58,)).astype(np.int32))
    budgets = [12] * 5 + [5]
    eng.generate(prompts, max_new_tokens=budgets)
    first = eng.telemetry.seq
    with jax.profiler.trace(str(tmp)):
        eng.generate(prompts, max_new_tokens=budgets)
    notes = xmeta.annotations(xtrace.find_xplane(str(tmp)))
    return {"eng": eng, "notes": notes, "first": first, "vectors": vectors,
            "spans": [a for a in notes if a["name"] in DISPATCH]}


@pytest.fixture(scope="module", params=[
    ("dense", True), ("dense", False), ("moe", True), ("moe", False)],
    ids=lambda p: f"{p[0]}-telemetry_{'on' if p[1] else 'off'}")
def served(request, tmp_path_factory):
    kind, enabled = request.param
    model = ({"num_experts": 4, "moe_k": 2, "moe_dropless": True}
             if kind == "moe" else {})
    out = _serve(tmp_path_factory.mktemp(f"seq_{kind}_{int(enabled)}"),
                 enabled, **model)
    out["kind"], out["enabled"] = kind, enabled
    return out


def test_seq_rises_by_one_from_span_to_span_across_kinds(served):
    spans = served["spans"]
    assert {a["name"] for a in spans} == set(DISPATCH)
    seqs = [a["args"]["seq"] for a in spans]
    # the first call's dispatches came before: the count is the engine's
    assert seqs == list(range(served["first"] + 1,
                              served["first"] + 1 + len(spans)))
    assert served["eng"].telemetry.seq == seqs[-1]


def test_program_is_the_jitted_callables_name(served):
    names = {fn.__name__ for fn in served["eng"]._steps.values()}
    for a in served["spans"]:
        assert a["args"]["program"] == PROGRAM[a["name"]]
        assert a["args"]["program"] in names


def test_the_labelled_counter_stays_under_enabled(served):
    tel = served["eng"].telemetry
    if not served["enabled"]:
        assert not hasattr(tel, "c_dispatch")
        return
    counted = sum(tel.c_dispatch.value(kind=k, **tel.labels)
                  for k in ("mixed", "decode", "burst"))
    assert counted == tel.seq


def test_moe_seq_names_the_dispatch_the_totals_hold_through(served):
    spans, vectors = served["spans"], served["vectors"]
    tel = served["eng"].telemetry
    if served["kind"] == "dense":
        assert not vectors and tel.moe_seq == 0
        assert all("moe_seq" not in a["args"] for a in spans)
        return
    # one vector a dispatch, handed over in order, none twice
    assert [s for s, _ in vectors] == list(range(1, tel.seq + 1))
    assert tel.moe_seq == tel.seq              # the call's end waits
    if not served["enabled"]:                  # no counters, no note
        assert all("moe_seq" not in a["args"] for a in spans)
        return
    through = [a["args"]["moe_seq"] for a in spans]
    assert all(m < a["args"]["seq"] for m, a in zip(through, spans))
    assert through == sorted(through)
    cum = np.cumsum([v for _, v in vectors], axis=0)
    for m, a in zip(through, spans):
        local, assign, touched = cum[m - 1]
        assert (a["args"]["moe_local"], a["args"]["moe_assign"],
                a["args"]["moe_touched"]) == (local, assign, touched)


def test_materialize_says_through_which_dispatch_and_how_far_ahead(served):
    notes = served["notes"]
    mats = [a for a in notes if a["name"] == "ds.materialize"]
    assert mats
    before = served["first"]          # the first call's last materialize
    for m in mats:
        args = m["args"]
        made = [a["args"]["seq"] for a in served["spans"]
                if a["end_ns"] <= m["start_ns"]]
        at_entry = made[-1] if made else served["first"]
        assert before < args["through_seq"] <= at_entry
        assert args["in_flight"] == at_entry - before
        assert args["records"] >= 1
        before = args["through_seq"]
    # the call's last dispatch samples a token, and the call ends fetched
    assert before == served["spans"][-1]["args"]["seq"]
    assert served["eng"]._through_seq == before


def test_put_dispatches_count_too(served):
    eng = served["eng"]
    seq = eng.telemetry.seq
    uid = 10_000
    eng.put([uid], [np.arange(5, dtype=np.int32)])      # mixed
    eng.put([uid], [np.asarray([7], np.int32)])         # decode
    eng.flush([uid])
    assert eng.telemetry.seq == seq + 2
    names = {fn.__name__ for fn in eng._steps.values()}
    assert {"ragged_forward", "ragged_decode_forward"} <= names


def test_train_dispatch_carries_its_program_and_step(tmp_path):
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT
    pool = np.random.default_rng(0).integers(0, 128, (8, 16)).astype(np.int32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(GPTConfig.tiny(vocab_size=128, max_seq_len=16)),
        config={"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 0.01}},
                "steps_per_print": 0},
        example_batch={"input_ids": pool[:1]})
    engine.train_batch({"input_ids": pool})
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            engine.train_batch({"input_ids": pool})
    notes = xmeta.annotations(xtrace.find_xplane(str(tmp_path)))
    spans = [a for a in notes if a["name"] == "ds.dispatch"]
    assert [a["args"]["step"] for a in spans] == [2, 3]
    assert {a["args"]["program"] for a in spans} == {
        engine._jit_train_batch.__name__} == {"train_batch"}
