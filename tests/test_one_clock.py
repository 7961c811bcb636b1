"""One clock and named phases (PR 26): the program's host spans reach a
``jax.profiler`` trace as ``ds.<name>`` annotations, its device programs carry
phase scopes in their op metadata, and the benchmark's readers
(``benchmark/readers/xmeta.py``, ``scope_time.py``, ``sched_rounds.py``) turn
both into numbers.  Everything here runs on the CPU: names, arguments,
structure and arithmetic, never a time of the device."""

import dataclasses
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, os.path.join(BENCH, "readers")):
    if p not in sys.path:
        sys.path.insert(0, p)

import sched_rounds  # noqa: E402
import scope_time  # noqa: E402
import xmeta  # noqa: E402
import xtrace  # noqa: E402

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import GPTChunkedLoss, GPTConfig  # noqa: E402
from deepspeed_tpu.parallel.mesh import single_device_mesh  # noqa: E402
from deepspeed_tpu.telemetry.serving import (  # noqa: E402
    ServingTelemetry, ServingTelemetryConfig)
from deepspeed_tpu.telemetry.tracer import SpanTracer  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
HAND = os.path.join(DATA, "scoped_trace.textproto")
RECORDED = os.path.join(DATA, "recorded_v5e.xplane.pb")


def profiled(tmp_path, body):
    """Run ``body`` under a profiler session; the trace's annotations."""
    with jax.profiler.trace(str(tmp_path)):
        body()
    return xmeta.annotations(xtrace.find_xplane(str(tmp_path)))


# ------------------------------------------------- spans on the profiler's clock

def _sinks():
    def tracer(on):
        return SpanTracer(enabled=on)

    def serving(on):
        return ServingTelemetry(ServingTelemetryConfig(trace_enabled=on),
                                pid=0)
    return {"tracer": tracer, "serving": serving}


@pytest.fixture(scope="module")
def span_trace(tmp_path_factory):
    """One profiler session for all four sinks: each opens the same spans."""
    made = {(kind, on): make(on) for kind, make in _sinks().items()
            for on in (True, False)}

    def body():
        for (kind, on), sink in made.items():
            tag = f"{kind}_{int(on)}"
            with sink.span(f"round_{tag}", n=7, host_ns=123, label="x"):
                with sink.span(f"h2d_{tag}"):
                    time.sleep(0.001)
    notes = profiled(tmp_path_factory.mktemp("spans"), body)
    return made, {a["name"]: a for a in notes}


@pytest.mark.parametrize("on", [True, False], ids=["buffer_on", "buffer_off"])
@pytest.mark.parametrize("kind", ["tracer", "serving"])
def test_span_reaches_the_host_plane_with_its_arguments(span_trace, kind,
                                                        on):
    made, notes = span_trace
    tag = f"{kind}_{int(on)}"
    outer, inner = notes[f"ds.round_{tag}"], notes[f"ds.h2d_{tag}"]
    assert outer["args"] == {"n": 7, "host_ns": 123, "label": "x"}
    assert inner["args"] == {}
    assert outer["start_ns"] <= inner["start_ns"]
    assert inner["end_ns"] <= outer["end_ns"]
    assert inner["end_ns"] - inner["start_ns"] >= 1e6       # the 1 ms sleep
    sink = made[(kind, on)]
    tracer = sink if kind == "tracer" else sink.tracer
    names = [e["name"] for e in tracer.events]
    # the tracer's own buffer stays gated as before
    assert names == ([f"h2d_{tag}", f"round_{tag}"] if on else [])
    if on:
        assert tracer.events[-1]["args"]["host_ns"] == 123


def test_span_costs_under_5us_with_no_session_open():
    tracer = SpanTracer(enabled=False)
    n, best = 20_000, float("inf")
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for i in range(n):
            with tracer.span("dispatch", step=i, tokens=512):
                pass
        best = min(best, (time.perf_counter_ns() - t0) / n)
    print(f"span with no session open: {best:.0f} ns a call")
    assert best < 5_000, best


def test_us_of_places_a_perf_counter_reading_on_the_tracer_epoch():
    tracer = SpanTracer()
    before = tracer.now_us()
    at = tracer.us_of(time.perf_counter())
    assert before <= at <= tracer.now_us()


# ------------------------------------------------------ scopes in the programs

_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? (dot|convolution|"
                    r"custom-call)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def heavy_ops(hlo_text):
    """(how many dot / convolution / custom-call instructions a compiled
    program has, those whose ``op_name`` names no known scope)."""
    n, bad = 0, []
    for line in hlo_text.splitlines():
        if not _INSTR.match(line):
            continue
        n += 1
        name = _OP_NAME.search(line)
        if scope_time.group_of(name.group(1) if name else "") == "unscoped":
            bad.append(line.strip()[:160])
    return n, bad


def scopes_in(lowered, known=scope_time.KNOWN):
    """``known=None``: every component of every name stack (the inner
    scopes a reader of one layer kind splits a phase by)."""
    text = lowered.as_text(debug_info=True)
    found = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        found.update(p for p in path.split("/") if known is None or p in known)
    return found


@pytest.fixture(scope="module")
def train_engine():
    import deepspeed_tpu
    cfg = GPTConfig.tiny(vocab_size=512, max_seq_len=64)
    ids = np.zeros((4, 64), np.int32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPTChunkedLoss(cfg),
        config={"train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 0.01}},
                "fp16": {"enabled": True, "initial_scale_power": 8},
                "zero_optimization": {"stage": 0}, "steps_per_print": 0},
        example_batch={"input_ids": ids},
        mesh=single_device_mesh(jax.devices()[0]))
    return engine


@pytest.fixture(scope="module")
def train_lowered(train_engine):
    engine = train_engine
    ids = np.zeros((4, 64), np.int32)
    batch = engine._shard_batch(engine._form_batch({"input_ids": ids})[0],
                                leading_gas=True)
    with engine.mesh:
        return engine._jit_train_batch.lower(engine.state, batch)


@pytest.mark.parametrize("scope", [s for s in scope_time.TRAIN
                                   if s != "prepare_params"])
def test_train_step_carries_the_phase_scope(train_lowered, scope):
    assert scope in scopes_in(train_lowered)


def test_param_preparation_is_scoped_where_it_emits_anything(train_engine,
                                                             monkeypatch):
    """With fp32 masters the cast happens in the model, so the tiny step has
    no op under ``prepare_params``; the cast of a master-less engine (like
    the ZeRO-3 gather and staged QDQ beside it) is under the scope."""
    monkeypatch.setattr(train_engine, "use_master_weights", False)
    monkeypatch.setattr(train_engine, "compute_dtype", jnp.bfloat16)
    low = jax.jit(lambda p: train_engine._prepare_params(p, None)).lower(
        train_engine.state.params)
    assert scopes_in(low) == {"prepare_params"}


def test_train_step_has_no_unscoped_matmul(train_lowered):
    text = train_lowered.compile().as_text()
    n, bad = heavy_ops(text)
    assert n > 0 and bad == []
    paths = _OP_NAME.findall(text)
    groups = {scope_time.group_of(p) for p in paths}
    assert {"fwd", "bwd", "loss", "grad_check", "optimizer"} <= groups


SERVE_SCOPES = ("embed", "attn_qkv", "kv_write", "attn_kernel", "attn_out",
                "mlp", "head", "sample", "kv_pool")


TINY = dict(slots=4, tokens=64, max_q=16, table_width=8, block_size=16,
            num_pages=32, steps=4)


def _lower_speculative_burst(cfg):
    """``speculative_burst`` as the engine jits it (both caches donated),
    the draft one layer of the same widths."""
    from deepspeed_tpu.inference.v2 import model as v2model
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.parallel.metadata import unbox
    S, MB = TINY["slots"], TINY["table_width"]
    i32, b1 = jnp.int32, jnp.bool_
    draft = dataclasses.replace(cfg, num_layers=1)

    def shapes(c):
        return (unbox(jax.eval_shape(
            lambda k: GPTLogits(c).init(k, jnp.zeros((1, 8), i32)),
            jax.random.PRNGKey(0))["params"]),
            jax.eval_shape(lambda: v2model.PagedKVCache.create(
                c, TINY["num_pages"], TINY["block_size"], jnp.float32)))
    (params, cache), (dparams, dcache) = shapes(cfg), shapes(draft)
    batch = {"active": jax.ShapeDtypeStruct((S,), b1),
             "from_device": jax.ShapeDtypeStruct((S,), b1),
             "block_table": jax.ShapeDtypeStruct((S, MB), i32),
             "tokens0": jax.ShapeDtypeStruct((S,), i32),
             "pos0": jax.ShapeDtypeStruct((S,), i32)}
    fn = v2model.named_partial(
        v2model.speculative_burst, cfg=cfg, draft_cfg=draft,
        block_size=TINY["block_size"], gamma=3, steps=2)
    return jax.jit(fn, donate_argnums=(2, 3)).lower(
        params, dparams, cache, dcache, batch, jax.ShapeDtypeStruct((S,), i32))


@pytest.fixture(scope="module")
def serve_lowered():
    """The three serving step programs at a tiny width, lowered as the
    engine jits them, and the greedy speculative burst beside them."""
    from conftest import lower_serving_steps
    cfg = dataclasses.replace(
        GPTConfig.llama(num_layers=2, hidden=64, heads=4, vocab_size=128,
                        max_seq_len=256, dtype=None), dtype=jnp.float32)
    return {**lower_serving_steps(cfg, jnp.float32, **TINY)[2],
            "speculative_burst": _lower_speculative_burst(cfg)}


@pytest.fixture(scope="module")
def scan_lowered():
    """The three programs of a model with a Mamba-2 scan layer beside an
    attention layer."""
    from conftest import lower_serving_steps
    cfg = GPTConfig(
        vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, hidden_size=64, mlp_dim_override=128, max_seq_len=256,
        use_rope=True, rope_layers="none", use_rmsnorm=True, gated_mlp=True,
        layer_types=("mamba", "attention"), ssm_heads=8, ssm_head_dim=16,
        ssm_state=16, ssm_chunk=8, dtype=jnp.float32)
    return lower_serving_steps(cfg, jnp.float32, **TINY)[2]


@pytest.fixture(scope="module")
def conv_lowered():
    """The three programs of a model with gated short-conv layers, an
    attention layer and experts."""
    from conftest import lower_serving_steps
    cfg = GPTConfig(
        vocab_size=128, num_layers=3, num_heads=4, num_kv_heads=2,
        head_dim=16, hidden_size=64, mlp_dim_override=128, max_seq_len=256,
        use_rope=True, use_rmsnorm=True, gated_mlp=True, qk_norm=True,
        layer_types=("conv", "attention", "conv"), conv_taps=3,
        num_experts=4, moe_k=2, moe_dropless=True, moe_router="sigmoid",
        moe_router_bias=True, moe_expert_dim=32, moe_dense_layers=1,
        dtype=jnp.float32)
    return lower_serving_steps(cfg, jnp.float32, **TINY)[2]


PROGRAMS = ("ragged_forward_sampled", "ragged_decode_sampled",
            "ragged_decode_burst")
# the scopes the benchmark's readers split a step by, for each kind of layer
# the one layer body (model.py ``_layer``) runs: (fixture, program, scope),
# the dense preset's ids as they were
PHASE_SCOPE_CASES = (
    [("serve_lowered", p, s) for p in PROGRAMS for s in SERVE_SCOPES]
    + [("serve_lowered", "speculative_burst", s)
       for s in SERVE_SCOPES + ("draft", "verify")]
    + [("scan_lowered", p, s) for p in PROGRAMS
       for s in SERVE_SCOPES + ("ssm_scan", "ssm_in_proj")]
    + [("conv_lowered", p, s) for p in PROGRAMS
       for s in SERVE_SCOPES + ("short_conv", "moe_experts")])


@pytest.mark.parametrize(
    "preset,program,scope", PHASE_SCOPE_CASES,
    ids=[f"{p}-{s}" if f == "serve_lowered" else f"{f[:4]}-{p}-{s}"
         for f, p, s in PHASE_SCOPE_CASES])
def test_serving_program_carries_the_phase_scope(request, preset, program,
                                                 scope):
    lowered = request.getfixturevalue(preset)[program]
    assert scope in scopes_in(lowered, known=None)


@pytest.fixture(scope="module")
def latent_lowered():
    """The same three programs of a model with latent attention (MLA) over
    a latent page pool and an expert layer."""
    from conftest import lower_serving_steps
    cfg = GPTConfig(
        num_layers=2, hidden_size=64, num_heads=4, head_dim=24,
        kv_lora_rank=128, qk_rope_head_dim=8, v_head_dim=16, use_rope=True,
        use_rmsnorm=True, gated_mlp=True, tie_embeddings=False,
        vocab_size=128, max_seq_len=256, mlp_dim_override=128, num_experts=4,
        moe_k=2, moe_dropless=True, moe_router="sigmoid",
        moe_router_bias=True, moe_shared_dim=32, moe_expert_dim=32,
        moe_dense_layers=1, dtype=jnp.float32)
    return lower_serving_steps(cfg, jnp.float32, slots=4, tokens=64,
                               max_q=16, table_width=8, block_size=16,
                               num_pages=32, steps=4)[2]


@pytest.mark.parametrize("scope", SERVE_SCOPES + ("mla_absorb",))
@pytest.mark.parametrize("program", PROGRAMS)
def test_latent_serving_program_carries_the_phase_scope(latent_lowered,
                                                        program, scope):
    """``mla_absorb`` (the two absorb products) nests inside ``attn_qkv``
    and ``attn_out``, which keep their meaning: ``scope_time`` counts its
    ops with theirs, ``benchmark/readers/latent.py`` reads it alone."""
    if scope != "mla_absorb":
        assert scope in scopes_in(latent_lowered[program])
        return
    text = latent_lowered[program].as_text(debug_info=True)
    paths = [p for p in re.findall(r'loc\("([^"]+)"', text)
             if "mla_absorb" in p.split("/")]
    assert {scope_time.group_of(p) for p in paths} == {"attn_qkv",
                                                       "attn_out"}


@pytest.mark.parametrize("program", PROGRAMS)
def test_serving_program_has_no_unscoped_matmul(serve_lowered, program):
    n, bad = heavy_ops(serve_lowered[program].compile().as_text())
    assert n > 0 and bad == []


@pytest.mark.parametrize("path,group", [
    ("jit(train_batch)/fwd_bwd/jvp(GPT)/backbone/block_0/MLP_0/dot_general",
     "fwd"),
    ("jit(train_batch)/fwd_bwd/transpose(jvp(GPT))/backbone/add_any", "bwd"),
    ("jit(train_batch)/fwd_bwd/transpose(jvp(GPT))/loss/dot_general", "loss"),
    # remat: the backward's recomputation replays the forward's whole path
    ("jit(train_batch)/fwd_bwd/transpose(jvp(GPT))/backbone/fwd_bwd/jvp(GPT)/"
     "backbone/block_2/MLP_0/dot_general", "bwd"),
    ("jit(train_batch)/fwd_bwd/jvp(GPT)/prepare_params/convert_element_type",
     "prepare_params"),
    ("jit(train_batch)/optimizer/cond/branch_1_fun/mul", "optimizer"),
    ("jit(ragged_decode_burst)/kv_pool/while/body/closed_call/attn_kernel/"
     "paged_decode/pallas_call", "attn_kernel"),
    ("jit(ragged_decode_burst)/kv_pool/while", "kv_pool"),
    ("jit(speculative_burst)/kv_pool/while/body/draft/mlp/dot_general",
     "mlp"),
    ("jit(train_batch)/add", "unscoped"), ("", "unscoped"),
    (None, "unscoped")])
def test_group_is_the_innermost_known_scope(path, group):
    assert scope_time.group_of(path) == group


# -------------------------------------------------- the readers on known traces

@pytest.fixture(scope="module")
def hand():
    trace = xtrace.load(HAND)
    ctx = {"_xmeta": {"devices": xmeta.device_ops(HAND),
                      "annotations": xmeta.annotations(HAND)},
           "trace": trace, "trace_window": xtrace.window_of(trace),
           "step_program": "train_batch"}
    return ctx


def test_xmeta_reads_op_metadata_by_event_not_by_name(hand):
    dev = hand["_xmeta"]["devices"][0]
    assert [m[0] for m in dev["modules"]] == [
        "train_batch", "train_batch", "ragged_decode_burst",
        "ragged_forward_sampled"]
    assert len(dev["ops"]) == 24
    meta = dev["meta"]
    # three instructions are all named fusion.1, each with its own scope
    same = sorted(m["tf_op"] for m in meta.values()
                  if m["name"] == "fusion.1")
    assert [scope_time.group_of(p) for p in same] == ["attn_qkv", "mlp",
                                                      "fwd"]
    assert meta[10]["source"] == "/repo/models/gpt.py:620"
    assert meta[10]["hlo_category"] == "convolution fusion"
    assert meta[11]["hlo_category"] == "data formatting"    # a ref_value
    assert (meta[20]["opcode"], meta[14]["opcode"], meta[22]["opcode"]) == (
        "while", "conditional", "custom-call")
    assert "tf_op" not in meta[16]                 # the compiler's own copy


def test_xmeta_reads_annotations_with_arguments(hand):
    notes = hand["_xmeta"]["annotations"]
    assert [a["name"] for a in notes][:4] == ["ds.round", "ds.gate",
                                              "ds.admit", "ds.build"]
    assert notes[0]["args"] == {"n": 1, "running": 2, "slots": 4,
                                "host_ns": 123456789}
    burst = [a for a in notes if a["name"] == "ds.burst_dispatch"][0]
    assert burst["args"] == {"steps": 2, "seqs": 3, "tokens": 6,
                             "ctx_tokens": 100}
    assert (burst["start_ns"], burst["end_ns"]) == (29000.0, 31000.0)


TRAIN_METRICS = {"train_fwd_device_ms": 0.002, "train_bwd_device_ms": 0.003,
                 "train_loss_device_ms": 0.001,
                 "train_grad_check_device_ms": 0.001,
                 "train_optimizer_device_ms": 0.002,
                 "train_unscoped_share": 10.0,
                 # per loop step of the one burst (2 steps)
                 "decode_attn_ms.batch": 0.005, "decode_mlp_ms.batch": 0.004,
                 "decode_pool_ms.batch": 0.001, "decode_other_ms.batch": 0.0,
                 # per execution of the mixed program
                 "mixed_attn_ms.batch": 0.0, "mixed_mlp_ms.batch": 0.004,
                 "mixed_pool_ms.batch": 0.0, "mixed_other_ms.batch": 0.002,
                 "serve_unscoped_share.batch": 100 * 2 / 26,
                 "sched_gap_schedule_ms.chat": 0.0015,
                 "sched_gap_h2d_ms.chat": 0.000375,
                 "sched_gap_launch_ms.chat": 0.000625,
                 "sched_slot_occupancy.chat": 62.5,
                 "decode_live_context_tokens.batch": 101.5,
                 "padding_waste_share.batch": 37.5}


@pytest.mark.parametrize("metric", sorted(TRAIN_METRICS))
def test_new_metric_reads_its_exact_value_from_the_hand_made_trace(
        hand, metric, capsys):
    import json
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        spec = json.load(f)
    reader = {"scope_time": scope_time, "sched_rounds": sched_rounds}[
        spec["reader"]]
    assert reader.read(hand, spec) == pytest.approx(TRAIN_METRICS[metric])


def test_scope_splits_close_on_the_hand_made_trace(hand):
    """A loop and a branch are containers, not work: the groups of a
    program add up to its busy time."""
    dev = hand["_xmeta"]["devices"]
    lo, hi = hand["trace_window"]
    train = scope_time.split(dev, lo, hi, lambda n: n == "train_batch")
    assert train["runs"] == 2 and sum(train["ns"].values()) == 20000.0
    burst = scope_time.split(dev, lo, hi,
                             lambda n: n.startswith("ragged_decode"))
    assert burst["loop_steps"] == 2
    assert sum(burst["ns"].values()) == 20000.0
    assert train["unscoped"] == {("copy", "", "f32[64,64]"): 2000.0}


def test_innermost_phase_pieces_tile_the_round(hand):
    notes = hand["_xmeta"]["annotations"]
    pieces = sched_rounds.innermost(notes)
    first = [p for p in pieces if p[1] < 40000]
    assert [p[0] for p in first] == [
        "ds.gate", "ds.admit", "ds.build", "ds.h2d", "ds.burst_dispatch",
        "ds.retire", "ds.materialize", "ds.retire"]
    assert first[0][1] == 23000.0 and first[-1][2] == 32000.0
    assert all(a[2] == b[1] for a, b in zip(first, first[1:]))


@pytest.mark.parametrize("reader,spec", [
    (scope_time, {"program": "@step_program", "what": "ms",
                  "groups": ["fwd"], "per": "run"}),
    (scope_time, {"program": "@step_program", "what": "unscoped_share"}),
    (sched_rounds, {"what": "slot_occupancy"}),
    (sched_rounds, {"what": "idle_ms", "phases": ["h2d"]})])
def test_a_program_without_scopes_or_spans_reads_nothing(reader, spec):
    """The trace recorded on the v5e before PR 26 (the parent's side of a
    traced run): metadata is there, no scope and no ``ds.*`` is."""
    dev = xmeta.device_ops(RECORDED)
    assert [m[0] for m in dev[0]["modules"]] == ["train_batch"] * 4
    fusion = [m for m in dev[0]["meta"].values() if m["name"] == "fusion"][0]
    assert fusion["tf_op"] == "jit(train_batch)/dot_general:"
    assert fusion["source"].endswith("record_small_trace.py:18")
    assert fusion["hlo_category"] == "convolution fusion"
    trace = xtrace.load(RECORDED)
    ctx = {"_xmeta": {"devices": dev,
                      "annotations": xmeta.annotations(RECORDED)},
           "trace": trace, "trace_window": xtrace.window_of(trace),
           "step_program": "train_batch"}
    assert ctx["_xmeta"]["annotations"] == []
    assert reader.read(ctx, spec) is None


def test_untraced_run_reads_nothing():
    class Off:
        dir, started_at = "/nonexistent", None
    for reader, spec in ((scope_time, {"program": "x", "what": "ms"}),
                         (sched_rounds, {"what": "padding_waste"})):
        assert reader.read({"tracer": Off()}, spec) is None


# ----------------------------------------------- one generate() call on the CPU

PHASES = {"ds.gate", "ds.idle_sleep", "ds.admit", "ds.build", "ds.h2d",
          "ds.mixed_dispatch", "ds.decode_dispatch", "ds.burst_dispatch",
          "ds.fence", "ds.retire", "ds.materialize"}


@pytest.fixture(scope="module")
def generate_trace(tmp_path_factory):
    cfg = GPTConfig.tiny(vocab_size=97, max_seq_len=64)
    eng = InferenceEngineV2(cfg, config={
        "dtype": "fp32", "telemetry": {"stream_sync": True},
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_batch_size": 64,
                          "kv_block_size": 8, "max_q_per_seq": 16}}, seed=0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, (9 + 5 * i,)).astype(np.int32)
               for i in range(5)]
    # the sixth ends so near max_seq_len that no burst fits: single decodes
    prompts.append(rng.integers(0, 97, (58,)).astype(np.int32))
    budgets = [12] * 5 + [5]
    eng.generate(prompts, max_new_tokens=budgets)        # compile
    ev0 = eng.telemetry.tracer.total_recorded
    notes = profiled(tmp_path_factory.mktemp("gen"), lambda: eng.generate(
        prompts, max_new_tokens=budgets,
        arrival_times=[0.0, 0.0, 0.01, 0.02, 0.3, 0.31]))
    events = list(eng.telemetry.tracer.events)[
        -(eng.telemetry.tracer.total_recorded - ev0):]
    return notes, events


def test_generate_rounds_are_tiled_by_their_phases(generate_trace):
    notes, _ = generate_trace
    rounds = [a for a in notes if a["name"] == "ds.round"]
    assert len(rounds) >= 5
    assert [r["args"]["n"] for r in rounds] == list(range(1, len(rounds) + 1))
    assert all(set(r["args"]) == {"n", "running", "waiting", "incoming",
                                  "slots", "host_ns"} for r in rounds)
    thread = rounds[0]["thread"]
    spans = [a for a in notes if a["thread"] == thread]
    assert {a["name"] for a in spans} - {"ds.round"} <= PHASES
    pieces = [p for p in sched_rounds.innermost(spans)]
    whole = sum(r["end_ns"] - r["start_ns"] for r in rounds)
    # What no phase covers is the SEAMS, where one span closes and the next
    # opens (some eight a round, 15-20 us each on an idle machine).  A seam
    # is the program's by what it costs EVERY time: a kind of seam (the
    # pieces before and after it) is charged its shortest occurrence times
    # its count, so that a stall that lands inside one occurrence (the
    # thread descheduled under a six-worker run's load: 5 ms seen among
    # 123 seams of 17 us under five competing processes, a 10% share needs
    # only 35 ms) is the machine's, while work done outside every phase is
    # long in every occurrence of its seam and still counted in full.
    seams = {}
    for i, (name, s, e) in enumerate(pieces):
        if name == "ds.round":
            kind = (pieces[i - 1][0] if i else None,
                    pieces[i + 1][0] if i + 1 < len(pieces) else None)
            seams.setdefault(kind, []).append(e - s)
    uncovered = sum(len(v) * min(v) for v in seams.values())
    assert 1 - uncovered / whole > 0.9, (uncovered / whole, seams)
    seen = {name for name, _, _ in pieces}
    assert {"ds.gate", "ds.admit", "ds.build", "ds.h2d", "ds.fence",
            "ds.retire", "ds.idle_sleep", "ds.materialize"} <= seen
    for r in rounds:                  # every phase lies inside its round
        inside = [a for a in spans if a["name"] != "ds.round"
                  and r["start_ns"] <= a["start_ns"] < r["end_ns"]]
        assert all(a["end_ns"] <= r["end_ns"] for a in inside)
        assert inside and inside[0]["name"] == "ds.gate"


def test_dispatch_spans_carry_what_a_reader_needs(generate_trace):
    notes, _ = generate_trace
    mixed = [a for a in notes if a["name"] == "ds.mixed_dispatch"]
    decode = [a for a in notes if a["name"] in ("ds.burst_dispatch",
                                                "ds.decode_dispatch")]
    assert mixed and decode
    for a in mixed:
        assert {"tokens", "bucket", "seqs", "ctx_tokens"} <= set(a["args"])
        assert 0 < a["args"]["tokens"] <= a["args"]["bucket"]
    for a in decode:
        assert {"tokens", "seqs", "ctx_tokens"} <= set(a["args"])
    burst = [a for a in decode if a["name"] == "ds.burst_dispatch"]
    assert all(a["args"]["tokens"] == a["args"]["steps"] * a["args"]["seqs"]
               for a in burst)
    gates = [a for a in notes if a["name"] == "ds.gate"]
    assert sum(a["args"]["released"] for a in gates) == 6


# One case per name of PERF.md section 3's span table, beside the two
# whole-picture tests above: a lost span or argument names itself.

ROUND_ARGS = ("n", "running", "waiting", "incoming", "slots", "host_ns")
PHASE_ARGS = {"ds.gate": ("released", "late_ms_max"), "ds.idle_sleep": (),
              "ds.admit": (), "ds.fence": (), "ds.retire": (),
              "ds.materialize": (), "ds.build": (), "ds.h2d": ()}
# what every dispatch span of a dense model carries (``_step_sampled``'s
# note and ``counter_note``'s running totals); a burst has no bucket, the
# other two no steps
DISPATCH_ARGS = {
    "ds.mixed_dispatch": ("tokens", "bucket", "seqs", "ctx_tokens",
                          "mixed_seqs", "one_row_seqs", "kv_bytes_per_token",
                          "qk_pairs", "one_row_slots", "ctx_tokens_one_row",
                          "prefill_items", "prefill_grid_items"),
    "ds.decode_dispatch": ("tokens", "bucket", "seqs", "ctx_tokens",
                           "mixed_seqs", "one_row_seqs",
                           "kv_bytes_per_token", "prefill_items",
                           "prefill_grid_items"),
    "ds.burst_dispatch": ("tokens", "steps", "seqs", "ctx_tokens",
                          "mixed_seqs", "one_row_seqs",
                          "kv_bytes_per_token", "prefill_items",
                          "prefill_grid_items")}


@pytest.mark.parametrize("arg", ROUND_ARGS)
def test_round_span_carries(generate_trace, arg):
    notes, _ = generate_trace
    rounds = [a for a in notes if a["name"] == "ds.round"]
    assert rounds and all(arg in r["args"] for r in rounds)
    assert all(float(r["args"][arg]) >= 0 for r in rounds)


@pytest.mark.parametrize("name", list(PHASE_ARGS))
def test_phase_span_is_emitted_with_its_arguments(generate_trace, name):
    notes, _ = generate_trace
    got = [a for a in notes if a["name"] == name]
    assert got, sorted({a["name"] for a in notes})
    assert all(set(PHASE_ARGS[name]) <= set(a["args"]) for a in got)


@pytest.mark.parametrize("name,arg", [(n, a) for n, args in
                                      DISPATCH_ARGS.items() for a in args])
def test_dispatch_span_carries(generate_trace, name, arg):
    notes, _ = generate_trace
    got = [a for a in notes if a["name"] == name]
    assert got, sorted({a["name"] for a in notes})
    assert all(arg in a["args"] for a in got), (name, arg)
    values = [float(a["args"][arg]) for a in got]
    # the running totals never fall; the others are counts of the step
    assert values == sorted(values) \
        if arg.endswith("_seqs") or arg.startswith("prefill_") \
        else min(values) >= 0


TRAIN_SPANS = {"ds.train_step": ("step", "host_ns"), "ds.batch_input": (),
               "ds.host_to_device": (), "ds.dispatch": (),
               "ds.step_bookkeeping": ()}


@pytest.fixture(scope="module")
def train_trace(train_engine, tmp_path_factory):
    ids = np.zeros((4, 64), np.int32)
    train_engine.train_batch({"input_ids": ids})          # compile
    return profiled(tmp_path_factory.mktemp("train"), lambda: [
        train_engine.train_batch({"input_ids": ids}) for _ in range(2)])


@pytest.mark.parametrize("name", list(TRAIN_SPANS))
def test_train_step_span_is_emitted_with_its_arguments(train_trace, name):
    got = [a for a in train_trace if a["name"] == name]
    assert len(got) == 2, sorted({a["name"] for a in train_trace})
    assert all(set(TRAIN_SPANS[name]) <= set(a["args"]) for a in got)
    if name != "ds.train_step":         # the phases lie inside their step
        steps = [a for a in train_trace if a["name"] == "ds.train_step"]
        assert all(any(s["start_ns"] <= a["start_ns"]
                       and a["end_ns"] <= s["end_ns"] for s in steps)
                   for a in got)


def test_host_ns_places_the_chrome_tracks_on_the_profiler_clock(
        generate_trace):
    """One offset maps the tracer's buffer (Chrome JSON, its own epoch) onto
    the profiler's timeline: ``host_ns`` is in both sinks."""
    notes, events = generate_trace
    rounds = [a for a in notes if a["name"] == "ds.round"]
    buffered = {e["args"]["host_ns"]: e for e in events
                if e["name"] == "round"}
    assert len(buffered) == len(rounds)
    offsets = [r["start_ns"] - buffered[r["args"]["host_ns"]]["ts"] * 1e3
               for r in rounds]
    assert max(offsets) - min(offsets) < 200_000        # ns: one offset
    assert any(e["name"] == "queue_wait" for e in events)
