"""The set-up account (``deepspeed_tpu/telemetry/startup.py``): jax's own
trace / lower / compile / cache-load durations, heard during the ONE jitted
call an engine makes anyway and booked to the program that paid them.

The guard that matters is ``test_no_work_twice``: an instrument of set-up
that traces or lowers a step program a second time to time it costs a
serving process its warm set-up again (PERF.md section 6, PR 40: PR 39 was
refused for +16 to +22 s in every serving cell)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import (MetricRegistry, SpanTracer,
                                     default_registry, setup_account)
from deepspeed_tpu.telemetry import startup
from deepspeed_tpu.telemetry.startup import ACCOUNT, SetupAccount

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"

SM = {"max_tracked_sequences": 4, "max_ragged_batch_size": 64,
      "kv_block_size": 8, "max_q_per_seq": 16}


def n_records():
    """Records ever closed (the account keeps the newest 1,024 and counts
    the rest: a worker that ran other files first may be past the bound)."""
    acc = setup_account()                       # the read closes what is open
    return acc["dropped_records"] + len(acc["records"])


def records_since(n):
    acc = setup_account()
    new = acc["dropped_records"] + len(acc["records"]) - n
    return acc["records"][-new:] if new else []


# ------------------------------------------------------- (a) a fresh jit

def fresh_jit():
    return jax.jit(lambda x: jnp.tanh(x) * 3 + jnp.cos(x))


def call_at_site(fn, x, program="probe", tracer=None, **key):
    """A dispatch site as the engines write it."""
    mark = ACCOUNT.booked
    out = fn(x)
    if ACCOUNT.booked != mark:
        ACCOUNT.close(program, mark, tracer, **key)
    return out


def test_a_fresh_jit_books_its_parts_and_one_record_a_shape():
    fn = fresh_jit()
    x8, x16 = jnp.ones(8), jnp.ones(16)         # made before the marks
    n = n_records()
    call_at_site(fn, x8, bucket=8)
    (rec,) = records_since(n)
    assert rec["program"] == "probe" and rec["bucket"] == 8
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    # compiled, or loaded where a test before left the executable in jax's
    # persistent cache: one of the two was paid
    assert rec["compile_s"] + rec["cache_load_s"] > 0
    assert rec["traces"] == 1 and rec["host_ns"] > 0 and rec["wall_s"] > 0
    call_at_site(fn, x16, bucket=16)            # a new shape: one more
    assert [r["bucket"] for r in records_since(n)] == [8, 16]


def test_the_second_call_of_a_shape_books_nothing():
    fn = fresh_jit()
    x = jnp.ones(8)
    call_at_site(fn, x)
    n, booked = n_records(), ACCOUNT.booked
    for _ in range(3):
        call_at_site(fn, x)
    assert ACCOUNT.booked == booked and n_records() == n


def test_what_was_compiled_before_the_mark_is_other():
    fn = fresh_jit()
    n = n_records()
    jnp.arange(7) * 1.5 + 2                     # one-op programs, no site
    x = jnp.ones(24)
    call_at_site(fn, x, bucket=24)
    got = records_since(n)
    assert [r["program"] for r in got] == ["other", "probe"]
    assert got[1]["traces"] == 1


def listeners():
    from jax._src import monitoring
    return ([cb for cb in monitoring.get_event_duration_listeners()
             if getattr(cb, "__self__", None) is ACCOUNT],
            [cb for cb in monitoring.get_event_listeners()
             if getattr(cb, "__self__", None) is ACCOUNT])


def test_a_second_engine_registers_no_second_listener(served):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    assert [len(x) for x in listeners()] == [1, 1]
    InferenceEngineV2(served.model_config,
                      {"dtype": "fp32", "state_manager": SM}, seed=4)
    assert [len(x) for x in listeners()] == [1, 1]


# -------------------------------------------------- (b) no work twice

@pytest.fixture(scope="module")
def served():
    """A tiny engine through the comparison's ``put`` calls and two warm
    ``generate`` calls: every site that calls a ``_steps`` jit."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPTConfig
    cfg = GPTConfig.tiny(vocab_size=97, max_seq_len=64, dropout=0.0,
                         dtype=jnp.float32)
    n = n_records()
    eng = InferenceEngineV2(cfg, {"dtype": "fp32", "state_manager": SM,
                                  "generation": {"do_sample": False}},
                            seed=3)
    eng.put([1, 2], [np.arange(5, dtype=np.int32),
                     np.arange(9, dtype=np.int32)])
    eng.put([1, 2], [np.array([3], np.int32), np.array([4], np.int32)])
    eng.flush([1, 2])
    prompts = [np.full(k, 7, np.int32) for k in (5, 20, 3)]
    eng.generate(prompts, max_new_tokens=6)
    eng.served_records = records_since(n)
    eng.prompts = prompts
    return eng


@pytest.fixture(scope="module")
def trained():
    import deepspeed_tpu
    from deepspeed_tpu.models import GPTChunkedLoss, GPTConfig
    from deepspeed_tpu.parallel.mesh import single_device_mesh
    T, rows = 32, 2
    cfg = GPTConfig.tiny(vocab_size=64, max_seq_len=T, dropout=0.0,
                         dtype=jnp.bfloat16, loss_chunk=16)
    n = n_records()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPTChunkedLoss(cfg),
        config={"train_micro_batch_size_per_gpu": rows,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2},
                # as the benchmark's train cells run: the tracer's buffer
                # is off, so the account itself has to hold the record
                "telemetry": {"enabled": False},
                "steps_per_print": 0, "seed": 5},
        example_batch={"input_ids": np.zeros((rows, T), np.int32)},
        mesh=single_device_mesh(jax.devices()[0]))
    rng = np.random.default_rng(1)
    for _ in range(3):
        engine.train_batch({"input_ids": rng.integers(
            0, 64, size=(rows, T)).astype(np.int32)})
    engine.trained_records = records_since(n)
    engine.batch = {"input_ids": rng.integers(
        0, 64, size=(rows, T)).astype(np.int32)}
    return engine


def step_records(records):
    return [r for r in records if r["program"] != "other"]


def test_no_work_twice_serving(served):
    """One trace a step program and shape: the traces booked to step
    programs are as many as the step jits hold compiled entries."""
    steps = step_records(served.served_records)
    programs = sum(f._cache_size() for f in served._steps.values())
    assert programs >= 4                # put_mixed, put_decode, mixed, burst
    assert sum(r["traces"] for r in steps) == programs == len(steps)
    assert {r["program"] for r in steps} >= {"put_mixed", "put_decode",
                                             "mixed", "burst"}


def test_no_work_twice_train(trained):
    steps = step_records(trained.trained_records)
    assert [r["program"] for r in steps] == ["train_batch"]
    assert steps[0]["traces"] == trained._jit_train_batch._cache_size() == 1
    assert steps[0]["step"] == 1


# ---------------------------------------------------- (c) the steady path

def test_the_steady_path_books_nothing_and_records_nothing(served, trained,
                                                           monkeypatch):
    served.generate(served.prompts, max_new_tokens=6)     # warm by now
    calls = []
    monkeypatch.setattr(SpanTracer, "record", lambda self, name, *a, **k:
                        calls.append(name))
    monkeypatch.setattr(SetupAccount, "close",
                        lambda self, *a, **k: calls.append("close"))
    booked = ACCOUNT.booked
    served.generate(served.prompts, max_new_tokens=6)
    trained.train_batch(trained.batch)
    assert ACCOUNT.booked == booked
    assert "close" not in calls and "program_setup" not in calls


# ------------------------------------------- where the records are kept

def test_the_record_is_mirrored_to_the_buffer_that_is_on(served, trained):
    mirrored = [e for e in served.telemetry.tracer.events
                if e["name"] == "program_setup"]
    steps = step_records(served.served_records)
    assert ([e["args"]["program"] for e in mirrored
             if e["args"]["program"] != "other"]
            == [r["program"] for r in steps])
    args = next(e for e in mirrored
                if e["args"]["program"] == "mixed")["args"]
    assert {"bucket", "table_width", "trace_s", "lower_s", "compile_s",
            "cache_load_s", "cache_hit", "host_ns"} <= set(args)
    # the train engine's buffer is off: the account alone holds its record
    assert not trained.telemetry.tracer.events
    assert step_records(trained.trained_records)


def test_the_seconds_go_to_the_default_registry(served):
    snap = default_registry.snapshot()["counters"]
    parts = {(s["labels"]["part"], s["labels"]["program"]): s["value"]
             for s in snap["setup_seconds_total"]["samples"]}
    assert parts[("trace", "mixed")] > 0 and parts[("lower", "burst")] > 0
    programs = {s["labels"]["program"]: s["value"]
                for s in snap["setup_programs_total"]["samples"]}
    assert programs["put_mixed"] >= 1 and programs["other"] >= 1


def test_the_engines_set_up_is_spans_that_book_themselves(served, trained):
    acc = setup_account()
    spans = {("inference_v2", "engine_init"), ("inference_v2", "init_params"),
             ("inference_v2", "init_cache"), ("train", "engine_init"),
             ("train", "init_state"), ("train", "init_optimizer")}
    assert spans <= {(s["engine"], s["part"]) for s in acc["init_spans"]}
    whole = {s["part"]: s["seconds"] for s in acc["init_spans"]
             if s["engine"] == "train"}
    assert whole["engine_init"] >= whole["init_state"] > 0
    buffered = {e["name"]: e["args"] for e in served.telemetry.tracer.events
                if e["name"] in ("engine_init", "init_params", "init_cache")}
    assert set(buffered) == {"engine_init", "init_params", "init_cache"}
    assert all(a["host_ns"] > 0 for a in buffered.values())
    gauges = default_registry.snapshot()["gauges"]
    assert {(s["labels"]["engine"], s["labels"]["part"])
            for s in gauges["init_seconds"]["samples"]} >= spans
    assert acc["import_seconds"] > 0     # its gauge: the subprocess test below


def test_the_snapshot_exporter_carries_the_counters(served):
    from deepspeed_tpu.telemetry import SnapshotExporter
    text = SnapshotExporter(default_registry).prometheus_text()
    for name in ("setup_seconds_total", "setup_programs_total",
                 "init_seconds"):
        assert name in text


# ----------------------------------------------- the account on its own

def account():
    return SetupAccount(registry=MetricRegistry())


def test_nested_traces_are_one_trace():
    # an inner jit's trace event ends inside the outer's interval
    ev = [("trace", 0.2, 10.3), ("trace", 0.1, 10.5), ("trace", 1.0, 10.9),
          ("lower", 0.5, 11.5), ("backend_compile", 2.0, 13.6)]
    s = startup._sum_parts(ev)
    assert s["traces"] == 1 and s["trace"] == 1.0
    assert s["lower"] == 0.5 and s["compile"] == 2.0


def test_a_trace_inside_lowering_is_lowering():
    ev = [("trace", 1.0, 11.0), ("trace", 0.1, 11.3), ("lower", 0.5, 11.6)]
    s = startup._sum_parts(ev)
    assert s["traces"] == 1 and s["trace"] == 1.0 and s["lower"] == 0.5


def test_sequential_programs_are_not_nested():
    ev = [("trace", 0.00005, 10.00005), ("trace", 1.0, 11.0001)]
    assert startup._sum_parts(ev)["traces"] == 2


def test_on_a_hit_compile_is_backend_less_the_load():
    # jax 0.9.0: backend_compile_duration wraps the cache read
    ev = [("trace", 1.0, 11.0), ("lower", 0.5, 11.6), ("hits", 0.0, 11.9),
          ("time_saved", 30.0, 11.9), ("cache_load", 0.3, 11.9),
          ("backend_compile", 0.35, 11.95)]
    s = startup._sum_parts(ev)
    assert s["cache_load"] == 0.3 and abs(s["compile"] - 0.05) < 1e-9
    assert s["hits"] == 1 and s["time_saved"] == 30.0


def test_close_splits_at_the_mark_and_unknown_events_are_not_booked():
    acc = account()
    acc.on_duration(TRACE, 0.001)
    acc.on_duration("/jax/some/other_duration", 5.0)
    acc.on_event("/jax/some/other_event")
    assert acc.booked == 1
    mark = acc.booked
    for name, s in ((TRACE, 0.002), (LOWER, 0.001), (LOAD, 0.0005),
                    (BACKEND, 0.001), (SAVED, 3.0)):
        acc.on_duration(name, s)
    acc.on_event(HIT)
    acc.close("decode", mark, None, bucket=4)
    other, mine = acc.records
    assert other["program"] == "other" and other["traces"] == 1
    assert mine["program"] == "decode" and mine["bucket"] == 4
    assert mine["cache_hit"] and mine["hits"] == 1 and mine["misses"] == 0
    assert mine["time_saved_s"] == 3.0 and mine["wall_s"] < 1.0     # a
    # saving is not an interval: it does not move the record's start
    assert acc.booked == 7 and not acc._open
    acc.on_event(MISS)
    d = acc.as_dict()                   # a read closes what is open
    assert d["misses"] == 1 and d["hits"] == 1
    assert d["by_program"]["decode"]["programs"] == 1
    assert d["by_program"]["other"]["programs"] == 2


def test_a_mark_that_cuts_a_saving_off_still_closes():
    # another thread's compile can straddle a site's mark
    acc = account()
    acc.on_duration(SAVED, 3.0)
    acc.close("decode", acc.booked)
    (rec,) = acc.records
    assert rec["program"] == "other" and rec["wall_s"] == 0.0


def test_the_records_are_bounded():
    acc = account()
    for i in range(startup.MAX_RECORDS + 5):
        acc.on_duration(TRACE, 0.0)
        acc.close("probe", i)
    assert len(acc.records) == startup.MAX_RECORDS
    assert acc.dropped_records == 5


def test_an_init_span_books_with_the_buffer_off():
    tracer = SpanTracer(enabled=False)
    with startup.init_span(tracer, "init_cache", "probe"):
        pass
    span = ACCOUNT.init_spans[-1]
    assert span["engine"] == "probe" and span["part"] == "init_cache"
    assert span["seconds"] >= 0 and span["host_ns"] > 0
    assert not tracer.events


# ------------------------------ what the account's first reading found

def test_an_engine_from_a_config_does_not_import_the_checkpoint_package():
    """``ds.engine_init`` read 15.2 s on the chip with 0.13 s inside
    ``ds.init_params`` and ``ds.init_cache``: the rest was
    ``deepspeed_tpu.checkpoint`` (orbax, google.cloud.logging) imported to
    ask whether a ``GPTConfig`` is a model directory."""
    import os
    import subprocess
    import sys
    code = (
        "import sys, jax.numpy as jnp\n"
        "from deepspeed_tpu.inference.v2 import InferenceEngineV2\n"
        "from deepspeed_tpu.models import GPTConfig\n"
        "cfg = GPTConfig.tiny(vocab_size=97, max_seq_len=64, dropout=0.0,"
        " dtype=jnp.float32)\n"
        f"InferenceEngineV2(cfg, {{'dtype': 'fp32', 'state_manager': {SM}}})\n"
        "bad = [m for m in sys.modules if m.split('.')[0] == 'orbax' or m in"
        " ('deepspeed_tpu.checkpoint', 'google.cloud.logging')]\n"
        "assert not bad, bad[:5]\n"
        # (the gauge of the package's import, here where no other test has
        # reset the process-wide registry)
        "from deepspeed_tpu.telemetry import (SnapshotExporter,"
        " default_registry, setup_account)\n"
        "g = default_registry.snapshot()['gauges']['import_seconds']\n"
        "assert g['samples'][0]['value'] =="
        " setup_account()['import_seconds'] > 0\n"
        "assert 'import_seconds' in"
        " SnapshotExporter(default_registry).prometheus_text()\n")
    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=75,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo})
    assert out.returncode == 0, out.stderr[-2000:]
