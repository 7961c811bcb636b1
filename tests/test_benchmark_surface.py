"""The seam between the program and the benchmark.

``benchmark/run.py``, ``benchmark/runners/*.py`` and
``benchmark/tools/sched_replay.py`` import, call and index names of the
program; the readers take the spans, arguments and counters of ``PERF.md``
section 3's table.  ``benchmark/tests/`` is outside tier-1, so a rename in
the program would pass here and show on the chip as a ``null`` per-layer
metric or a cell that fails.  Each case below reads ONE such name the way
the line of the benchmark that uses it does, on tiny engines on the CPU: a
lost name fails the case that carries it and no other.

(a) ``RUNNER_READS`` / ``TRAIN_READS`` / ``REPLAY_READS``: what the runners
    and the replay tool use;
(b) ``COUNTERS``: the counters of the table, by registry name and labels;
(c) the records that exist only in the tracer's buffer;
(d) ``AFMOE_SPAN_ARGS``: the dispatch-span arguments that only a model with
    experts and window layers writes;
(e) ``LATENT_READS``: what ``benchmark/readers/latent.py`` and
    ``benchmark/tools/mla_compare.py`` take of a model with latent attention;
(f) ``SETUP_READS``: the set-up account's counters, ``program_setup`` record
    and init spans, as ``benchmark/readers/setup_account.py`` takes them
    through ``deepspeed_tpu.telemetry.setup_account()`` (PR 40);
(g) ``SELECTING_READS``: how often a selecting model's prompt chunks take
    the masked prefill kernel, as a counter and in the dispatch spans (PR
    43; no manifest entry reads them yet, a ``benchmark`` issue may).
(h) ``BLOCK_READS``: what ``benchmark/readers/sala.py``, ``span_counters.py``
    and the serving runner take of a model that selects its keys by blocks
    (PR 57): the rows by path, the kept blocks and pairs, each dispatch's
    own needs.
The other spans and their arguments are held by ``tests/test_one_clock.py``."""

import dataclasses
import inspect
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, os.path.join(BENCH, "readers"),
          os.path.join(BENCH, "reference")):
    if p not in sys.path:
        sys.path.insert(0, p)

import _afmoe  # noqa: E402  (the benchmark's reference: its program_config)
import xmeta  # noqa: E402
import xtrace  # noqa: E402


# ------------------------------------------------------------ serving runner

SM = {"max_tracked_sequences": 4, "max_ragged_batch_size": 64,
      "kv_block_size": 8, "max_q_per_seq": 16}


def _setup_records_ever(acc):
    """The set-up account keeps its newest 1,024 records and counts the rest:
    a worker that ran other files first may be past the bound."""
    return acc["dropped_records"] + len(acc["records"])


def _setup_records_since(acc, n):
    new = _setup_records_ever(acc) - n
    return acc["records"][-new:] if new else []


@pytest.fixture(scope="module")
def served():
    """One tiny engine built and driven line for line as
    ``benchmark/runners/serve.py:run`` does it: weights through
    ``GPTLogits`` / ``unbox``, the engine's config dict, ``put`` / ``flush``
    for the comparison, a warm call on a dispatch clock, a streamed
    open-loop window, then a window that a drain cuts."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.engine_v2 import EngineDrained
    from deepspeed_tpu.models import GPTConfig
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.ops.registry import dispatch_log, reset_dispatch_log
    from deepspeed_tpu.parallel.metadata import unbox

    model_cfg = GPTConfig.tiny(vocab_size=97, max_seq_len=64, dropout=0.0,
                               dtype=jnp.float32)
    lm = GPTLogits(dataclasses.replace(model_cfg, param_dtype=jnp.float32))
    params = jax.jit(lambda key: unbox(lm.init(
        key, jnp.zeros((1, 8), jnp.int32)))["params"])(jax.random.PRNGKey(3))
    reset_dispatch_log()
    cleared = list(dispatch_log())
    from deepspeed_tpu.telemetry import setup_account
    setup0 = _setup_records_ever(setup_account())
    eng = InferenceEngineV2(
        model_cfg,
        {"dtype": "fp32", "state_manager": SM,
         "generation": {"do_sample": False},
         "telemetry": {"stream_sync": True}},
        params=params, seed=3)
    o = {"eng": eng, "cleared": cleared, "EngineDrained": EngineDrained,
         "paged_impl": eng.paged_impl, "block_size": eng.state.block_size,
         "vocab": model_cfg.vocab_size}

    rng = np.random.default_rng(0)
    seq = rng.integers(0, 97, size=13).astype(np.int32)
    o["put_prefill"] = eng.put([1], [seq[:12]])
    o["put_decode"] = eng.put([1], [seq[12:13]])
    eng.flush([1])
    o["tracked_after_flush"] = list(eng.state.tracked)

    # the warm call's clock: the engine's own count of mixed dispatches
    calls = [0]

    def clock():
        calls[0] += 1
        return eng.telemetry.c_dispatch.value(kind="mixed") + calls[0] * 1e-7

    eng.generate([np.full(n, 7, np.int32) for n in (12, 9)],
                 max_new_tokens=[3, 1], arrival_times=[0.0, 0.5],
                 now_fn=clock, stream=False)
    o["clock_calls"] = calls[0]
    o["programs"] = sum(f._cache_size() for f in eng._steps.values())
    o["setup_records"] = _setup_records_since(setup_account(), setup0)

    # ---- the window, as the runner opens and reads it
    prompts = [rng.integers(0, 97, (9 + 5 * i,)).astype(np.int32)
               for i in range(5)] + [rng.integers(0, 97, (58,))
                                     .astype(np.int32)]
    max_new = [12] * 5 + [5]        # the last: no burst fits its context
    o["prompts"], o["max_new"] = prompts, max_new
    log0 = len(eng.telemetry.request_log)
    ev0 = eng.telemetry.tracer.total_recorded
    counters0 = {k: eng.telemetry.c_dispatch.value(kind=k)
                 for k in ("mixed", "decode", "burst")}
    tokens0 = {p: eng.telemetry.c_tokens.value(phase=p)
               for p in ("prefill", "decode")}
    o["outs"] = eng.generate(
        prompts, max_new_tokens=max_new,
        arrival_times=[0.0, 0.0, 0.01, 0.02, 0.3, 0.31], stream=True)
    o["log"] = {-(r["uid"]) - 1: r
                for r in eng.telemetry.request_log[log0:]}
    events = list(eng.telemetry.tracer.events)
    o["events"] = events[-(eng.telemetry.tracer.total_recorded - ev0):]
    o["dispatches"] = {k: eng.telemetry.c_dispatch.value(kind=k) - v
                       for k, v in counters0.items()}
    o["tokens"] = {p: eng.telemetry.c_tokens.value(phase=p) - v
                   for p, v in tokens0.items()}

    # ---- a closed list that the drain cuts after its first tokens
    seen = [0]

    def draining_clock():
        seen[0] += 1
        if eng.telemetry.c_tokens.value(phase="decode") \
                - tokens0["decode"] - o["tokens"]["decode"] >= 8:
            eng.request_drain()
        return float(seen[0])

    try:
        eng.generate(prompts[:5], max_new_tokens=[30] * 5,
                     now_fn=draining_clock, stream=False)
        o["drained"] = False
    except EngineDrained:
        o["drained"] = True
        o["completed"], o["pending"] = eng.export_pending_requests()
        eng.clear_drain()
    o["after_drain"] = eng.generate(prompts[:1], max_new_tokens=2)
    o["kernels"] = list(dispatch_log())
    return o


def _uids_index_the_requests(o):
    assert sorted(o["log"]) == list(range(len(o["prompts"])))


def _generated_tokens(o):
    for i, r in o["log"].items():
        assert r["generated_tokens"] == len(o["outs"][i]) == o["max_new"][i]


def _outcome(o):
    assert {r["outcome"] for r in o["log"].values()} == {"completed"}


def _latency(key):
    def read(o):
        assert o["log"] and all(r[key] > 0 for r in o["log"].values())
    return read


def _dispatch_kind(kind):
    def read(o):
        assert set(o["dispatches"]) == {"mixed", "decode", "burst"}
        assert o["dispatches"][kind] >= 1, o["dispatches"]
    return read


def _token_phase(phase):
    def read(o):
        if phase == "prefill":      # every prompt token is scheduled once
            assert o["tokens"][phase] == sum(len(p) for p in o["prompts"])
        else:       # at least every answer token after a request's first
            assert o["tokens"][phase] >= (sum(o["max_new"])
                                          - len(o["prompts"]))
    return read


def _events_window(o):
    assert o["events"] and all(
        {"name", "ts", "dur", "args"} <= set(e) for e in o["events"])
    eng = o["eng"]
    assert eng.telemetry.tracer.total_recorded >= len(o["events"])


def _dispatch_events_in_the_buffer(o):
    starts = sorted(e["ts"] for e in o["events"]
                    if e["name"].endswith("_dispatch"))
    assert len(starts) == sum(o["dispatches"].values())
    assert max(b - a for a, b in zip(starts, starts[1:])) > 0


def _stream_sync(o):
    assert o["eng"].config.telemetry.stream_sync is True
    params = inspect.signature(o["eng"].generate).parameters
    assert {"max_new_tokens", "arrival_times", "now_fn",
            "stream"} <= set(params)


def _now_fn(o):
    assert o["clock_calls"] >= 2        # the gate asked the runner's clock


def _drain(o):
    assert o["drained"]
    assert issubclass(o["EngineDrained"], RuntimeError)


def _export_pending(o):
    completed, pending = o["completed"], o["pending"]
    generated = (sum(len(g) for g in completed.values())
                 + sum(len(p["generated"]) for p in pending))
    assert len(completed) + len(pending) == 5 and generated >= 8
    assert all("generated" in p for p in pending)


def _clear_drain(o):
    assert len(o["after_drain"]) == 1 and len(o["after_drain"][0]) == 2


def _dispatch_log(o):
    assert o["cleared"] == []
    ops = {d["op"] for d in o["kernels"]}
    assert {"paged_attention", "ragged_prefill_attention"} <= ops, ops
    assert all(d["impl"] in ("pallas", "xla") for d in o["kernels"])


def _put_and_flush(o):
    assert np.asarray(o["put_prefill"]).shape == (1, o["vocab"])
    assert np.asarray(o["put_decode"]).shape == (1, o["vocab"])
    assert o["tracked_after_flush"] == []


def _engine_attributes(o):
    assert o["paged_impl"] in ("pallas", "xla")
    assert o["block_size"] == SM["kv_block_size"]
    jax.block_until_ready(o["eng"].cache.k)
    assert o["programs"] >= 2


RUNNER_READS = {
    "telemetry.c_dispatch.value(kind=mixed)": _dispatch_kind("mixed"),
    "telemetry.c_dispatch.value(kind=decode)": _dispatch_kind("decode"),
    "telemetry.c_dispatch.value(kind=burst)": _dispatch_kind("burst"),
    "telemetry.c_tokens.value(phase=prefill)": _token_phase("prefill"),
    "telemetry.c_tokens.value(phase=decode)": _token_phase("decode"),
    "request_log[uid]": _uids_index_the_requests,
    "request_log[generated_tokens]": _generated_tokens,
    "request_log[outcome]": _outcome,
    "request_log[ttft_ms]": _latency("ttft_ms"),
    "request_log[tpot_ms]": _latency("tpot_ms"),
    "tracer.events,total_recorded": _events_window,
    "tracer.events[*_dispatch].ts": _dispatch_events_in_the_buffer,
    "config telemetry.stream_sync,generate(stream=)": _stream_sync,
    "generate(arrival_times=,now_fn=)": _now_fn,
    "request_drain,EngineDrained": _drain,
    "export_pending_requests": _export_pending,
    "clear_drain": _clear_drain,
    "ops.registry.dispatch_log,reset_dispatch_log": _dispatch_log,
    "put,flush": _put_and_flush,
    "paged_impl,state.block_size,cache.k,_steps": _engine_attributes,
}


@pytest.mark.parametrize("name", list(RUNNER_READS))
def test_serving_runner_reads(served, name):
    RUNNER_READS[name](served)


# ----------------------------------------------- (c) records of the buffer

@pytest.mark.parametrize("name", ["queue_wait", "prefill", "decode"])
def test_request_records_in_the_buffer(served, name):
    got = [e for e in served["events"] if e["name"] == name]
    n = len(served["prompts"])
    # the runner keys the waits by request: -(uid) - 1, dur in us
    assert sorted(-(e["args"]["uid"]) - 1 for e in got) == list(range(n))
    assert all(e["dur"] >= 0 for e in got)


# ------------------------------------------------------------- train runner

@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """``benchmark/runners/train.py:run`` at a tiny size, with telemetry's
    buffer on for the one record it reads nowhere else
    (``checkpoint_write``)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPTChunkedLoss, GPTConfig
    from deepspeed_tpu.ops.registry import dispatch_log, reset_dispatch_log
    from deepspeed_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                             single_device_mesh)

    T, rows = 64, 4
    model_cfg = GPTConfig.tiny(vocab_size=128, max_seq_len=T, dropout=0.0,
                               dtype=jnp.bfloat16, remat=False,
                               loss_chunk=32)
    ds_config = {
        "train_micro_batch_size_per_gpu": rows,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "overlap": {"enabled": False},
        "telemetry": {"enabled": True, "trace_enabled": True,
                      "snapshot_interval": 0},
        "steps_per_print": 0, "seed": 5}
    devices = jax.devices()
    reset_dispatch_log()
    from deepspeed_tpu.telemetry import setup_account
    setup0 = _setup_records_ever(setup_account())
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPTChunkedLoss(model_cfg), config=ds_config,
        example_batch={"input_ids": np.zeros((rows, T), np.int32)},
        mesh=single_device_mesh(devices[0]))
    jax.block_until_ready(engine.state.params)
    # as the runner reads them for its reference, before any step
    masters = engine.state.params
    masters = masters.get("params", masters)
    master_sizes = [int(np.prod(x.shape))
                    for x in jax.tree_util.tree_leaves(masters)]
    del masters
    rng = np.random.default_rng(1)

    def batches():
        while True:
            yield {"input_ids": rng.integers(0, 128, (rows, T))
                   .astype(np.int32)}
    it0 = batches()
    m = engine.train_batch(next(it0))
    loader = engine.prefetch_loader(it0)
    it = iter(loader)
    try:
        m2 = engine.train_batch(next(it))
        jax.block_until_ready(m2.loss)
    finally:
        loader.close()
    engine.save_checkpoint(str(tmp_path_factory.mktemp("ckpt")))
    engine.wait_for_checkpoint()
    return {"engine": engine, "first_loss": float(m.loss),
            "master_sizes": master_sizes, "attn": [d for d in dispatch_log()
                                         if d["op"] == "causal_attention"],
            "mesh4": build_mesh(MeshSpec(dp=1, fsdp=len(devices[:4])),
                                devices=devices[:4]),
            "events": list(engine.telemetry.tracer.events),
            "setup_records": _setup_records_since(setup_account(), setup0)}


def _train_loss(t):
    assert np.isfinite(t["first_loss"])


def _train_masters(t):
    assert t["master_sizes"] and min(t["master_sizes"]) > 0


def _train_attention_dispatch(t):
    assert t["attn"] and all("impl" in d for d in t["attn"])


def _train_num_parameters(t):
    assert int(t["engine"].num_parameters) == sum(t["master_sizes"])


def _train_mesh(t):
    assert dict(t["mesh4"].shape)["fsdp"] == min(4, len(jax.devices()))
    assert t["engine"].mesh.devices.size == 1


def _checkpoint_write(t):
    got = [e for e in t["events"] if e["name"] == "checkpoint_write"]
    assert len(got) == 1 and got[0]["dur"] > 0
    assert got[0]["args"]["op"] == "save"


TRAIN_READS = {
    "initialize(mesh=),train_batch().loss": _train_loss,
    "engine.state.params": _train_masters,
    "dispatch_log[causal_attention]": _train_attention_dispatch,
    "engine.num_parameters": _train_num_parameters,
    "MeshSpec,build_mesh(devices=),single_device_mesh": _train_mesh,
    "record checkpoint_write": _checkpoint_write,
}


@pytest.mark.parametrize("name", list(TRAIN_READS))
def test_train_runner_reads(trained, name):
    TRAIN_READS[name](trained)


def test_run_py_places_the_compile_cache_through_the_program(monkeypatch,
                                                              tmp_path):
    """``benchmark/run.py`` calls ``enable_compilation_cache()`` with no
    argument and prints what it returns."""
    from deepspeed_tpu.runtime.resilience import enable_compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert enable_compilation_cache() == str(tmp_path)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


# ------------------------------- (b) counters of the afmoe configuration's

@pytest.fixture(scope="module")
def afmoe_served(tmp_path_factory):
    """A tiny afmoe engine (a held share of sigmoid-routed experts, window
    layers and a global one) built from the benchmark reference's
    ``program_config``, after a short ``generate()`` past the window under
    a profiler session: (telemetry, engine, the trace's annotations)."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPTConfig
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.parallel.metadata import unbox

    period = ["sliding_attention"] * 3 + ["full_attention"]
    sizes = dict(
        model_type="afmoe", hidden_act="silu", hidden_size=32,
        intermediate_size=64, moe_intermediate_size=24,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_hidden_layers=5, num_dense_layers=1,
        layers_kept=[0, 8, 9, 10, 11], layer_types=period * 3,
        num_experts=4, router_width=16, expert_offset=4,
        num_experts_per_tok=4, num_shared_experts=1, n_group=1,
        rms_norm_eps=1e-5, rope_theta=10000, route_norm=True,
        route_scale=2.448, score_func="sigmoid", mup_enabled=True,
        sliding_window=12, tie_word_embeddings=False, vocab_size=96)
    cfg = GPTConfig(**_afmoe.program_config(sizes), max_seq_len=128)
    params = unbox(GPTLogits(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    eng = InferenceEngineV2(cfg, {
        "dtype": "float32", "generation": {"do_sample": False},
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 32, "max_q_per_seq": 8,
                          "kv_block_size": 4, "num_kv_blocks": 64,
                          "num_kv_window_blocks": 24}}, params=params)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 96, size=n) for n in (30, 9, 17)]
    # a first call, so that every traced span carries the MoE totals
    # (``counter_note`` leaves them out until the device has reported)
    eng.generate(prompts, max_new_tokens=10)
    trace_dir = str(tmp_path_factory.mktemp("afmoe"))
    with jax.profiler.trace(trace_dir):
        eng.generate(prompts, max_new_tokens=10)
    notes = xmeta.annotations(xtrace.find_xplane(trace_dir))
    return eng.telemetry, eng, notes


COUNTERS = {
    # name -> (engine, the labels a reader or the runner asks for)
    "serving_dispatches_total{kind=mixed}": ("dense", {"kind": "mixed"}),
    "serving_dispatches_total{kind=decode}": ("dense", {"kind": "decode"}),
    "serving_dispatches_total{kind=burst}": ("dense", {"kind": "burst"}),
    "serving_tokens_total{phase=prefill}": ("dense", {"phase": "prefill"}),
    "serving_tokens_total{phase=decode}": ("dense", {"phase": "decode"}),
    "serving_mixed_slots_total": ("dense", {}),
    "serving_one_row_slots_total": ("dense", {}),
    "serving_prefill_items_total": ("dense", {}),
    "serving_prefill_grid_items_total": ("dense", {}),
    "moe_assignments_total": ("afmoe", {}),
    "moe_local_assignments_total": ("afmoe", {}),
    "moe_experts_touched_total": ("afmoe", {}),
    "kv_pages_in_use{group=global}": ("afmoe", {"group": "global"}),
    "kv_pages_in_use{group=window}": ("afmoe", {"group": "window"}),
    "kv_pages_released_total{group=window}": ("afmoe", {"group": "window"}),
    "kv_pages_allocated_total{group=window}": ("afmoe",
                                               {"group": "window"}),
}


@pytest.mark.parametrize("name", list(COUNTERS))
def test_counter_of_the_table_by_name_and_labels(served, afmoe_served, name):
    which, labels = COUNTERS[name]
    tel = served["eng"].telemetry if which == "dense" else afmoe_served[0]
    metric = tel.registry._metrics[name.split("{")[0]]
    series = [dict(k) for k, _ in metric.samples()]
    assert any(labels.items() <= s.items() for s in series), series
    value = metric.value(**labels, **tel.labels)
    if name.startswith("kv_pages_in_use"):
        assert value >= 0           # a gauge: 0 again once the list is done
    else:
        assert value > 0, (name, series)


def test_moe_and_window_totals_are_consistent(afmoe_served):
    tel, eng, _ = afmoe_served
    assign = tel.c_moe_assign.value(**tel.labels)
    local = tel.c_moe_local.value(**tel.labels)
    assert 0 < local <= assign
    released = tel.c_kv_released.value(group="window", **tel.labels)
    allocated = tel.c_kv_allocated.value(group="window", **tel.labels)
    assert 0 < released <= allocated <= eng.state.w_allocated_total


# ------------- (d) span arguments of a model with experts and window layers

AFMOE_SPAN_ARGS = [
    # what window_rooflines takes of a mixed step ...
    ("ds.mixed_dispatch", "ctx_tokens_window"),
    ("ds.mixed_dispatch", "qk_pairs"),
    ("ds.mixed_dispatch", "qk_pairs_window"),
    # ... and of the decode programs
    ("ds.burst_dispatch", "ctx_tokens_window"),
    # span_counters: the running totals in every dispatch span
    ("ds.mixed_dispatch", "moe_assign"),
    ("ds.mixed_dispatch", "moe_local"),
    ("ds.mixed_dispatch", "moe_touched"),
    ("ds.burst_dispatch", "moe_assign"),
    ("ds.burst_dispatch", "moe_local"),
    ("ds.burst_dispatch", "moe_touched"),
    ("ds.mixed_dispatch", "kvw_allocated"),
    ("ds.mixed_dispatch", "kvw_released"),
    ("ds.burst_dispatch", "kvw_allocated"),
    ("ds.burst_dispatch", "kvw_released"),
    # (of those, the pages a burst's reservation gave back: PR 53)
    ("ds.mixed_dispatch", "kvw_released_decode"),
    ("ds.burst_dispatch", "kvw_released_decode"),
    ("ds.mixed_dispatch", "kv_pages_window"),
    ("ds.mixed_dispatch", "kv_pages_global"),
    # span_counters: the prefill kernel's live work items and its grid's
    ("ds.mixed_dispatch", "prefill_items"),
    ("ds.mixed_dispatch", "prefill_grid_items"),
    ("ds.burst_dispatch", "prefill_items"),
    ("ds.burst_dispatch", "prefill_grid_items"),
]


@pytest.mark.parametrize("span,arg", AFMOE_SPAN_ARGS)
def test_afmoe_dispatch_span_carries(afmoe_served, span, arg):
    notes = afmoe_served[2]
    got = [a for a in notes if a["name"] == span]
    assert got, sorted({a["name"] for a in notes})
    assert all(arg in a["args"] for a in got), (span, arg)
    values = [float(a["args"][arg]) for a in got]
    if arg.startswith(("moe_", "kvw_", "prefill_")):    # totals never fall
        assert values == sorted(values) and values[-1] > 0
    else:
        assert min(values) >= 0


# --------------- (e) a model with latent attention over a latent page pool

@pytest.fixture(scope="module")
def latent_served(tmp_path_factory):
    """A tiny deepseek_v3 engine (latent attention, every expert held) built
    from the benchmark reference's ``program_config``, after a short
    ``generate()`` under a profiler session: (engine, annotations)."""
    import _deepseek_mla
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPTConfig
    sizes = dict(
        model_type="deepseek_v3", hidden_act="silu", hidden_size=32,
        intermediate_size=64, moe_intermediate_size=24,
        num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, kv_lora_rank=128,
        q_lora_rank=None, num_hidden_layers=3, first_k_dense_replace=1,
        moe_layer_freq=1, n_routed_experts=8, num_experts_per_tok=3,
        n_shared_experts=2, n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.446, scoring_func="sigmoid",
        rms_norm_eps=1e-5, rope_theta=50000, attention_bias=False,
        ep_size=1, tie_word_embeddings=False, vocab_size=96)
    cfg = GPTConfig(**_deepseek_mla.program_config(sizes), max_seq_len=128)
    eng = InferenceEngineV2(cfg, {
        "dtype": "float32", "generation": {"do_sample": False},
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 32, "max_q_per_seq": 8,
                          "kv_block_size": 4, "num_kv_blocks": 64}}, seed=0)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 96, size=n) for n in (30, 9, 17)]
    eng.generate(prompts, max_new_tokens=10)
    trace_dir = str(tmp_path_factory.mktemp("latent"))
    with jax.profiler.trace(trace_dir):
        eng.generate(prompts, max_new_tokens=10)
    return eng, xmeta.annotations(xtrace.find_xplane(trace_dir))


def _latent_span_arg(span, arg):
    def read(o):
        eng, notes = o
        got = [a for a in notes if a["name"] == span]
        assert got and all(arg in a["args"] for a in got), (span, arg)
        if arg.startswith("prefill_"):      # live items within the grid's
            assert all(0 < float(a["args"]["prefill_items"])
                       < float(a["args"]["prefill_grid_items"]) for a in got)
        if arg == "kv_bytes_per_token":     # 3 layers x 256 columns x fp32
            assert {float(a["args"][arg]) for a in got} == {3 * 256 * 4.0}
            assert eng.telemetry.value("kv_bytes_per_token") == 3 * 256 * 4
    return read


def _latent_model_cfg(o):
    cfg = o[0].model_config                # the runner's ctx["model_cfg"]
    assert (cfg.kv_lora_rank, cfg.latent_dim, cfg.latent_page_dim,
            cfg.num_layers, cfg.num_heads) == (128, 136, 256, 3, 4)


def _latent_put_with_routes(o):
    logits, routes = o[0].put([9], [np.arange(5, dtype=np.int32)],
                              with_routes=True)
    o[0].flush([9])
    assert logits.shape == (1, 96) and routes[0].shape == (2, 5, 3)


LATENT_READS = {
    **{f"{span}.{arg}": _latent_span_arg(span, arg) for span, arg in [
        ("ds.mixed_dispatch", "kv_bytes_per_token"),
        ("ds.burst_dispatch", "kv_bytes_per_token"),
        ("ds.mixed_dispatch", "qk_pairs"),
        ("ds.mixed_dispatch", "one_row_slots"),
        ("ds.mixed_dispatch", "ctx_tokens_one_row"),
        ("ds.mixed_dispatch", "moe_local"),
        ("ds.burst_dispatch", "moe_touched"),
        ("ds.mixed_dispatch", "prefill_items"),
        ("ds.mixed_dispatch", "prefill_grid_items")]},
    "model_cfg.kv_lora_rank,latent_dim,num_layers,num_heads":
        _latent_model_cfg,
    "eng.put(with_routes=True)": _latent_put_with_routes,
}


@pytest.mark.parametrize("name", list(LATENT_READS))
def test_latent_reads(latent_served, name):
    LATENT_READS[name](latent_served)


# --------------------------------------------------- tools/sched_replay.py

def _sig(fn):
    return list(inspect.signature(fn).parameters)


def _replay_step_sampled(served):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    assert _sig(InferenceEngineV2._step_sampled) == [
        "self", "uids", "toks_np", "from_device", "served_slots", "gen",
        "prev", "rng"]


def _replay_run_burst(served):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    assert _sig(InferenceEngineV2._run_burst) == [
        "self", "reqs", "steps", "gen", "prev", "rng"]


def _replay_state(served):
    state = served["eng"].state
    seq = state.get(77) or state.create(77)
    try:
        state.ensure_blocks(seq, 5)
        assert seq.seen_tokens == 0
        assert 77 in state.tracked
        assert state.max_tracked_sequences == SM["max_tracked_sequences"]
        assert served["eng"].config.state_manager.max_ragged_batch_size \
            == SM["max_ragged_batch_size"]
    finally:
        served["eng"].flush([77])
    assert 77 not in state.tracked


REPLAY_READS = {
    "InferenceEngineV2._step_sampled(...)": _replay_step_sampled,
    "InferenceEngineV2._run_burst(...)": _replay_run_burst,
    "state.get,create,ensure_blocks,tracked,seen_tokens": _replay_state,
}


@pytest.mark.parametrize("name", list(REPLAY_READS))
def test_sched_replay_reads(served, name):
    REPLAY_READS[name](served)


# ------------------------------------------- (f) the set-up account (PR 40)

def _setup_series(name, **labels):
    from deepspeed_tpu.telemetry import default_registry
    metric = default_registry._metrics[name]
    assert any(labels.items() <= dict(k).items()
               for k, _ in metric.samples()), (name, labels)
    return metric.value(**labels)


def _setup_counter(name, **labels):
    assert _setup_series(name, **labels) > 0, (name, labels)


def _setup_record(program, *key):
    def check(s, t):
        recs = (t if program == "train_batch" else s)["setup_records"]
        got = [r for r in recs if r["program"] == program]
        assert got, [r["program"] for r in recs]
        for r in got:
            assert set(key) | {"trace_s", "lower_s", "compile_s",
                               "cache_load_s", "cache_hit", "host_ns",
                               "traces"} <= set(r)
            assert r["traces"] == 1 and r["trace_s"] > 0
    return check


def _setup_span(engine, part):
    def check(s, t):
        from deepspeed_tpu.telemetry import setup_account
        got = [x for x in setup_account()["init_spans"]
               if (x["engine"], x["part"]) == (engine, part)]
        assert got and all(x["seconds"] >= 0 and x["host_ns"] > 0
                           for x in got)
        assert _setup_series("init_seconds", engine=engine, part=part) >= 0
        events = (t["events"] if engine == "train"
                  else s["eng"].telemetry.tracer.events)
        assert any(e["name"] == part and "host_ns" in e["args"]
                   for e in events)             # ds.<part> where a buffer is
    return check


def _setup_step_programs_are_the_warm_programs(s, t):
    """``setup_step_programs`` against the runner's ``warm_programs`` note:
    every site that calls a ``_steps`` jit is booked, or the two differ."""
    steps = [r for r in s["setup_records"] if r["program"] != "other"]
    assert len(steps) == s["programs"]


def _setup_mirrored_record(s, t):
    for events, program in ((s["eng"].telemetry.tracer.events, "mixed"),
                            (t["events"], "train_batch")):
        assert any(e["name"] == "program_setup"
                   and e["args"]["program"] == program for e in events)


def _setup_accessor(s, t):
    from deepspeed_tpu.telemetry import setup_account
    acc = setup_account()
    assert {"seconds", "by_program", "hits", "misses", "records",
            "dropped_records", "import_seconds", "init_spans"} <= set(acc)
    assert set(acc["seconds"]) == {"trace", "lower", "compile", "cache_load"}
    # (the gauge import_seconds is held by tests/test_setup_account.py in a
    # fresh process: tests of other files reset the process-wide registry)
    assert acc["import_seconds"] > 0


SETUP_READS = {
    "telemetry.setup_account()": _setup_accessor,
    "counter setup_seconds_total{part=trace,program=mixed}":
        lambda s, t: _setup_counter("setup_seconds_total", part="trace",
                                    program="mixed"),
    "counter setup_seconds_total{part=lower,program=burst}":
        lambda s, t: _setup_counter("setup_seconds_total", part="lower",
                                    program="burst"),
    "counter setup_seconds_total{program=train_batch}":
        lambda s, t: _setup_counter("setup_seconds_total", part="trace",
                                    program="train_batch"),
    "counter setup_seconds_total{program=other}":
        lambda s, t: _setup_counter("setup_seconds_total", part="lower",
                                    program="other"),
    "counter setup_programs_total{program=put_mixed}":
        lambda s, t: _setup_counter("setup_programs_total",
                                    program="put_mixed"),
    "counter setup_programs_total{program=put_decode}":
        lambda s, t: _setup_counter("setup_programs_total",
                                    program="put_decode"),
    "record program_setup[put_mixed]": _setup_record(
        "put_mixed", "bucket", "table_width"),
    "record program_setup[put_decode]": _setup_record("put_decode", "bucket"),
    "record program_setup[mixed]": _setup_record(
        "mixed", "bucket", "table_width"),
    "record program_setup[burst]": _setup_record("burst", "steps"),
    "record program_setup[train_batch]": _setup_record("train_batch", "step"),
    "record program_setup in the buffers": _setup_mirrored_record,
    "setup_step_programs == warm_programs":
        _setup_step_programs_are_the_warm_programs,
    "span ds.engine_init (inference_v2)": _setup_span("inference_v2",
                                                      "engine_init"),
    "span ds.init_params": _setup_span("inference_v2", "init_params"),
    "span ds.init_cache": _setup_span("inference_v2", "init_cache"),
    "span ds.engine_init (train)": _setup_span("train", "engine_init"),
    "span ds.init_state": _setup_span("train", "init_state"),
    "span ds.init_optimizer": _setup_span("train", "init_optimizer"),
}


@pytest.mark.parametrize("name", list(SETUP_READS))
def test_setup_account_reads(served, trained, name):
    SETUP_READS[name](served, trained)


# ---------- (g) a selecting model's masked steps, by the program's own rule

@pytest.fixture(scope="module")
def selecting_notes():
    """``_ctx_note`` of a model that selects its keys (``index_topk`` 2,048
    over a table of 65,536 tokens, two selecting layers), asked as
    ``_step_sampled`` asks it for three mixed steps (one chunk of 1,024 rows
    beside two riders: under, at and past ``MASKED_REACH``) and as
    ``_build_burst`` does for a burst: (telemetry, the four notes with the
    running totals a dispatch span carries)."""
    import types

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.telemetry.serving import ServingTelemetry
    tel = ServingTelemetry(pid=0)
    eng = types.SimpleNamespace(
        telemetry=tel, _block_size=512, model_config=types.SimpleNamespace(
            index_topk=2048, max_seq_len=65536, num_layers=5,
            sliding_window=0, window_for_layer=lambda i: 513 if i > 1
            else None))
    notes = []
    for ctx in (7168, 31744, 32768):
        notes.append(InferenceEngineV2._ctx_note(
            eng, [ctx, 30000, 9000], [1024, 1, 1], table_tokens=65536))
        notes[-1].update(tel.counter_note(None))
    notes.append(InferenceEngineV2._ctx_note(eng, [31000, 9000], steps=8))
    notes[-1].update(tel.counter_note(None))
    return tel, notes


def _selecting_counter(o):
    tel, notes = o
    assert tel.registry._metrics[
        "serving_selected_masked_steps_total"].value(**tel.labels) == 2


def _selecting_total(o):
    # a running total in EVERY dispatch span; the burst leaves it still
    assert [n["sel_masked_steps"] for n in o[1]] == [1, 2, 2, 2]


def _selecting_reach(o):
    # the chunk's context after the step, not the riders'; mixed spans only
    from deepspeed_tpu.ops.sparse_index import MASKED_REACH
    assert [n.get("sel_reach") for n in o[1]] == [8192, 32768, 33792, None]
    assert MASKED_REACH == 32768


SELECTING_READS = {
    "serving_selected_masked_steps_total": _selecting_counter,
    "ds.*_dispatch.sel_masked_steps": _selecting_total,
    "ds.mixed_dispatch.sel_reach": _selecting_reach,
}


@pytest.mark.parametrize("name", list(SELECTING_READS))
def test_selecting_reads(selecting_notes, name):
    SELECTING_READS[name](selecting_notes)


# ---------- (h) a selection by blocks: rows by path, kept pairs, the needs

@pytest.fixture(scope="module")
def block_notes():
    """``_ctx_note`` of a model that selects by blocks (MiniCPM-SALA's
    sizes: 64 blocks of 64 past 8,192, three selecting layers), asked as
    ``_step_sampled`` asks it for a mixed step (a chunk of 1,024 rows that
    crosses ``dense_len`` beside a rider past it and one within it) and as
    ``_build_burst`` does for a burst of 8: (telemetry, the two notes with
    the running totals a dispatch span carries)."""
    import types

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.ops.block_select import BlockGeometry
    from deepspeed_tpu.telemetry.serving import ServingTelemetry
    tel = ServingTelemetry(pid=0)
    tel.set_block_selection(3)
    eng = types.SimpleNamespace(
        telemetry=tel, _block_size=128, model_config=types.SimpleNamespace(
            index_topk=0, max_seq_len=66048, num_layers=12, sliding_window=0,
            attention_layers=(0, 7, 8),
            block_geometry=BlockGeometry(32, 16, 64, 64, 2048, 1, 8192)))
    eng._block_note = lambda *a: InferenceEngineV2._block_note(eng, *a)
    notes = [InferenceEngineV2._ctx_note(eng, [7680, 20000, 5000],
                                         [1024, 1, 1])]
    notes[-1].update(tel.counter_note(None))
    notes.append(InferenceEngineV2._ctx_note(eng, [20001, 5001], steps=8))
    notes[-1].update(tel.counter_note(None))
    return tel, notes


def _block_rows(o):
    # the chunk's rows at positions 7,680-8,191 and the rider at 5,000 are
    # dense; 8 steps of the second slot too; on three layers
    assert [n["blk_dense_rows"] for n in o[1]] == [3 * 513, 3 * (513 + 8)]
    assert [n["blk_sparse_rows"] for n in o[1]] == [3 * 513, 3 * (513 + 8)]
    assert o[1][-1]["blk_kept_blocks"] == 64 * 3 * (513 + 8)
    tel = o[0]
    assert tel.registry._metrics["serving_block_rows_total"].value(
        path="sparse", **tel.labels) == 3 * 521
    assert tel.registry._metrics["serving_block_kept_blocks_total"].value(
        **tel.labels) == 64 * 3 * 521


def _block_needs(o):
    mixed, burst = o[1]
    chunk = sum(63 * 64 + t % 64 + 1 for t in range(8192, 8704))
    rider = 63 * 64 + 20000 % 64 + 1
    assert mixed["blk_pairs_step"] == chunk + rider
    assert mixed["blk_pairs_one_row"] == rider
    assert mixed["blk_ctx_chunk"] == 8704
    assert mixed["blk_pooled_pairs"] == sum((t - 31) // 16 + 1
                                            for t in range(8192, 8704))
    assert mixed["blk_pooled_chunk"] == (8704 - 31) // 16 + 1
    # a burst: a row a slot a step, every one its slot's own
    assert burst["blk_pairs_one_row"] == burst["blk_pairs_step"] == sum(
        63 * 64 + t % 64 + 1 for t in range(20001, 20009))
    assert burst["blk_ctx_chunk"] == burst["blk_pooled_pairs"] == 0


def _block_pairs(o):
    # kept < causal once rows select; what the runner's need counters and
    # ``index_selected_share.sparse`` read
    last = o[1][-1]
    assert 0 < last["sel_pairs"] < last["global_pairs"]
    assert last["index_pairs"] > 0
    tel = o[0]
    assert tel.c_sel_pairs.value() == last["sel_pairs"]
    assert tel.c_index_pairs.value() == last["index_pairs"]


BLOCK_READS = {
    "ds.*_dispatch.blk_dense_rows / blk_sparse_rows / blk_kept_blocks":
        _block_rows,
    "ds.*_dispatch.blk_pairs_step / blk_pairs_one_row / blk_ctx_chunk / "
    "blk_pooled_pairs / blk_pooled_chunk": _block_needs,
    "ds.*_dispatch.sel_pairs / global_pairs / index_pairs": _block_pairs,
}


@pytest.mark.parametrize("name", list(BLOCK_READS))
def test_block_selection_reads(block_notes, name):
    BLOCK_READS[name](block_notes)
