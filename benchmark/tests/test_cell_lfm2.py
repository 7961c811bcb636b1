"""The cell ``serve-lfm2-ragdoc-batch`` end to end on the CPU at its tiny
preset (``--rehearse``: one attention layer among five short-conv layers,
two dense and four expert layers, the Pallas paged kernels interpreted, the
comparison with the plain lfm2_moe reference across two ``put_chunked``
boundaries beside a two-row prompt), a planted fault through the harness,
its metrics' entries, files and readers, the configuration against the
catalog's row, ``costs_conv``'s need against the arithmetic written out, the
new readers on spans as the program writes them, and that the cell came by
new files, new entries and its name at the end of the lists it joined."""

import json
import os
import subprocess
import sys
import types

import attn_rooflines
import conv_rooflines
import conv_scope_time
import conv_spans
import costs_conv
import costs_serve
import serve_mfu
from test_cells import (assert_reads_what_it_was_accepted_with, ENV, MANIFEST,
                        name_since_pr59, no_longer_read, readings, run_cell)
from test_serve_mfu import model_cfg as program_cfg

CELL = "serve-lfm2-ragdoc-batch"
CONFIG = "lfm2-24b-a2b-10l"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["decode_conv_ms", "mixed_conv_ms", "conv_state_bytes_per_slot",
       "kv_pool_bytes_per_token", "short_conv_roofline",
       "paged_decode_roofline.conv", "ragged_prefill_roofline.conv",
       "serve_step_mfu.conv"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cell_rehearses_agrees_with_its_reference_and_finishes():
    out = run_cell(CELL, 0, extra=["--rehearse"])
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert list(last)[-1] == "compared" and last["rehearsal"] is True
    notes = next(x for x in lines if x.get("phase") == "notes")
    assert {d["op"] for d in notes["kernel_dispatch"]} == {
        "paged_attention", "ragged_prefill_attention"}
    assert all(d["impl"] == "pallas" for d in notes["kernel_dispatch"])
    # the closed list finishes inside the window
    assert not notes["drained_at_deadline"]
    assert notes["completed_in_log"] == notes["requests"]
    assert notes["dispatches"]["burst"] > 0
    assert notes["need_counts"]["moe_local"] > 0
    # the comparison crossed two put_chunked boundaries and ended two rows
    # behind the second, beside a two-row prompt
    run = {**config()["run"], **config()["rehearsal"]["run"]}
    q = run["state_manager"]["max_q_per_seq"]
    assert run["compare"]["prefill_tokens"] == [2 * q + 2, 2]


def test_a_planted_fault_reads_not_correct_through_the_harness():
    """``tools/conv_compare.py --plant`` is ``run.py`` with the reference
    swapped for one with a fault in: the runner's own comparison, under the
    configuration's own limits, has to say ``correct: false``.  At the tiny
    preset the C gate left out reads 0.26 against the rehearsal's 0.05; the
    published widths' readings are the chip's (PERF.md section 6, PR 46)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "conv_compare.py"),
         "--workload", CELL, "--seed", "5", "--rehearse",
         "--plant", "c_gate_left_out"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()
             if x.startswith("{")]
    assert lines[-1]["correct"] is False and lines[-1]["rehearsal"] is True
    seen = next(x for x in lines
                if x.get("phase") == "notes")["logits_vs_reference"]
    assert seen["rel_rms"] > seen["logits_rel_rms"]


def test_its_metrics_are_entries_with_files_and_readers():
    mine = readings(CELL)                  # what a traced run reads
    names = [p["name"] for p in mine]
    assert {name_since_pr59(n) for n in NEW} <= set(names)
    assert not no_longer_read(CELL)
    # the accepted readers that would misreckon are not joined: a call a
    # layer times num_layers, Mamba-2's mixers (the whole step's share and
    # the two paged kernels' ask each layer its kind since PR 59)
    for name in ("paged_decode_roofline", "ssm_decode_roofline",
                 "mixed_moe_shared_ms", "decode_moe_shared_ms",
                 "moe_local_share_of_assignments"):
        assert name not in names, name
    for name in ("serve_step_mfu", "paged_decode_roofline.by_layer",
                 "expert_gemm_roofline.joined", "decode_moe_experts_ms",
                 "mixed_moe_experts_ms", "moe_rows_per_touched_expert",
                 "decode_step_device_ms.batch", "peak_hbm_gib.batch"):
        assert name in names, name
    assert {p["moves"] for p in mine} == {"serve_tokens_per_s", "setup_s"}
    for p in mine:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               p["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: p[k] for k in ("unit", "better", "source", "layer", "moves")}
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "ragdoc-batch")
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers",
                                "max_position_embeddings"]


def test_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "ragdoc-batch.json")) as f:
        mix = json.load(f)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.8, "min": 512, "max": 8192}
    assert mix["output_tokens"] == {"dist": "fixed", "value": 256}
    assert mix["arrivals"]["process"] == "all_at_zero"
    assert mix["order_block"] == 16 and mix["stream_sync"] is False
    n = round(mix["arrivals"]["requests_per_window_s"] * 45)
    assert n % 16 == 0 and n > 0
    assert 0 < mix["trace"]["start_share"] < 1


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under its own name and value, but for
    the depth and the positions; ``layer_types`` is the published list,
    whole, and ``layers_kept`` the indices held here."""
    cfg = config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers"]
    assert cfg["layers_kept"] == list(range(10)) == list(
        range(cfg["num_hidden_layers"]))
    kinds = [cfg["layer_types"][i] for i in cfg["layers_kept"]]
    assert kinds.count("full_attention") == 2 and kinds.count("conv") == 8
    assert cfg["max_position_embeddings"] == cfg["run"]["max_seq_len"] == 8448
    for key in ("assumed", "deployment", "tolerances", "rehearsal"):
        assert cfg[key]
    for key in ("conv_split", "conv", "norms", "qk_layernorm", "rope",
                "tied_head", "router", "eos_token_id", "weights"):
        assert cfg["assumed"][key], key
    sm = cfg["run"]["state_manager"]
    assert sm["num_kv_blocks"] == sm["max_tracked_sequences"] * 66
    assert 66 * sm["kv_block_size"] == 8448
    assert cfg["run"]["compare"]["prefill_tokens"] == [
        2 * sm["max_q_per_seq"] + 2, 2]
    assert cfg["run"]["compare"]["decode_positions"] >= 200
    kept = cfg["rehearsal"]["layers_kept"]
    assert [cfg["layer_types"][i] for i in kept].count("full_attention") == 1


def model_cfg(layers=10):
    """The published sizes as the need functions see them."""
    kinds = (["conv", "conv", "attention", "conv"] * 10)[:layers]
    return types.SimpleNamespace(
        num_layers=layers, hidden_size=2048, num_heads=32, kv_heads=8,
        head_dim=64, mlp_dim=11776, gated_mlp=True, vocab_size=65536,
        conv_taps=3, num_experts=64, local_experts=64, expert_dim=1536,
        layer_types=tuple(kinds), layer_kind=lambda i: kinds[i],
        is_moe_layer=lambda i: i >= 2)


def test_need_functions_against_a_hand_count():
    cfg = model_cfg()
    assert costs_conv.layers(cfg) == (8, 2)
    w = costs_serve.row_weights(program_cfg(CONFIG))
    # a conv layer: in 2,048 x 6,144, out 2,048 x 2,048
    assert w["conv_proj"] == 8 * (2048 * 6144 + 2048 * 2048) == 134217728
    assert w["attention"] == 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    assert w["mlp"] == 2 * 3 * 2048 * 11776
    assert w["router"] == 8 * 2048 * 64
    # 4,096 B of pages a token here, 20,480 B in the whole model; 64 KB of
    # conv tails a sequence
    assert costs_conv.kv_bytes_per_token(cfg) == 4096
    assert costs_conv.kv_bytes_per_token(model_cfg(40)) == 20480
    assert costs_conv.state_bytes_per_slot(cfg) == 8 * 2 * 2048 * 2 == 65536
    # what a decode step streams: the 8 expert layers' 9.66 GB, and 0.87 GB
    # of every other matmul weight (the head among them)
    stream = costs_conv.decode_stream_bytes(program_cfg(CONFIG))
    assert stream["experts"] == 8 * 64 * 3 * 2048 * 1536 * 2 == 9663676416
    assert round(stream["other"] / 1e9, 2) == 0.87
    # a decode step of 64 slots through one conv layer: a row in and out
    # (2,048 wide, bf16) and a 2-row tail in and out a slot
    flops, byts = costs_conv.short_conv_cost(cfg, 64, 64)
    assert flops == 64 * 2 * 3 * 2048
    assert byts == 64 * 2 * 2048 * 2 + 64 * 2 * (2 * 2048 * 2)
    # the paged kernels on the TWO attention layers
    real = program_cfg(CONFIG)
    two = attn_rooflines.calling_layers(real)
    f, b = attn_rooflines.step_need(real, two, (100000, 0), (100000, 0), 64)
    assert f == 2 * 2 * 2 * 32 * 64 * 100000
    assert b == 2 * (2 * 8 * 64 * 100000 + 2 * 64 * 32 * 64) * 2
    f, b = attn_rooflines.step_need(real, two, (5e6, 0), (3000, 0), 1024)
    assert f == 2 * 2 * 2 * 32 * 64 * 5e6
    assert b == 2 * (2 * 8 * 64 * 3000 + 2 * 1024 * 32 * 64) * 2
    # a window: 1,000 rows, 100 tokens produced, 50,000 pairs a layer,
    # 4 experts a row on 8 layers
    need = costs_serve.window_need(real, {
        "rows": 1000, "sampled": 100, "pairs_global": 50000,
        "moe_local": 32000})
    t = need["terms"]
    assert t["weights_conv_proj"] == 2 * 134217728 * 1000
    assert t["conv"] == 8 * 1000 * (2 * 3 + 2) * 2048
    assert t["attention"] == 2 * 2 * 2 * 32 * 64 * 50000      # TWO layers
    assert t["weights_experts"] == 2 * 3 * 2048 * 1536 * 32000
    assert t["weights_head"] == 2 * 2048 * 65536 * 100
    assert need["flops"] == sum(t.values()) and not need["left_out"]
    lost = costs_serve.window_need(real, {"rows": 1000, "sampled": 100,
                                          "pairs_global": None,
                                          "moe_local": None})
    assert "attention" not in lost["terms"] and len(lost["left_out"]) == 2


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


def test_span_readers_on_spans_and_on_a_program_without_them():
    ctx = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, conv_chunk_rows=8000,
             conv_step_rows=400, conv_state_bytes_per_slot=65536,
             kv_bytes_per_token=4096, tokens=1024, seqs=40,
             one_row_slots=39, ctx_tokens_one_row=120000, qk_pairs=4000000,
             ctx_tokens=123000),
        span("ds.burst_dispatch", 30, conv_chunk_rows=8000,
             conv_step_rows=4496, conv_state_bytes_per_slot=65536,
             kv_bytes_per_token=4096, ctx_tokens=200000, seqs=64,
             steps=8)]},
        "trace_window": (0, 100)}
    assert conv_spans.read(ctx, {"what": "state_bytes_per_slot"}) == 65536
    assert conv_spans.read(ctx, {"what": "kv_bytes_per_token"}) == 4096
    keys, slots, seen = attn_rooflines.decode_step(
        ctx["_xmeta"]["annotations"], False)
    assert (keys[0], slots, seen["span_steps"], seen["from"]) == (
        (8 * 200000 + 64 * 36) / 8, 64, 8, "decode spans")
    keys, slots, seen = attn_rooflines.decode_step(
        ctx["_xmeta"]["annotations"][:1], False)
    assert (keys[0], slots, seen["span_steps"], seen["from"]) == (
        120039, 39, 1, "one-row slots of mixed spans")
    bare = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, tokens=5, kv_bytes_per_token=131072)]},
        "trace_window": (0, 100)}
    for what in ("state_bytes_per_slot", "kv_bytes_per_token"):
        assert conv_spans.read(bare, {"what": what}) is None  # the parent's
    dense = types.SimpleNamespace(layer_types=())
    scan = types.SimpleNamespace(layer_types=("mamba", "attention"))
    for other in (dense, scan):
        assert conv_rooflines.read(
            {**ctx, "peaks": {"x": 1}, "model_cfg": other},
            {"kernel": "short_conv"}) is None
    assert conv_rooflines.read(
        {**ctx, "peaks": {"x": 1}, "model_cfg": model_cfg()},
        {"kernel": "short_conv"}) is None                     # no device ops
    for kernel in ("paged_decode", "ragged_prefill"):
        assert attn_rooflines.read(
            {**ctx, "peaks": {"x": 1}, "model_cfg": program_cfg(CONFIG)},
            {"kernel": kernel, "program": "ragged_"}) is None
    spec = {"program": "ragged_decode", "groups": ["short_conv"]}
    assert conv_scope_time.read(bare, spec) is None
    assert conv_scope_time.group_of(
        {"tf_op": "jit(f)/kv_write/short_conv/scatter"}) == "short_conv"
    assert conv_scope_time.group_of(
        {"tf_op": "jit(f)/attn_kernel/short_conv/while/body/attn_kernel/"
                  "short_conv/mul"}) == "short_conv"
    assert conv_scope_time.group_of(
        {"tf_op": "jit(f)/attn_qkv/conv_in_proj/dot_general"}) \
        == "conv_in_proj"
    assert conv_scope_time.group_of({"tf_op": "jit(f)/attn_qkv/dot"}) \
        == "attn_qkv"


def test_mfu_reader_on_a_window():
    peaks = {"bf16_flops_per_s": 197e12}
    counts = {"rows": 900000, "sampled": 50000, "pairs_global": 2.0e9,
              "moe_local": 900000 * 32}
    real = program_cfg(CONFIG)
    got = serve_mfu.read(
        {"serve_window": {"counts": counts}, "peaks": peaks,
         "model_cfg": real, "window_s": 33.0}, {"name": "x"})
    need = costs_serve.window_need(real, counts)["flops"]
    assert got == 100.0 * need / (33.0 * 197e12) and 0 < got < 100


def test_the_cell_reads_what_it_was_accepted_with():
    """Held by names through ``run.metric_applies``, not by a count or a
    place in the manifest, which the next cell's entries move."""
    assert_reads_what_it_was_accepted_with(CELL, NEW)
