"""The cell ``serve-granite4h-shortchat-batch`` end to end on the CPU at its
tiny preset (``--rehearse``: two attention layers among scan layers, the
Pallas paged kernels interpreted, the comparison with the plain
granitemoehybrid reference across a ``put_chunked`` boundary), a planted
fault through the harness, its metrics' entries, files and readers, the
configuration against the catalog's row, ``costs_ssm``'s and the layers'
need against the arithmetic written out, the new readers on spans as the
program writes them, and that the cell reads what it was accepted with."""

import json
import os
import subprocess
import sys
import types

import costs_serve
import costs_ssm
import ssm_spans
from test_cells import (assert_reads_what_it_was_accepted_with, ENV, MANIFEST,
                        name_since_pr59, no_longer_read, readings, run_cell)
from test_serve_mfu import model_cfg as program_cfg

CELL = "serve-granite4h-shortchat-batch"
CONFIG = "granite-4.0-h-micro"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["serve_step_mfu.scan", "ssm_decode_roofline", "ssm_prefill_roofline",
       "decode_ssm_scan_ms", "mixed_ssm_scan_ms", "decode_ssm_proj_ms",
       "mixed_ssm_proj_ms", "ssm_state_bytes_per_slot", "ssm_step_rows_share"]


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
    notes = next(x for x in lines if x.get("phase") == "notes")
    assert {d["op"] for d in notes["kernel_dispatch"]} == {
        "paged_attention", "ragged_prefill_attention"}
    assert all(d["impl"] == "pallas" for d in notes["kernel_dispatch"])
    # the closed list finishes inside the window
    assert not notes["drained_at_deadline"]
    assert notes["completed_in_log"] == notes["requests"]
    assert notes["dispatches"]["burst"] > 0
    # the comparison crossed a put_chunked boundary: a prompt longer than a
    # forward takes of one sequence
    run = {**config()["run"], **config()["rehearsal"]["run"]}
    assert max(run["compare"]["prefill_tokens"]) > run["state_manager"][
        "max_q_per_seq"]


def test_a_planted_fault_reads_not_correct_through_the_harness():
    """``tools/ssm_compare.py --plant`` is ``run.py`` with the reference
    swapped for one with a fault in: the runner's own comparison, under the
    configuration's own limits, has to say ``correct: false``.  At the tiny
    preset (matrices of std 0.02 at a width of 64: the states carry little)
    a residual multiplier of 1 reads 0.12 against the rehearsal's 0.02; the
    published widths' readings are the chip's (PERF.md section 6, PR 44)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "ssm_compare.py"),
         "--workload", CELL, "--seed", "5", "--rehearse",
         "--plant", "residual_multiplier_one"],
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
    # the whole step's share under the one name since PR 59: the need asks
    # each layer its kind (``costs_serve`` once reckoned attention on all)
    assert "serve_step_mfu" in names
    assert "paged_decode_roofline" not in names   # ... times num_layers
    # the decoding sequences' contexts from the mixed spans' riders, the one
    # source that is there when the host dispatched the bursts ahead of the
    # traced stretch (the burst spans' reading found nothing on the chip)
    assert "decode_live_context_tokens.latent" in names
    assert "decode_live_context_tokens.batch" not in names
    assert {p["moves"] for p in mine} == {"serve_tokens_per_s", "setup_s"}
    for p in mine:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               p["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "shortchat-batch")
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["max_position_embeddings"]


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under its own name and value, but for
    ``max_position_embeddings``; nothing of the model is cut."""
    cfg = config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert differs == ["max_position_embeddings"] == list(cfg["reduced"])
    assert cfg["max_position_embeddings"] == cfg["run"]["max_seq_len"] == 2560
    assert cfg["layer_types"].count("attention") == 4
    for key in ("assumed", "deployment", "tolerances", "rehearsal"):
        assert cfg[key]
    for key in ("gate_before_norm", "gated_norm_group", "time_step_limit",
                "A_log", "dt_bias", "D", "ssm_state_dtype", "eos_token_id",
                "weights"):
        assert cfg["assumed"][key], key
    sm = cfg["run"]["state_manager"]
    assert sm["num_kv_blocks"] == sm["max_tracked_sequences"] * 20
    assert 20 * sm["kv_block_size"] == 2560
    lens = cfg["run"]["compare"]["prefill_tokens"]
    assert max(lens) > sm["max_q_per_seq"]
    assert all(n % cfg["mamba_chunk_size"] for n in lens)
    assert cfg["run"]["compare"]["decode_positions"] >= 640
    kinds = cfg["rehearsal"]["layer_types"]
    assert kinds.count("attention") == 2 and kinds.count("mamba") >= 2


def model_cfg():
    """The published sizes as the need functions see them."""
    kinds = config()["layer_types"]
    return types.SimpleNamespace(
        num_layers=40, hidden_size=2048, num_heads=32, kv_heads=8,
        head_dim=64, mlp_dim=8192, gated_mlp=True, vocab_size=100352,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        layer_types=tuple(kinds),
        is_scan_layer=lambda i: kinds[i] == "mamba")


def test_need_functions_against_a_hand_count():
    cfg = model_cfg()
    assert costs_ssm.layers(cfg) == (36, 4)
    w = costs_serve.row_weights(program_cfg(CONFIG))
    # a scan layer: in 2,048 x (4,096 + 4,352 + 64), out 4,096 x 2,048
    assert w["scan_proj"] == 36 * (2048 * 8512 + 4096 * 2048) == 929562624
    assert w["attention"] == 4 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    assert w["mlp"] == 40 * 3 * 2048 * 8192
    assert costs_ssm.recurrence_flops(cfg, 1) == 4 * 4096 * 128
    assert costs_ssm.state_bytes(cfg) == 64 * 64 * 128 * 4 == 2 ** 21
    # a decode step of 64 slots through one layer: 64 states in and out,
    # and a row's x, z, y (4,096), B, C (128) in bf16 and dt (64) in float32
    flops, byts = costs_ssm.scan_cost(cfg, 64, 64)
    assert flops == 64 * 4 * 4096 * 128
    assert byts == 64 * 2 * 2 ** 21 + 64 * ((3 * 4096 + 256) * 2 + 256)
    # a window: 1,000 rows, 100 tokens produced, 50,000 pairs a layer
    need = costs_serve.window_need(
        program_cfg(CONFIG), {"rows": 1000, "sampled": 100,
                              "pairs_global": 50000})
    t = need["terms"]
    assert t["weights_scan_proj"] == 2 * 929562624 * 1000
    assert t["recurrence"] == 36 * 1000 * 4 * 4096 * 128
    assert t["attention"] == 4 * 2 * 2 * 32 * 64 * 50000     # FOUR layers
    assert t["weights_head"] == 2 * 2048 * 100352 * 100
    assert need["flops"] == sum(t.values()) and not need["left_out"]
    lost = costs_serve.window_need(
        program_cfg(CONFIG), {"rows": 1000, "sampled": 100,
                              "pairs_global": None})
    assert "attention" not in lost["terms"] and lost["left_out"]


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


def test_span_readers_on_spans_and_on_a_program_without_them():
    ctx = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, ssm_chunk_rows=3600, ssm_step_rows=720,
             ssm_state_bytes_per_slot=76437504, tokens=400, seqs=21),
        span("ds.burst_dispatch", 30, ssm_chunk_rows=7200,
             ssm_step_rows=11520, ssm_state_bytes_per_slot=76437504)]},
        "trace_window": (0, 100)}
    assert ssm_spans.read(ctx, {"what": "state_bytes_per_slot"}) == 76437504
    assert ssm_spans.read(ctx, {"what": "step_rows_share"}) == 75.0
    bare = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, tokens=5)]}, "trace_window": (0, 100)}
    for what in ("state_bytes_per_slot", "step_rows_share"):
        assert ssm_spans.read(bare, {"what": what}) is None   # the parent's
    import ssm_rooflines
    import ssm_scope_time
    spec = {"program": "ragged_decode", "path": "step", "groups": ["ssm_scan"]}
    assert ssm_rooflines.read({**bare, "peaks": {}, "model_cfg": model_cfg()},
                              spec) is None                   # no device ops
    assert ssm_scope_time.read(bare, spec) is None
    assert ssm_scope_time.group_of(
        {"tf_op": "jit(f)/kv_write/ssm_scan/scatter"}) == "ssm_scan"
    assert ssm_scope_time.group_of(
        {"tf_op": "jit(f)/attn_kernel/ssm_scan/while/body/attn_kernel/"
                  "ssm_conv/mul"}) == "ssm_conv"
    assert ssm_scope_time.group_of({"tf_op": "jit(f)/attn_qkv/dot"}) \
        == "attn_qkv"


def test_the_cell_reads_what_it_was_accepted_with():
    """Held by names through ``run.metric_applies``, not by a count or a
    place in the manifest, which the next cell's entries move."""
    assert_reads_what_it_was_accepted_with(CELL, NEW)
