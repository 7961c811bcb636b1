"""The cell ``serve-moonlight-longctx-batch`` end to end on the CPU at its
tiny preset (``--rehearse``: both Pallas kernels interpreted in their latent
form over the latent page pool, every expert held, the comparison with the
plain deepseek_v3 reference), its metrics' entries, files and readers, the
``latent`` reader on spans as the program writes them, the need functions
against a hand count, and that a cell comes by new files, new entries and
its name at the end of the lists it joins."""

import json
import os
import subprocess

import costs_mla
import latent
from test_cells import (ENV, MANIFEST, no_longer_read, readings,
                        run_cell)

CELL = "serve-moonlight-longctx-batch"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


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


def test_a_planted_fault_reads_not_correct_through_the_harness():
    """``tools/mla_compare.py --plant`` is ``run.py`` with the reference
    swapped for one with a fault in: the runner's own comparison, under the
    configuration's own limits, has to say ``correct: false``.  At the tiny
    preset only the missing ``kv_a_layernorm`` reads past the rehearsal's
    wide limits (0.91 against 0.15); the published widths' readings are the
    chip's (PERF.md section 6, PR 33)."""
    import sys
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "mla_compare.py"),
         "--workload", CELL, "--seed", "5", "--rehearse",
         "--plant", "no_kv_a_layernorm"],
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
    names = {p["name"] for p in mine}
    assert not no_longer_read(CELL)       # held by name, not by a count
    assert {"latent_decode_roofline", "latent_prefill_roofline",
            "expert_gemm_roofline.joined", "latent_pool_bytes_per_token",
            "decode_mla_absorb_ms", "mixed_mla_absorb_ms",
            "decode_live_context_tokens.latent"} <= names
    assert {p["moves"] for p in mine} == {"serve_tokens_per_s", "setup_s"}
    for p in mine:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               p["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers"]
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's row under the same key, but for the
    depth; 64 experts with 6 a token and 2 shared, the whole vocabulary."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "moonlight-16b-a3b-7l.json")) as f:
        cfg = json.load(f)
    want = dict(hidden_size=2048, intermediate_size=11264, kv_lora_rank=512,
                moe_intermediate_size=1408, n_routed_experts=64,
                n_shared_experts=2, num_attention_heads=16,
                num_experts_per_tok=6, num_key_value_heads=16,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                vocab_size=163840, first_k_dense_replace=1, q_lora_rank=None,
                rope_theta=50000, routed_scaling_factor=2.446,
                max_position_embeddings=8192, num_hidden_layers=7)
    assert {k: cfg[k] for k in want} == want
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 27
    for key in ("assumed", "deployment", "tolerances", "rehearsal"):
        assert cfg[key]
    sm = cfg["run"]["state_manager"]
    assert sm["num_kv_blocks"] == sm["max_tracked_sequences"] * 60
    assert cfg["run"]["max_seq_len"] == 60 * sm["kv_block_size"] == 7680


def test_the_cell_reads_what_it_was_accepted_with():
    """PR 33 brought this cell by new files, new entries and its name at
    the end of ``serve_tokens_per_s``'s cells, and that is how a cell comes.
    What it reads is held by names through ``run.metric_applies``, not by a
    count or a place in the manifest (the next cell's entries move both):
    what it read when PR 59 started (``data/manifest_lists_pr58.json``) it
    reads today under today's names.  The files themselves are the driver's
    to hold: a spec file no longer names cells, so joining a metric edits
    none."""
    assert not no_longer_read(CELL)
    assert not no_longer_read(CELL, [
        "latent_decode_roofline", "latent_prefill_roofline",
        "latent_pool_bytes_per_token", "decode_live_context_tokens.latent",
        "expert_gemm_roofline", "serve_step_mfu"])


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


def test_latent_reader_on_spans_and_on_a_program_without_them():
    ctx = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, kv_bytes_per_token=8960, tokens=5),
        span("ds.burst_dispatch", 30, kv_bytes_per_token=8960)]},
        "trace_window": (0, 100)}
    spec = {"what": "pool_bytes_per_token"}
    assert latent.read(ctx, spec) == 8960.0
    bare = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, tokens=5)]}, "trace_window": (0, 100)}
    assert latent.read(bare, spec) is None           # the parent's spans
    for what in ("scope_ms", "roofline"):            # no device trace
        assert latent.read(bare, {"what": what, "program": "ragged_",
                                  "scope": "mla_absorb",
                                  "kernel": "paged_decode"}) is None
    assert latent.read({"_xmeta": None}, spec) is None


def test_decode_readings_have_one_source_the_mixed_spans_riders():
    """The host dispatches a cohort's bursts in one clump ahead of the chip,
    so a traced window may or may not hold their spans: the decode readings
    take the mixed spans' one-row slots for the decoding sequences, whatever
    else the window holds."""
    live = {"what": "live_context"}
    mixed = [span("ds.mixed_dispatch", 10, tokens=1024, one_row_slots=40,
                  ctx_tokens_one_row=100000, ctx_tokens=103000, seqs=41),
             span("ds.mixed_dispatch", 20, tokens=1024, one_row_slots=0,
                  ctx_tokens_one_row=0, ctx_tokens=3000, seqs=1),
             span("ds.mixed_dispatch", 30, tokens=1024, one_row_slots=42,
                  ctx_tokens_one_row=110000, ctx_tokens=113000, seqs=43)]
    burst = span("ds.burst_dispatch", 40, steps=4, seqs=48, ctx_tokens=120000)
    ctx = {"_xmeta": {"devices": {}, "annotations": mixed},
           "trace_window": (0, 100)}
    assert latent.read(ctx, live) == 105000.0        # riders, steps with some
    ctx["_xmeta"]["annotations"] = mixed + [burst]
    assert latent.read(ctx, live) == 105000.0        # a burst span moves nothing
    ctx["_xmeta"]["annotations"] = [burst]
    assert latent.read(ctx, live) is None


def test_need_functions_against_a_hand_count():
    # one decode step of 64 slots at 3,000 tokens each, 7 layers, 16 heads
    flops, byts = costs_mla.latent_decode_cost(64 * 3000, 64, 7, 16, 576, 512)
    assert flops == 2 * (576 + 512) * 16 * 64 * 3000 * 7     # 46.8 GFLOP
    assert byts == (576 * 64 * 3000 + 64 * 16 * (576 + 512)) * 7 * 2
    assert 1.5e9 < byts < 1.6e9               # 1,152 B a key a layer
    # a chunk of 1,024 rows at context 6,000: row i sees 6,001 + i keys
    pairs = sum(6001 + i for i in range(1024))
    flops, byts = costs_mla.latent_prefill_cost(pairs, 7024, 1024, 7, 16,
                                                576, 512)
    assert flops == 2 * (576 + 512) * 16 * pairs * 7
    assert byts == (576 * 7024 + 1024 * 16 * 1088) * 7 * 2
    assert costs_mla.absorbed_pair_flops(576, 512) == 2176
    assert costs_mla.expanded_pair_flops(192, 128) == 640
