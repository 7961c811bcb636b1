"""The cell ``serve-xing4-shortdoc-batch`` end to end on the CPU at its tiny
preset (``--rehearse``: four residual streams round two dense and two expert
layers of latent attention under YaRN, the Pallas paged kernels
interpreted, the comparison with the plain xing4_0 reference across two
``put_chunked`` boundaries, the page edge and YaRN's original positions), a
planted fault through the harness, its metrics' entries, files and readers,
the configuration against the catalog's row, ``costs_hc``'s need against
the arithmetic written out, the new readers on spans as the program writes
them, and that the cell came by new files, new entries and its name at the
end of the lists it joined."""

import json
import os
import subprocess
import sys
import types

import costs
import costs_hc
import hc_roofline
import hc_scope_time
from test_cells import (assert_reads_what_it_was_accepted_with, ENV, MANIFEST,
                        no_longer_read, readings, run_cell)

CELL = "serve-xing4-shortdoc-batch"
CONFIG = "xing4.0-29b-a4b-7l"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["mixed_hc_coef_ms", "mixed_hc_mix_ms", "decode_hc_coef_ms",
       "decode_hc_mix_ms", "hc_residual_bytes_per_row", "hc_mix_roofline"]


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
    # the comparison crossed two put_chunked boundaries, the page edge and
    # YaRN's original positions
    cfg = config()
    run = {**cfg["run"], **cfg["rehearsal"]["run"]}
    q = run["state_manager"]["max_q_per_seq"]
    orig = cfg["rehearsal"]["rope_scaling"][
        "original_max_position_embeddings"]
    a, b = run["compare"]["prefill_tokens"]
    assert a > 2 * q and a > orig
    assert b < run["state_manager"]["kv_block_size"] \
        < b + run["compare"]["decode_positions"]


def test_a_planted_fault_reads_not_correct_through_the_harness():
    """``tools/hc_compare.py --plant`` is ``run.py`` with the reference
    swapped for one with a fault in: the runner's own comparison, under the
    configuration's own limits, has to say ``correct: false``.  ``Hres``
    the identity (the streams never mix) at the tiny preset; the published
    widths' readings are the chip's (PERF.md section 6, PR 50)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "hc_compare.py"),
         "--workload", CELL, "--seed", "5", "--rehearse",
         "--plant", "hres_identity"],
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
    # Moonlight's (its lists, every one joined) and the six new ones
    assert set(NEW) <= set(names) and not no_longer_read(CELL)
    moon = {p["name"] for p in readings("serve-moonlight-longctx-batch")}
    assert set(names) - set(NEW) == moon
    for name in ("serve_step_mfu", "expert_gemm_roofline.joined",
                 "latent_decode_roofline", "latent_prefill_roofline",
                 "mixed_mla_absorb_ms", "decode_moe_shared_ms",
                 "moe_rows_per_touched_expert", "peak_hbm_gib.batch"):
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
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "shortdoc-batch")
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers",
                                "num_nextn_predict_layers"]


def test_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "shortdoc-batch.json")) as f:
        mix = json.load(f)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.8, "min": 128, "max": 4096}
    assert mix["output_tokens"] == {"dist": "fixed", "value": 128}
    assert mix["arrivals"]["process"] == "all_at_zero"
    assert mix["order_block"] == 16 and mix["stream_sync"] is False
    assert mix["warm_share"] == 0.0
    n = round(mix["arrivals"]["requests_per_window_s"] * 45)
    assert n % 16 == 0 and n > 0
    assert 0 < mix["trace"]["start_share"] < 1
    assert mix["trace"]["length_s"] <= 10


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under its own name and value, but for
    the depth and the multi-token-prediction module; every width, all 64
    experts and the whole vocabulary."""
    cfg = config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert (cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]) \
        == (7, 0)
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"]
    for key in ("published", "deployment", "tolerances", "rehearsal"):
        assert cfg[key]
    for key in ("sinkhorn_order", "res_clamp", "hc_norm", "hc_ends",
                "hc_precision", "hc_weights", "rope_pairing", "yarn",
                "kv_a_layernorm", "eos_token_id", "weights", "page_row"):
        assert cfg["assumed"][key], key
    sm = cfg["run"]["state_manager"]
    assert sm["num_kv_blocks"] == sm["max_tracked_sequences"] * 33
    assert 33 * sm["kv_block_size"] == cfg["run"]["max_seq_len"] == 4224
    assert sm["max_q_per_seq"] == sm["max_ragged_batch_size"]
    a, b = cfg["run"]["compare"]["prefill_tokens"]
    n_dec = cfg["run"]["compare"]["decode_positions"]
    assert a > 2 * sm["max_q_per_seq"] and a > 4096      # two chunk edges
    assert b < 4096 < b + n_dec <= 4224                  # decodes past 4,096
    assert a + n_dec <= 4224


def model_cfg():
    """The published sizes as the need functions see them."""
    return types.SimpleNamespace(hc_mult=4, hidden_size=3584, num_layers=7)


def test_need_functions_against_a_hand_count():
    cfg = model_cfg()
    assert costs_hc.residual_bytes_per_row(cfg) == 4 * 3584 * 2 == 28672
    # a row a sublayer: the streams read twice and written once, the
    # sublayer's row written once and read once
    flops, byts = costs_hc.mix_cost(cfg, 1)
    assert byts == (3 * 4 * 3584 + 2 * 3584) * 2 == 100352
    assert flops == 2 * 14336 * 24 + 2 * 14336 + 2 * 16 * 3584 + 2 * 14336
    # a full mixed step: 2,048 rows through 14 sublayers, 2.88 GB, 3.5 ms
    # at 819 GB/s
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    step = costs_hc.mix_cost(cfg, 2048 * 14)
    share, bound = costs.roofline_share(*step, 0.0035, peaks)
    assert bound == "memory" and round(share, 1) == 100.4
    assert costs.roofline_share(*step, 0.01, peaks)[0] < 36


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


def test_span_readers_on_spans_and_on_a_program_without_them():
    cfg = model_cfg()
    spans = [
        span("ds.mixed_dispatch", 10, tokens=2048, hc_rows=14 * 2048,
             hc_residual_bytes_per_row=28672, one_row_slots=30),
        span("ds.mixed_dispatch", 20, tokens=1024, hc_rows=14 * 1024,
             hc_residual_bytes_per_row=28672, one_row_slots=50),
        span("ds.burst_dispatch", 30, tokens=512, steps=8, seqs=64,
             hc_rows=14 * 512, hc_residual_bytes_per_row=28672)]
    ctx = {"_xmeta": {"devices": {}, "annotations": spans},
           "trace_window": (0, 100), "model_cfg": cfg,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert hc_roofline.read(ctx, {"what": "residual_bytes_per_row"}) == 28672
    assert hc_roofline._mean_rows(spans, cfg) == (14 * 1536, 14 * 64,
                                                  "decode spans")
    assert hc_roofline._mean_rows(spans[:2], cfg) == (
        14 * 1536, 14 * 40, "one-row slots of mixed spans")
    assert hc_roofline.read(ctx, {"what": "roofline"}) is None  # no device
    bare = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, tokens=5, kv_bytes_per_token=8960)]},
        "trace_window": (0, 100), "model_cfg": cfg}
    assert hc_roofline.read(bare, {"what": "residual_bytes_per_row"}) is None
    one_stream = types.SimpleNamespace(hc_mult=0, hidden_size=2048)
    for what in ("residual_bytes_per_row", "roofline"):          # the parent
        assert hc_roofline.read({**ctx, "model_cfg": one_stream},
                                {"what": what}) is None
    spec = {"program": "ragged_decode", "groups": ["hc_coef"]}
    assert hc_scope_time.read(bare, spec) is None
    assert hc_scope_time.group_of(
        {"tf_op": "jit(f)/attn_qkv/hc_coef/jit(hc_maps)/mul"}) == "hc_coef"
    assert hc_scope_time.group_of(
        {"tf_op": "jit(f)/mlp/hc_post/jit(hc_write)/add"}) == "hc_post"
    assert hc_scope_time.group_of(
        {"tf_op": "jit(f)/mlp/hc_pre/jit(hc_read)/mul"}) == "hc_pre"
    assert hc_scope_time.group_of({"tf_op": "jit(f)/mlp/moe_route/dot"}) \
        == "mlp"


def test_the_cell_reads_what_it_was_accepted_with():
    """Held by names through ``run.metric_applies``, not by a count or a
    place in the manifest, which the next cell's entries move."""
    assert_reads_what_it_was_accepted_with(CELL, NEW)
