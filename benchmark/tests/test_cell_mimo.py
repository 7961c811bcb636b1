"""The cell ``serve-mimo-reasoning-batch`` end to end on the CPU at its tiny
preset (``--rehearse``: window layers with a sink beside full layers of
another key/value geometry, keys 24 wide and values 16, a pool a page group,
4 of 16 experts held; the Pallas paged kernels interpreted; the comparison
with the plain mimo_v2_flash reference across ``put_chunked`` boundaries past
the window and over ring turns), a planted fault through the harness, its
metrics' entries, files and readers, the configuration against the catalog's
row, ``costs_swa``'s need against the arithmetic written out, the new readers
on spans as the program writes them, and that the cell came by new files, new
entries and its name at the end of the lists it joined."""

import json
import os
import subprocess
import sys
import types

import attn_rooflines
import costs
import costs_serve
import costs_swa
import swa_scope_time
import swa_spans
from layer_costs import attention
from test_cells import (assert_reads_what_it_was_accepted_with, ENV, MANIFEST,
                        name_since_pr59, no_longer_read, readings, run_cell)

CELL = "serve-mimo-reasoning-batch"
CONFIG = "mimo-v2-flash-7l-ep16"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["paged_decode_roofline.reasoning", "ragged_prefill_roofline.reasoning",
       "decode_attn_window_ms", "decode_attn_global_ms",
       "mixed_attn_window_ms", "mixed_attn_global_ms",
       "kv_window_released_in_decode_share",
       "kv_pool_bytes_per_token.global", "kv_pool_bytes_per_token.window",
       "serve_step_mfu.swa"]


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
    assert not notes["drained_at_deadline"]
    assert notes["completed_in_log"] == notes["requests"]
    assert notes["dispatches"]["burst"] > 0
    assert notes["need_counts"]["moe_local"] > 0
    assert 0 < notes["need_counts"]["pairs_window"] \
        < notes["need_counts"]["pairs_global"]
    # the comparison fed chunks that start past the window and decoded over
    # a page edge, the window group giving pages back
    cfg = config()
    run = {**cfg["run"], **cfg["rehearsal"]["run"]}
    q = run["state_manager"]["max_q_per_seq"]
    a, b = run["compare"]["prefill_tokens"]
    assert a > 2 * q > cfg["rehearsal"]["sliding_window"] and b > q
    assert a < 2 * run["state_manager"]["kv_block_size"] \
        < a + run["compare"]["decode_positions"]


def test_a_planted_fault_reads_not_correct_through_the_harness():
    """``tools/swa_compare.py --plant`` is ``run.py`` with the reference
    swapped for one with a fault in: the runner's own comparison, under the
    configuration's own limits, has to say ``correct: false``.  The sink
    left out at the tiny preset; the published widths' readings are the
    chip's (PERF.md section 6, PR 53)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "swa_compare.py"),
         "--workload", CELL, "--seed", "5", "--rehearse",
         "--plant", "sink_left_out"],
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
    new = {name_since_pr59(n) for n in NEW}
    assert new <= set(names) and len(mine) == len(set(names))
    assert not no_longer_read(CELL)
    # everything Trinity's cell reads but what a shared expert needs, and
    # the ten new ones, three of which are Trinity's too since PR 59 (the
    # need of the step and of the two paged kernels asks each layer its own
    # heads and widths)
    trinity = {p["name"] for p in readings("serve-trinity-mixedlen-batch")}
    assert trinity - set(names) == {"mixed_moe_shared_ms",
                                    "decode_moe_shared_ms"}
    assert set(names) - trinity == new - {
        "serve_step_mfu", "paged_decode_roofline.by_layer",
        "ragged_prefill_roofline.by_layer"}
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
    assert (cell["config"], cell["traffic"]) == (CONFIG, "reasoning-batch")
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "max_position_embeddings"]
    assert CELL in next(e for e in MANIFEST["end_to_end"]
                        if e["name"] == "serve_tokens_per_s")["workloads"]


def test_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "reasoning-batch.json")) as f:
        mix = json.load(f)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.9, "min": 128, "max": 4096}
    assert mix["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert mix["arrivals"]["process"] == "all_at_zero"
    assert mix["order_block"] == 16 and mix["stream_sync"] is False
    assert mix["warm_share"] == 0.0
    n = round(mix["arrivals"]["requests_per_window_s"] * 45)
    slots = config()["run"]["state_manager"]["max_tracked_sequences"]
    assert n % 16 == 0 and n >= 1.5 * slots       # 1.5 fills of the slots
    assert 0 < mix["trace"]["start_share"] < 1
    assert mix["trace"]["length_s"] <= 10


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under its own name and value, but for
    the depth, the experts held, the vocabulary slice and the positions;
    every width; both patterns whole."""
    cfg = config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["router_width"], cfg["expert_offset"]) \
        == (7, 16, 19072, 256, 64)
    assert cfg["layers_kept"] == list(range(7))
    assert [cfg["hybrid_layer_pattern"][i] for i in range(7)] == [
        0, 1, 1, 1, 1, 0, 1]
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    for key in ("published", "deployment", "tolerances", "rehearsal"):
        assert cfg[key]
    for key in ("rotated_columns", "value_scale", "sink", "window",
                "selection_bias", "routed_scaling_factor", "qk_norm",
                "attention_chunk_size", "mtp_layers", "eos_token_id",
                "weights"):
        assert cfg["assumed"][key], key
    sm = cfg["run"]["state_manager"]
    assert sm["num_kv_blocks"] == sm["max_tracked_sequences"] * 40
    assert 40 * sm["kv_block_size"] == cfg["run"]["max_seq_len"] == 5120
    assert cfg["run"]["state_manager_why"]["sweep"]
    a, b = cfg["run"]["compare"]["prefill_tokens"]
    n_dec = cfg["run"]["compare"]["decode_positions"]
    assert a > 2 * sm["max_q_per_seq"] and b > sm["max_q_per_seq"]
    assert n_dec >= 600 and a + n_dec <= 5120      # each ring turns 4x


def model_cfg():
    """The published sizes as the need functions see them: the program's
    own ``GPTConfig`` of the cell's configuration."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "reference"))
    import _mimo_v2
    from deepspeed_tpu.models import GPTConfig
    return GPTConfig(**_mimo_v2.program_config(config()), max_seq_len=5120)


def test_need_functions_against_a_hand_count():
    cfg = model_cfg()
    full, win = cfg.for_layer(0), cfg.for_layer(1)
    # ISSUE 53's arithmetic: a full layer's attention 89.13 M, a window
    # layer's 94.37 M weights
    assert attention.weights(full, 4096) == 4096 * (
        64 * 192 + 4 * 192 + 4 * 128 + 64 * 128) == 89_128_960
    assert attention.weights(win, 4096) == 94_371_840
    w = costs_serve.row_weights(cfg)
    assert w["attention"] == 2 * 89_128_960 + 5 * 94_371_840
    assert w["mlp"] == 3 * 4096 * 16384 and w["router"] == 6 * 4096 * 256
    # a value counted as wide as its key would be 106.95 M a full layer
    assert attention.weights(types.SimpleNamespace(
        num_heads=64, kv_heads=4, head_dim=192), 4096) == 106_954_752
    # a cached token: 2 full layers x 4 heads x 320 x 2 B; 5 window x 8
    assert costs_swa.kv_bytes_per_token(cfg) == (5120, 25600)
    assert attention.pair_flops(full) == 2 * 64 * 320
    # a decode step at 96 slots and 2,500 of context: 96 x 2,500 keys on
    # each full layer, 96 x 128 on each window layer
    keys_g, keys_w = 96 * 2500, 96 * 128
    layers = attn_rooflines.calling_layers(cfg)
    flops, byts = attn_rooflines.step_need(
        cfg, layers, (keys_g, keys_w), (keys_g, keys_w), 96)
    assert flops == 2 * 64 * 320 * (2 * keys_g + 5 * keys_w)
    assert byts == (2560 * 2 * keys_g + 5120 * 5 * keys_w
                    + 7 * 96 * 64 * 320 * 2)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share, bound = costs.roofline_share(flops, byts, 0.002, peaks)
    assert bound == "memory" and 90 < share < 100       # 1.9 ms at the roof
    need = costs_serve.window_need(cfg, {
        "rows": 1000, "sampled": 10, "moe_local": 3000,
        "pairs_global": 5e6, "pairs_window": 1e5})
    assert need["left_out"] == []
    assert need["terms"]["attention"] == 2 * 64 * 320 * (2 * 5e6 + 5 * 1e5)
    assert need["terms"]["weights_experts"] == 6.0 * 4096 * 2048 * 3000
    assert need["terms"]["weights_head"] == 2.0 * 4096 * 19072 * 10


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


def test_span_readers_on_spans_and_on_a_program_without_them():
    cfg = model_cfg()
    groups = dict(kv_bytes_per_token_global=5120,
                  kv_bytes_per_token_window=25600)
    spans = [
        span("ds.mixed_dispatch", 10, tokens=512, seqs=60, qk_pairs=900000,
             qk_pairs_window=70000, ctx_tokens=150000,
             ctx_tokens_window=7000, one_row_slots=59,
             ctx_tokens_one_row=140000, ctx_tokens_window_one_row=7552,
             kvw_released=90, kvw_released_decode=60, kvw_allocated=100,
             **groups),
        span("ds.burst_dispatch", 30, tokens=6144, steps=64, seqs=96,
             ctx_tokens=240000, ctx_tokens_window=12288, kvw_released=100,
             kvw_released_decode=70, kvw_allocated=110, **groups)]
    ctx = {"_xmeta": {"devices": {}, "annotations": spans},
           "trace_window": (0, 100), "model_cfg": cfg,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert swa_spans.read(ctx, {"what": "kv_bytes_per_token_global"}) == 5120
    assert swa_spans.read(ctx, {"what": "kv_bytes_per_token_window"}) == 25600
    import span_counters
    assert span_counters.read(ctx, {
        "num": "kvw_released_decode", "den": "kvw_released", "scale": 100.0,
        "over": "run"}) == 70.0
    # one decode step of the burst: the mean over its 64 steps
    layers = attn_rooflines.calling_layers(cfg)
    keys, slots, seen = attn_rooflines.decode_step(spans, True)
    assert seen["seqs"] == slots == 96 and seen["span_steps"] == 64
    assert seen["ctx_tokens"] == 240000 + 96 * 65 / 2
    flops, byts = attn_rooflines.step_need(cfg, layers, keys, keys, slots)
    assert flops == 2 * 64 * 320 * (2 * seen["ctx_tokens"] + 5 * 12288)
    # the mixed step's prefill kernel: its 59 one-row slots taken off
    _, _, rows, seen = attn_rooflines.prefill_step(spans, True)
    assert seen["rows"] == rows == 512 - 59 and seen["spans"] == 1
    assert seen["qk_pairs"] == 900000 - 140000 - 59
    assert seen["qk_pairs_window"] == 70000 - 7552
    assert attn_rooflines.read(ctx, {"kernel": "paged_decode",
                                     "program": "ragged_decode"}) is None
    bare = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, tokens=5, kv_bytes_per_token=8960)]},
        "trace_window": (0, 100), "model_cfg": cfg}
    assert swa_spans.read(bare, {"what": "kv_bytes_per_token_window"}) is None
    assert swa_scope_time.read(bare, {"program": "ragged_decode",
                                      "group": "attn_window"}) is None
    path = "jit(f)/kv_pool/while/body/attn_window/jit(attend)/attn_kernel/"
    assert swa_scope_time.group_of(
        {"tf_op": path + "paged_decode/pallas_call"}) == "attn_window"
    assert swa_scope_time.group_of(
        {"tf_op": "jit(f)/attn_global/jit(_mixed_attention)/attn_kernel/"
         "ragged_prefill/pallas_call"}) == "attn_global"
    assert swa_scope_time.group_of(
        {"tf_op": "jit(f)/attn_kernel/paged_decode/pallas_call"}) is None
    assert swa_scope_time.group_of({"tf_op": "jit(f)/mlp/dot"}) is None


def test_the_cell_reads_what_it_was_accepted_with():
    """Held by names through ``run.metric_applies``, not by a count or a
    place in the manifest, which the next cell's entries move."""
    assert_reads_what_it_was_accepted_with(CELL, NEW)
