"""The cell ``serve-sala-longdoc-batch`` end to end on the CPU at its tiny
preset (``--rehearse``: lightning layers beside attention layers that select
4 blocks of 8 keys a KV head past 32 tokens; the Pallas paged kernels and the
state update interpreted; the comparison with the plain minicpm_sala
reference across ``put_chunked`` boundaries and ``dense_len``), a planted
fault through the harness, its metrics' entries, files and readers, the
configuration against the catalog's row, ``costs_sala``'s need against the
arithmetic written out at two shapes, the new readers on a hand-made trace
and spans, and that the cell reads what it was accepted with."""

import dataclasses
import json
import os
import subprocess
import sys
import types

import costs_sala
import costs_serve
import sala
import serve_mfu
from test_cells import (assert_reads_what_it_was_accepted_with, ENV, MANIFEST,
                        name_since_pr59, no_longer_read, readings, run_cell)
from test_serve_mfu import model_cfg as program_cfg

CELL = "serve-sala-longdoc-batch"
CONFIG = "minicpm-sala-12l"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["serve_step_mfu.sala", "block_sparse_prefill_roofline",
       "block_sparse_decode_roofline", "block_select_roofline",
       "block_dense_rows_share"]
JOINED = [
    "sched_gap_ms_per_round.batch", "sched_tokens_per_dispatch",
    "decode_step_device_ms.batch", "peak_hbm_gib.batch",
    "decode_attn_ms.batch", "mixed_attn_ms.batch",
    "padding_waste_share.batch", "mixed_one_row_slot_share.batch",
    "prefill_live_item_share.batch", "dispatch_join_share.batch",
    "dispatch_queue_ms.batch", "decode_mlp_ms.batch", "mixed_mlp_ms.batch",
    "decode_other_ms.batch", "mixed_other_ms.batch",
    "serve_unscoped_share.batch", "setup_trace_s", "setup_lower_s",
    "setup_compile_s", "setup_cache_load_s", "setup_step_programs",
    "setup_step_programs_s", "setup_engine_init_s", "setup_import_s",
    "ssm_decode_roofline", "ssm_prefill_roofline", "decode_ssm_scan_ms",
    "mixed_ssm_scan_ms", "decode_ssm_proj_ms", "mixed_ssm_proj_ms",
    "ssm_state_bytes_per_slot", "ssm_step_rows_share",
    "decode_index_ms.sparse", "mixed_index_ms.sparse",
    "index_selected_share.sparse", "index_pool_bytes_per_token",
    "decode_live_context_tokens.latent"]


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
    # the window's rows crossed dense_len: the selection kept fewer pairs
    # than are causal, and scored pooled keys
    need = notes["need_counts"]
    assert 0 < need["selected_pairs"] and need["index_pairs"] > 0
    # the comparison crossed put_chunked boundaries and dense_len
    tiny = config()["rehearsal"]
    assert max(tiny["run"]["compare"]["prefill_tokens"]) > max(
        tiny["run"]["state_manager"]["max_q_per_seq"],
        tiny["sparse_config"]["dense_len"])


def test_a_planted_fault_reads_not_correct_through_the_harness():
    """``tools/sala_compare.py --plant`` is ``run.py`` with the reference
    swapped for one with a fault in: the runner's own comparison, under the
    rehearsal's own limits, has to say ``correct: false``."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "sala_compare.py"),
         "--workload", CELL, "--seed", "5", "--rehearse",
         "--plant", "decay_a_head_off"],
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
    assert len(mine) == len(set(names))
    assert {name_since_pr59(n) for n in JOINED + NEW} | {
        "compile_cache_misses", "compiles_in_window"} <= set(names)
    assert not no_longer_read(CELL)
    # they count every causal pair, or dots3's costs (the whole step's share
    # is under the one name since PR 59: the need asks each layer its kind)
    assert "serve_step_mfu" in names
    for other in ("sparse_decode_roofline", "sparse_prefill_roofline",
                  "index_score_roofline", "kv_pool_bytes_per_token"):
        assert other not in names
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
    assert len(MANIFEST["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "longdoc16k-batch")
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers",
                                "max_position_embeddings"]
    assert CELL in next(e for e in MANIFEST["end_to_end"]
                        if e["name"] == "serve_tokens_per_s")["workloads"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under its own name and value, but for
    the two that ``reduced`` names; the layers kept are the published 9-20,
    three ``minicpm4`` among nine ``lightning-attn``."""
    cfg = config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers"]
    assert cfg["layers_kept"] == list(range(9, 21))
    kinds = [cfg["mixer_types"][i] for i in cfg["layers_kept"]]
    assert kinds.count("minicpm4") == 3 and kinds.count("lightning-attn") == 9
    assert kinds[0] == kinds[7] == kinds[8] == "minicpm4"
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["max_position_embeddings"] == cfg["run"]["max_seq_len"] == 66048
    for key in ("assumed", "deployment", "tolerances", "rehearsal"):
        assert cfg[key]
    for key in ("sparse_config", "pooled_softmax", "block_score",
                "lightning_decay", "lightning_rope", "lightning_norms",
                "lightning_state_dtype", "mup_denominator", "eos_token_id",
                "weights"):
        assert cfg["assumed"][key], key
    assert cfg["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "window_size": 2048, "init_blocks": 1, "dense_len": 8192}
    sm = cfg["run"]["state_manager"]
    assert sm["kv_block_size"] == 128 and 516 * 128 == 66048
    assert sm["max_q_per_seq"] == sm["max_ragged_batch_size"] == 1024
    assert cfg["run"]["compare"] == {"prefill_tokens": [20480, 12288],
                                     "decode_positions": 256}
    tiny = cfg["rehearsal"]["sparse_config"]
    assert tiny == {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                    "topk": 4, "window_size": 16, "init_blocks": 1,
                    "dense_len": 32}


def test_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longdoc16k-batch.json")) as f:
        mix = json.load(f)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 16384,
                                    "sigma": 0.6, "min": 8192, "max": 65536}
    assert mix["output_tokens"] == {"dist": "fixed", "value": 512}
    assert mix["arrivals"]["process"] == "all_at_zero"
    assert mix["order_block"] == 8 and mix["stream_sync"] is False
    n = round(mix["arrivals"]["requests_per_window_s"] * 45)
    assert n % 8 == 0 and 0 < mix["trace"]["start_share"] < 1
    import traffic
    lens = traffic.quantile_lengths(mix["prompt_tokens"], n)
    assert lens.min() >= 8192 and lens.max() + 512 <= 66048


def model_cfg(layers=12):
    """The published sizes as the need functions see them."""
    cfg = config()
    kinds = ["attention" if cfg["mixer_types"][i] == "minicpm4"
             else "lightning" for i in cfg["layers_kept"]][:layers]
    return types.SimpleNamespace(
        num_layers=len(kinds), hidden_size=4096, num_heads=32, kv_heads=2,
        head_dim=128, mlp_dim=16384, gated_mlp=True, attn_gate=True,
        vocab_size=73448, ssm_heads=32, ssm_head_dim=128, ssm_state=128,
        ssm_groups=32, layer_types=tuple(kinds), block_topk=64,
        attention_layers=tuple(i for i, k in enumerate(kinds)
                               if k == "attention"),
        is_scan_layer=lambda i: kinds[i] == "lightning")


def test_need_functions_against_a_hand_count():
    cfg = model_cfg()
    assert costs_sala.layers(cfg) == (9, 3)
    real = program_cfg(CONFIG)
    w = costs_serve.row_weights(real)
    assert w["lightning_proj"] == 9 * 5 * 4096 * 4096
    assert w["attention"] == 3 * (3 * 4096 * 4096 + 2 * 4096 * 256)
    assert w["mlp"] == 12 * 3 * 4096 * 16384
    # the issue's 3.930 B parameters less the gains: a row passes every
    # matrix but the embedding
    assert sum(w.values()) + 2 * 73448 * 4096 == 3929972864 - 4096 * 25 \
        - 128 * (3 * 2 + 9 * 3)
    # a decode step, one slot at a context of 16,384 on 3 layers: 63 whole
    # blocks and the own block's 1 key a KV head
    pairs = 63 * 64 + 1
    flops, byts = costs_sala.kept_attention_cost(pairs, pairs, 3, 32, 2, 128)
    assert flops == 3 * pairs * 32 * 4 * 128
    assert byts == 3 * pairs * 2 * 2 * 128 * 2          # 1,024 B a key
    # a chunk of 1,024 rows at contexts 16,384-17,407 scores 1,023-1,086
    # pooled keys a row and reads the slot's 1,087 once
    pooled = sum((t - 31) // 16 + 1 for t in range(16384, 17408))
    flops, byts = costs_sala.block_score_cost(pooled, 1087, 3, 32, 2, 128)
    assert flops == 3 * pooled * 32 * 2 * 128
    assert byts == 3 * 1087 * 2 * 128 * 2
    # a window: 1,000 rows, 100 tokens produced, 50,000 kept pairs and
    # 9,000 pooled pairs over the selecting layers
    need = costs_serve.window_need(real, {"rows": 1000, "sampled": 100,
                                          "selected_pairs": 50000,
                                          "index_pairs": 9000})
    t = need["terms"]
    assert t["weights_lightning_proj"] == 2 * 9 * 5 * 4096 * 4096 * 1000
    assert t["recurrence"] == 9 * 1000 * 4 * 32 * 128 * 128
    assert t["attention_kept"] == 50000 * 32 * 4 * 128
    assert t["block_scores"] == 9000 * 32 * 2 * 128
    assert t["weights_head"] == 2 * 4096 * 73448 * 100
    assert need["flops"] == sum(t.values()) and len(need["left_out"]) == 1
    lost = costs_serve.window_need(real, {"rows": 1000, "sampled": 100,
                                          "selected_pairs": None,
                                          "index_pairs": None})
    assert "attention_kept" not in lost["terms"] and len(lost["left_out"]) == 3
    # a second shape: two layers (one of each kind), half the rows
    small = model_cfg(2)
    assert costs_sala.layers(small) == (1, 1)
    kinds = tuple(small.layer_types)
    half = costs_serve.window_need(
        dataclasses.replace(real, num_layers=2, layer_types=kinds),
        {"rows": 500, "sampled": 0, "selected_pairs": 10,
         "index_pairs": 10})["terms"]
    assert half["weights_mlp"] == 2 * 2 * 3 * 4096 * 16384 * 500
    assert half["recurrence"] == 500 * 4 * 32 * 128 * 128
    assert half["weights_head"] == 0


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


def traced(ops_ns):
    """A hand-made trace: one mixed and one decode program, each with one
    operation of ``ops_ns`` nanoseconds under ``block_attention`` and one
    under ``attn_index``, and the spans of a mixed step whose chunk of
    1,024 rows selects at a context of 17,408 beside 8 decoding slots."""
    meta = {1: {"opcode": "fusion", "tf_op": "jit(f)/attn_kernel/"
                "block_attention/dot", "name": "a", "text": ""},
            2: {"opcode": "fusion", "tf_op": "jit(f)/attn_kernel/attn_index/"
                "dot", "name": "b", "text": ""}}
    half = 2 * ops_ns + 2000
    dev = {"meta": meta,
           "ops": [(1, 1000, 1000 + ops_ns), (2, 1000 + ops_ns,
                                              1000 + 2 * ops_ns),
                   (1, half + 1000, half + 1000 + ops_ns),
                   (2, half + 1000 + ops_ns, half + 1000 + 2 * ops_ns)],
           "modules": [("ragged_forward_sampled", 900, half),
                       ("ragged_decode_burst", half + 900, 2 * half)]}
    pairs_one = 8 * (63 * 64 + 33)
    pairs_chunk = sum(63 * 64 + t % 64 + 1 for t in range(16384, 17408))
    pooled = sum((t - 31) // 16 + 1 for t in range(16384, 17408))
    spans = [span("ds.mixed_dispatch", 10, blk_pairs_step=pairs_chunk
                  + pairs_one, blk_pairs_one_row=pairs_one,
                  blk_ctx_chunk=17408, blk_pooled_pairs=pooled,
                  blk_pooled_chunk=1087, one_row_slots=8,
                  blk_dense_rows=300, blk_sparse_rows=100),
             span("ds.mixed_dispatch", 30, blk_pairs_step=pairs_chunk
                  + pairs_one, blk_pairs_one_row=pairs_one,
                  blk_ctx_chunk=17408, blk_pooled_pairs=pooled,
                  blk_pooled_chunk=1087, one_row_slots=8,
                  blk_dense_rows=600, blk_sparse_rows=1100)]
    return {"_xmeta": {"devices": {0: dev}, "annotations": spans},
            "trace_window": (0, 2 * half + 1), "model_cfg": model_cfg(),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def spec_of(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


def test_the_readers_on_a_hand_made_trace_and_spans():
    ctx = traced(10_000_000)                        # 10 ms an operation
    pairs_chunk = sum(63 * 64 + t % 64 + 1 for t in range(16384, 17408))
    got = sala.read(ctx, spec_of("block_sparse_prefill_roofline"))
    # compute bound: 3 layers x pairs x 32 heads x 512 FLOP over 10 ms
    want = 100 * 3 * pairs_chunk * 32 * 512 / 197e12 / 0.010
    assert abs(got - want) < 1e-9 and 0 < got < 100
    got = sala.read(ctx, spec_of("block_sparse_decode_roofline"))
    byts = 3 * 8 * (63 * 64 + 33) * 1024            # memory bound
    assert abs(got - 100 * byts / 819e9 / 0.010) < 1e-9 and 0 < got < 100
    got = sala.read(ctx, spec_of("block_select_roofline"))
    assert 0 < got < 100
    # 300 of the 1,300 rows between the two dispatches took the dense path
    assert sala.read(ctx, spec_of("block_dense_rows_share")) == (
        100 * 300 / 1300)
    # the need is the least any implementation does: at the time the
    # roofline itself allows, a share reads 100 and no more
    flops = 3 * pairs_chunk * 32 * 512
    floor = traced(int(flops / 197e12 * 1e9) + 1)
    assert 99.9 < sala.read(floor, spec_of(
        "block_sparse_prefill_roofline")) <= 100.0


def test_the_readers_read_nothing_of_a_program_without_the_mechanism():
    """The parent's spans and scopes, another model's configuration: every
    new reader returns None and raises nothing."""
    bare = {"_xmeta": {"devices": {0: {"meta": {}, "ops": [], "modules": []}},
                       "annotations": [span("ds.mixed_dispatch", 10,
                                            tokens=5)]},
            "trace_window": (0, 100), "peaks": {"bf16_flops_per_s": 1.0,
                                                "hbm_bytes_per_s": 1.0}}
    dense = types.SimpleNamespace(layer_types=(), block_topk=0)
    for name in NEW[1:]:
        for cfg in (dense, model_cfg()):
            assert sala.read({**bare, "model_cfg": cfg},
                             spec_of(name)) is None, name
    assert sala.read({"model_cfg": model_cfg()},
                     spec_of("block_dense_rows_share")) is None
    assert serve_mfu.read(
        {"serve_window": {"counts": {"rows": 1}}, "peaks": {},
         "model_cfg": dense, "window_s": 1.0}, {"name": "x"}) is None
    assert serve_mfu.read({}, {"name": "x"}) is None


def test_the_cell_reads_what_it_was_accepted_with():
    """Held by names through ``run.metric_applies``, not by a count or a
    place in the manifest, which the next cell's entries move."""
    assert_reads_what_it_was_accepted_with(CELL, NEW + JOINED)
