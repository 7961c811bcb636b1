"""The cell ``serve-dots3-longdoc-batch`` end to end on the CPU at its tiny
preset (``--rehearse``: the selection binding, both page groups' pools and
the index-key pool, the paged kernels interpreted in their latent form at the
window layers' width, the comparison with the plain dots3_note reference), its
metrics' entries, files and readers, the ``sparse`` reader on spans as the
program writes them, the need functions against a hand count, and the
configuration against the catalog's row."""

import json
import os
import subprocess
import sys

import costs_dsa
import sparse
from test_cells import (ENV, MANIFEST, no_longer_read, readings,
                        run_cell)

CELL = "serve-dots3-longdoc-batch"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots3-note-prev-5l-ep8.json")) as f:
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
    assert not notes["drained_at_deadline"]
    assert notes["completed_in_log"] == notes["requests"]
    assert notes["dispatches"]["burst"] > 0


def test_a_planted_fault_reads_not_correct_through_the_harness():
    """``tools/dsa_compare.py --plant`` is ``run.py`` with the reference
    swapped for one with a fault in: the runner's own comparison, under the
    configuration's own limits, has to say ``correct: false``.  At the tiny
    preset the dropped rescale of the latents reads past the rehearsal's
    wide limits; the published widths' readings are the chip's (PERF.md
    section 6, PR 36)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "dsa_compare.py"),
         "--workload", CELL, "--seed", "5", "--rehearse",
         "--plant", "lora_rescale_dropped"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()
             if x.startswith("{")]
    assert lines[-1]["correct"] is False and lines[-1]["rehearsal"] is True


def test_its_metrics_are_entries_with_files_and_readers():
    # what a traced run reads: the two every cell reads, the 30 the cell
    # came with, the six PR 36 left out at the contract's cap, which PR 38
    # gave back by the cell's name in six lists, and what later PRs joined
    # it to: held by name against the lists PR 59 started from
    mine = readings(CELL)
    names = {p["name"] for p in mine}
    assert not no_longer_read(CELL)
    assert {"index_score_roofline", "sparse_decode_roofline",
            "sparse_prefill_roofline", "window_latent_decode_roofline",
            "window_latent_prefill_roofline", "index_selected_share.sparse",
            "index_pool_bytes_per_token", "decode_index_ms.sparse",
            "mixed_index_ms.sparse", "kv_window_pages_released_share",
            "decode_moe_route_ms", "mixed_moe_route_ms",
            "decode_moe_shared_ms", "moe_local_share_of_assignments",
            "decode_mla_absorb_ms", "mixed_mla_absorb_ms"} <= names
    assert {p["moves"] for p in mine} == {"serve_tokens_per_s", "setup_s"}
    for p in mine:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               p["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
        if p["name"].endswith("_roofline") and spec["reader"] == "sparse":
            assert p["unit"] == "%" and spec["need"]
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == list(_config()["reduced"])
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's row under the same key but the four
    listed in ``reduced``; the nested ``layer_types`` whole."""
    cfg = _config()
    want = dict(
        hidden_size=5120, intermediate_size=13824, moe_intermediate_size=1536,
        num_attention_heads=128, num_key_value_heads=128, kv_lora_rank=512,
        q_lora_rank=1024, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, index_head_dim=128, index_n_heads=64,
        index_topk=2048, swa_num_attention_heads=64,
        swa_num_key_value_heads=64, swa_kv_lora_rank=1024,
        swa_q_lora_rank=1024, swa_qk_nope_head_dim=192,
        swa_qk_rope_head_dim=64, swa_v_head_dim=128, swa_rope_theta=50000,
        sliding_window_size=513, rope_theta=80000000, num_experts_per_tok=8,
        n_shared_experts=1, routed_scaling_factor=1, first_k_dense_replace=1,
        rms_norm_eps=1e-05, num_hidden_layers=5, n_routed_experts=32,
        vocab_size=19008, max_position_embeddings=32768)
    assert {k: cfg[k] for k in want} == want
    assert len(cfg["layer_types"]) == 46 and cfg["layers_kept"] == [
        0, 1, 2, 3, 4]
    assert list(cfg["reduced"]) == ["num_hidden_layers", "n_routed_experts",
                                    "vocab_size", "max_position_embeddings"]
    assert cfg["published"]["n_routed_experts"] == 256 == cfg["router_width"]
    assert cfg["published"]["num_hidden_layers"] == 46
    for key in ("assumed", "deployment", "tolerances", "rehearsal"):
        assert cfg[key]
    sm = cfg["run"]["state_manager"]
    pages = cfg["run"]["max_seq_len"] // sm["kv_block_size"]
    assert sm["num_kv_blocks"] == sm["max_tracked_sequences"] * pages
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longdoc-batch.json")) as f:
        mix = json.load(f)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                    "sigma": 0.7, "min": 4096, "max": 32512}
    assert mix["output_tokens"] == {"dist": "fixed", "value": 256}
    assert mix["order_block"] == 8 and mix["stream_sync"] is False
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["value"]
            == cfg["run"]["max_seq_len"])


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


def test_sparse_reader_on_spans_and_on_a_program_without_them():
    ctx = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, index_bytes_per_token=512, tokens=5),
        span("ds.burst_dispatch", 30, index_bytes_per_token=512)]},
        "trace_window": (0, 100)}
    spec = {"what": "span_arg", "arg": "index_bytes_per_token"}
    assert sparse.read(ctx, spec) == 512.0
    bare = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, tokens=5)]}, "trace_window": (0, 100)}
    assert sparse.read(bare, spec) is None           # the parent's spans
    assert sparse.read(bare, {"what": "roofline", "program": "ragged_",
                              "scope": "selected_attention",
                              "need": "selected_mixed",
                              "name": "x"}) is None  # no device trace
    assert sparse.read({"_xmeta": None}, spec) is None


def test_needs_come_from_the_spans_of_the_steps_that_took_the_path():
    import types
    win = types.SimpleNamespace(num_heads=64, latent_dim=1088,
                                kv_lora_rank=1024)
    full = types.SimpleNamespace(num_heads=128, latent_dim=576,
                                 kv_lora_rank=512, index_n_heads=64,
                                 index_head_dim=128)
    cfg = types.SimpleNamespace(
        num_layers=5, index_topk=2048,
        window_for_layer=lambda i: 513 if i >= 2 else None,
        for_layer=lambda i: win if i >= 2 else full)
    spans = [
        # a chunk of 1,024 at context 6,000 beside 3 decode rows at 5,000
        span("ds.mixed_dispatch", 10, tokens=1027, ctx_tokens=21000,
             one_row_slots=3, ctx_tokens_one_row=15000,
             index_pairs_step=6669827, sel_pairs_step=2048 * 1027,
             sel_pairs_one_row=3 * 2048, qk_pairs_window=513 * 1027,
             ctx_tokens_window=4 * 513, ctx_tokens_window_one_row=3 * 513),
        # a first chunk alone in a program no wider than the selection
        span("ds.mixed_dispatch", 20, tokens=1024, ctx_tokens=0,
             one_row_slots=0, ctx_tokens_one_row=0, index_pairs_step=0,
             sel_pairs_step=524800, sel_pairs_one_row=0,
             qk_pairs_window=400000, ctx_tokens_window=0,
             ctx_tokens_window_one_row=0)]
    flops, byts, seen = sparse.need({"need": "index_mixed"}, spans, cfg)
    assert flops == 2 * 64 * 128 * (6669827 - 15003) * 2
    assert byts == 128 * (21000 + 1027 - 15003) * 2 * 2
    flops, byts, _ = sparse.need({"need": "selected_mixed"}, spans, cfg)
    assert flops == 2 * 1088 * 128 * 2048 * 1027 * 2
    assert byts == 576 * (21000 + 1027) * 2 * 2      # a key once a chunk
    flops, byts, _ = sparse.need({"need": "selected_decode"}, spans, cfg)
    assert flops == 2 * 1088 * 128 * 3 * 2048 * 2
    assert byts == 576 * 3 * 2048 * 2 * 2            # 1,152 B a kept key
    flops, byts, _ = sparse.need({"need": "window_decode"}, spans, cfg)
    assert flops == 2 * 2112 * 64 * 3 * 513 * 3
    assert byts == 1088 * 3 * 513 * 3 * 2            # 2,176 B a key
    flops, _, seen = sparse.need({"need": "window_mixed"}, spans, cfg)
    assert seen["tokens"] == 1025.5                  # over both spans
    assert sparse.need({"need": "selected_decode"}, spans[1:], cfg) is None


def test_need_functions_against_a_hand_count():
    # a chunk of 1,024 rows at context 30,000 on two selecting layers
    pairs = sum(30001 + i for i in range(1024))
    flops, byts = costs_dsa.index_score_cost(pairs, 31024, 2, 64, 128)
    assert flops == 2 * 64 * 128 * pairs * 2          # 1.0 TFLOP
    assert byts == 256 * 31024 * 2
    flops, byts = costs_dsa.selected_attention_cost(
        1024 * 2048, 31024, 2, 128, 576, 512)
    assert flops == 2 * (576 + 512) * 128 * 1024 * 2048 * 2
    assert byts == 1152 * 31024 * 2
    flops, byts = costs_dsa.window_latent_cost(24 * 513, 24 * 513, 3, 64,
                                               1088, 1024)
    assert flops == 2 * (1088 + 1024) * 64 * 24 * 513 * 3
    assert byts == 2176 * 24 * 513 * 3


def test_rooflines_on_a_device_trace_as_the_readers_see_it():
    """A hand-made device trace (one mixed program with the index kernel, an
    op under ``selected_attention`` and a window kernel; one decode program
    of two loop steps) through ``sparse.roofline``: each share is needed
    work over peak over the named operations' time, and a program without
    them reads nothing."""
    import types
    win = types.SimpleNamespace(num_heads=64, latent_dim=1088,
                                kv_lora_rank=1024)
    full = types.SimpleNamespace(num_heads=128, latent_dim=576,
                                 kv_lora_rank=512, index_n_heads=64,
                                 index_head_dim=128)
    cfg = types.SimpleNamespace(
        num_layers=5, index_topk=2048,
        window_for_layer=lambda i: 513 if i >= 2 else None,
        for_layer=lambda i: win if i >= 2 else full)
    pre = "jit(f)/attn_kernel/"
    meta = {
        1: {"opcode": "custom-call",
            "tf_op": pre + "attn_index/while/body/jit(_score_block)/"
            "index_score_kernel/pallas_call"},
        2: {"opcode": "fusion", "tf_op": pre + "selected_attention/dot"},
        3: {"opcode": "custom-call",
            "tf_op": pre + "window_latent/ragged_prefill/pallas_call"},
        4: {"opcode": "custom-call", "tf_op": pre + "window_latent/"
            "jit(_paged_decode_call)/paged_decode/pallas_call"},
        5: {"opcode": "fusion", "tf_op": "jit(f)/mlp/dot"}}
    ms = 1_000_000
    ops = [(1, 0, 5 * ms), (2, 5 * ms, 45 * ms), (3, 45 * ms, 49 * ms),
           (5, 49 * ms, 50 * ms),
           (4, 60 * ms, 61 * ms), (2, 61 * ms, 62 * ms), (5, 62 * ms, 63 * ms),
           (4, 63 * ms, 64 * ms), (2, 64 * ms, 65 * ms), (5, 65 * ms, 66 * ms)]
    dev = {"meta": meta, "ops": ops,
           "modules": [("ragged_forward_sampled", 0, 50 * ms),
                       ("ragged_decode_burst", 60 * ms, 66 * ms)]}
    spans = [span("ds.mixed_dispatch", 10, tokens=1027, ctx_tokens=21000,
                  one_row_slots=3, ctx_tokens_one_row=15000,
                  index_pairs_step=6669827, sel_pairs_step=2048 * 1027,
                  sel_pairs_one_row=3 * 2048, qk_pairs_window=513 * 1027,
                  ctx_tokens_window=4 * 513,
                  ctx_tokens_window_one_row=3 * 513)]
    ctx = {"_xmeta": {"devices": {0: dev}, "annotations": spans},
           "trace_window": (0, 100 * ms), "model_cfg": cfg,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = {}
    for name in ("index_score_roofline", "sparse_prefill_roofline",
                 "sparse_decode_roofline", "window_latent_prefill_roofline",
                 "window_latent_decode_roofline"):
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            got[name] = sparse.read(ctx, json.load(f))
    flops = 2 * 64 * 128 * (6669827 - 15003) * 2
    assert abs(got["index_score_roofline"]
               - 100 * flops / 197e12 / 0.005) < 1e-6
    flops = 2 * 1088 * 128 * 2048 * 1027 * 2
    assert abs(got["sparse_prefill_roofline"]
               - 100 * flops / 197e12 / 0.040) < 1e-6
    byts = 576 * 3 * 2048 * 2 * 2 * 2               # two loop steps
    flops = 2 * 1088 * 128 * 3 * 2048 * 2 * 2       # (the two nearly meet)
    assert abs(got["sparse_decode_roofline"]
               - 100 * max(byts / 819e9, flops / 197e12) / 0.002) < 1e-6
    assert 0 < got["window_latent_prefill_roofline"] < 100
    assert 0 < got["window_latent_decode_roofline"] < 100
    dev["modules"] = [("ragged_forward_sampled", 49 * ms, 50 * ms)]
    assert sparse.read(ctx, {"what": "roofline", "name": "x",
                             "program": "ragged_forward",
                             "scope": "selected_attention",
                             "need": "selected_mixed"}) is None
