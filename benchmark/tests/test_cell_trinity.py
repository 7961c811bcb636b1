"""The cell ``serve-trinity-mixedlen-batch`` end to end on the CPU at its
tiny preset (``--rehearse``: the Pallas kernels interpreted, both page groups,
the expert layer's share, the comparison with the plain afmoe reference), the
readers of its per-layer metrics on spans as the program writes them, and
the need functions against a hand count."""

import json
import os
import sys

import costs_moe
import span_counters
from test_cells import MANIFEST, no_longer_read, readings, run_cell

CELL = "serve-trinity-mixedlen-batch"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_the_cell_rehearses_and_agrees_with_its_reference():
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


def test_its_metrics_are_entries_with_files_and_readers():
    mine = readings(CELL)                  # what a traced run reads
    names = {p["name"] for p in mine}
    assert not no_longer_read(CELL)       # held by name, not by a count
    assert {"expert_gemm_roofline.joined", "paged_decode_roofline.by_layer",
            "ragged_prefill_roofline.by_layer", "serve_step_mfu",
            "kv_window_pages_released_share",
            "moe_local_share_of_assignments", "decode_moe_experts_ms",
            "decode_live_context_tokens.batch"} <= names
    assert {p["moves"] for p in mine} == {"serve_tokens_per_s", "setup_s"}
    for p in mine:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               p["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


def test_span_counters_take_window_deltas_and_run_totals():
    ctx = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, moe_local=100, moe_assign=800,
             moe_touched=30, kvw_allocated=50, kvw_released=5),
        span("ds.round", 20),
        span("ds.burst_dispatch", 30, moe_local=300, moe_assign=2400,
             moe_touched=80, kvw_allocated=90, kvw_released=30),
        span("ds.decode_dispatch", 1000, moe_local=9, moe_assign=9)]},
        "trace_window": (0, 100)}
    share = {"num": "moe_local", "den": "moe_assign", "scale": 100.0,
             "over": "window"}
    assert span_counters.read(ctx, share) == 100.0 * 200 / 1600
    rows = {"num": "moe_local", "den": "moe_touched", "over": "window"}
    assert span_counters.read(ctx, rows) == 200 / 50
    released = {"num": "kvw_released", "den": "kvw_allocated",
                "scale": 100.0, "over": "run"}
    assert abs(span_counters.read(ctx, released) - 100.0 * 30 / 90) < 1e-9
    # a program without the arguments (the parent, a dense model)
    bare = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, tokens=5)]}, "trace_window": (0, 100)}
    assert span_counters.read(bare, share) is None
    assert span_counters.read({"_xmeta": None}, share) is None


def test_need_functions_against_a_hand_count():
    H, M = 3072, 3072
    # one decode step, one layer: 32 local rows over 20 experts
    flops, byts = costs_moe.expert_gemm_cost(32, 20, H, M)
    assert flops == 32 * 3 * 2 * H * M                       # 1.81 GFLOP
    assert byts == (20 * 3 * H * M + 32 * 3 * (H + M)) * 2   # 1.13 GB
    # one decode step of 64 slots at 6,000 tokens each: the global layer
    # reads all, each of 4 window layers 4,096
    flops, byts = costs_moe.paged_decode_window_cost(
        64 * 6000, 64 * 4096, 1, 4, 48, 8, 128, 64)
    keys = 64 * 6000 + 4 * 64 * 4096
    assert flops == 4 * 48 * 128 * keys
    assert byts == (2 * 8 * 128 * keys + 2 * 64 * 48 * 128 * 5) * 2
    # a chunk of 256 rows at context 8,000: row i sees 8,001 + i keys on
    # the global layer and 4,096 on a window layer
    pg = sum(8001 + i for i in range(256))
    pw = 256 * 4096
    flops, byts = costs_moe.ragged_prefill_window_cost(
        pg, pw, 8256, 4096 + 256, 256, 1, 4, 48, 8, 128)
    assert flops == 4 * 48 * 128 * (pg + 4 * pw)
    assert byts == (2 * 8 * 128 * (8256 + 4 * 4352)
                    + 2 * 256 * 48 * 128 * 5) * 2
