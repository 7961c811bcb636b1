"""What bounds a serving claim (PR 42): ``costs_serve`` against a hand count
on each serving configuration's ``rehearsal`` preset (dense, window +
experts, latent, selecting); since PR 59 the ONE need, asked layer by layer
of the files under ``layer_costs/``, against what the parent's five family
functions gave for counts recorded on the chip; ``serve_mfu`` without a
profiler trace; a closed list's traced stretch placed by the list's
progress."""

import json
import os
import time
import types

import pytest

import costs_serve
import run as bench_run
import layer_costs
import serve_mfu
from layer_costs import latent, selecting

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def model_cfg(name, rehearsal=False):
    """The model configuration ``run.py`` builds for the configuration
    ``name``, at its published widths or at its ``rehearsal`` preset."""
    from deepspeed_tpu.models import GPTConfig
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        sizes = json.load(f)
    if rehearsal:
        sizes = {**sizes, **sizes["rehearsal"]}
    ref = bench_run.load_module(
        os.path.join(BENCH, "reference", name + ".py"),
        "ref_" + "".join(c if c.isalnum() else "_" for c in name))
    return GPTConfig(**ref.program_config(sizes),
                     **({"max_seq_len": 512} if rehearsal else {}))


def rehearsal_cfg(name):
    return model_cfg(name, rehearsal=True)


# hidden 64 everywhere; weights a row passes outside the routed experts,
# the operations an assignment, a pair and a sampled row need: by hand
HAND = {
    # 2 layers: q, o 64 x 4 x 16 each, k, v 64 x 2 x 16 each; SwiGLU 128
    "mistral-7b-v0.3-16l": {
        "weights": {"attention": 2 * (2 * 4096 + 2 * 2048),
                    "mlp": 2 * 3 * 64 * 128, "shared": 0, "router": 0},
        "counts": {"rows": 100, "sampled": 10, "pairs_global": 5000,
                   "pairs_window": None, "moe_local": None,
                   "index_pairs": None, "selected_pairs": None},
        "terms": {"weights_attention": 2 * 24576 * 100,
                  "weights_mlp": 2 * 49152 * 100,
                  "weights_head": 2 * 64 * 512 * 10,
                  # 2 products of 2 x 16 a head, 4 heads, 2 layers
                  "attention": 4 * 4 * 16 * 5000 * 2}},
    # 5 layers (0 dense; 0-3 window 48, 4 global), each q, o, k, v as above
    # and an elementwise gate 64 x 4 x 16; 4 of 16 experts of 32 held, the
    # router 16 wide, a shared expert of 32
    "trinity-large-preview-5l-ep8": {
        "weights": {"attention": 5 * (2 * 4096 + 2 * 2048 + 4096),
                    "mlp": 3 * 64 * 128, "shared": 4 * 3 * 64 * 32,
                    "router": 4 * 64 * 16},
        "counts": {"rows": 100, "sampled": 10, "pairs_global": 5000,
                   "pairs_window": 3000, "moe_local": 70,
                   "index_pairs": None, "selected_pairs": None},
        "terms": {"weights_attention": 2 * 81920 * 100,
                  "weights_mlp": 2 * 24576 * 100,
                  "weights_shared": 2 * 24576 * 100,
                  "weights_router": 2 * 4096 * 100,
                  "weights_head": 2 * 64 * 512 * 10,
                  "weights_experts": 2 * 3 * 64 * 32 * 70,
                  "attention": 4 * 4 * 16 * (5000 * 1 + 3000 * 4)}},
    # 3 layers (0 dense): wq 64 x 4 x 24, wkv_a 64 x 136, wkv_b 128 x 4 x
    # (16 + 16), wo 4 x 16 x 64; 8 experts of 32, two shared ones as one of 64
    "moonlight-16b-a3b-7l": {
        "weights": {"attention": 3 * (6144 + 8704 + 16384 + 4096),
                    "mlp": 3 * 64 * 128, "shared": 2 * 3 * 64 * 64,
                    "router": 2 * 64 * 8},
        "counts": {"rows": 100, "sampled": 10, "pairs_global": 5000,
                   "pairs_window": None, "moe_local": 200,
                   "index_pairs": None, "selected_pairs": None},
        "terms": {"weights_attention": 2 * 105984 * 100,
                  "weights_mlp": 2 * 24576 * 100,
                  "weights_shared": 2 * 24576 * 100,
                  "weights_router": 2 * 1024 * 100,
                  "weights_head": 2 * 64 * 512 * 10,
                  "weights_experts": 2 * 3 * 64 * 32 * 200,
                  # absorbed: 2 x (136 + 128) a pair a head
                  "attention": 2 * (136 + 128) * 4 * 5000 * 3}},
    # 5 layers: 0 (dense) and 1 full and selecting, 2-4 sliding (window 33).
    # full: wq_a 64 x 48, wq_b 48 x 4 x 24, wkv_a 64 x 136, wkv_b 128 x 4 x
    # 32, wo 4 x 16 x 64, gate 64 x 4, indexer 48 x 8 x 128 + 64 x 128 + 64
    # x 8 = 94,976; sliding: wq_a, wq_b 48 x 2 x 32, wkv_a, wkv_b 128 x 2 x
    # (24 + 128), wo 2 x 128 x 64, gate 64 x 2 = 70,272
    "dots3-note-prev-5l-ep8": {
        "weights": {"attention": 2 * 94976 + 3 * 70272,
                    "mlp": 3 * 64 * 128, "shared": 4 * 3 * 64 * 32,
                    "router": 4 * 64 * 16},
        "counts": {"rows": 100, "sampled": 10, "pairs_global": 5000,
                   "pairs_window": 3000, "moe_local": 70,
                   "index_pairs": 9000, "selected_pairs": 4000},
        "terms": {"weights_attention": 2 * 400768 * 100,
                  "weights_mlp": 2 * 24576 * 100,
                  "weights_shared": 2 * 24576 * 100,
                  "weights_router": 2 * 4096 * 100,
                  "weights_head": 2 * 64 * 512 * 10,
                  "weights_experts": 2 * 3 * 64 * 32 * 70,
                  # kept pairs (the counter: summed over the two selecting
                  # layers) at 2 x (136 + 128) x 4 heads; the window layers'
                  # pairs at 2 x (136 + 128) x 2 heads x 3 layers; the
                  # causal pairs of a selecting layer are no need
                  "attention": 2 * 264 * 4 * 4000 + 2 * 264 * 2 * 3000 * 3,
                  "index": 2 * 8 * 128 * 9000}},
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_the_need_against_a_hand_count_on_the_rehearsal_preset(name):
    cfg, hand = rehearsal_cfg(name), HAND[name]
    assert {k: n for k, n in costs_serve.row_weights(cfg).items() if n} \
        == {k: n for k, n in hand["weights"].items() if n}
    need = costs_serve.window_need(cfg, hand["counts"])
    assert need["terms"] == {k: float(v) for k, v in hand["terms"].items()}
    assert need["flops"] == sum(hand["terms"].values())
    assert need["left_out"] == []


def test_the_published_widths_give_the_configurations_own_parameter_count():
    """dots3's file states its attention's parameters a kind of layer
    (``published.parameters``): the weights a row passes are those."""
    full = types.SimpleNamespace(
        num_heads=128, kv_heads=128, head_dim=192, v_head_dim=128,
        kv_lora_rank=512, qk_rope_head_dim=64, q_lora_rank=1024)
    cfg = types.SimpleNamespace(
        hidden_size=5120, for_layer=lambda i: full, attn_gate=True,
        attn_gate_headwise=True, index_n_heads=64, index_head_dim=128)
    # wq_a 5.24 + wq_b 25.17 + wkv_a 2.95 + wkv_b 16.78 + wo 83.89 + gate
    # 0.66 + indexer 9.37 M, as that file lists them (its 144.1 M has the
    # norms' vectors too)
    assert selecting.row_weights(cfg, 0) == {"attention": (
        5242880 + 25165824 + 2949120 + 16777216 + 83886080 + 655360
        + 9371648)}
    # Moonlight: 13.76 M a layer
    moon = types.SimpleNamespace(
        num_heads=16, kv_heads=16, head_dim=192, v_head_dim=128,
        kv_lora_rank=512, qk_rope_head_dim=64, q_lora_rank=0)
    assert round(latent.weights(moon, 2048) / 1e6, 2) == 13.76


# ---- PR 59: one need, asked layer by layer -----------------------------
with open(os.path.join(HERE, "data", "mfu_lines.json")) as _f:
    RECORDED = json.load(_f)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    _M = json.load(_f)
CONFIG_OF = {w["name"]: w["config"] for w in _M["workloads"]}


@pytest.mark.parametrize("cell,k", [(c, k) for c in sorted(RECORDED)
                                    for k in range(len(RECORDED[c]))])
def test_the_need_is_the_parents_on_counts_recorded_on_the_chip(cell, k):
    """``data/mfu_lines.json``: ``counts`` of ``{"phase": "mfu"}`` lines
    under ``chiprun_out/`` (and, last of a cell, the first line's with the
    counters withheld), each with what the PARENT's family function gave for
    them at the cell's published widths (``costs_serve`` / ``costs_ssm`` /
    ``costs_conv`` / ``costs_swa`` / ``costs_sala`` ``.window_need`` of
    commit 556662a, picked as its five readers picked it)."""
    line = RECORDED[cell][k]
    need = costs_serve.window_need(model_cfg(CONFIG_OF[cell]),
                                   line["counts"])
    want = line["parent"]
    assert set(need["terms"]) == set(want["terms"])
    for name, flops in want["terms"].items():
        assert need["terms"][name] == pytest.approx(flops, rel=1e-9), name
    assert need["flops"] == pytest.approx(want["flops"], rel=1e-9)
    assert sorted(need["left_out"]) == sorted(want["left_out"])
    if line["recorded_needed_flops"]:    # and what the chip run printed
        assert need["flops"] == pytest.approx(
            line["recorded_needed_flops"], rel=1e-9)


def test_every_kind_of_layer_the_cells_have_is_a_file():
    kinds = set()
    for cell in RECORDED:
        cfg = model_cfg(CONFIG_OF[cell])
        for i in range(cfg.num_layers):
            kinds |= set(layer_costs.kinds(cfg, i))
    assert kinds == {"attention", "latent", "selecting", "block_selecting",
                     "mamba", "lightning", "conv", "mlp", "experts"}
    for kind in kinds:
        cost = layer_costs.find(kind)
        assert callable(cost.row_weights) and callable(cost.window_terms)
    assert hasattr(layer_costs.find("attention"), "paged_attention_cost")


def test_a_kind_of_layer_without_a_cost_file_reads_nothing_and_is_named(
        capsys):
    """A configuration that brings a new kind of layer and not its file:
    no silent zero for its layers, no share at all, and a line that says
    which file is missing."""
    cfg = rehearsal_cfg("mistral-7b-v0.3-16l")
    odd = types.SimpleNamespace(**{
        k: getattr(cfg, k) for k in (
            "num_layers", "hidden_size", "vocab_size", "for_layer",
            "window_for_layer", "is_moe_layer", "block_topk", "index_topk")},
        layer_kind=lambda i: "attention" if i else "gated_delta")
    assert layer_costs.find("gated_delta") is None
    with pytest.raises(costs_serve.NoCostFile, match="gated_delta"):
        costs_serve.window_need(odd, {"rows": 1})
    ctx = {"peaks": PEAKS, "model_cfg": odd, "window_s": 2.0,
           "serve_window": {"counts": {"rows": 1}, "fenced_s": 0.5}}
    assert serve_mfu.read(ctx, {"name": "serve_step_mfu"}) is None
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["no_cost_file"] == "gated_delta" and line["value"] is None


def test_a_new_kind_of_layer_joins_by_a_file(tmp_path):
    """What a later ``model_config`` PR does: in a scratch copy of the
    benchmark, ONE new file under ``layer_costs/`` named as the program
    names the kind, and nothing that is there edited; the same need then
    counts the new layers by it."""
    import shutil
    import subprocess
    import sys
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "reference", "tools"))
    (bench / "layer_costs" / "gated_delta.py").write_text(
        "def row_weights(cfg, i):\n"
        "    return {'delta_proj': 7 * cfg.hidden_size}\n\n\n"
        "def window_terms(cfg, i, counts, alike):\n"
        "    return {'delta_rule': 6.0 * counts['rows'] / alike}, []\n")
    code = (
        "import sys, types, json\n"
        f"sys.path.insert(0, {str(bench)!r})\n"
        "import costs_serve\n"
        "cfg = types.SimpleNamespace(num_layers=3, hidden_size=64, "
        "vocab_size=512, gated_mlp=True, mlp_dim=128, "
        "layer_kind=lambda i: 'gated_delta', "
        "is_moe_layer=lambda i: False)\n"
        "print(json.dumps(costs_serve.window_need(cfg, {'rows': 10, "
        "'sampled': 1})))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    need = json.loads(out.stdout.strip().splitlines()[-1])
    assert need["terms"] == {
        "weights_delta_proj": 2.0 * 3 * 7 * 64 * 10,
        "weights_mlp": 2.0 * 3 * 3 * 64 * 128 * 10,
        "weights_head": 2.0 * 64 * 512, "delta_rule": 6.0 * 10}
    assert need["left_out"] == []


def test_a_count_the_program_did_not_give_is_left_out_and_named():
    cfg = rehearsal_cfg("dots3-note-prev-5l-ep8")
    counts = dict(HAND["dots3-note-prev-5l-ep8"]["counts"], moe_local=None,
                  index_pairs=None)
    need = costs_serve.window_need(cfg, counts)
    assert len(need["left_out"]) == 2
    assert "weights_experts" not in need["terms"]
    assert "index" not in need["terms"]
    full = costs_serve.window_need(cfg, HAND["dots3-note-prev-5l-ep8"][
        "counts"])
    assert 0 < need["flops"] < full["flops"]         # a lower bound
    lost = costs_serve.window_need(cfg, dict(counts, pairs_global=None))
    assert "attention" not in lost["terms"] and len(lost["left_out"]) == 2


def ev(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


def test_pairs_of_the_buffers_dispatch_events():
    events = [
        # a chunk of 3 rows at context 10 and a rider at context 20
        ev("mixed_dispatch", 0, 5, qk_pairs=(11 + 12 + 13) + 21,
           qk_pairs_window=(11 + 12 + 13) + 16, ctx_tokens=30),
        # a burst of 4 steps over 2 slots at contexts 20 and 30, window 16
        ev("burst_dispatch", 10, 5, steps=4, seqs=2, ctx_tokens=50,
           ctx_tokens_window=32),
        ev("round", 0, 20)]
    pg, pw = costs_serve.pairs_of_dispatches(events)
    assert pg == 57 + (21 + 22 + 23 + 24) + (31 + 32 + 33 + 34)
    assert pw == 52 + 4 * 32
    assert costs_serve.pairs_of_dispatches(
        [ev("decode_dispatch", 0, 1, qk_pairs=7, ctx_tokens=5)]) == (7, None)


def test_fenced_seconds_run_from_a_dispatch_to_the_end_of_its_fence():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_runner_serve", os.path.join(BENCH, "runners", "serve.py"))
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    events = [ev("mixed_dispatch", 100, 10), ev("fence", 115, 85),
              ev("retire", 200, 50),
              ev("decode_dispatch", 300, 10), ev("fence", 311, 39),
              ev("burst_dispatch", 400, 20)]           # never fenced
    seconds, unfenced = serve.fenced_seconds(events)
    assert abs(seconds - (100 + 50 + 20) / 1e6) < 1e-12 and unfenced == 1


def test_serve_mfu_reads_without_a_profiler_trace(capsys):
    cfg = rehearsal_cfg("trinity-large-preview-5l-ep8")
    hand = HAND["trinity-large-preview-5l-ep8"]
    ctx = {"trace": None, "peaks": PEAKS, "model_cfg": cfg, "window_s": 2.0,
           "serve_window": {"counts": hand["counts"], "fenced_s": 0.5}}
    flops = sum(hand["terms"].values())
    got = serve_mfu.read(ctx, {"name": "serve_step_mfu"})
    assert abs(got - 100 * flops / (2.0 * 197e12)) < 1e-12
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "mfu" and line["left_out"] == []
    assert abs(sum(line["terms"].values()) - got) < 1e-12  # the terms add up
    chat = serve_mfu.read(ctx, {"name": "serve_step_mfu.chat",
                                "over": "fenced_dispatches"})
    assert abs(chat - 4 * got) < 1e-12
    assert serve_mfu.read({**ctx, "peaks": None}, {"name": "x"}) is None
    assert serve_mfu.read({"peaks": PEAKS}, {"name": "x"}) is None


class FakeTrace(bench_run.WindowTrace):
    """``WindowTrace`` without the profiler: records when it started."""

    def _start(self):
        self.started_at = time.perf_counter()
        self.state = "tracing"
        self._note("trace_start")

    def _stop(self):
        self._note("trace_stop")
        self.state = "done"


def test_the_stretch_starts_at_the_share_and_not_at_a_clock_time():
    done = [0.0]
    tr = FakeTrace(True, "unused", start_s=0.0, length_s=50.0,
                   start_share=0.6, latest_start_s=100.0)
    tr.progress = lambda: done[0]
    tr.open(time.perf_counter())
    tr.poll()
    assert tr.state == "idle"            # start_s 0 is not what places it
    done[0] = 0.59
    tr.poll()
    assert tr.state == "idle"
    done[0] = 0.61
    tr.poll()
    assert tr.state == "tracing"
    assert tr.placed["progress_at_trace_start"] == 0.61
    tr.length_s = 0.0                    # the cap
    done[0] = 0.8
    tr.poll()
    assert tr.state == "done" and tr.placed["progress_at_trace_stop"] == 0.8
    # without a progress the clock places it, as for a trainer
    tr = FakeTrace(True, "unused", start_s=0.0, length_s=1.0, start_share=0.6)
    tr.open(time.perf_counter())
    tr.poll()
    assert tr.state == "tracing"
    # a window cut short still holds a trace: the latest start
    tr = FakeTrace(True, "unused", start_s=9.0, length_s=1.0,
                   start_share=0.6, latest_start_s=0.0)
    tr.progress = lambda: 0.1
    tr.open(time.perf_counter())
    tr.poll()
    assert tr.state == "tracing"


@pytest.mark.parametrize("reaches", [False, True])
def test_a_list_that_ends_first_still_closes_cleanly(reaches):
    tr = FakeTrace(True, "unused", start_s=0.0, length_s=60.0,
                   start_share=0.5 if reaches else 2.0, latest_start_s=60.0)
    tr.progress = lambda: 1.0
    tr.open(time.perf_counter())
    tr.run_in_thread()
    time.sleep(0.1)
    assert tr.state == ("tracing" if reaches else "idle")
    tr.close()                           # the list ended: no wait for 60 s
    assert tr.state == "done" and not tr._thread.is_alive()
    assert ("trace_stop_s" in tr.placed) == reaches
    assert (tr.started_at is not None) == reaches
