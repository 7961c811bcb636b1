"""What bounds a serving claim (PR 42): ``costs_serve`` against a hand count
on each serving configuration's ``rehearsal`` preset (dense, window +
experts, latent, selecting); ``serve_mfu`` without a profiler trace; a closed
list's traced stretch placed by the list's progress; the expert GEMM's time
taken by scope."""

import json
import os
import time
import types

import pytest

import costs_serve
import run as bench_run
import serve_mfu
import window_rooflines

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def rehearsal_cfg(name):
    """The model configuration ``run.py --rehearse`` builds for ``name``."""
    from deepspeed_tpu.models import GPTConfig
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        sizes = json.load(f)
    sizes = {**sizes, **sizes["rehearsal"]}
    ref = bench_run.load_module(
        os.path.join(BENCH, "reference", name + ".py"),
        "ref_" + "".join(c if c.isalnum() else "_" for c in name))
    return GPTConfig(**ref.program_config(sizes), max_seq_len=512)


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
    assert costs_serve.row_weights(cfg) == hand["weights"]
    need = costs_serve.window_need(cfg, hand["counts"])
    assert need["terms"] == {k: float(v) for k, v in hand["terms"].items()}
    assert need["flops"] == sum(hand["terms"].values())
    assert need["left_out"] == []


def test_the_published_widths_give_the_configurations_own_parameter_count():
    """dots3's file states its attention's parameters a kind of layer
    (``published.parameters``): the weights a row passes are those."""
    full = costs_serve.attention_weights(
        5120, 128, 128, 192, v_head_dim=128, kv_lora_rank=512,
        qk_rope_head_dim=64, q_lora_rank=1024, gate="headwise",
        index_heads=64, index_dim=128)
    # wq_a 5.24 + wq_b 25.17 + wkv_a 2.95 + wkv_b 16.78 + wo 83.89 + gate
    # 0.66 + indexer 9.37 M, as that file lists them (its 144.1 M has the
    # norms' vectors too)
    assert full == (5242880 + 25165824 + 2949120 + 16777216 + 83886080
                    + 655360 + 9371648)
    # Moonlight: 13.76 M a layer
    assert round(costs_serve.attention_weights(
        2048, 16, 16, 192, v_head_dim=128, kv_lora_rank=512,
        qk_rope_head_dim=64) / 1e6, 2) == 13.76


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


def test_the_expert_gemm_is_taken_by_scope_whatever_its_name():
    """A hand-made trace whose expert product is a custom call of another
    name under ``.../mlp/moe_experts/...`` reads the same
    ``expert_gemm_roofline`` as one named ``ragged-dot-none`` (which keeps no
    scope path), and what the scope costs around the product counts."""
    with open(os.path.join(BENCH, "metrics",
                           "expert_gemm_roofline.json")) as f:
        spec = json.load(f)
    ms = 1_000_000
    pre = "jit(ragged_forward_sampled)/while/body/mlp/"

    def ctx_with(product):
        meta = {1: product,
                2: {"name": "fusion.7", "opcode": "fusion",
                    "tf_op": pre + "moe_experts/mul"},        # the activation
                3: {"name": "fusion.9", "opcode": "fusion",
                    "tf_op": pre + "moe_route/top_k"},
                4: {"name": "while.1", "opcode": "while",
                    "tf_op": pre + "moe_experts/while"}}      # a container
        ops = [(3, 0, 1 * ms), (4, 1 * ms, 9 * ms), (1, 1 * ms, 7 * ms),
               (2, 7 * ms, 9 * ms)]
        dev = {"meta": meta, "ops": ops,
               "modules": [("ragged_forward_sampled", 0, 10 * ms)]}
        spans = [{"name": "ds.mixed_dispatch", "thread": "t",
                  "start_ns": t, "end_ns": t + 5,
                  "args": {"moe_local": str(a), "moe_touched": str(b)}}
                 for t, a, b in ((10, 1000, 40), (20, 9000, 200))]
        cfg = types.SimpleNamespace(
            num_layers=1, hidden_size=3072, expert_dim=3072,
            window_for_layer=lambda i: None)
        return {"_xmeta": {"devices": {0: dev}, "annotations": spans},
                "trace_window": (0, 100 * ms), "model_cfg": cfg,
                "peaks": PEAKS}

    named = window_rooflines.read(ctx_with(
        {"name": "ragged-dot-none", "opcode": "custom-call", "tf_op": ""}),
        spec)
    other = window_rooflines.read(ctx_with(
        {"name": "expert_mlp_kernel", "opcode": "custom-call",
         "tf_op": pre + "moe_experts/pallas_call"}), spec)
    assert named == other
    # 8,000 rows over 160 touched experts in 8 ms of scope time (6 of the
    # product, 2 of the activation; the container is not work)
    flops = 8000 * 3 * 2 * 3072 * 3072
    byts = (160 * 3 * 3072 * 3072 + 8000 * 3 * 6144) * 2
    assert abs(named - 100 * max(flops / 197e12, byts / 819e9) / 0.008) < 1e-9
    # outside the scope and under another name it is not the expert GEMM
    assert window_rooflines.read(ctx_with(
        {"name": "expert_mlp_kernel", "opcode": "custom-call",
         "tf_op": pre + "moe_route/pallas_call"}), spec) == pytest.approx(
             100 * max(flops / 197e12, byts / 819e9) / 0.002)
