"""The two paged attention kernels' rooflines with the need asked layer by
layer (PR 59: ``paged_decode_roofline.by_layer``,
``ragged_prefill_roofline.by_layer``): each family's need against what its
cost function of before the fold gives (Trinity's ``costs_moe`` functions
stay; LFM2's and MiMo's arithmetic written out, as their cells' tests had
it), the riders' pairs off the prefill need, the decode step's contexts from
the mixed spans' one-row slots only where the window holds no decode span,
and a share that cannot pass 100%."""

import json
import os

import pytest

import attn_rooflines
import costs
import costs_moe
from layer_costs import attention
from test_serve_mfu import PEAKS, model_cfg

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TRINITY, LFM2, MIMO = ("trinity-large-preview-5l-ep8", "lfm2-24b-a2b-10l",
                       "mimo-v2-flash-7l-ep16")
MS = 1_000_000


def spec_of(kernel):
    with open(os.path.join(BENCH, "metrics",
                           f"{kernel}_roofline.by_layer.json")) as f:
        return json.load(f)


def cfg_of(name):
    return model_cfg(name)


def need(cfg, pairs, keys, rows):
    return attn_rooflines.step_need(
        cfg, attn_rooflines.calling_layers(cfg), pairs, keys, rows)


# (context tokens on a full layer, on a window layer, slots)
DECODE_SHAPES = [(64 * 6000, 64 * 4096, 64), (16 * 900, 16 * 900, 16)]
# (pairs full, window, keys full, window, rows)
PREFILL_SHAPES = [(sum(8001 + i for i in range(256)), 256 * 4096, 8256,
                   4096 + 256, 256),
                  (1024 * 513, 1024 * 513, 1024, 1024, 1024)]


def test_the_layers_that_call_the_kernels_are_the_attention_layers():
    assert [(i, w) for i, _, w in attn_rooflines.calling_layers(
        cfg_of(TRINITY))] == [(0, True), (1, True), (2, True), (3, True),
                              (4, False)]
    lfm2 = cfg_of(LFM2)
    assert [(i, w) for i, _, w in attn_rooflines.calling_layers(lfm2)] \
        == [(i, False) for i in lfm2.attention_layers] and len(
            lfm2.attention_layers) == 2
    assert sum(w for _, _, w in attn_rooflines.calling_layers(
        cfg_of(MIMO))) == 5
    # a scan or lightning layer, a latent or a selecting one calls neither
    for name in ("moonlight-16b-a3b-7l", "dots3-note-prev-5l-ep8",
                 "minicpm-sala-12l"):
        assert attn_rooflines.calling_layers(cfg_of(name)) == []
    assert len(attn_rooflines.calling_layers(
        cfg_of("granite-4.0-h-micro"))) == 4


@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_trinitys_decode_need_is_costs_moes(shape):
    ctx_g, ctx_w, slots = shape
    cfg = cfg_of(TRINITY)
    assert need(cfg, (ctx_g, ctx_w), (ctx_g, ctx_w), slots) == \
        costs_moe.paged_decode_window_cost(
            ctx_g, ctx_w, 1, 4, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            slots)


@pytest.mark.parametrize("shape", PREFILL_SHAPES)
def test_trinitys_prefill_need_is_costs_moes(shape):
    pg, pw, kg, kw, rows = shape
    cfg = cfg_of(TRINITY)
    assert need(cfg, (pg, pw), (kg, kw), rows) == \
        costs_moe.ragged_prefill_window_cost(
            pg, pw, kg, kw, rows, 1, 4, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim)


@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_lfm2s_decode_need_is_its_attention_layers_alone(shape):
    """``costs_conv.paged_decode_cost`` of before the fold:
    ``costs.paged_decode_cost`` a layer, two layers of ten."""
    ctx_g, _, slots = shape
    cfg = cfg_of(LFM2)
    f, b = costs.paged_decode_cost(ctx_g, cfg.num_heads, cfg.kv_heads,
                                   cfg.head_dim, slots)
    assert need(cfg, (ctx_g, 0), (ctx_g, 0), slots) == (2 * f, 2 * b)


@pytest.mark.parametrize("shape", PREFILL_SHAPES)
def test_lfm2s_prefill_need_is_its_attention_layers_alone(shape):
    pg, _, kg, _, rows = shape
    cfg = cfg_of(LFM2)
    assert need(cfg, (pg, 0), (kg, 0), rows) == \
        costs_moe.ragged_prefill_window_cost(
            pg, 0.0, kg, 0.0, rows, 2, 0, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim)


@pytest.mark.parametrize("shape", DECODE_SHAPES + [
    (p[0], p[1], p[4]) for p in PREFILL_SHAPES])
def test_mimos_need_is_each_layer_at_its_own_heads_and_widths(shape):
    """``costs_swa.attention_cost`` of before the fold, written out: two full
    layers of 64 query and 4 kv heads, five window layers of 64 and 8, keys
    192 wide and values 128."""
    pairs_g, pairs_w, rows = shape
    cfg = cfg_of(MIMO)
    flops, byts = need(cfg, (pairs_g, pairs_w), (pairs_g, pairs_w), rows)
    assert flops == 2 * 64 * 320 * (2 * pairs_g + 5 * pairs_w)
    assert byts == ((2 * 4 * pairs_g + 5 * 8 * pairs_w) * 320 * 2
                    + 7 * rows * 64 * 320 * 2)
    full, win = cfg.for_layer(0), cfg.for_layer(1)
    assert attention.pair_flops(full) == 2 * 64 * 320
    assert (attention.token_bytes(full), attention.token_bytes(win)) \
        == (4 * 320 * 2, 8 * 320 * 2)


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


# a chunk of 3 rows at context 10 beside two riders at contexts 20 and
# 5,000 (window 4,096); then a burst of 4 steps over 2 slots
MIXED = span("ds.mixed_dispatch", 10, tokens=5, seqs=3,
             ctx_tokens=10 + 20 + 5000,
             qk_pairs=(11 + 12 + 13) + 21 + 5001,
             qk_pairs_window=(11 + 12 + 13) + 21 + 4096,
             ctx_tokens_window=10 + 20 + 4096, one_row_slots=2,
             ctx_tokens_one_row=5020, ctx_tokens_window_one_row=21 + 4096)
BURST = span("ds.burst_dispatch", 30, steps=4, seqs=2, ctx_tokens=50,
             ctx_tokens_window=32)


def test_the_riders_pairs_come_off_the_prefill_need():
    (pg, pw), (kg, kw), rows, seen = attn_rooflines.prefill_step(
        [MIXED, BURST], True)
    assert (pg, pw, rows) == (11 + 12 + 13, 11 + 12 + 13, 3)
    assert kg == 10 + 3                   # the chunk's context and its rows
    assert kw == (10 + 20 + 4096) - (21 + 4096 - 2) + 3
    assert seen["one_row_slots"] == 2 and seen["spans"] == 1
    # had they stayed (the parent's Trinity reading), and a span without
    # the riders' arguments: nothing comes off
    stayed = attn_rooflines.prefill_step([MIXED], True, riders_off=False)
    bare = dict(MIXED, args={k: v for k, v in MIXED["args"].items()
                             if "one_row" not in k})
    assert stayed[:3] == attn_rooflines.prefill_step([bare], True)[:3] \
        == ((5058.0, 4153.0), (5035.0, 4131.0), 5.0)
    # a model without window layers asks for no window argument
    assert attn_rooflines.prefill_step([bare], False)[0][0] == 5058.0
    assert attn_rooflines.prefill_step([BURST], True) is None


def test_a_decode_step_reads_the_riders_only_without_decode_spans():
    (ctx_g, ctx_w), slots, seen = attn_rooflines.decode_step(
        [MIXED, BURST], True)
    assert seen["from"] == "decode spans" and seen["span_steps"] == 4
    assert ctx_g == (4 * 50 + 2 * 4 * 5 / 2) / 4 and ctx_w == 32
    assert slots == 2
    (ctx_g, ctx_w), slots, seen = attn_rooflines.decode_step([MIXED], True)
    assert seen["from"] == "one-row slots of mixed spans"
    assert (ctx_g, ctx_w, slots) == (5020 + 2, 21 + 4096, 2)
    # a mixed span without riders, or without their arguments, gives none
    assert attn_rooflines.decode_step(
        [span("ds.mixed_dispatch", 1, tokens=5, one_row_slots=0)],
        False) is None
    assert attn_rooflines.decode_step(
        [span("ds.mixed_dispatch", 1, tokens=5)], False) is None


def trace_ctx(cfg, spans, kernel_ns):
    """One mixed program and one burst of 4 steps, each step and layer a
    kernel event ``kernel_ns`` long."""
    pre = "jit(ragged_forward_sampled)/while/body/attn/attn_kernel/"
    layers = len(attn_rooflines.calling_layers(cfg))
    # a burst's loop body runs each layer's kernel once a step
    meta = {10 + k: {"name": f"paged_decode.{k}", "opcode": "custom-call",
                     "tf_op": "jit(ragged_decode_burst)/while/body/attn/"
                              "attn_kernel/paged_decode/pallas_call"}
            for k in range(layers)}
    meta |= {2: {"name": "ragged_prefill.1", "opcode": "custom-call",
                "tf_op": pre + "ragged_prefill/pallas_call"},
            3: {"name": "paged_decode.2", "opcode": "custom-call",
                "tf_op": pre + "paged_decode/pallas_call"}}
    ops, t = [], 0
    for _ in range(layers):
        ops += [(2, t, t + kernel_ns), (3, t + kernel_ns, t + 2 * kernel_ns)]
        t += 2 * kernel_ns
    modules = [("ragged_forward_sampled", 0, t)]
    a = t = t + MS
    for k in range(4 * layers):
        ops.append((10 + k % layers, t, t + kernel_ns))
        t += kernel_ns
    modules.append(("ragged_decode_burst", a, t))
    dev = {"meta": meta, "ops": ops, "modules": modules}
    return {"_xmeta": {"devices": {0: dev}, "annotations": spans},
            "trace_window": (0, t + MS), "model_cfg": cfg, "peaks": PEAKS}


@pytest.mark.parametrize("name", [TRINITY, LFM2, MIMO])
@pytest.mark.parametrize("kernel", ["paged_decode", "ragged_prefill"])
def test_the_reader_on_a_hand_made_trace_and_spans(name, kernel, capsys):
    cfg = cfg_of(name)
    ctx = trace_ctx(cfg, [MIXED, BURST], 50_000)
    got = attn_rooflines.read(ctx, spec_of(kernel))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    layers = attn_rooflines.calling_layers(cfg)
    if kernel == "paged_decode":
        flops, byts = attn_rooflines.step_need(
            cfg, layers, (55.0, 32.0), (55.0, 32.0), 2.0)
        n, seconds = 4, 4 * len(layers) * 50e-6
        assert "with_riders" not in line
    else:
        flops, byts = attn_rooflines.step_need(
            cfg, layers, (36.0, 36.0), (13.0, 14.0), 3.0)
        n, seconds = 1, len(layers) * 50e-6
        # the riders' share of what the need was with them in it
        assert line["with_riders"]["needed_flops"] > line["needed_flops"]
        assert line["with_riders"]["share"] > got
    assert got == costs.roofline_share(flops * n, byts * n, seconds,
                                       PEAKS)[0]
    assert line["name"] == f"{kernel}_roofline.by_layer"
    assert line["layers"] == len(layers) and 0 < got < 100
    # a model none of whose layers calls the kernels, a program without the
    # kernels, spans or peaks: nothing
    assert attn_rooflines.read(
        {**ctx, "model_cfg": cfg_of("moonlight-16b-a3b-7l")},
        spec_of(kernel)) is None
    assert attn_rooflines.read({**ctx, "peaks": None}, spec_of(kernel)) is None
    bare = trace_ctx(cfg, [span("ds.mixed_dispatch", 10, tokens=5)], 50_000)
    assert attn_rooflines.read(bare, spec_of(kernel)) is None
    bare = trace_ctx(cfg, [MIXED, BURST], 50_000)
    bare["_xmeta"]["devices"][0]["meta"] = {}
    assert attn_rooflines.read(bare, spec_of(kernel)) is None


@pytest.mark.parametrize("name", [TRINITY, LFM2, MIMO])
def test_a_kernel_at_the_chips_peak_reads_100_and_none_can_read_more(name):
    """The need is the least any implementation does (every pair scored
    once, every key and value read once, q in and o out once): a kernel that
    took exactly the time the chip's peak allows reads 100%, and the chip
    allows no less."""
    cfg = cfg_of(name)
    layers = attn_rooflines.calling_layers(cfg)
    flops, byts = attn_rooflines.step_need(
        cfg, layers, (55.0, 32.0), (55.0, 32.0), 2.0)
    least = max(flops / PEAKS["bf16_flops_per_s"],
                byts / PEAKS["hbm_bytes_per_s"])
    assert costs.roofline_share(flops, byts, least, PEAKS)[0] \
        == pytest.approx(100.0)
    # riders off is never more need than riders in
    off = attn_rooflines.prefill_step([MIXED], True)
    stayed = attn_rooflines.prefill_step([MIXED], True, riders_off=False)
    assert all(a <= b for a, b in zip(
        attn_rooflines.step_need(cfg, layers, *off[:3]),
        attn_rooflines.step_need(cfg, layers, *stayed[:3])))
