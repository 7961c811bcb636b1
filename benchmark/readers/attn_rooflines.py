"""Roofline shares of the two paged attention kernels, the need asked layer by
layer: needed work over peak over the kernels' time in the device trace.

The kernels are taken by name: they carry their ``pallas_call(name=)`` in
their scope path (``.../attn_kernel/paged_decode/pallas_call``,
``.../ragged_prefill/pallas_call``), inside the programs named
``spec["program"]*`` that lie in the traced window; in a mixed step the
one-row slots' paged decode kernel is not the prefill kernel's time.

The need of ONE step is the sum over the layers that CALL the kernels, the
layers whose kind's file has a ``paged_attention_cost``
(``layer_costs/attention.py``; not a scan, lightning or conv layer, and not a
latent or selecting one, whose kernels have entries of their own), each at
ITS query heads, kv heads, key and value widths, a window layer at
``min(context, window)`` keys and a full layer at all of them.  So a model
joins these entries by a line of ``BENCHMARK.json``, whatever mix of layers
it has.

What a step needs depends on its contexts, which the device trace does not
hold; the program's dispatch spans do (``ctx_tokens``, ``ctx_tokens_window``,
``qk_pairs``, ``qk_pairs_window``, ``tokens``, ``seqs``, ``steps``).  The host
runs ahead of the chip by up to a second, so spans and kernel events of one
traced window are not of the same steps: the need is the MEAN need of a step
of the kind over the window's spans times the steps of the kind the trace
holds.  A mixed step's one-row slots go to the decode kernel, so their pairs
(``ctx_tokens_one_row`` + ``one_row_slots``, ``ctx_tokens_window_one_row``)
come off the prefill kernel's need; where a span lacks them nothing comes
off.  A decode step's contexts come from the window's decode and burst spans
or, where the window holds none (a cohort's bursts are dispatched in one
clump ahead of the chip), from the one-row slots of its mixed spans
(``latent``'s rule).  Inside a burst the window layers' growth is left out (a
little less need, never more).  The bytes are the least any implementation
moves, so a share can read low and never over 100%.  A program without the
spans' arguments or the kernels reads nothing.
"""

import bisect
import json

import costs
import layer_costs
import serve_trace
import span_counters
import xmeta

KERNEL_SCOPE = {"paged_decode": "/paged_decode/",
                "ragged_prefill": "/ragged_prefill/"}


def kernel_time(dev, lo, hi, program, kernel):
    """(kernel ns, runs, loop steps) of the programs named ``program*``
    that lie wholly inside the traced window."""
    meta, scope = dev["meta"], KERNEL_SCOPE[kernel]
    k_ns = runs = steps = 0
    starts = [op[1] for op in dev["ops"]]      # sorted by start
    for name, a, b in dev["modules"]:
        if a < lo or b > hi or not name.startswith(program):
            continue
        inside = [(mid, s, e) for mid, s, e in dev["ops"][
            bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]
            if e <= b and mid in meta]
        runs += 1
        steps += serve_trace.loop_steps(inside)
        k_ns += sum(e - s for mid, s, e in inside
                    if meta[mid]["opcode"] == "custom-call"
                    and scope in (meta[mid].get("tf_op") or ""))
    return k_ns, runs, steps


def calling_layers(cfg):
    """[(layer, its kind's file, whether it has a window)] of the layers
    whose attention runs through the two paged kernels."""
    out = []
    for i in range(cfg.num_layers):
        cost = layer_costs.find(layer_costs.kinds(cfg, i)[0])
        if hasattr(cost, "paged_attention_cost"):
            out.append((i, cost, cfg.window_for_layer(i) is not None))
    return out


def step_need(cfg, layers, pairs, keys, rows):
    """(flops, bytes) of one step over ``layers``; ``pairs`` and ``keys``
    are (on a full layer, on a window layer)."""
    flops = byts = 0.0
    for i, cost, windowed in layers:
        f, b = cost.paged_attention_cost(cfg, i, pairs[windowed],
                                         keys[windowed], rows)
        flops, byts = flops + f, byts + b
    return flops, byts


def decode_step(spans, windowed):
    """((keys on a full layer, on a window layer), slots, seen) of ONE
    decode step: the mean over the steps of the window's decode and burst
    spans, else over the one-row slots of its mixed spans.  A full layer
    reads every cached key and the row's own."""
    ctx_g = ctx_w = slots = n = 0.0
    source = "decode spans"
    for a in spans:
        args = a["args"]
        if a["name"] == "ds.mixed_dispatch" or "ctx_tokens" not in args \
                or (windowed and "ctx_tokens_window" not in args):
            continue
        k = float(args.get("steps", 1))
        ctx_g += (k * float(args["ctx_tokens"])
                  + float(args["seqs"]) * k * (k + 1) / 2)
        ctx_w += k * float(args.get("ctx_tokens_window", 0))
        slots += k * float(args["seqs"])
        n += k
    if not n:
        source = "one-row slots of mixed spans"
        for a in spans:
            args = a["args"]
            if a["name"] != "ds.mixed_dispatch" \
                    or not float(args.get("one_row_slots", 0)) \
                    or (windowed and "ctx_tokens_window_one_row" not in args):
                continue
            ctx_g += (float(args["ctx_tokens_one_row"])
                      + float(args["one_row_slots"]))
            ctx_w += float(args.get("ctx_tokens_window_one_row", 0))
            slots += float(args["one_row_slots"])
            n += 1
    if not n:
        return None
    seen = {"ctx_tokens": ctx_g / n, "ctx_tokens_window": ctx_w / n,
            "seqs": slots / n, "span_steps": n, "from": source}
    return (ctx_g / n, ctx_w / n), slots / n, seen


def prefill_step(spans, windowed, riders_off=True):
    """((pairs full, window), (keys full, window), rows, seen) of ONE mixed
    step's prefill kernel, the mean over the mixed spans, its one-row slots
    (the decode kernel's) taken off."""
    mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
             and "qk_pairs" in a["args"]
             and (not windowed or "qk_pairs_window" in a["args"])]
    if not mixed:
        return None

    def mean(key, off=False):
        if off and not riders_off:
            return 0.0
        return sum(float(m.get(key, 0)) for m in mixed) / len(mixed)
    one = mean("one_row_slots", off=True)
    rows = mean("tokens") - one
    pairs_g = mean("qk_pairs") - mean("ctx_tokens_one_row", off=True) - one
    pairs_w = (mean("qk_pairs_window")
               - mean("ctx_tokens_window_one_row", off=True))
    keys_g = (mean("ctx_tokens") - mean("ctx_tokens_one_row", off=True)
              + rows)
    keys_w = (mean("ctx_tokens_window")
              - max(mean("ctx_tokens_window_one_row", off=True) - one, 0.0)
              + rows)
    seen = {"qk_pairs": pairs_g, "qk_pairs_window": pairs_w,
            "keys": keys_g, "keys_window": keys_w, "rows": rows,
            "one_row_slots": one, "spans": len(mixed)}
    return (pairs_g, pairs_w), (keys_g, keys_w), rows, seen


def read(ctx, spec):
    run, peaks = xmeta.of_run(ctx), ctx.get("peaks")
    spans = span_counters.dispatches(ctx)
    cfg = ctx.get("model_cfg")
    if not run or not run["devices"] or not peaks or not spans or cfg is None:
        return None
    layers = calling_layers(cfg)
    if not layers:
        return None
    lo, hi = ctx["trace_window"]
    kernel = spec["kernel"]
    dev = run["devices"][min(run["devices"])]
    k_ns, runs, steps = kernel_time(dev, lo, hi, spec["program"], kernel)
    if not k_ns:
        return None
    windowed = any(w for _, _, w in layers)
    extra = {}
    if kernel == "paged_decode":
        got = decode_step(spans, windowed)
        if got is None:
            return None
        keys, slots, seen = got
        flops, byts = step_need(cfg, layers, keys, keys, slots)
        n = steps
    else:
        got = prefill_step(spans, windowed)
        if got is None:
            return None
        pairs, keys, rows, seen = got
        flops, byts = step_need(cfg, layers, pairs, keys, rows)
        n = runs
        # what the riders' pairs were of a mixed step's attention: the need
        # had they stayed the prefill kernel's
        pairs, keys, rows, _ = prefill_step(spans, windowed, riders_off=False)
        f, b = step_need(cfg, layers, pairs, keys, rows)
        extra = {"with_riders": {
            "needed_flops": f * n, "needed_bytes": b * n,
            "share": costs.roofline_share(f * n, b * n, k_ns / 1e9,
                                          peaks)[0]}}
    flops, byts = flops * n, byts * n
    share, bound = costs.roofline_share(flops, byts, k_ns / 1e9, peaks)
    print(json.dumps({"phase": "roofline", "kernel": kernel,
                      "name": spec["name"], "bound": bound,
                      "kernel_s": k_ns / 1e9, "needed_flops": flops,
                      "needed_bytes": byts, "runs": runs, "steps": steps,
                      "layers": len(layers), **extra,
                      "mean_per_step_from_spans": seen}), flush=True)
    return share
