"""Roofline shares of the kernels of a model with short-conv layers: needed
work (``costs_conv.py``) over peak over device time.

``kernel: short_conv``: the conv of all conv layers, its time the device
time of EVERYTHING under the scope ``short_conv`` in the decode programs
(``ragged_decode*``: single steps and fused bursts) and the mixed ones
(``ragged_forward*``) (``conv_scope_time``'s rule: by scope, not by kernel
name, so a kernel of any name moves the reading and none can send it past
100%; the plan, the gather of a prompt chunk's rows and the write-back of
the tails are under the scope too).  Its need: each row's ``B * X`` in and
``v`` out, and a tail in and out for every one-row slot (a decode step's
slots, a mixed step's riders); a prompt chunk's tail, 2 rows beside its
hundreds, is left out: a little less need, never more.

``kernel: paged_decode`` / ``ragged_prefill``: the two Pallas attention
kernels, taken by name (they carry their ``pallas_call(name=)`` in their
scope path), with the need of the ATTENTION layers alone
(``costs_conv.paged_decode_cost`` / ``ragged_prefill_cost``): the accepted
readers reckon a call a layer over ``num_layers``, which a conv layer is
not.  The host runs ahead of the chip, so spans and kernel events of one
traced window are not of the same steps: the need is the MEAN need of a
step of the kind over the window's spans times the steps of the kind the
trace holds.  A decode step's contexts come from the window's decode and
burst spans or, where the window holds none (a cohort's bursts are
dispatched in one clump ahead of the chip), from the one-row slots of its
mixed spans (``latent``'s rule); a mixed step's one-row slots go to the
decode kernel, so their pairs are taken off the prefill need.

A program without the scopes, the kernels or the spans' arguments (a model
with no conv layer, the parent) reads nothing.
"""

import bisect
import json

import conv_scope_time
import costs
import costs_conv
import serve_trace
import span_counters
import xmeta

KERNEL_SCOPE = {"paged_decode": "/paged_decode/",
                "ragged_prefill": "/ragged_prefill/"}


def _conv_model(ctx):
    cfg = ctx.get("model_cfg")
    kinds = getattr(cfg, "layer_types", ()) or ()
    return cfg if "conv" in kinds else None


def _short_conv(ctx, spec, cfg, peaks, spans):
    dec = conv_scope_time.of_program(ctx, "ragged_decode")
    mix = conv_scope_time.of_program(ctx, "ragged_forward")
    ns = sum(g["ns"].get("short_conv", 0) for g in (dec, mix) if g)
    if not ns:
        return None
    conv_layers = costs_conv.layers(cfg)[0]
    flops = byts = 0.0
    seen = {}
    if dec:                      # every live slot one row a step
        _, slots, n, source = _decode_steps(spans)
        if not n:
            return None
        f, b = costs_conv.short_conv_cost(cfg, slots / n, slots / n)
        flops += conv_layers * dec["loop_steps"] * f
        byts += conv_layers * dec["loop_steps"] * b
        seen["decode"] = {"steps": dec["loop_steps"], "rows": slots / n,
                          "from": source}
    if mix:                      # prompt chunks and the one-row riders
        mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
                 and "tokens" in a["args"]]
        if not mixed:
            return None
        rows = sum(float(m["tokens"]) for m in mixed) / len(mixed)
        one = sum(float(m.get("one_row_slots", 0)) for m in mixed) \
            / len(mixed)
        f, b = costs_conv.short_conv_cost(cfg, rows, one)
        flops += conv_layers * mix["runs"] * f
        byts += conv_layers * mix["runs"] * b
        seen["mixed"] = {"runs": mix["runs"], "rows": rows,
                         "one_row_slots": one}
    share, bound = costs.roofline_share(flops, byts, ns / 1e9, peaks)
    print(json.dumps({"phase": "roofline", "kernel": "short_conv",
                      "bound": bound, "scope_s": ns / 1e9,
                      "needed_flops": flops, "needed_bytes": byts,
                      "mean_per_step_from_spans": seen}), flush=True)
    return share


def _kernel_time(ctx, spec):
    run = xmeta.of_run(ctx)
    if not run or not run["devices"] or "trace_window" not in ctx:
        return None
    lo, hi = ctx["trace_window"]
    dev = run["devices"][min(run["devices"])]
    meta, scope = dev["meta"], KERNEL_SCOPE[spec["kernel"]]
    starts = [op[1] for op in dev["ops"]]
    k_ns = runs = steps = 0
    for name, a, b in dev["modules"]:
        if a < lo or b > hi or not name.startswith(spec["program"]):
            continue
        inside = [(mid, s, e) for mid, s, e in dev["ops"][
            bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]
            if e <= b and mid in meta]
        runs += 1
        steps += serve_trace.loop_steps(inside)
        k_ns += sum(e - s for mid, s, e in inside
                    if meta[mid]["opcode"] == "custom-call"
                    and scope in (meta[mid].get("tf_op") or ""))
    return (k_ns, runs, steps) if k_ns else None


def _decode_steps(spans):
    """(context tokens, slots, steps) summed over the window's decode and
    burst spans, else over the one-row slots of its mixed spans."""
    ctx_tokens = slots = n = 0.0
    for a in spans:
        args = a["args"]
        if a["name"] == "ds.mixed_dispatch" or "ctx_tokens" not in args:
            continue
        k = float(args.get("steps", 1))
        ctx_tokens += (k * float(args["ctx_tokens"])
                       + float(args["seqs"]) * k * (k + 1) / 2)
        slots += k * float(args["seqs"])
        n += k
    if n:
        return ctx_tokens, slots, n, "decode spans"
    for a in spans:
        args = a["args"]
        if a["name"] == "ds.mixed_dispatch" \
                and float(args.get("one_row_slots", 0)):
            ctx_tokens += (float(args["ctx_tokens_one_row"])
                           + float(args["one_row_slots"]))
            slots += float(args["one_row_slots"])
            n += 1
    return ctx_tokens, slots, n, "one-row slots of mixed spans"


def _attention(ctx, spec, cfg, peaks, spans):
    timed = _kernel_time(ctx, spec)
    if not timed:
        return None
    k_ns, runs, steps = timed
    if spec["kernel"] == "paged_decode":
        ctx_tokens, slots, n, source = _decode_steps(spans)
        if not n:
            return None
        seen = {"ctx_tokens": ctx_tokens / n, "seqs": slots / n,
                "span_steps": n, "from": source}
        flops, byts = costs_conv.paged_decode_cost(cfg, ctx_tokens / n,
                                                   slots / n)
        flops, byts = flops * steps, byts * steps
    else:
        mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
                 and "qk_pairs" in a["args"]]
        if not mixed:
            return None
        mean = lambda key: sum(float(m[key]) for m in mixed) / len(mixed)  # noqa: E731
        seen = {k: mean(k) for k in ("qk_pairs", "ctx_tokens", "tokens",
                                     "seqs", "ctx_tokens_one_row",
                                     "one_row_slots")}
        seen["spans"] = len(mixed)
        riders = seen["ctx_tokens_one_row"] + seen["one_row_slots"]
        flops, byts = costs_conv.ragged_prefill_cost(
            cfg, seen["qk_pairs"] - riders,
            seen["ctx_tokens"] + seen["tokens"] - riders,
            seen["tokens"] - seen["one_row_slots"])
        flops, byts = flops * runs, byts * runs
    share, bound = costs.roofline_share(flops, byts, k_ns / 1e9, peaks)
    print(json.dumps({"phase": "roofline", "kernel": spec["kernel"],
                      "bound": bound, "kernel_s": k_ns / 1e9,
                      "needed_flops": flops, "needed_bytes": byts,
                      "runs": runs, "steps": steps,
                      "mean_per_step_from_spans": seen}), flush=True)
    return share


def read(ctx, spec):
    cfg, peaks = _conv_model(ctx), ctx.get("peaks")
    spans = span_counters.dispatches(ctx)
    if cfg is None or not peaks or not spans:
        return None
    fn = _short_conv if spec["kernel"] == "short_conv" else _attention
    return fn(ctx, spec, cfg, peaks, spans)
