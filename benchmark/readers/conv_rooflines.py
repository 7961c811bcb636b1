"""Roofline share of the short conv of a model with short-conv layers:
needed work (``costs_conv.py``) over peak over device time.

The conv of all conv layers, its time the device time of EVERYTHING under
the scope ``short_conv`` in the decode programs (``ragged_decode*``: single
steps and fused bursts) and the mixed ones (``ragged_forward*``)
(``conv_scope_time``'s rule: by scope, not by kernel name, so a kernel of any
name moves the reading and none can send it past 100%; the plan, the gather
of a prompt chunk's rows and the write-back of the tails are under the scope
too).  Its need: each row's ``B * X`` in and ``v`` out, and a tail in and out
for every one-row slot (a decode step's slots, a mixed step's riders); a
prompt chunk's tail, 2 rows beside its hundreds, is left out: a little less
need, never more.  The host runs ahead of the chip, so spans and device
events of one traced window are not of the same steps: the need is the MEAN
need of a step of the kind over the window's spans times the steps of the
kind the trace holds, a decode step's slots as ``attn_rooflines.decode_step``
finds them (the decode and burst spans, else the one-row slots of the mixed
ones).  The two paged attention kernels of such a model read through
``attn_rooflines``.

A program without the scope or the spans' arguments (a model with no conv
layer, the parent) reads nothing.
"""

import json

import attn_rooflines
import conv_scope_time
import costs
import costs_conv
import span_counters


def read(ctx, spec):
    cfg, peaks = ctx.get("model_cfg"), ctx.get("peaks")
    spans = span_counters.dispatches(ctx)
    if "conv" not in (getattr(cfg, "layer_types", ()) or ()) \
            or not peaks or not spans:
        return None
    dec = conv_scope_time.of_program(ctx, "ragged_decode")
    mix = conv_scope_time.of_program(ctx, "ragged_forward")
    ns = sum(g["ns"].get("short_conv", 0) for g in (dec, mix) if g)
    if not ns:
        return None
    conv_layers = costs_conv.layers(cfg)[0]
    flops = byts = 0.0
    seen = {}
    if dec:                      # every live slot one row a step
        step = attn_rooflines.decode_step(spans, False)
        if step is None:
            return None
        _, slots, from_spans = step
        f, b = costs_conv.short_conv_cost(cfg, slots, slots)
        flops += conv_layers * dec["loop_steps"] * f
        byts += conv_layers * dec["loop_steps"] * b
        seen["decode"] = {"steps": dec["loop_steps"], "rows": slots,
                          "from": from_spans["from"]}
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

