"""``serve_mfu`` for a model with short-conv layers: the share of the chip's
bf16 peak that a closed list's whole window needed, over the host clock's
seconds, with the need reckoned by ``costs_conv.window_need`` (matmul
weights by kind of layer, 4 experts a row from the assignment counter, the
conv and its gates, causal pairs on the attention layers only, the head a
produced token) from the same counts the runner gathers for ``serve_mfu``.
Reads no profiler trace.  A model without conv layers reads nothing here:
``serve_step_mfu`` (or ``.scan``) is its entry."""

import json

import costs_conv
import costs_serve


def read(ctx, spec):
    got, peaks, cfg = (ctx.get("serve_window"), ctx.get("peaks"),
                       ctx.get("model_cfg"))
    if not got or not peaks \
            or "conv" not in (getattr(cfg, "layer_types", ()) or ()):
        return None
    seconds = ctx["window_s"]
    need = costs_conv.window_need(cfg, got["counts"])
    if not seconds or not need["flops"]:
        return None
    share = costs_serve.share_of_peak(need["flops"], seconds, peaks)
    print(json.dumps({
        "phase": "mfu", "name": spec["name"], "value": share,
        "seconds": seconds, "needed_flops": need["flops"],
        "terms": {k: costs_serve.share_of_peak(v, seconds, peaks)
                  for k, v in need["terms"].items()},
        "counts": got["counts"], "left_out": need["left_out"]}), flush=True)
    return share
