"""``serve_mfu`` for a model that selects its keys by blocks beside lightning
layers (MiniCPM-SALA): the share of the chip's bf16 peak that a closed list's
whole window needed, over the host clock's seconds, with the need reckoned by
``costs_sala.window_need`` (matmul weights by kind of layer with both output
gates, the recurrence on the lightning layers, the KEPT pairs on the selecting
layers and the causal ones within ``block_dense_len``, the pooled scores, the
head a produced token) from the same counts the runner gathers for
``serve_mfu``.  Reads no profiler trace.  A model without a selection by
blocks (the parent's, another cell's) reads nothing here."""

import json

import costs_sala


def read(ctx, spec):
    got, peaks, cfg = (ctx.get("serve_window"), ctx.get("peaks"),
                       ctx.get("model_cfg"))
    if not got or not peaks or not getattr(cfg, "block_topk", 0):
        return None
    seconds = ctx["window_s"]
    need = costs_sala.window_need(cfg, got["counts"])
    if not seconds or not need["flops"]:
        return None
    share = costs_sala.share_of_peak(need["flops"], seconds, peaks)
    print(json.dumps({
        "phase": "mfu", "name": spec["name"], "value": share,
        "seconds": seconds, "needed_flops": need["flops"],
        "terms": {k: costs_sala.share_of_peak(v, seconds, peaks)
                  for k, v in need["terms"].items()},
        "counts": got["counts"], "left_out": need["left_out"]}), flush=True)
    return share
