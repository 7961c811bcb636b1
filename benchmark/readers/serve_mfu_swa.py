"""``serve_step_mfu`` for a model whose layers differ in kv heads and whose
values are narrower than its keys: the share of the chip's bf16 peak that a
serving window's needed operations are, over the host clock's seconds of the
whole window, with ``costs_swa.window_need`` (every layer at its own heads
and widths; ``costs_serve`` counts a value as wide as its key).  Reads no
profiler trace.  A model without a value width of its own reads nothing."""

import json

import costs_serve
import costs_swa


def read(ctx, spec):
    got, peaks, cfg = (ctx.get("serve_window"), ctx.get("peaks"),
                       ctx.get("model_cfg"))
    if (not got or not peaks or cfg is None
            or (getattr(cfg, "v_head_dim", None) or cfg.head_dim)
            == cfg.head_dim or getattr(cfg, "kv_lora_rank", 0)):
        return None
    seconds = ctx["window_s"]
    need = costs_swa.window_need(cfg, got["counts"])
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
