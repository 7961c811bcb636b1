"""Share of the chip's bf16 peak that a serving window's needed operations
are (``costs_serve.window_need``: over the layers, each by its kind and
widths, the configuration's shapes times the program's counts over the WHOLE
window, which the runner gathers once the window has closed), over the host
clock's seconds: the whole window's (``serve_step_mfu``, a closed list: it
moves with ``serve_tokens_per_s``), or (``over: fenced_dispatches``, the open
loop, whose arrival rate fixes the tokens a second) the seconds the engine
was inside its fenced dispatches.  Reads no profiler trace: a run whose trace
held nothing still gives it.  A model with a kind of layer that has no file
under ``layer_costs/`` reads nothing, and the line says which."""

import json

import costs_serve


def read(ctx, spec):
    got, peaks = ctx.get("serve_window"), ctx.get("peaks")
    if not got or not peaks:
        return None
    fenced = spec.get("over") == "fenced_dispatches"
    seconds = got["fenced_s"] if fenced else ctx["window_s"]
    try:
        need = costs_serve.window_need(ctx["model_cfg"], got["counts"])
    except costs_serve.NoCostFile as kinds:
        print(json.dumps({"phase": "mfu", "name": spec["name"],
                          "value": None, "no_cost_file": str(kinds)}),
              flush=True)
        return None
    if not seconds or not need["flops"]:
        return None
    share = costs_serve.share_of_peak(need["flops"], seconds, peaks)
    print(json.dumps({
        "phase": "mfu", "name": spec["name"], "value": share,
        "over": spec.get("over", "window"), "seconds": seconds,
        "needed_flops": need["flops"],
        "terms": {k: costs_serve.share_of_peak(v, seconds, peaks)
                  for k, v in need["terms"].items()},
        "counts": got["counts"], "left_out": need["left_out"]}), flush=True)
    return share
