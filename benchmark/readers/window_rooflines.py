"""Roofline shares of the kernels of a model with expert layers and with
window and global attention layers: needed work (``costs_moe.py``) over peak
over the kernels' time in the device trace.

The two Pallas attention kernels are taken by name: they carry their
``pallas_call(name=)`` in their scope path (``.../attn_kernel/paged_decode/
pallas_call``, ``.../ragged_prefill/pallas_call``).  The expert GEMM's time
is taken BY SCOPE, by ``moe_scope_time``'s one rule (``group_of``: an op
named ``ragged-dot*``, the compiler's custom calls, which keep no scope
path, OR an op whose innermost known scope is ``moe_experts``): everything
the expert scope costs, the row permutation and the activation around the
three products too, which a fused kernel would subsume.  So a kernel of any
name under that scope moves the reading, and one that replaces the
``ragged-dot`` in one step program alone cannot send it past 100%.  The
printed line gives the time by name beside it (``named_s``).

What a step needs depends on its contexts, which the device trace does not
hold; the program's dispatch spans do (``ctx_tokens``, ``ctx_tokens_window``,
``qk_pairs``, ``qk_pairs_window``, ``tokens``, ``seqs``, ``steps``; the MoE
counters ``moe_local``, ``moe_touched``).  The host runs ahead of the chip by
up to a second, so spans and kernel events of one traced window are not of
the same steps: the attention kernels' need is the MEAN need of a step of the
kind over the window's spans times the steps of the kind the trace holds,
and the expert GEMM's the counters' growth over the window.  A window layer
needs ``min(context, window)`` keys, a global layer all of them; inside a
burst the window layers' growth is left out (a little less need, never
more).  A program without the spans' arguments or the kernels reads nothing.
"""

import bisect
import json

import costs
import costs_moe
import serve_trace
import span_counters
import xmeta
import xtrace
from moe_scope_time import GROUPED_GEMM, group_of

KERNEL_SCOPE = {"paged_decode": "/paged_decode/",
                "ragged_prefill": "/ragged_prefill/"}


def _is_kernel(meta, kernel):
    if kernel == "expert_gemm":
        return (group_of(meta) == "moe_experts"
                and meta["opcode"] not in xtrace.CONTAINERS)
    return (meta["opcode"] == "custom-call"
            and KERNEL_SCOPE[kernel] in (meta.get("tf_op") or ""))


def _layers(cfg):
    window = sum(cfg.window_for_layer(i) is not None
                 for i in range(cfg.num_layers))
    return cfg.num_layers - window, window


def read(ctx, spec):
    run, peaks = xmeta.of_run(ctx), ctx.get("peaks")
    spans = span_counters.dispatches(ctx)
    if not run or not run["devices"] or not peaks or not spans:
        return None
    lo, hi = ctx["trace_window"]
    cfg = ctx["model_cfg"]
    kernel = spec["kernel"]
    dev = run["devices"][min(run["devices"])]
    meta = dev["meta"]
    k_ns, named_ns, runs, steps = 0, 0, 0, 0
    starts = [op[1] for op in dev["ops"]]      # sorted by start
    for name, a, b in dev["modules"]:
        if a < lo or b > hi or not name.startswith(spec["program"]):
            continue
        inside = [(mid, s, e) for mid, s, e in dev["ops"][
            bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]
            if e <= b and mid in meta]
        runs += 1
        steps += serve_trace.loop_steps(inside)
        # a group's time in one execution is the union of its operations'
        # intervals (``scope_time``); kernels never nest, so theirs is the sum
        k_ns += xtrace.total(xtrace.union(
            (s, e) for mid, s, e in inside if _is_kernel(meta[mid], kernel)))
        named_ns += sum(e - s for mid, s, e in inside
                        if meta[mid]["name"].startswith(GROUPED_GEMM))
    if not k_ns:
        return None
    g_layers, w_layers = _layers(cfg)
    seen = {}                 # what the need was computed from, for the line
    if kernel == "expert_gemm":
        got = span_counters.totals(spans, ("moe_local", "moe_touched"),
                                   "window")
        if not got:
            return None
        seen = got
        flops, byts = costs_moe.expert_gemm_cost(
            got["moe_local"], got["moe_touched"], cfg.hidden_size,
            cfg.expert_dim)
    elif kernel == "paged_decode":
        ctx_g = ctx_w = n = slots = 0.0
        for a in spans:
            args = a["args"]
            if a["name"] == "ds.mixed_dispatch" \
                    or "ctx_tokens_window" not in args:
                continue
            k = float(args.get("steps", 1))
            ctx_g += (k * float(args["ctx_tokens"])
                      + float(args["seqs"]) * k * (k + 1) / 2)
            ctx_w += k * float(args["ctx_tokens_window"])
            slots += k * float(args["seqs"])
            n += k
        if not n:
            return None
        seen = {"ctx_tokens": ctx_g / n, "ctx_tokens_window": ctx_w / n,
                "seqs": slots / n, "span_steps": n}
        flops, byts = costs_moe.paged_decode_window_cost(
            ctx_g / n, ctx_w / n, g_layers, w_layers, cfg.num_heads,
            cfg.kv_heads, cfg.head_dim, slots / n)
        flops, byts = flops * steps, byts * steps
    else:
        mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
                 and "qk_pairs" in a["args"]]
        if not mixed:
            return None
        mean = lambda key: sum(float(m[key]) for m in mixed) / len(mixed)  # noqa: E731
        seen = {k: mean(k) for k in ("qk_pairs", "qk_pairs_window",
                                     "ctx_tokens", "ctx_tokens_window",
                                     "tokens", "seqs")}
        seen["spans"] = len(mixed)
        flops, byts = costs_moe.ragged_prefill_window_cost(
            mean("qk_pairs"), mean("qk_pairs_window"),
            mean("ctx_tokens") + mean("tokens"),
            mean("ctx_tokens_window") + mean("tokens"), mean("tokens"),
            g_layers, w_layers, cfg.num_heads, cfg.kv_heads, cfg.head_dim)
        flops, byts = flops * runs, byts * runs
    share, bound = costs.roofline_share(flops, byts, k_ns / 1e9, peaks)
    by_name = ({"named_s": named_ns / 1e9, "share_by_name": (
        costs.roofline_share(flops, byts, named_ns / 1e9, peaks)[0]
        if named_ns else None)} if kernel == "expert_gemm" else {})
    print(json.dumps({"phase": "roofline", "kernel": kernel, "bound": bound,
                      "kernel_s": k_ns / 1e9, **by_name,
                      "needed_flops": flops,
                      "needed_bytes": byts, "runs": runs, "steps": steps,
                      "mean_per_step_from_spans": seen}),
          flush=True)
    return share
