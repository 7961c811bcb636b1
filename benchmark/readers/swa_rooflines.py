"""Roofline shares of the two paged attention kernels in a model whose window
and full layers differ in kv heads and whose values are narrower than its
keys: needed work (``costs_swa.py``: each page group at ITS heads and widths,
a window layer at ``min(context, window)`` keys) over peak over the kernels'
time in the device trace, both groups together, the kernels taken by name
(``.../attn_kernel/paged_decode/pallas_call``, ``.../ragged_prefill/
pallas_call``: in a mixed step the one-row slots' paged decode kernel is not
the prefill kernel's time).

What a step needs depends on its contexts, which the device trace does not
hold; the program's dispatch spans do (``ctx_tokens``, ``ctx_tokens_window``,
``qk_pairs``, ``qk_pairs_window``, ``tokens``, ``seqs``, ``steps``), as
``window_rooflines`` reads them: the MEAN need of a step of the kind over the
window's spans times the steps of the kind the trace holds.  A mixed step's
one-row slots go to the decode kernel: their pairs (``ctx_tokens_one_row`` +
``one_row_slots``, ``ctx_tokens_window_one_row``) are taken off the prefill
kernel's need.  A program without the spans' arguments, the kernels or a
value width of its own reads nothing.
"""

import bisect
import json

import costs
import costs_swa
import serve_trace
import span_counters
import xmeta
import xtrace

KERNEL_SCOPE = {"paged_decode": "/paged_decode/",
                "ragged_prefill": "/ragged_prefill/"}


def _kernel_seconds(dev, lo, hi, program, kernel):
    """(kernel ns, runs, loop steps) of the programs named ``program*``
    that lie wholly inside the traced window."""
    meta = dev["meta"]
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
                    and KERNEL_SCOPE[kernel] in (meta[mid].get("tf_op")
                                                 or ""))
    return k_ns, runs, steps


def decode_need(cfg, spans):
    """(flops, bytes, seen) of ONE decode step, the mean over the decode
    spans' steps: a full layer reads every cached key and the row's own, a
    window layer ``min(context, window)``."""
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
    return costs_swa.attention_cost(cfg, ctx_g / n, ctx_w / n, ctx_g / n,
                                    ctx_w / n, slots / n) + (seen,)


def prefill_need(cfg, spans):
    """(flops, bytes, seen) of ONE mixed step's prefill kernel, the mean
    over the mixed spans, its one-row slots (the decode kernel's) taken
    off."""
    mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
             and "qk_pairs_window" in a["args"]]
    if not mixed:
        return None

    def mean(key):
        return sum(float(m.get(key, 0)) for m in mixed) / len(mixed)
    one = mean("one_row_slots")
    rows = mean("tokens") - one
    pairs_g = mean("qk_pairs") - mean("ctx_tokens_one_row") - one
    pairs_w = mean("qk_pairs_window") - mean("ctx_tokens_window_one_row")
    keys_g = mean("ctx_tokens") - mean("ctx_tokens_one_row") + rows
    keys_w = (mean("ctx_tokens_window")
              - max(mean("ctx_tokens_window_one_row") - one, 0.0) + rows)
    seen = {"qk_pairs": pairs_g, "qk_pairs_window": pairs_w,
            "keys": keys_g, "keys_window": keys_w, "rows": rows,
            "one_row_slots": one, "spans": len(mixed)}
    return costs_swa.attention_cost(cfg, pairs_g, pairs_w, keys_g, keys_w,
                                    rows) + (seen,)


def read(ctx, spec):
    run, peaks = xmeta.of_run(ctx), ctx.get("peaks")
    spans = span_counters.dispatches(ctx)
    cfg = ctx.get("model_cfg")
    if (not run or not run["devices"] or not peaks or not spans
            or not getattr(cfg, "sliding_window", None)):
        return None
    lo, hi = ctx["trace_window"]
    kernel = spec["kernel"]
    dev = run["devices"][min(run["devices"])]
    k_ns, runs, steps = _kernel_seconds(dev, lo, hi, spec["program"], kernel)
    if not k_ns:
        return None
    need = (decode_need if kernel == "paged_decode" else prefill_need)(
        cfg, spans)
    if need is None:
        return None
    flops, byts, seen = need
    n = steps if kernel == "paged_decode" else runs
    flops, byts = flops * n, byts * n
    share, bound = costs.roofline_share(flops, byts, k_ns / 1e9, peaks)
    print(json.dumps({"phase": "roofline", "kernel": kernel, "reader": "swa",
                      "bound": bound, "kernel_s": k_ns / 1e9,
                      "needed_flops": flops, "needed_bytes": byts,
                      "runs": runs, "steps": steps,
                      "mean_per_step_from_spans": seen}), flush=True)
    return share
