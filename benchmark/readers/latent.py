"""The per-layer metrics of a model with latent attention (MLA) over a
latent page pool; a metric's file says ``what``:

- ``pool_bytes_per_token``: the ``kv_bytes_per_token`` argument of the traced
  window's last dispatch span: what the pool STORES for a cached token over
  all layers (pad columns included), to set against the need in
  ``costs_mla.py``.
- ``scope_ms``: device time of the operations under the scope the file names
  (``scope``: ``mla_absorb``, the two absorb products, nested inside
  ``attn_qkv`` and ``attn_out``, whose own metrics still count them), per run
  or per loop step of the programs ``program`` prefixes.
- ``roofline``: needed work (``costs_mla.py``, the absorbed form) over peak
  over the kernel's time, for ``paged_decode`` in the decode programs and
  ``ragged_prefill`` in the mixed one.  As in ``window_rooflines``, spans and
  kernel events of one traced window are not of the same steps (the host
  runs ahead of the chip), so the need is the MEAN need of a step of the
  kind over the window's spans times the steps of the kind in the trace.  A
  mixed step's one-row slots go to the decode kernel: their pairs
  (``ctx_tokens_one_row`` + their count) are taken off the prefill need.
- ``live_context``: the mean, over the same decode steps as the decode
  roofline takes (next paragraph), of the context tokens a step's slots
  hold.

**Where the decode readings take their steps from.**  Nothing syncs host
and chip in a closed list with no EOS, and a cohort's bursts are dispatched
in one clump several programs ahead of the chip: the traced ten seconds hold
the chip's bursts and, in every traced run of the cell so far, none of the
host's burst spans (PERF.md section 7, PR 33).  So both decode readings have
ONE source, the window's mixed spans: their one-row slots ARE the decoding
sequences of those steps (``one_row_slots``, ``ctx_tokens_one_row``), and
the mean over them stands for a decode step's slots and contexts.  A window
without such a span reads nothing.

A program without the spans' arguments, the scope or a latent pool (the
parent, another model) reads nothing.
"""

import bisect
import json

import costs
import costs_mla
import serve_trace
import span_counters
import xmeta
import xtrace

KERNEL_SCOPE = {"paged_decode": "/paged_decode/",
                "ragged_prefill": "/ragged_prefill/"}


def _program_ops(dev, lo, hi, prefix):
    """(ops inside one execution) for each execution of a program named
    ``prefix...`` that lies wholly inside [lo, hi]."""
    meta = dev["meta"]
    starts = [op[1] for op in dev["ops"]]      # sorted by start
    for name, a, b in dev["modules"]:
        if a < lo or b > hi or not name.startswith(prefix):
            continue
        yield [(mid, s, e) for mid, s, e in dev["ops"][
            bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]
            if e <= b and mid in meta]


def _traced(ctx):
    run = xmeta.of_run(ctx)
    if not run or not run["devices"] or "trace_window" not in ctx:
        return None
    return run["devices"][min(run["devices"])]


def pool_bytes_per_token(ctx, spec):
    spans = span_counters.dispatches(ctx)
    if not spans or "kv_bytes_per_token" not in spans[-1]["args"]:
        return None
    return float(spans[-1]["args"]["kv_bytes_per_token"])


def scope_ms(ctx, spec):
    dev = _traced(ctx)
    if dev is None:
        return None
    lo, hi = ctx["trace_window"]
    meta, scope = dev["meta"], spec["scope"]
    ns = runs = steps = 0
    for inside in _program_ops(dev, lo, hi, spec["program"]):
        runs += 1
        steps += serve_trace.loop_steps(inside)
        ns += xtrace.total(xtrace.union([
            (s, e) for mid, s, e in inside
            if meta[mid]["opcode"] not in xtrace.CONTAINERS
            and scope in (meta[mid].get("tf_op") or "").split("/")]))
    if not ns:
        return None
    return ns / 1e6 / (steps if spec.get("per") == "loop_step" else runs)


def _decode_steps(spans):
    """(context tokens, slots) of the decoding sequences at each mixed
    dispatch of the window that carried any (module docstring)."""
    return [(float(a["args"]["ctx_tokens_one_row"]),
             float(a["args"]["one_row_slots"]))
            for a in spans if a["name"] == "ds.mixed_dispatch"
            and float(a["args"].get("one_row_slots", 0))]


def live_context(ctx, spec):
    steps = _decode_steps(span_counters.dispatches(ctx) or [])
    return sum(c for c, _ in steps) / len(steps) if steps else None


def roofline(ctx, spec):
    dev, peaks = _traced(ctx), ctx.get("peaks")
    spans = span_counters.dispatches(ctx)
    cfg = ctx.get("model_cfg")
    if dev is None or not peaks or not spans \
            or not getattr(cfg, "kv_lora_rank", 0):
        return None
    lo, hi = ctx["trace_window"]
    meta, kernel = dev["meta"], spec["kernel"]
    k_ns = runs = steps = 0
    for inside in _program_ops(dev, lo, hi, spec["program"]):
        runs += 1
        steps += serve_trace.loop_steps(inside)
        k_ns += sum(e - s for mid, s, e in inside
                    if meta[mid]["opcode"] == "custom-call"
                    and KERNEL_SCOPE[kernel] in (meta[mid].get("tf_op")
                                                 or ""))
    if not k_ns:
        return None
    dims = (cfg.num_layers, cfg.num_heads, cfg.latent_dim, cfg.kv_lora_rank)
    if kernel == "paged_decode":
        decode = _decode_steps(spans)
        if not decode:
            return None
        n = len(decode)
        # keys read: the step's own token too
        ctx_mean = sum(c + s for c, s in decode) / n
        seqs = sum(s for _, s in decode) / n
        seen = {"ctx_tokens": ctx_mean, "seqs": seqs, "span_steps": n}
        flops, byts = costs_mla.latent_decode_cost(ctx_mean, seqs, *dims)
        flops, byts = flops * steps, byts * steps
    else:
        mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
                 and "qk_pairs" in a["args"]]
        if not mixed:
            return None
        mean = lambda key: sum(float(m.get(key, 0)) for m in mixed) / len(mixed)  # noqa: E731
        seen = {k: mean(k) for k in ("qk_pairs", "ctx_tokens", "tokens",
                                     "seqs", "ctx_tokens_one_row",
                                     "one_row_slots")}
        seen["spans"] = len(mixed)
        riders = seen["ctx_tokens_one_row"] + seen["one_row_slots"]
        flops, byts = costs_mla.latent_prefill_cost(
            seen["qk_pairs"] - riders,
            seen["ctx_tokens"] + seen["tokens"] - riders,
            seen["tokens"] - seen["one_row_slots"], *dims)
        flops, byts = flops * runs, byts * runs
    share, bound = costs.roofline_share(flops, byts, k_ns / 1e9, peaks)
    print(json.dumps({"phase": "roofline", "kernel": "latent_" + kernel,
                      "bound": bound, "kernel_s": k_ns / 1e9,
                      "needed_flops": flops, "needed_bytes": byts,
                      "runs": runs, "steps": steps,
                      "mean_per_step_from_spans": seen}), flush=True)
    return share


def read(ctx, spec):
    return {"pool_bytes_per_token": pool_bytes_per_token,
            "scope_ms": scope_ms, "roofline": roofline,
            "live_context": live_context}[spec["what"]](ctx, spec)
