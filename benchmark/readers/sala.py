"""The per-layer metrics of a model that selects its keys by BLOCKS from
pooled keys (``GPTConfig.block_topk``; MiniCPM-SALA); a metric's file says
``what``:

- ``roofline``: needed work (``costs_sala.py``) over peak over the device time
  of EVERYTHING under the scope the file names (``scope``:
  ``block_attention``, attention over the kept blocks whichever way a row
  reads them, or ``attn_index``, the block scores and the choice) in the
  programs ``program`` prefixes: by scope and not by kernel name, so a kernel
  of any name moves the reading and none can send it past 100%.  The file's
  ``need`` picks the need and the spans' arguments it takes:
  ``kept_mixed`` (a mixed step's prompt chunks: ``blk_pairs_step`` less the
  one-row slots' ``blk_pairs_one_row``; keys: at most the selecting chunks'
  contexts ``blk_ctx_chunk``), ``kept_decode`` (``blk_pairs_one_row`` pairs,
  each slot's kept keys its own) and ``scores_mixed`` (``blk_pooled_pairs``
  pooled pairs, ``blk_pooled_chunk`` pooled keys).
- ``dense_rows_share``: of the rows through the selecting layers over the
  traced window, the share that took the dense path, from the growth of
  ``blk_dense_rows`` and ``blk_sparse_rows``, in %.

As in ``sparse`` and ``latent``, spans and device events of one traced window
are not of the same steps (the host runs ahead of the chip), so a need is the
MEAN need of a step of the kind over the window's spans times the steps of
the kind in the trace that spent any time under the scope; and the decode
programs' needs come from the mixed spans' one-row slots, which ARE the
decoding sequences of those steps.

A program without the spans' arguments or the scope (the parent, another
model) reads nothing.
"""

import json

import costs
import costs_sala
import latent
import serve_trace
import span_counters
import xtrace


def _scope_ns(dev, inside, scope):
    meta = dev["meta"]
    return xtrace.total(xtrace.union([
        (s, e) for mid, s, e in inside
        if meta[mid]["opcode"] not in xtrace.CONTAINERS
        and scope in (meta[mid].get("tf_op") or "").split("/")]))


def _mean(spans, keys, where):
    rows = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
            and all(k in a["args"] for k in keys) and where(a["args"])]
    if not rows:
        return None
    return {k: sum(float(r[k]) for r in rows) / len(rows) for k in keys}


def need(spec, spans, cfg):
    """(flops, bytes, what was seen) of ONE step of the kind."""
    n_sel = len(cfg.attention_layers)
    dims = (n_sel, cfg.num_heads, cfg.kv_heads, cfg.head_dim)
    kind = spec["need"]
    if kind == "kept_mixed":
        seen = _mean(spans, ("blk_pairs_step", "blk_pairs_one_row",
                             "blk_ctx_chunk"),
                     lambda a: float(a["blk_ctx_chunk"]) > 0)
        if not seen:
            return None
        pairs = seen["blk_pairs_step"] - seen["blk_pairs_one_row"]
        return costs_sala.kept_attention_cost(
            pairs, min(pairs, seen["blk_ctx_chunk"]), *dims) + (seen,)
    if kind == "kept_decode":
        seen = _mean(spans, ("blk_pairs_one_row", "one_row_slots"),
                     lambda a: float(a["blk_pairs_one_row"]) > 0)
        if not seen:
            return None
        # (each KV head keeps its own blocks, so a kept key is one head's:
        # a pair's bytes are one head's key and value)
        flops, byts = costs_sala.kept_attention_cost(
            seen["blk_pairs_one_row"], seen["blk_pairs_one_row"], *dims)
        return flops, byts, seen
    if kind == "scores_mixed":
        seen = _mean(spans, ("blk_pooled_pairs", "blk_pooled_chunk"),
                     lambda a: float(a["blk_pooled_pairs"]) > 0)
        if not seen:
            return None
        return costs_sala.block_score_cost(
            seen["blk_pooled_pairs"], seen["blk_pooled_chunk"],
            *dims) + (seen,)
    raise ValueError(f"unknown need {kind!r}")


def roofline(ctx, spec):
    dev, peaks = latent._traced(ctx), ctx.get("peaks")
    spans = span_counters.dispatches(ctx)
    cfg = ctx.get("model_cfg")
    if dev is None or not peaks or not spans \
            or not getattr(cfg, "block_topk", 0):
        return None
    lo, hi = ctx["trace_window"]
    k_ns = runs = steps = 0
    for inside in latent._program_ops(dev, lo, hi, spec["program"]):
        ns = _scope_ns(dev, inside, spec["scope"])
        if not ns:          # a step of the kind that took the dense path
            continue
        runs += 1
        steps += serve_trace.loop_steps(inside) \
            if spec.get("per") == "loop_step" else 1
        k_ns += ns
    one = need(spec, spans, cfg)
    if not k_ns or one is None:
        return None
    flops, byts, seen = one
    flops, byts = flops * steps, byts * steps
    share, bound = costs.roofline_share(flops, byts, k_ns / 1e9, peaks)
    print(json.dumps({"phase": "roofline", "kernel": spec["name"],
                      "bound": bound, "scope_s": k_ns / 1e9,
                      "needed_flops": flops, "needed_bytes": byts,
                      "runs": runs, "steps": steps,
                      "mean_per_step_from_spans": seen}), flush=True)
    return share


def dense_rows_share(ctx, spec):
    got = span_counters.totals(span_counters.dispatches(ctx),
                               ("blk_dense_rows", "blk_sparse_rows"),
                               "window")
    if not got or not sum(got.values()):
        return None
    return 100.0 * got["blk_dense_rows"] / sum(got.values())


def read(ctx, spec):
    return {"roofline": roofline,
            "dense_rows_share": dense_rows_share}[spec["what"]](ctx, spec)
