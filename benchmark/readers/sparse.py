"""The per-layer metrics of a model whose global layers SELECT their keys
(an indexer, ``index_topk``) over latent pools, a pool a page group; a
metric's file says ``what``:

- ``span_arg``: the argument ``arg`` of the traced window's last dispatch
  span (``index_bytes_per_token``: what the index-key pool stores for a
  cached token over the selecting layers).
- ``roofline``: needed work (``costs_dsa.py``) over peak over the time of
  the operations the file names, in the programs ``program`` prefixes:
  ``kernel`` (the custom calls whose path holds that name, and ``within``
  where given) or ``scope`` (every operation under that scope: an op that
  is XLA's own and no kernel).  The file's ``need`` picks the need function
  and the spans' arguments it takes (below).

As in ``latent`` and ``window_rooflines``, spans and device events of one
traced window are not of the same steps (the host runs ahead of the chip),
so a need is the MEAN need of a step of the kind over the window's spans
times the steps of the kind in the trace; and the decode programs' needs
come from the mixed spans' one-row slots, which ARE the decoding sequences
of those steps (``latent``'s docstring says why).

``need``:

- ``index_mixed``: the index-score kernel in the mixed step: the pairs the
  step scores (``index_pairs_step``) less its one-row slots' (they take the
  batched product, not the kernel); keys: the multi-row slots' contexts and
  rows.
- ``selected_mixed`` / ``selected_decode``: attention over the selected rows:
  ``sel_pairs_step`` pairs and at most the contexts' keys a mixed step;
  ``sel_pairs_one_row`` pairs, each its own key, a decode step.
- ``window_mixed`` / ``window_decode``: the window layers' kernels:
  ``qk_pairs_window`` less the one-row slots' part and the multi-row slots'
  windows and rows; ``ctx_tokens_window_one_row`` pairs and keys.

A program without the spans' arguments, the scope or the kernel (the
parent, another model) reads nothing.
"""

import json

import costs
import costs_dsa
import latent
import serve_trace
import span_counters
import xtrace


def span_arg(ctx, spec):
    spans = span_counters.dispatches(ctx)
    if not spans or spec["arg"] not in spans[-1]["args"]:
        return None
    return float(spans[-1]["args"][spec["arg"]])


def _op_ns(dev, inside, spec):
    """Device time, inside one execution, of the operations a roofline's
    file names: custom calls by kernel name, or everything under a scope."""
    meta = dev["meta"]
    if "kernel" in spec:
        want = [spec["kernel"]] + ([spec["within"]] if spec.get("within")
                                   else [])
        return sum(e - s for mid, s, e in inside
                   if meta[mid]["opcode"] == "custom-call"
                   and all(w in (meta[mid].get("tf_op") or "") for w in want))
    return xtrace.total(xtrace.union([
        (s, e) for mid, s, e in inside
        if meta[mid]["opcode"] not in xtrace.CONTAINERS
        and spec["scope"] in (meta[mid].get("tf_op") or "").split("/")]))


def _mixed_mean(spans, keys, selecting=False):
    """Means over the window's mixed spans (``selecting``: over those whose
    step program scored any pair; a program no wider than the selection
    takes the dense kernels)."""
    mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
             and all(k in a["args"] for k in keys)
             and (not selecting or float(a["args"]["index_pairs_step"]))]
    if not mixed:
        return None
    return {k: sum(float(m[k]) for m in mixed) / len(mixed) for k in keys}


def _riders_mean(spans, keys):
    """Means over the mixed spans that carried one-row slots: a decode
    step's slots."""
    mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
             and float(a["args"].get("one_row_slots", 0))
             and all(k in a["args"] for k in keys)]
    if not mixed:
        return None
    return {k: sum(float(m[k]) for m in mixed) / len(mixed) for k in keys}


def need(spec, spans, cfg):
    """(flops, bytes, what was seen) of ONE step of the kind."""
    full = cfg.for_layer(next(i for i in range(cfg.num_layers)
                              if cfg.window_for_layer(i) is None))
    wins = [i for i in range(cfg.num_layers)
            if cfg.window_for_layer(i) is not None]
    n_full = cfg.num_layers - len(wins)
    kind = spec["need"]
    if kind == "index_mixed":
        seen = _mixed_mean(spans, ("index_pairs_step", "ctx_tokens",
                                   "tokens", "ctx_tokens_one_row",
                                   "one_row_slots"), selecting=True)
        if not seen:
            return None
        riders = seen["ctx_tokens_one_row"] + seen["one_row_slots"]
        return costs_dsa.index_score_cost(
            seen["index_pairs_step"] - riders,
            seen["ctx_tokens"] + seen["tokens"] - riders, n_full,
            full.index_n_heads, full.index_head_dim) + (seen,)
    if kind == "selected_mixed":
        seen = _mixed_mean(spans, ("sel_pairs_step", "ctx_tokens", "tokens",
                                   "index_pairs_step"), selecting=True)
        if not seen:
            return None
        return costs_dsa.selected_attention_cost(
            seen["sel_pairs_step"],
            min(seen["sel_pairs_step"], seen["ctx_tokens"] + seen["tokens"]),
            n_full, full.num_heads, full.latent_dim,
            full.kv_lora_rank) + (seen,)
    if kind == "selected_decode":
        seen = _riders_mean(spans, ("sel_pairs_one_row", "one_row_slots"))
        if not seen:
            return None
        return costs_dsa.selected_attention_cost(
            seen["sel_pairs_one_row"], seen["sel_pairs_one_row"], n_full,
            full.num_heads, full.latent_dim, full.kv_lora_rank) + (seen,)
    if not wins:
        return None
    win = cfg.for_layer(wins[0])
    dims = (len(wins), win.num_heads, win.latent_dim, win.kv_lora_rank)
    if kind == "window_decode":
        seen = _riders_mean(spans, ("ctx_tokens_window_one_row",
                                    "one_row_slots"))
        if not seen:
            return None
        n = seen["ctx_tokens_window_one_row"]
        return costs_dsa.window_latent_cost(n, n, *dims) + (seen,)
    if kind == "window_mixed":
        seen = _mixed_mean(spans, ("qk_pairs_window", "ctx_tokens_window",
                                   "ctx_tokens_window_one_row", "tokens",
                                   "one_row_slots"))
        if not seen:
            return None
        riders = seen["ctx_tokens_window_one_row"]
        return costs_dsa.window_latent_cost(
            seen["qk_pairs_window"] - riders,
            seen["ctx_tokens_window"] + seen["tokens"] - riders, *dims) \
            + (seen,)
    raise ValueError(f"unknown need {kind!r}")


def roofline(ctx, spec):
    dev, peaks = latent._traced(ctx), ctx.get("peaks")
    spans = span_counters.dispatches(ctx)
    cfg = ctx.get("model_cfg")
    if dev is None or not peaks or not spans \
            or not getattr(cfg, "index_topk", 0):
        return None
    lo, hi = ctx["trace_window"]
    k_ns = runs = steps = 0
    for inside in latent._program_ops(dev, lo, hi, spec["program"]):
        ns = _op_ns(dev, inside, spec)
        if not ns:          # a program of the kind that took another path
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
                      "bound": bound, "kernel_s": k_ns / 1e9,
                      "needed_flops": flops, "needed_bytes": byts,
                      "runs": runs, "steps": steps,
                      "mean_per_step_from_spans": seen}), flush=True)
    return share


def read(ctx, spec):
    return {"span_arg": span_arg, "roofline": roofline}[spec["what"]](
        ctx, spec)
