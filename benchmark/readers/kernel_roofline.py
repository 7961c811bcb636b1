"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes the algorithm needs (``costs.py``, from shapes) over
the time the kernel's events took in the device trace.  An earlier line of
the run says which bound it is."""

import json

import costs
import kernel_names
import xtrace


def read(ctx, spec):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not trace["devices"] or not peaks:
        return None
    lo, hi = ctx["trace_window"]
    chip = min(trace["devices"])
    cfg = ctx["model_cfg"]
    runs = xtrace.module_runs(trace, lo, hi, chip)
    ops = trace["devices"][chip]["ops"]
    kernel = spec["kernel"]
    if kernel == "flash_attention":
        steps = [r for r in runs if r[0] == ctx["step_program"]]
        if not steps:
            return None
        a, b = steps[0][1], steps[-1][2]
        k_ns = sum(e - s for n, s, e in ops if s >= a and e <= b
                   and kernel_names.is_kernel(trace, n, kernel))
        if not k_ns:
            return None
        rows = ctx["tokens_per_step"] // cfg.max_seq_len // ctx["chips"]
        flops, byts = costs.flash_attention_train_cost(
            rows, cfg.num_heads, cfg.kv_heads, cfg.max_seq_len,
            cfg.head_dim)
        flops *= cfg.num_layers * len(steps)
        byts *= cfg.num_layers * len(steps)
    else:
        # paged decode: the bytes depend on the live contexts, which the
        # trace does not hold; the runner's counters give the mean context
        # and live slots over the window
        live = ctx.get("decode_context_tokens_per_step")
        decode = [r for r in runs if r[0].startswith("ragged_decode")]
        if not decode or not live:
            return None
        import serve_trace
        k_ns, steps = 0, 0
        for _, a, b in decode:
            inside = [(n, s, e) for n, s, e in ops if s >= a and e <= b]
            steps += serve_trace.loop_steps(inside)
            k_ns += sum(e - s for n, s, e in inside
                        if kernel_names.is_kernel(trace, n, kernel))
        if not k_ns:
            return None
        flops, byts = costs.paged_decode_cost(
            live["context_tokens"], cfg.num_heads, cfg.kv_heads,
            cfg.head_dim, live["slots"])
        flops *= steps * cfg.num_layers          # one call a layer a step
        byts *= steps * cfg.num_layers
    share, bound = costs.roofline_share(flops, byts, k_ns / 1e9, peaks)
    print(json.dumps({"phase": "roofline", "kernel": kernel,
                      "bound": bound, "kernel_s": k_ns / 1e9,
                      "needed_flops": flops, "needed_bytes": byts}),
          flush=True)
    return share
