"""The serving scheduler's rounds, from the program's own host spans in the
trace (``ds.round`` and, tiling it, ``ds.gate``, ``ds.idle_sleep``,
``ds.admit``, ``ds.build``, ``ds.h2d``, ``ds.*_dispatch``, ``ds.fence``,
``ds.retire``, with ``ds.materialize`` nested where a sync fell due) and the
chip's idle gaps between step programs.

``idle_ms``: chip-idle time under the named phases (the innermost span open
at each instant of a gap), per step program that ran in the traced window:
the same gaps and the same denominator as ``sched_gap_ms_per_round``, so the
phases' shares cannot exceed it.  ``slot_occupancy``: the time-weighted mean
of ``running / slots`` over the rounds, in percent.  ``live_context``: the
mean, over decode steps, of the context tokens the step's slots hold
(``ctx_tokens`` at the dispatch, growing by ``seqs`` a step inside a burst).
``padding_waste``: sum(bucket - tokens) / sum(bucket) over the mixed
dispatches, in percent.  A program without the spans reads nothing.
"""

import fnmatch

import xmeta
import xtrace

ROUND = "ds.round"


def innermost(spans):
    """Nested spans of one thread -> disjoint (name, start, end) pieces,
    each named by the innermost span open there."""
    out, stack = [], []          # stack of [name, end, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cur = stack.pop()
            if end > cur:
                out.append((name, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)
    for s in sorted(spans, key=lambda s: (s["start_ns"], -s["end_ns"])):
        close(s["start_ns"])
        if stack:
            name, _, cur = stack[-1]
            if s["start_ns"] > cur:
                out.append((name, cur, s["start_ns"]))
            stack[-1][2] = s["start_ns"]
        stack.append([s["name"], s["end_ns"], s["start_ns"]])
    close(float("inf"))
    return sorted(out, key=lambda p: p[1])


def idle_by_phase(pieces, gaps):
    """{phase name: ns of the gaps spent under it}."""
    acc, j = {}, 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][2] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][1] < b:
            name, s, e = pieces[k]
            ov = min(e, b) - max(s, a)
            if ov > 0:
                acc[name] = acc.get(name, 0) + ov
            k += 1
    return acc


def read(ctx, spec):
    run = xmeta.of_run(ctx)
    trace = ctx.get("trace")
    if not run or not trace or "trace_window" not in ctx:
        return None
    lo, hi = ctx["trace_window"]
    spans = [a for a in run["annotations"]
             if a["start_ns"] >= lo and a["end_ns"] <= hi]
    rounds = [a for a in spans if a["name"] == ROUND]
    if not rounds:
        return None
    what = spec["what"]
    if what == "slot_occupancy":
        num = sum((r["end_ns"] - r["start_ns"]) * float(r["args"]["running"])
                  / float(r["args"]["slots"]) for r in rounds)
        den = sum(r["end_ns"] - r["start_ns"] for r in rounds)
        return 100.0 * num / den if den else None
    if what == "live_context":
        ctx_steps = steps = 0.0
        for a in spans:
            if a["name"] in ("ds.decode_dispatch", "ds.burst_dispatch"):
                n = float(a["args"].get("steps", 1))
                ctx_steps += (n * float(a["args"]["ctx_tokens"])
                              + float(a["args"]["seqs"]) * n * (n - 1) / 2)
                steps += n
        return ctx_steps / steps if steps else None
    if what == "padding_waste":
        mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"]
        bucket = sum(float(m["bucket"]) for m in mixed)
        return (100.0 * (bucket - sum(float(m["tokens"]) for m in mixed))
                / bucket if bucket else None)
    # idle_ms: the chip's gaps between step programs, by host phase
    if not trace["devices"]:
        return None
    chip = min(trace["devices"])
    dev = trace["devices"][chip]
    runs = xtrace.module_runs(trace, lo, hi, chip)
    if not runs:
        return None
    a, b = runs[0][1], runs[-1][2]
    busy = xtrace.union((s, e) for _, s, e in xtrace.device_events(dev, a, b))
    thread = rounds[0]["thread"]
    pieces = innermost([s for s in run["annotations"]
                        if s["thread"] == thread])
    idle = idle_by_phase(pieces, xtrace.gaps(busy, a, b))
    wanted = [xmeta.ANNOTATION_PREFIX + p for p in spec["phases"]]
    return sum(v for name, v in idle.items()
               if any(fnmatch.fnmatchcase(name, w) for w in wanted)
               ) / 1e6 / len(runs)
