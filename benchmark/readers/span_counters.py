"""Ratios of the program's own running totals, which the serving engine
writes into the arguments of its dispatch spans (``ds.mixed_dispatch``,
``ds.decode_dispatch``, ``ds.burst_dispatch``; telemetry/serving.py
``counter_note``): the MoE counters as far as the device had reported them,
and the window page group's pages.

A metric's file gives ``num`` and ``den`` (argument names), ``scale`` and
``over``: ``window`` takes both as the difference between the last and the
first dispatch of the traced window, ``run`` as they stood at the last (a
share of everything since start-up).  A program whose spans lack the
arguments (a dense model, one page group, a program from before them) reads
nothing.
"""

import xmeta

DISPATCH = ("ds.mixed_dispatch", "ds.decode_dispatch", "ds.burst_dispatch")


def dispatches(ctx):
    """The traced window's dispatch spans in order, or None."""
    run = xmeta.of_run(ctx)
    if not run or "trace_window" not in ctx:
        return None
    lo, hi = ctx["trace_window"]
    return [a for a in run["annotations"] if a["name"] in DISPATCH
            and a["start_ns"] >= lo and a["end_ns"] <= hi]


def totals(spans, names, over):
    """{name: value} over the window (last minus first) or of the run (at
    the last); None where an argument is missing."""
    if not spans:
        return None
    first, last = spans[0]["args"], spans[-1]["args"]
    if any(n not in last or (over == "window" and n not in first)
           for n in names):
        return None
    return {n: float(last[n]) - (float(first[n]) if over == "window" else 0.0)
            for n in names}


def read(ctx, spec):
    got = totals(dispatches(ctx), (spec["num"], spec["den"]),
                 spec.get("over", "window"))
    if not got or not got[spec["den"]]:
        return None
    return float(spec.get("scale", 1.0)) * got[spec["num"]] / got[spec["den"]]
