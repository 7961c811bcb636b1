"""Exposed collective time per training step: the time, averaged over the
chips, in which a collective operation ran on a chip and no other operation
did, over the step programs in the traced window."""

import re

import xtrace

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|all_gather|all_reduce|reduce_scatter|all_to_all|collective_permute")


def read(ctx, spec):
    trace = ctx.get("trace")
    if not trace or len(trace["devices"]) < 2:
        return None
    lo, hi = ctx["trace_window"]
    steps = [r for r in xtrace.module_runs(trace, lo, hi)
             if r[0] == ctx["step_program"]]
    if not steps:
        return None
    a, b = steps[0][1], steps[-1][2]
    exposed = xtrace.exposed_collective_seconds(
        trace, a, b, lambda n: bool(COLLECTIVE.search(
            trace["opcode"].get(n) or n)))
    return exposed * 1e3 / len(steps)
