"""Per training step, from the device trace: the time an operation ran on
the chip (``step_device_ms``) and the time the chip sat idle between
consecutive step programs (``host_gap_ms``), both averaged over the chips
and over the step programs that ran wholly inside the traced window."""

import xtrace


def read(ctx, spec):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    lo, hi = ctx["trace_window"]
    busy_ms, gap_ms, steps = 0.0, 0.0, 0
    for chip, dev in trace["devices"].items():
        runs = [r for r in xtrace.module_runs(trace, lo, hi, chip)
                if r[0] == ctx["step_program"]]
        if len(runs) < 2:
            continue
        a, b = runs[0][1], runs[-1][2]
        busy = xtrace.union((s, e) for _, s, e in
                            xtrace.device_events(dev, a, b))
        busy_ms += xtrace.total(busy) / 1e6
        gap_ms += xtrace.total(xtrace.gaps(busy, a, b)) / 1e6
        steps += len(runs)
    if not steps:
        return None
    return {"step_device_ms": busy_ms / steps,
            "host_gap_ms": gap_ms / steps}[spec["what"]]
