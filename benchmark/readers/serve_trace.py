"""The serving step programs in the device trace.

``decode_step_device_ms``: device time inside the decode programs
(``ragged_decode_*`` by name: the fused bursts and the single step) per
decode step; a burst is a loop, so its steps are how often the commonest
operation of its body ran inside the program.  ``prefill_share``: device time inside the
mixed (prompt-chunk) programs ``ragged_forward_*`` over all device busy
time.  ``gap_ms_per_round``: the chip's idle time between consecutive step
programs, per program.
"""

import collections

import xtrace


def _inside(events, a, b):
    return [(n, s, e) for n, s, e in events if s >= a and e <= b]


def loop_steps(inside):
    """Steps of one decode program: each operation of a burst's loop body
    runs once a step, everything else once."""
    counts = collections.Counter(n for n, _, _ in inside)
    return max(counts.values()) if counts else 1


def read(ctx, spec):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    lo, hi = ctx["trace_window"]
    chip = min(trace["devices"])
    dev = trace["devices"][chip]
    runs = xtrace.module_runs(trace, lo, hi, chip)
    if not runs:
        return None
    ops = xtrace.clip(dev["ops"], lo, hi)
    what = spec["what"]
    if what == "gap_ms_per_round":
        a, b = runs[0][1], runs[-1][2]
        busy = xtrace.union((s, e) for _, s, e in
                            xtrace.device_events(dev, a, b))
        return xtrace.total(xtrace.gaps(busy, a, b)) / 1e6 / len(runs)
    decode_ns, steps, prefill_ns = 0, 0, 0
    for name, a, b in runs:
        inside = _inside(ops, a, b)
        t = xtrace.total(xtrace.union((s, e) for _, s, e in inside))
        if name.startswith("ragged_decode"):
            decode_ns += t
            steps += loop_steps(inside)
        elif name.startswith("ragged_forward"):
            prefill_ns += t
    if what == "decode_step_device_ms":
        return decode_ns / 1e6 / steps if steps else None
    busy_ns = xtrace.total(xtrace.union((s, e) for _, s, e in ops))
    return 100.0 * prefill_ns / busy_ns if busy_ns else None
