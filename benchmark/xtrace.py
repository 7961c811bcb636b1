"""From a profiler trace (``.xplane.pb``) to numbers: the yardstick's own
reduction, read with nothing but ``jax.profiler.ProfileData``.

What a TPU trace holds (looked at by hand on the v5e, PR 25): one plane per
chip named ``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per
executed program, named ``jit_<function>(<fingerprint>)``), a line ``XLA Ops``
(one event per HLO operation the chip ran, named by the whole instruction,
``%fusion.123 = bf16[...] fusion(...)``; a Pallas kernel is a ``custom-call``
named after the scope it was traced in, e.g. ``%Attention_0.97``), a line
``Async XLA Ops`` (copies in flight, not read here) and a line ``Steps``.  The host is the plane
``/host:CPU``, one line per thread, with the runtime's and the benchmark's
``TraceAnnotation`` spans.  All times are nanoseconds on one clock.

Everything below works on plain tuples so that the tests can feed it by hand.
"""

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench_trace_window"
POLL_THREAD = "bench_trace_poll_thread"   # run.py's own thread: not the host

_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(text):
    """``%fusion.123 = bf16[..] fusion(..)`` -> ``fusion.123``."""
    return text.split(" = ", 1)[0].lstrip("%")


def opcode_of(text):
    """The HLO opcode of an instruction's text (``custom-call``, ``fusion``,
    ``all-gather-start``...), or "" where the event is named without one."""
    if " = " not in text:
        return ""
    m = _OPCODE.search(text.split(" = ", 1)[1])
    return m.group(1) if m else ""


def op_family(name):
    """``%fusion.123`` -> ``fusion``; ``copy.4.clone`` stays distinct."""
    return _SUFFIX.sub("", short_name(name))


def module_name(name):
    """``jit_ragged_decode_burst(123)`` -> ``ragged_decode_burst``."""
    return _MODULE.match(name).group(1)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path):
    """{"devices": {chip: {"ops": [(name, start_ns, end_ns)], "modules":
    [...]}}, "host": {thread: [(name, start_ns, end_ns)]}, "opcode": {op
    name: HLO opcode}}.  Op names are cut to the instruction's name."""
    from jax.profiler import ProfileData
    if path.endswith(".textproto"):
        with open(path) as f:
            data = ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    else:
        data = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}, "opcode": {}}
    short = {}                      # instruction text -> its name, once

    def name_of(text):
        if text not in short:
            short[text] = short_name(text)
            out["opcode"][short[text]] = opcode_of(text)
        return short[text]
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key].extend(
                    (name_of(ev.name) if key == "ops" else ev.name,
                     ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            for key in dev:
                dev[key].sort(key=lambda e: e[1])
            out["devices"][int(m.group(1))] = dev
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                # the benchmark's own polling thread sleeps through every
                # gap: it says nothing of what the program's host did
                if evs and not any(n.endswith(POLL_THREAD) for n, _, _ in evs):
                    key = line.name or "thread"
                    out["host"][key if key not in out["host"]
                                else f"{key}~{i}"] = evs
    return out


def clip(events, lo, hi):
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def union(intervals):
    """Merged, sorted (start, end) pairs of possibly overlapping ones."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


def window_of(trace):
    """(start_ns, end_ns): the benchmark's own window span on the host if
    the trace holds one, else first device event to last."""
    for events in trace["host"].values():
        for name, a, b in events:
            if name == WINDOW_SPAN:
                return a, b
    starts = [e[1] for d in trace["devices"].values()
              for k in ("ops", "modules") for e in d[k]]
    ends = [e[2] for d in trace["devices"].values()
            for k in ("ops", "modules") for e in d[k]]
    if not starts:
        return 0, 0
    return min(starts), max(ends)


def device_events(dev, lo, hi):
    """The chip's op events in the window (module events where the trace
    has no op line)."""
    return clip(dev["ops"] or dev["modules"], lo, hi)


def busy(trace, lo, hi):
    """{chip: merged busy intervals inside [lo, hi]}."""
    return {chip: union((a, b) for _, a, b in device_events(dev, lo, hi))
            for chip, dev in trace["devices"].items()}


def busy_seconds(trace, lo, hi):
    """Seconds an operation ran, averaged over the chips in the trace."""
    per = [total(iv) for iv in busy(trace, lo, hi).values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def op_seconds(trace, lo, hi, family=op_family):
    """{op family: seconds}, summed over events and averaged over chips."""
    acc = defaultdict(float)
    n = max(1, len(trace["devices"]))
    for dev in trace["devices"].values():
        for name, a, b in clip(dev["ops"], lo, hi):
            acc[family(name)] += (b - a) / 1e9 / n
    return dict(acc)


def module_runs(trace, lo, hi, chip=None):
    """[(program, start_ns, end_ns)] of the programs that ran wholly inside
    the window on one chip (the lowest-numbered by default)."""
    if not trace["devices"]:
        return []
    chip = min(trace["devices"]) if chip is None else chip
    return [(module_name(n), a, b)
            for n, a, b in trace["devices"][chip]["modules"]
            if a >= lo and b <= hi]


def gaps(intervals, lo, hi):
    """The idle stretches between merged busy intervals inside [lo, hi]."""
    out, t = [], lo
    for a, b in intervals:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_span_in(trace, a, b):
    """``thread:span`` of the innermost host span open in the gap [a, b]:
    the shortest of the spans that cover at least half of it, else the one
    that covers most."""
    inner, most = None, None
    for thread, events in trace["host"].items():
        for name, s, e in events:
            if name == WINDOW_SPAN:
                continue
            ov = min(e, b) - max(s, a)
            if ov <= 0:
                continue
            label = f"{thread}:{name}"
            if 2 * ov >= b - a and (inner is None or e - s < inner[0]):
                inner = (e - s, label)
            if most is None or ov > most[0]:
                most = (ov, label)
    return (inner or most or (0, "none"))[1]


def breakdown(trace, lo, hi, top_ops=10, top_gaps=5):
    """The contract's ``breakdown``: the device operations with most time,
    and the longest idle gaps named by what the host was doing."""
    ops = sorted(op_seconds(trace, lo, hi).items(), key=lambda kv: -kv[1])
    chip = min(trace["devices"]) if trace["devices"] else None
    idle = []
    if chip is not None:
        g = gaps(busy(trace, lo, hi)[chip], lo, hi)
        for a, b in sorted(g, key=lambda ab: ab[0] - ab[1])[:top_gaps]:
            idle.append([host_span_in(trace, a, b), (b - a) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops[:top_ops]],
            "idle_gaps": idle}


CONTAINERS = ("while", "conditional", "call")   # their bodies' ops are events


def exposed_collective_seconds(trace, lo, hi, is_collective):
    """Seconds, averaged over chips, in which a collective ran on a chip
    and no other operation ran on it.  A loop or a branch is an event that
    spans its body's events: it is a container, not work, and hides
    nothing."""
    per = []
    for dev in trace["devices"].values():
        events = [e for e in clip(dev["ops"], lo, hi)
                  if trace["opcode"].get(e[0]) not in CONTAINERS]
        coll = union((a, b) for n, a, b in events if is_collective(n))
        comp = union((a, b) for n, a, b in events if not is_collective(n))
        hidden = 0
        j = 0
        for a, b in coll:
            while j < len(comp) and comp[j][1] <= a:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < b:
                hidden += min(b, comp[k][1]) - max(a, comp[k][0])
                k += 1
        per.append((total(coll) - hidden) / 1e9)
    return sum(per) / len(per) if per else 0.0
