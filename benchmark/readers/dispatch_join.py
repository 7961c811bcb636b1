"""Every step program's device run joined to the dispatch span that launched
it, through what the runtime itself writes into the trace.

What a v5e trace holds beside names and times (``jax.profiler.start_trace``
at its defaults; ``ProfileData`` gives it as ``dict(ev.stats)``): every event
of a chip's ``XLA Modules`` line carries a stat ``run_id``, and the host
plane holds an event ``DoEnqueueProgram`` with the same ``run_id`` and a
``device_ordinal`` where the runtime hands the program to the chip's queue.
In the recording under ``benchmark/tests/data`` that event lies on the
launching thread, inside ``PJRT_LoadedExecutable_Execute``; today's runtime
(step 0 of PR 55, ``chiprun_out/pr55/step0_*.json``) enqueues from worker
threads (``pjrt-tpu-tasks/<tid>``, ``tfrt-non-blocking-queue/<tid>``), up to
tens of launches behind the launching thread.  Either way the runtime links
the two itself: the launching thread's ``tpu::System::Execute`` carries a
producer id (``_p``), the ``tpu::System::Execute=>IssueSequencedEvent``
that holds the enqueue, on whatever thread, the same id as its consumer
(``_c``).  The program's dispatch spans (``ds.mixed_dispatch``,
``ds.decode_dispatch``, ``ds.burst_dispatch``, ``ds.spec_dispatch``; the
train step's ``ds.dispatch``) lie round the launch and say which dispatch
of the engine this was (``seq``; a train step's ``step``) and which program
it meant to launch (``program``).  So:

- an enqueue event belongs to the dispatch span whose interval holds the
  instant it was launched (its own start where the trace holds no such
  link); two dispatch spans that overlap in time join nothing and are
  counted ``ambiguous``;
- ``(device_ordinal, run_id)`` finds the module event; what is no step
  program (``jnp.float32(temperature)`` inside the span is a program too) is
  passed over, and the join is kept only where the module's name starts
  with the span's ``program``, else counted ``mismatched``;
- the chip runs programs in the order they were enqueued and every
  step-program dispatch advances ``seq`` by one: the j-th step-program run
  before a chip's first joined run (of ``seq`` s0) is dispatch s0 - j.  Such a
  run has a ``seq`` and no span (its dispatch came before the trace opened)
  and is marked ``by_order``;
- where the host is so far ahead that NO run of a chip joins by ``run_id``
  (Moonlight: a cohort of bursts in flight, seconds of them), the order is
  anchored by what the host learnt of the device's progress: a span's MoE
  totals hold through dispatch ``moe_seq``, folded inside the ``ds.build``
  before it from the vectors that were ready, so the last step-program run
  that had ended when that build began is dispatch ``moe_seq``.  A build with
  a run's end within ``GUARD_NS`` of it says nothing (the clocks' skew, the
  lag of ``is_ready``); the builds that speak have to agree, or nothing is
  numbered (``anchor_conflicts``).

The two timelines are NOT one clock: a run's module event may start before
the enqueue event that launched it began (1.4-1.5 ms in the recording under
``benchmark/tests/data``).  A program cannot start before it is enqueued, so
the largest such lead is the least by which the clocks disagree
(``clock_skew_ms``); a queue time is read with the device clock shifted by
it.

``joined(ctx)`` is the function other readers call; ``read(ctx, spec)`` serves
the entries (``what``: ``join_share``, ``queue_ms``, ``clock_skew_ms``,
``expert_gemm_joined``) and prints one ``dispatch_join`` line a traced run.  A
trace without a TPU plane, a runtime that writes no ``run_id`` or a program
whose spans carry no ``seq`` reads nothing and raises nothing.

    python3 benchmark/readers/dispatch_join.py <trace dir or file>

prints the line of a trace ``run.py --trace 1`` left behind (a cell none of
whose entries names this reader: the two train cells).
"""

import bisect
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import xmeta  # noqa: E402
import xtrace  # noqa: E402

ENQUEUE = "DoEnqueueProgram"
LAUNCH = "tpu::System::Execute"             # stat ``_p``: the link's id
ISSUE = "tpu::System::Execute=>IssueSequencedEvent"     # stat ``_c``
TRAIN_SPAN = "ds.dispatch"                 # its ``step`` is its seq
SERVE_SPANS = ("ds.mixed_dispatch", "ds.decode_dispatch",
               "ds.burst_dispatch", "ds.spec_dispatch")
MATERIALIZE = "ds.materialize"
BUILD = "ds.build"                          # where the MoE vectors are folded
GUARD_NS = 5e6          # a run's end this near a fold does not date it
# what a step program's name starts with: the serving engine's, the train
# engine's.  A span's own ``program`` counts as well.
STEP_PROGRAMS = ("ragged_", "speculative_burst", "train_batch", "grads_")


def decode(path):
    """{"runs": {chip: [(run_id, name, start_ns, end_ns)]}, "enqueues":
    [(device_ordinal, run_id, start_ns, end_ns, launched_ns)]} of a trace
    file, both by start: the events that carry the runtime's ``run_id``,
    an enqueue with the instant its launch began on the launching thread."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(xmeta._serialized(path))
    runs, enqueues, launched = {}, [], {}
    for plane in data.planes:
        m = xtrace.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != xtrace.MODULES_LINE:
                    continue
                got = runs.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    rid = dict(ev.stats).get("run_id")
                    if rid is not None:
                        got.append((rid, xtrace.module_name(ev.name),
                                    ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
        elif plane.name == xtrace.HOST_PLANE:
            for line in plane.lines:
                issues, mine = [], []
                for ev in line.events:
                    if ev.name not in (ENQUEUE, LAUNCH, ISSUE):
                        continue
                    stats = dict(ev.stats)
                    if ev.name == LAUNCH and "_p" in stats:
                        launched[stats["_p"]] = ev.start_ns
                    elif ev.name == ISSUE and "_c" in stats:
                        issues.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns,
                                       stats["_c"]))
                    elif ev.name == ENQUEUE and "run_id" in stats:
                        mine.append((int(stats.get("device_ordinal", 0)),
                                     stats["run_id"], ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
                issues.sort()
                starts = [i[0] for i in issues]
                for e in mine:      # the issue that holds it: its link
                    i = bisect.bisect_right(starts, e[2]) - 1
                    enqueues.append(e + (
                        issues[i][2] if i >= 0 and issues[i][1] >= e[2]
                        else None,))
    for got in runs.values():
        got.sort(key=lambda r: r[2])
    enqueues = sorted((e[:4] + (launched.get(e[4], e[2]),)
                       for e in enqueues), key=lambda e: e[2])
    return {"runs": runs, "enqueues": enqueues}


def _on_xmeta_clock(run, modules, starts):
    """``ProfileData`` cuts a start and a duration to whole nanoseconds;
    ``xmeta`` keeps the picoseconds, and its op events are laid against its
    module events.  The same event there, where there is one."""
    rid, name, a, b = run
    i = bisect.bisect_left(starts, a)
    if i < len(modules) and modules[i][1] - a < 1.0 \
            and modules[i][0] == name:
        return modules[i]
    return name, a, b


def _is_step(name, programs):
    return name.startswith(STEP_PROGRAMS) or name in programs


def join(raw, annotations, devices=None):
    """The join of one decoded trace: {"dispatches": what ``joined``
    returns, "step_runs": {chip: [(name, start_ns, end_ns, seq or None)]},
    "tail": spans whose run the trace does not hold, "mismatched",
    "ambiguous"}; None where one side is missing."""
    spans = [dict(a, seq=a["args"].get(
        "step" if a["name"] == TRAIN_SPAN else "seq"))
        for a in annotations
        if a["name"] == TRAIN_SPAN or a["name"] in SERVE_SPANS]
    spans = [s for s in spans
             if s["seq"] is not None and "program" in s["args"]]
    if not spans or not raw["runs"] or not raw["enqueues"]:
        return None
    spans.sort(key=lambda s: s["start_ns"])
    ambiguous, reach = set(), None
    for i, s in enumerate(spans):
        if reach is not None and s["start_ns"] < reach[0]:
            ambiguous.update((i, reach[1]))
        if reach is None or s["end_ns"] > reach[0]:
            reach = (s["end_ns"], i)
    clear = [s for i, s in enumerate(spans) if i not in ambiguous]
    starts = [s["start_ns"] for s in clear]
    by_key = {}
    for chip, got in raw["runs"].items():
        mods = (devices or {}).get(chip, {}).get("modules", [])
        mod_starts = [m[1] for m in mods]
        for run in got:
            by_key[chip, run[0]] = _on_xmeta_clock(run, mods, mod_starts)
    programs = {s["args"]["program"] for s in spans}
    found = {}                      # place in ``clear`` -> dispatch
    seq_of = {}                     # (chip, start_ns) -> seq
    mismatched = 0
    for ordinal, rid, a, b, at in raw["enqueues"]:
        i = bisect.bisect_right(starts, at) - 1
        if i < 0 or clear[i]["end_ns"] < at:
            continue                # no dispatch span's: not a step program
        span = clear[i]
        d = found.setdefault(i, {
            "seq": span["seq"], "span": span, "enqueue": None,
            "enqueues": {}, "by_order": False, "runs": {}})
        run = by_key.get((ordinal, rid))
        if run is None:
            continue                # enqueued, and run after the trace closed
        if not _is_step(run[0], programs):
            continue                # a scalar's conversion beside the call
        if not run[0].startswith(span["args"]["program"]):
            mismatched += 1
            continue
        d["runs"][ordinal] = run
        d["enqueues"][ordinal] = (a, b, at)
        seq_of[ordinal, run[1]] = span["seq"]
    dispatches = {d["seq"]: d for d in found.values() if d["runs"]}
    for d in dispatches.values():
        d["enqueue"] = (min(e[0] for e in d["enqueues"].values()),
                        max(e[1] for e in d["enqueues"].values()))
    tail = [s for i, s in enumerate(clear)
            if i not in found or not found[i]["runs"]]
    step_runs, anchors, conflicts = {}, {}, 0
    for chip, got in raw["runs"].items():
        mine = [by_key[chip, r[0]] for r in got]
        mine = [r for r in mine if _is_step(r[0], programs)]
        seqs = [seq_of.get((chip, r[1])) for r in mine]
        head = next((i for i, s in enumerate(seqs) if s is not None), None)
        anchors[chip], offset = "run_id", None  # a run's seq minus its place
        if head is not None:
            offset = seqs[head] - head
        else:                       # no run of this chip joined by run_id
            offsets = _by_completion(mine, clear, annotations)
            anchors[chip] = "moe_seq" if len(offsets) == 1 else None
            conflicts += len(offsets) > 1
            if len(offsets) == 1:
                head, offset = len(mine), offsets.pop()
        for j in range(head or 0):              # the head, by order
            seqs[j] = j + offset
            d = dispatches.setdefault(seqs[j], {
                "seq": seqs[j], "span": None, "enqueue": None,
                "enqueues": {}, "by_order": True, "runs": {}})
            d["runs"][chip] = mine[j]
        step_runs[chip] = [r + (s,) for r, s in zip(mine, seqs)]
    return {"dispatches": [dispatches[s] for s in sorted(dispatches)],
            "step_runs": step_runs, "tail": tail, "mismatched": mismatched,
            "ambiguous": len(ambiguous), "anchors": anchors,
            "anchor_conflicts": conflicts}


def _by_completion(runs, spans, annotations):
    """{seq of a chip's k-th step-program run minus k}, from every build
    that dates the device's progress: one value where they agree."""
    ends = [r[2] for r in runs]                 # in order: one queue a chip
    builds = sorted((a["end_ns"], a["start_ns"]) for a in annotations
                    if a["name"] == BUILD)
    build_ends = [b[0] for b in builds]
    offsets = set()
    for s in spans:
        if "moe_seq" not in s["args"]:
            continue
        i = bisect.bisect_right(build_ends, s["start_ns"]) - 1
        if i < 0:
            continue
        lo, hi = builds[i][1] - GUARD_NS, builds[i][0] + GUARD_NS
        done = bisect.bisect_left(ends, lo)     # runs ended before the fold
        if done and bisect.bisect_right(ends, hi) == done:
            offsets.add(int(s["args"]["moe_seq"]) - (done - 1))
    return offsets


def of_run(ctx):
    """The traced run's join, made once; None where there is nothing to
    join."""
    if "_dispatch_join" not in ctx:
        run = xmeta.of_run(ctx)
        got = None
        if run and run["devices"]:
            got = join(decode(xtrace.find_xplane(ctx["tracer"].dir)),
                       run["annotations"], run["devices"])
        ctx["_dispatch_join"] = got
    return ctx["_dispatch_join"]


def joined(ctx):
    """The trace's dispatches in order of ``seq``: [{"seq", "span" (the
    annotation; None in the head), "enqueue": (start_ns, end_ns) (None in
    the head; several chips': first start to last end, each chip's own
    with the instant it was launched under "enqueues"), "by_order", "runs":
    {chip: (name, start_ns, end_ns)}}], run times as
    ``xmeta.of_run(ctx)["devices"][chip]["modules"]`` has them."""
    got = of_run(ctx)
    return got["dispatches"] if got else []


def summary(got, lo, hi, annotations=()):
    """The ``dispatch_join`` line's numbers over the stretch [lo, hi]."""
    def whole(runs):
        return sum(1 for r in runs if r[1] >= lo and r[2] <= hi)
    spanned = [d for d in got["dispatches"] if not d["by_order"]]
    head = sum(whole(d["runs"].values()) for d in got["dispatches"]
               if d["by_order"])
    n_joined = sum(whole(d["runs"].values()) for d in spanned)
    pairs = [(r, d["enqueues"][chip]) for d in spanned
             for chip, r in d["runs"].items()]
    skew = (max(0.0, -min(r[1] - e[0] for r, e in pairs)) if pairs
            else None)
    queue = [(r[1] + skew - e[1]) / 1e6 for r, e in pairs]
    launch = [(e[0] - e[2]) / 1e6 for _, e in pairs]
    flights = [float(a["args"]["in_flight"]) for a in annotations
               if a["name"] == MATERIALIZE and "in_flight" in a["args"]
               and a["start_ns"] >= lo and a["end_ns"] <= hi]
    return {"runs": sum(whole(runs) for runs in got["step_runs"].values()),
            "joined": n_joined, "unjoined_head": head,
            "unjoined_tail": sum(1 for s in got["tail"]
                                 if s["start_ns"] >= lo
                                 and s["end_ns"] <= hi),
            "mismatched": got["mismatched"], "ambiguous": got["ambiguous"],
            "anchor": got["anchors"][min(got["anchors"])],
            "anchor_conflicts": got["anchor_conflicts"],
            "queue_ms": ({"mean": statistics.fmean(queue),
                          "p50": statistics.median(queue),
                          "max": max(queue)} if queue else None),
            "launch_ms": ({"mean": statistics.fmean(launch),
                           "max": max(launch)} if launch else None),
            "clock_skew_ms": None if skew is None else skew / 1e6,
            "in_flight_mean": (statistics.fmean(flights) if flights
                               else None)}


def line_of(ctx):
    """The run's ``dispatch_join`` numbers, printed the first time."""
    if "_dispatch_join_line" not in ctx:
        got = of_run(ctx)
        line = None
        if got and "trace_window" in ctx:
            line = summary(got, *ctx["trace_window"],
                           xmeta.of_run(ctx)["annotations"])
            print(json.dumps({"phase": "dispatch_join", **line}), flush=True)
        ctx["_dispatch_join_line"] = line
    return ctx["_dispatch_join_line"]


def expert_gemm_joined(ctx, spec):
    """Need and time of the SAME dispatches: the MoE totals a span carries
    are the device's through dispatch ``moe_seq``; between the two spans of
    the stretch whose ``moe_seq`` lie furthest apart with every dispatch
    between them run whole inside the stretch, the totals' growth is what
    exactly those runs needed, and the ``moe_experts`` scope's time inside
    exactly those runs is what they took."""
    import costs
    import costs_moe
    from moe_scope_time import group_of
    got, peaks = of_run(ctx), ctx.get("peaks")
    if not got or not peaks or "trace_window" not in ctx:
        return None
    lo, hi = ctx["trace_window"]
    devices = xmeta.of_run(ctx)["devices"]
    chip = min(devices)
    dev = devices[chip]
    runs = {d["seq"]: d["runs"][chip] for d in got["dispatches"]
            if chip in d["runs"] and d["runs"][chip][1] >= lo
            and d["runs"][chip][2] <= hi
            and d["runs"][chip][0].startswith(spec["program"])}
    totals = {}                                 # moe_seq -> (local, touched)
    for a in xmeta.of_run(ctx)["annotations"]:
        if a["name"] in SERVE_SPANS and "moe_seq" in a["args"] \
                and a["start_ns"] >= lo and a["end_ns"] <= hi:
            totals[int(a["args"]["moe_seq"])] = (
                float(a["args"]["moe_local"]),
                float(a["args"]["moe_touched"]))
    marks = sorted(totals)
    best, reach = None, -1
    for m1 in marks:
        if m1 <= reach:             # an earlier mark spans further
            continue
        reach = m1
        while reach + 1 in runs:
            reach += 1
        m2 = max((m for m in marks if m1 < m <= reach), default=None)
        if m2 is not None and (best is None or m2 - m1 > best[1] - best[0]):
            best = (m1, m2)
    if best is None:
        return None
    m1, m2 = best
    meta = dev["meta"]
    ops = dev["ops"]
    starts = [op[1] for op in ops]
    k_ns = 0
    for seq in range(m1 + 1, m2 + 1):
        _, a, b = runs[seq]
        k_ns += xtrace.total(xtrace.union(
            (s, e) for mid, s, e in ops[bisect.bisect_left(starts, a):
                                        bisect.bisect_left(starts, b)]
            if e <= b and mid in meta
            and meta[mid]["opcode"] not in xtrace.CONTAINERS
            and group_of(meta[mid]) == "moe_experts"))
    if not k_ns:
        return None
    local = totals[m2][0] - totals[m1][0]
    touched = totals[m2][1] - totals[m1][1]
    cfg = ctx["model_cfg"]
    flops, byts = costs_moe.expert_gemm_cost(local, touched, cfg.hidden_size,
                                             cfg.expert_dim)
    share, bound = costs.roofline_share(flops, byts, k_ns / 1e9, peaks)
    print(json.dumps({
        "phase": "roofline", "kernel": "expert_gemm.joined", "bound": bound,
        "kernel_s": k_ns / 1e9, "needed_flops": flops, "needed_bytes": byts,
        "from_moe_seq": m1, "through_moe_seq": m2, "runs": m2 - m1,
        "by_order": sum(1 for d in got["dispatches"]
                        if d["by_order"] and m1 < d["seq"] <= m2),
        "moe_local": local, "moe_touched": touched}), flush=True)
    return share


def read(ctx, spec):
    what = spec["what"]
    line = line_of(ctx)
    if not line:
        return None
    if what == "expert_gemm_joined":
        return expert_gemm_joined(ctx, spec)
    if what == "join_share":
        return 100.0 * line["joined"] / line["runs"] if line["runs"] else None
    if what == "queue_ms":
        return line["queue_ms"]["mean"] if line["queue_ms"] else None
    if what == "clock_skew_ms":
        return line["clock_skew_ms"]
    raise ValueError(f"dispatch_join reads no {what!r}")


def main(argv=None):
    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = xtrace.find_xplane(path)
    notes = xmeta.annotations(path)
    got = join(decode(path), notes, xmeta.device_ops(path))
    line = (summary(got, *xtrace.window_of(xtrace.load(path)), notes)
            if got else None)
    print(json.dumps({"phase": "dispatch_join", **(line or {})}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
