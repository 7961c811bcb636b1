#!/usr/bin/env python3
"""What a profiler trace says of which device run a dispatch launched (PR 55,
step 0): the stats of the chips' ``XLA Modules`` events, the host events that
carry a ``run_id``, and everything that starts inside one dispatch span.

    python3 scripts/step0_run_ids.py <trace dir or .xplane.pb> --tag <name>
        [--span ds.burst_dispatch]

reads the file ``benchmark/run.py --trace 1`` leaves under
``benchmark_out/trace/<cell>`` with ``jax.profiler.ProfileData`` alone and
writes ``chiprun_out/pr55/step0_<tag>.json``; the summary goes to stdout.
"""

import argparse
import collections
import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def _stats_dict(st):
    return {k: (v if isinstance(v, (int, float, str)) else repr(v))
            for k, v in st.items()}


def _stats(ev):
    return _stats_dict(dict(ev.stats))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--span", default="ds.burst_dispatch")
    args = ap.parse_args()
    path = args.trace
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"file": path, "bytes": os.path.getsize(path), "planes": [],
           "modules": {}, "run_id_events": {}, "inside_span": None}
    modules = {}                     # (chip, run_id) -> (name, start, end)
    module_runs = collections.defaultdict(list)
    for plane in data.planes:
        out["planes"].append({"name": plane.name, "lines": [
            ln.name for ln in plane.lines]})
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        chip = int(m.group(1))
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            evs = list(line.events)
            out["modules"][chip] = {
                "events": len(evs),
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "duration_ns": e.duration_ns, "stats": _stats(e)}
                          for e in evs[:3]]}
            for e in evs:
                rid = dict(e.stats).get("run_id")
                module_runs[chip].append(rid)
                modules[chip, rid] = (e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
    host = [p for p in data.planes if p.name == "/host:CPU"]
    carriers = collections.Counter()
    enqueues = []                    # (line, name, start, end, stats)
    spans = []                       # (line, name, start, end, stats)
    events = []
    for plane in host:
        for i, line in enumerate(plane.lines):
            lname = f"{line.name}~{i}"
            for e in line.events:
                st = dict(e.stats)
                rec = (lname, e.name, e.start_ns, e.start_ns + e.duration_ns,
                       st)
                events.append(rec)
                if "run_id" in st:
                    carriers[lname.split("~")[0].split("/")[0], e.name] += 1
                    if "device_ordinal" in st and "Enqueue" in e.name:
                        enqueues.append(rec)
                if e.name.startswith("ds.") and e.name.endswith("dispatch"):
                    spans.append(rec)
    out["run_id_events"] = {f"{ln}:{n}": c for (ln, n), c in
                            sorted(carriers.items())}
    out["dispatch_spans"] = collections.Counter(s[1] for s in spans)
    out["span_lines"] = sorted({s[0] for s in spans})
    out["enqueue_lines"] = sorted({e[0] for e in enqueues})
    # what starts inside one span of the asked name (the middle one)
    asked = [s for s in spans if s[1] == args.span]
    if asked:
        ln, name, a, b, st = asked[len(asked) // 2]
        out["inside_span"] = {
            "span": {"line": ln, "name": name, "start_ns": a, "end_ns": b,
                     "args": _stats_dict(st)},
            "events": [{"line": l2, "name": n2, "start_ns": a2, "end_ns": b2,
                        "stats": _stats_dict(s2)}
                       for l2, n2, a2, b2, s2 in sorted(
                           events, key=lambda r: r[2])
                       if a <= a2 <= b and n2 != name]}
    # enqueues per span, and the device's lead over its enqueue
    per_span = collections.Counter()
    leads = collections.defaultdict(list)
    unmatched = 0
    spans.sort(key=lambda r: r[2])
    for ln, name, a, b, st in enqueues:
        hit = [s for s in spans if s[2] <= a <= s[3]]
        per_span[len(hit)] += 1
        key = (int(st["device_ordinal"]), st["run_id"])
        if key in modules:
            leads[key[0]].append((modules[key][1] - a) / 1e6)
        else:
            unmatched += 1
    # the chain from the launching thread to the enqueue: the launch
    # (``tpu::System::Execute``, producer id ``_p``) -> the issue on whatever
    # thread (``...=>IssueSequencedEvent``, consumer id ``_c``) -> the
    # enqueue nested in it
    launches = {r[4]["_p"]: r for r in events
                if r[1] == "tpu::System::Execute" and "_p" in r[4]}
    issues = [r for r in events
              if r[1] == "tpu::System::Execute=>IssueSequencedEvent"
              and "_c" in r[4]]
    chained = in_span = 0
    lag = []
    per_launch_span = collections.Counter()
    for ln, name, a, b, st in enqueues:
        up = [i for i in issues if i[0] == ln and i[2] <= a <= i[3]]
        if not up or up[-1][4]["_c"] not in launches:
            continue
        chained += 1
        launch = launches[up[-1][4]["_c"]]
        lag.append((a - launch[2]) / 1e6)
        hit = [s for s in spans if s[2] <= launch[2] <= s[3]]
        in_span += bool(hit)
        for s in hit:
            per_launch_span[s[2]] += 1
    out["chain"] = {
        "launches": len(launches), "issues": len(issues),
        "launch_lines": sorted({r[0] for r in launches.values()}),
        "issue_lines": sorted({r[0] for r in issues}),
        "enqueues_chained_to_a_launch": chained,
        "of_them_launched_inside_a_dispatch_span": in_span,
        "launched_enqueues_per_dispatch_span": dict(collections.Counter(
            per_launch_span.values())),
        "enqueue_start_minus_launch_start_ms": (
            {"min": min(lag), "max": max(lag), "mean": sum(lag) / len(lag)}
            if lag else None)}
    out["enqueues"] = len(enqueues)
    out["enqueues_by_spans_holding_them"] = dict(per_span)
    out["enqueues_without_a_module_event"] = unmatched
    out["enqueues_per_dispatch_span"] = dict(collections.Counter(
        sum(1 for e in enqueues if s[2] <= e[2] <= s[3]) for s in spans))
    out["device_start_minus_enqueue_start_ms"] = {
        chip: {"n": len(v), "min": min(v), "max": max(v),
               "mean": sum(v) / len(v)} for chip, v in leads.items() if v}
    out["run_ids"] = {
        chip: {"n": len(v), "min": min(x for x in v if x is not None),
               "max": max(x for x in v if x is not None),
               "none": sum(x is None for x in v)}
        for chip, v in module_runs.items() if any(x is not None for x in v)}
    shared = collections.Counter(rid for (_, rid) in modules)
    out["run_ids_seen_on_n_chips"] = dict(collections.Counter(
        shared.values()))
    os.makedirs("chiprun_out/pr55", exist_ok=True)
    dest = f"chiprun_out/pr55/step0_{args.tag}.json"
    with open(dest, "w") as f:
        json.dump(out, f, indent=1, default=str)
    brief = {k: v for k, v in out.items() if k not in ("inside_span",
                                                       "planes")}
    print(json.dumps(brief, default=str))
    if out["inside_span"]:
        print(json.dumps({"inside_span": out["inside_span"]["span"],
                          "events": [
                              (e["line"], e["name"], e["stats"])
                              for e in out["inside_span"]["events"]][:60]},
                         default=str))
    print("wrote", dest)


if __name__ == "__main__":
    main()
