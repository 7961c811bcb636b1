#!/usr/bin/env python
"""Merge N Chrome traces into one clock-aligned Perfetto timeline.

Every tracer in this repo (training hosts, serving replicas) writes its
own Chrome-trace JSON with timestamps relative to ITS OWN construction —
useful alone, useless side by side: a fleet replica death is only
diagnosable when the dead replica's last dispatch, the router's retry, and
the survivor's pickup sit on one timeline.  This tool merges them:

- **clock alignment**: each trace carries ``otherData.epoch_unix_time``
  (the wall time of its ts=0 — stamped by SpanTracer since this change);
  events are shifted by the trace's offset from the EARLIEST epoch, so
  "the same wall moment" lines up across files.  Traces without the stamp
  merge unshifted with a warning (relative timing across files is then
  meaningless, within-file timing still correct).
- **pid remapping**: each input file becomes one Perfetto process
  (``pid`` = file index, process_name = the trace's own process_name
  metadata + the file label), so N replicas' track-0 dispatch rows don't
  collapse onto each other.  Thread (tid) metadata — the per-request
  track names — is carried through untouched.
- **flow-id remapping**: flow events (``ph`` s/t/f) are keyed by
  ``(otherData.flow_id_scope, id)`` — files written by the same process
  share one id space (their stitched request trees survive the merge),
  while files from different processes are remapped onto disjoint ids so
  unrelated requests never collide into one accidental flow.  Files
  missing the scope stamp get a per-file scope (safe, but cross-file
  stitching is then impossible for them).

Usage:

    python scripts/merge_traces.py -o fleet.json trace_r0.json trace_r1.json
    python scripts/merge_traces.py -o out.json telemetry/*/trace.json

Over a fleet's per-replica traces, a kill → migrate → recover sequence
reads off one screen.
Exit status: 0 ok, 2 usage/load errors.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional


def load_trace(path: str) -> dict:
    with open(path) as f:
        obj = json.load(f)
    if isinstance(obj, list):                 # bare-array trace form
        obj = {"traceEvents": obj}
    if "traceEvents" not in obj:
        raise ValueError(f"{path}: no traceEvents key")
    return obj


def merge_traces(traces: List[dict],
                 labels: Optional[List[str]] = None) -> dict:
    """Merge parsed trace dicts into one clock-aligned timeline dict."""
    labels = labels or [f"trace{i}" for i in range(len(traces))]
    epochs = [t.get("otherData", {}).get("epoch_unix_time")
              for t in traces]
    known = [e for e in epochs if e is not None]
    t0 = min(known) if known else None
    unaligned: List[str] = []
    events: List[dict] = []
    # (flow_id_scope, original id) -> merged id.  Same-scope inputs map
    # identical ids to the SAME merged id (stitching survives); distinct
    # scopes can never share a merged id (no collisions).
    flow_ids: dict = {}
    for pid, (trace, label, epoch) in enumerate(
            zip(traces, labels, epochs)):
        if epoch is None:
            offset_us = 0.0
            unaligned.append(label)
        else:
            offset_us = (epoch - t0) * 1e6
        scope = trace.get("otherData", {}).get("flow_id_scope") \
            or f"__file{pid}"
        proc_name = label
        for ev in trace["traceEvents"]:
            if ev.get("ph") == "M":
                if ev.get("name") == "process_name":
                    base = (ev.get("args") or {}).get("name", "")
                    proc_name = f"{base} [{label}]" if base else label
                    continue               # re-emitted with the new pid
                ev = dict(ev, pid=pid)     # thread_name metadata rides
                events.append(ev)
                continue
            ev = dict(ev, pid=pid)
            if ev.get("ph") in ("s", "t", "f") and "id" in ev:
                key = (scope, ev["id"])
                if key not in flow_ids:
                    flow_ids[key] = len(flow_ids) + 1
                ev["id"] = flow_ids[key]
            if offset_us and "ts" in ev:
                ev["ts"] = round(float(ev["ts"]) + offset_us, 3)
            events.append(ev)
        events.insert(0, {"name": "process_name", "ph": "M", "pid": pid,
                          "tid": 0, "args": {"name": proc_name}})
    dropped = sum(int(t.get("otherData", {}).get("dropped_events", 0))
                  for t in traces)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "merged_from": labels,
            "epoch_unix_time": t0,
            "dropped_events": dropped,
            "unaligned": unaligned,
        },
    }


def merge_files(out_path: str, in_paths: List[str]) -> dict:
    traces = [load_trace(p) for p in in_paths]
    labels = [os.path.splitext(os.path.basename(p))[0] for p in in_paths]
    merged = merge_traces(traces, labels)
    d = os.path.dirname(out_path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f)
    os.replace(tmp, out_path)
    return merged


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="merge per-host/per-replica Chrome traces into one "
                    "clock-aligned Perfetto timeline (pid = input file, "
                    "tid metadata preserved)")
    ap.add_argument("inputs", nargs="+", help="trace.json files to merge")
    ap.add_argument("-o", "--output", required=True,
                    help="merged trace path")
    args = ap.parse_args(argv)
    try:
        merged = merge_files(args.output, args.inputs)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"merge_traces: {e}", file=sys.stderr)
        return 2
    od = merged["otherData"]
    n_ev = len(merged["traceEvents"])
    print(f"merge_traces: {len(args.inputs)} traces -> {args.output} "
          f"({n_ev} events, {od['dropped_events']} dropped at source)")
    if od["unaligned"]:
        print(f"merge_traces: WARNING — no epoch_unix_time stamp in "
              f"{', '.join(od['unaligned'])}: merged unshifted, "
              f"cross-file timing is not comparable", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
