#!/usr/bin/env python
"""perf_report — render a telemetry snapshot into a step-time-budget report.

One command that answers "where did the step time go?" from artifacts the
telemetry layer already writes — the attribution that names a
per-dispatch floor without a human:

    python scripts/perf_report.py telemetry_snapshot.json --step-ms 259
    python scripts/perf_report.py BENCH_r06.json            # bench record:
                                                            # step time, comm
                                                            # ms and snapshot
                                                            # path from extra
    python scripts/perf_report.py telemetry/<job>/postmortem/<bundle>/
                                                            # postmortem mode

Sections:

1. **step-time budget** (telemetry/profiler.py) — measured step decomposed
   into compute / exposed_comm / hbm_bound / host_gap / dispatch_floor,
   with achieved MFU and `mfu_lost{cause}` shares;
2. **roofline** (telemetry/roofline.py) — per-op-class flops / HBM bytes /
   wire bytes against the accelerator peak table, the attainable-time
   floor, and which resource binds each class;
3. **per-link collective bytes** — the `collective_bytes_total{link=
   ici|dcn}` split per kind/axis (trace-time wire convention);
4. **span summary** — the heaviest host phases.

Input sniffing: a directory containing ``meta.json`` is a postmortem
bundle (spans from meta.json, metrics parsed out of ``snapshot.prom``,
step time from the records' ``spans_ms`` unless ``--step-ms`` overrides);
a JSON with a ``metric`` key is a bench record (step time / comm ms /
snapshot path read from ``extra``); anything else is a snapshot.json.

Exit status: 0 report printed, 2 load/usage errors.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_PROM_LINE = re.compile(
    r"^(\w+?)(?:\{(.*)\})?\s+(-?[0-9.eE+\-]+|NaN|\+Inf|-Inf)$")
_PROM_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str, namespace: str = "deepspeed_tpu"
                     ) -> dict:
    """Minimal exposition-format parser → the exporter's snapshot-dict
    shape (counters/gauges only — enough to feed the report sections)."""
    types: Dict[str, str] = {}
    snap: Dict[str, dict] = {"counters": {}, "gauges": {}}
    prefix = namespace + "_"
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(None, 3)
            types[name] = kind
            continue
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        if not m:
            continue
        full, labels_s, value_s = m.groups()
        kind = types.get(full)
        if kind not in ("counter", "gauge"):
            continue
        name = full[len(prefix):] if full.startswith(prefix) else full
        labels = {k: v.replace(r"\"", '"').replace(r"\\", "\\")
                  for k, v in _PROM_LABEL.findall(labels_s or "")}
        try:
            value = float(value_s)
        except ValueError:
            continue
        bucket = snap["counters" if kind == "counter" else "gauges"]
        bucket.setdefault(name, {"help": "", "samples": []})[
            "samples"].append({"labels": labels, "value": value})
    return snap


def load_bundle(path: str) -> Tuple[dict, Optional[float]]:
    """Postmortem bundle dir → (snapshot-like dict, derived step_ms)."""
    snap: dict = {"counters": {}, "gauges": {}}
    prom = os.path.join(path, "snapshot.prom")
    if os.path.exists(prom):
        with open(prom) as f:
            snap = parse_prometheus(f.read())
    meta = os.path.join(path, "meta.json")
    if os.path.exists(meta):
        with open(meta) as f:
            snap["spans"] = json.load(f).get("spans", {})
    step_ms = None
    records = os.path.join(path, "records.jsonl")
    if os.path.exists(records):
        sums: List[float] = []
        with open(records) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                spans = rec.get("spans_ms") or {}
                if spans:
                    sums.append(sum(spans.values()))
        if sums:
            step_ms = sum(sums) / len(sums)
    return snap, step_ms


def find_bundle(path: str) -> str:
    """Accept a bundle dir or a postmortem/ parent (newest bundle wins) —
    same convenience as telemetry/postmortem.py."""
    if os.path.exists(os.path.join(path, "meta.json")):
        return path
    candidates = sorted(
        d for d in (os.path.join(path, n) for n in os.listdir(path))
        if os.path.isdir(d) and os.path.exists(os.path.join(d,
                                                            "meta.json")))
    if not candidates:
        raise ValueError(f"{path}: no postmortem bundle (meta.json) found")
    return candidates[-1]


def link_section(snap: dict) -> str:
    """Per-link collective-byte table from the trace-time counters."""
    metric = snap.get("counters", {}).get("collective_bytes_total")
    if not metric:
        return ("per-link collective bytes: no collective_bytes_total "
                "counters in this snapshot")
    totals: Dict[Tuple[str, str], Dict[str, float]] = {}
    for s in metric["samples"]:
        lab = s.get("labels") or {}
        key = (lab.get("kind", "?"), lab.get("axis", "?"))
        rec = totals.setdefault(key, {})
        rec[lab.get("link", "total")] = float(s["value"])
    lines = ["per-link collective bytes (trace-time wire convention)",
             f"  {'kind':<24}{'axis':<14}{'total':>12}{'ici':>12}"
             f"{'dcn':>12}"]
    for (kind, axis), rec in sorted(totals.items()):
        lines.append(f"  {kind:<24}{axis:<14}"
                     f"{rec.get('total', 0):>12.0f}"
                     f"{rec.get('ici', 0):>12.0f}"
                     f"{rec.get('dcn', 0):>12.0f}")
    return "\n".join(lines)


def span_section(snap: dict, top: int = 8) -> str:
    spans = snap.get("spans") or {}
    if not spans:
        return "spans: none recorded (trace off)"
    lines = ["host phase spans (per-occurrence mean, heaviest first)",
             f"  {'phase':<28}{'count':>8}{'mean_ms':>10}{'max_ms':>10}"]
    ranked = sorted(spans.items(), key=lambda kv: -kv[1].get("total_ms", 0))
    for name, rec in ranked[:top]:
        lines.append(f"  {name:<28}{rec.get('count', 0):>8}"
                     f"{rec.get('mean_ms', 0):>10.3f}"
                     f"{rec.get('max_ms', 0):>10.3f}")
    return "\n".join(lines)


def report(snap: dict, *, step_ms: Optional[float], fn: str,
           comm_ms: Optional[float], as_json: bool = False) -> str:
    from deepspeed_tpu.telemetry import profiler, roofline

    sections: List[str] = []
    budget = None
    if step_ms:
        budget = profiler.step_time_budget(snap, step_ms=step_ms, fn=fn,
                                           comm_total_ms=comm_ms)
        sections.append(profiler.render(budget))
    else:
        sections.append("step-time budget: no measured step time "
                        "(pass --step-ms, or use a bench record / bundle "
                        "with step records)")

    executables = snap.get("executables") or {}
    rendered_roofline = False
    for name, exe in sorted(executables.items()):
        model = exe.get("roofline")
        if model:
            sections.append(roofline.render(model, title=name))
            rendered_roofline = True
    if not rendered_roofline:
        att = snap.get("gauges", {}).get("roofline_attainable_ms")
        if att:
            lines = ["roofline (gauges only — full class table lives in "
                     "snapshot.json)"]
            for s in att["samples"]:
                lines.append(
                    f"  attainable >= {s['value']:.3f} ms "
                    f"(fn={(s.get('labels') or {}).get('fn', '?')})")
            sections.append("\n".join(lines))
        else:
            sections.append("roofline: no compiled-HLO analysis in this "
                            "snapshot (telemetry.hlo_stats off?)")

    sections.append(link_section(snap))
    sections.append(span_section(snap))

    env = snap.get("env")
    if env:
        regime = env.get("resolved", env)
        sections.append("scheduler regime: "
                        + json.dumps(regime, sort_keys=True)[:400])

    if as_json:
        return json.dumps({"budget": budget,
                           "roofline": {n: e.get("roofline")
                                        for n, e in executables.items()
                                        if e.get("roofline")}},
                          indent=1, sort_keys=True)
    return "\n\n".join(sections)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="render a telemetry snapshot / bench record / "
                    "postmortem bundle into a step-time-budget + roofline "
                    "report")
    ap.add_argument("path", help="snapshot.json, bench record JSON, or "
                                 "postmortem bundle dir")
    ap.add_argument("--fn", default="train_batch",
                    help="jitted function to attribute (default "
                         "train_batch)")
    ap.add_argument("--step-ms", type=float, default=None,
                    help="measured step wall time override")
    ap.add_argument("--comm-ms", type=float, default=None,
                    help="profiled per-step collective latency override")
    ap.add_argument("--json", action="store_true",
                    help="emit the budget + roofline as JSON instead of "
                         "the rendered report")
    args = ap.parse_args(argv)

    step_ms, comm_ms = args.step_ms, args.comm_ms
    try:
        if os.path.isdir(args.path):
            bundle = find_bundle(args.path)
            snap, derived = load_bundle(bundle)
            step_ms = step_ms or derived
        else:
            with open(args.path) as f:
                obj = json.load(f)
            if "metric" in obj or "parsed" in obj:
                rec = obj.get("parsed", obj)
                extra = rec.get("extra") or {}
                if step_ms is None and extra.get("step_time_s"):
                    step_ms = float(extra["step_time_s"]) * 1e3
                if comm_ms is None and extra.get("comm_total_ms"):
                    comm_ms = float(extra["comm_total_ms"])
                snap_path = extra.get("telemetry_snapshot")
                snap = {}
                if snap_path:
                    for base in (os.path.dirname(os.path.abspath(
                            args.path)), os.getcwd()):
                        cand = os.path.join(base, snap_path)
                        if os.path.exists(cand):
                            with open(cand) as f:
                                snap = json.load(f)
                            break
                if not snap:
                    print(f"perf_report: bench record's telemetry "
                          f"snapshot ({snap_path!r}) not found — "
                          f"budget limited to record columns",
                          file=sys.stderr)
                    snap = {"counters": {}, "gauges": {}}
                    ratio = extra.get("collective_exposed_ratio")
                    if ratio is not None:
                        snap["gauges"]["collective_exposed_ratio"] = {
                            "help": "", "samples": [{
                                "labels": {"fn": args.fn},
                                "value": float(ratio)}]}
            else:
                snap = obj
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"perf_report: cannot load {args.path}: {e}",
              file=sys.stderr)
        return 2

    print(report(snap, step_ms=step_ms, fn=args.fn, comm_ms=comm_ms,
                 as_json=args.json))
    return 0


if __name__ == "__main__":
    sys.exit(main())
