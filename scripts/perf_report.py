#!/usr/bin/env python
"""perf_report — render a telemetry snapshot's collective bytes and spans.

    python scripts/perf_report.py telemetry_snapshot.json
    python scripts/perf_report.py telemetry/<job>/postmortem/<bundle>/
                                                            # postmortem mode

Sections:

1. **per-link collective bytes** — the `collective_bytes_total{link=
   ici|dcn}` split per kind/axis (trace-time wire convention);
2. **span summary** — the heaviest host phases.

Where the step's time goes on the device is the benchmark's to say
(`benchmark/run.py --trace 1`, PERF.md section 5), from the device trace.

Input sniffing: a directory containing ``meta.json`` is a postmortem
bundle (spans from meta.json, metrics parsed out of ``snapshot.prom``);
anything else is a snapshot.json.

Exit status: 0 report printed, 2 load/usage errors.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

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


def load_bundle(path: str) -> dict:
    """Postmortem bundle dir → snapshot-like dict."""
    snap: dict = {"counters": {}, "gauges": {}}
    prom = os.path.join(path, "snapshot.prom")
    if os.path.exists(prom):
        with open(prom) as f:
            snap = parse_prometheus(f.read())
    meta = os.path.join(path, "meta.json")
    if os.path.exists(meta):
        with open(meta) as f:
            snap["spans"] = json.load(f).get("spans", {})
    return snap


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


def report(snap: dict) -> str:
    sections = [link_section(snap), span_section(snap)]
    env = snap.get("env")
    if env:
        regime = env.get("resolved", env)
        sections.append("scheduler regime: "
                        + json.dumps(regime, sort_keys=True)[:400])
    return "\n\n".join(sections)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="render a telemetry snapshot / postmortem bundle's "
                    "per-link collective bytes and host spans")
    ap.add_argument("path", help="snapshot.json or postmortem bundle dir")
    args = ap.parse_args(argv)
    try:
        if os.path.isdir(args.path):
            snap = load_bundle(find_bundle(args.path))
        else:
            with open(args.path) as f:
                snap = json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_report: cannot load {args.path}: {e}",
              file=sys.stderr)
        return 2
    print(report(snap))
    return 0


if __name__ == "__main__":
    sys.exit(main())
