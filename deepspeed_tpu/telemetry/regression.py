"""Bench regression sentinel — diff a bench record against a baseline ledger.

The r05 round spent a full measurement cycle manually diagnosing two
"regressions" that a trajectory check would have framed in seconds —
and nothing compared one ``BENCH_r*.json`` to the next at all.
This module is the comparison: a committed **baseline ledger**
(``BENCH_BASELINE.json``, seeded from the r05 record) holding one value +
noise band per metric, and a ``compare()`` that classifies each current
metric as ok / regressed / improved with direction awareness (tokens/s
up is good; ``*_ms`` up is bad).

Input formats (``load_bench_file`` sniffs all three):

- a bench metric line / ``BENCH_r*.json`` wrapper (``{"metric", "value",
  "extra": {...}}``, optionally nested under ``"parsed"``),
- the per-leg JSONL records bench.py / bench_serving.py append
  (``{"metric", "value", "env", "unix_time"}`` per line —
  :func:`append_bench_records` is the writer),
- a flat ``{metric: value}`` dict.

Comparison rules:

- config echoes and workload descriptors (``params_m``, ``slots``,
  ``n_requests``, arrival rates, …) are excluded — they are identity, not
  performance;
- a baseline of exactly 0 is never ratio-compared (division blowup; a
  counter that SHOULD stay 0, like ``prefetch_starvation``, is flagged on
  any nonzero current value instead);
- a delta beyond the metric's noise band in the BAD direction is a
  regression; beyond it in the good direction an improvement (reported,
  never failing);
- metrics missing from the current record are listed (a silently dropped
  leg is itself a regression signal) but only fail with ``strict``.

``scripts/check_bench.py`` is the CLI gate (nonzero exit on regression);
bench.py / bench_serving.py run the same compare non-fatally and surface a
``bench_regressions`` column.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

BASELINE_SCHEMA = "deepspeed_tpu.bench_baseline.v1"
DEFAULT_NOISE_BAND = 0.08

# metrics that are identity / workload echo, not performance — never compared
_IGNORE_EXACT = frozenset((
    "params_m", "loss", "slots", "n_requests", "legs_complete", "model",
    "telemetry_snapshot", "serving_telemetry_dir", "open_loop_slo",
    "fleet_trace",
))
_IGNORE_SUBSTR = ("arrival_rate", "kill_at", "replicas", "num_chunks",
                  "params_m", "train_loss", "error", "_dir", "_path")

# lower-is-better name patterns (everything else defaults to higher-better)
_LOWER_SUFFIX = ("_ms", "_s", "_bytes", "_bytes_per_step")
_LOWER_SUBSTR = ("step_time", "exposed", "fragmentation", "misses",
                 "starvation", "anomalies", "dumps", "regressions",
                 "padding_waste", "drop_rate")
# zero-baseline metrics where ANY nonzero current value is a trip
_ZERO_SENTINELS = ("starvation", "anomalies", "dumps", "misses_after_warm")


def is_perf_metric(name: str, value) -> bool:
    if not isinstance(value, (int, float)) or isinstance(value, bool):
        return False
    if name in _IGNORE_EXACT:
        return False
    return not any(s in name for s in _IGNORE_SUBSTR)


def metric_direction(name: str) -> int:
    """+1 when a bigger value is better (throughput, MFU, ratios), -1 when
    smaller is better (latencies, exposed time, failure counters)."""
    if name.endswith(_LOWER_SUFFIX) and not name.endswith(
            ("_per_s", "_per_sec")):
        return -1
    if any(s in name for s in _LOWER_SUBSTR):
        return -1
    return +1


# ---------------------------------------------------------------------------
# record loading
# ---------------------------------------------------------------------------

def flatten_bench_record(obj) -> Dict[str, float]:
    """Bench metric-line dict (or ``BENCH_r*.json`` wrapper) → flat
    ``{metric: value}`` including every numeric ``extra`` entry."""
    if "parsed" in obj and isinstance(obj["parsed"], dict):
        obj = obj["parsed"]
    out: Dict[str, float] = {}
    if "metric" in obj and isinstance(obj.get("value"), (int, float)):
        out[str(obj["metric"])] = float(obj["value"])
    for k, v in (obj.get("extra") or {}).items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[str(k)] = float(v)
    for k, v in obj.items():
        if k in ("metric", "value", "extra", "unit", "vs_baseline",
                 "schema"):
            continue
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[str(k)] = float(v)
    return out


def load_bench_file(path: str) -> Dict[str, float]:
    """Sniff + flatten one bench artifact: JSON (metric line, BENCH_r*
    wrapper, or flat dict) or JSONL of per-leg records (last write per
    metric wins)."""
    with open(path) as f:
        text = f.read().strip()
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict):
        if "metric" in obj or "parsed" in obj or "extra" in obj:
            return flatten_bench_record(obj)
        if all(isinstance(v, (int, float, bool)) for v in obj.values()):
            return {k: float(v) for k, v in obj.items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)}
        return flatten_bench_record(obj)
    # JSONL: one record per line
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "metric" in rec \
                and isinstance(rec.get("value"), (int, float)):
            out[str(rec["metric"])] = float(rec["value"])
    return out


# ---------------------------------------------------------------------------
# baseline ledger
# ---------------------------------------------------------------------------

def seed_baseline(current: Dict[str, float], source: str = "",
                  default_band: float = DEFAULT_NOISE_BAND) -> dict:
    """Build a baseline ledger dict from a flat metric map."""
    return {
        "schema": BASELINE_SCHEMA,
        "seeded_from": source,
        "seeded_unix_time": time.time(),
        "default_noise_band": float(default_band),
        "metrics": {
            name: {"value": float(v)}
            for name, v in sorted(current.items())
            if is_perf_metric(name, v)
        },
    }


def load_baseline(path: str) -> dict:
    with open(path) as f:
        ledger = json.load(f)
    if ledger.get("schema") != BASELINE_SCHEMA:
        raise ValueError(f"{path}: not a {BASELINE_SCHEMA} ledger "
                         f"(schema={ledger.get('schema')!r})")
    return ledger


def save_baseline(ledger: dict, path: str) -> str:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(ledger, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def compare(current: Dict[str, float], baseline: dict,
            band: Optional[float] = None,
            strict_missing: bool = False) -> dict:
    """Diff ``current`` against a baseline ledger.

    Returns ``{"regressions", "improvements", "ok", "missing", "new",
    "failed"}`` where each finding is ``{metric, baseline, current,
    delta, band, direction}`` and ``delta`` is the signed relative change
    (positive = metric went up).  ``band`` overrides the ledger's
    default noise band (per-metric ``band`` entries always win).
    """
    default_band = (float(band) if band is not None
                    else float(baseline.get("default_noise_band",
                                            DEFAULT_NOISE_BAND)))
    metrics = baseline.get("metrics", {})
    regressions: List[dict] = []
    improvements: List[dict] = []
    ok: List[dict] = []
    missing: List[str] = []
    for name, entry in sorted(metrics.items()):
        base = float(entry["value"])
        mband = float(entry.get("band", default_band))
        if name not in current:
            missing.append(name)
            continue
        cur = float(current[name])
        direction = metric_direction(name)
        finding = {"metric": name, "baseline": base, "current": cur,
                   "band": mband, "direction": direction}
        if base == 0.0:
            # ratio-free path: counters that must stay 0 trip on any
            # nonzero; anything else with a 0 baseline is uncheckable
            if cur != 0.0 and direction < 0 \
                    and any(s in name for s in _ZERO_SENTINELS):
                finding["delta"] = float("inf")
                regressions.append(finding)
            else:
                finding["delta"] = 0.0
                ok.append(finding)
            continue
        delta = (cur - base) / abs(base)
        finding["delta"] = delta
        goodness = delta * direction          # positive = got better
        if goodness < -mband:
            regressions.append(finding)
        elif goodness > mband:
            improvements.append(finding)
        else:
            ok.append(finding)
    new = sorted(n for n, v in current.items()
                 if n not in metrics and is_perf_metric(n, v))
    failed = bool(regressions) or (strict_missing and bool(missing))
    return {"regressions": regressions, "improvements": improvements,
            "ok": ok, "missing": missing, "new": new, "failed": failed,
            "checked": len(metrics) - len(missing)}


def render(result: dict, baseline_name: str = "baseline") -> str:
    lines: List[str] = []

    def fmt(f: dict) -> str:
        arrow = "↓" if f["delta"] < 0 else "↑"
        return (f"    {f['metric']}: {f['baseline']:g} -> "
                f"{f['current']:g}  ({arrow}{abs(f['delta']):.1%}, "
                f"band ±{f['band']:.0%}, "
                f"{'higher' if f['direction'] > 0 else 'lower'}-is-better)")

    lines.append(f"check_bench: {result['checked']} metrics checked "
                 f"against {baseline_name}")
    if result["regressions"]:
        lines.append(f"  REGRESSIONS ({len(result['regressions'])}):")
        lines.extend(fmt(f) for f in result["regressions"])
    if result["improvements"]:
        lines.append(f"  improvements ({len(result['improvements'])}):")
        lines.extend(fmt(f) for f in result["improvements"])
    if result["missing"]:
        lines.append(f"  missing from current record "
                     f"({len(result['missing'])}): "
                     + ", ".join(result["missing"]))
    if result["new"]:
        lines.append(f"  new metrics not in the ledger "
                     f"({len(result['new'])}): " + ", ".join(result["new"]))
    lines.append("  verdict: "
                 + ("REGRESSED" if result["failed"] else "ok"))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# canned fixtures (sentinel self-test: trips on a 10% slowdown, quiet on
# in-band noise)
# ---------------------------------------------------------------------------

def make_fixture(baseline: dict, kind: str) -> Dict[str, float]:
    """Synthesize a current-record fixture from a ledger:
    ``kind="regression"`` shifts every metric 10% in its BAD direction,
    ``kind="noise"`` jitters deterministically by a quarter of each
    metric's own noise band (strictly inside it, whatever per-metric
    bands the ledger carries)."""
    if kind not in ("regression", "noise"):
        raise ValueError(f"unknown fixture kind {kind!r}")
    default_band = float(baseline.get("default_noise_band",
                                      DEFAULT_NOISE_BAND))
    out: Dict[str, float] = {}
    for i, (name, entry) in enumerate(sorted(
            baseline.get("metrics", {}).items())):
        base = float(entry["value"])
        direction = metric_direction(name)
        if kind == "regression":
            out[name] = base * (1.0 - 0.10 * direction)
        else:
            jitter = 0.25 * float(entry.get("band", default_band))
            out[name] = base * (1.0 + (jitter if i % 2 else -jitter))
    return out


# ---------------------------------------------------------------------------
# per-leg JSONL records (the sentinel's native input; bench.py /
# bench_serving.py append these next to their stdout JSON line)
# ---------------------------------------------------------------------------

def append_bench_records(path: str, metrics: Dict[str, float],
                         env: Optional[dict] = None,
                         unit: str = "") -> int:
    """Append one JSONL record per numeric metric: ``{"metric", "value",
    "unit", "env", "unix_time"}``.  Returns the number of lines written;
    failures must be caught by the caller (bench output must never die on
    telemetry bookkeeping)."""
    now = time.time()
    env = env or {}
    lines = []
    for name, value in sorted(metrics.items()):
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            continue
        lines.append(json.dumps({
            "metric": str(name), "value": float(value), "unit": unit,
            "env": env, "unix_time": now}, sort_keys=True))
    if not lines:
        return 0
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n")
    return len(lines)
