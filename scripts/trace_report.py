#!/usr/bin/env python
"""trace_report — critical-path latency budget from a merged serving trace.

The distributed trace stitches a disaggregated request across replica
files (telemetry/tracecontext.py + scripts/merge_traces.py); this tool
answers the follow-up question: *where did the latency go?*  It walks
every completed request in the trace, decomposes its end-to-end time
into queue_wait / prefill / handoff / decode_wait / decode terms that
sum to the measured e2e **by construction**
(telemetry/critical_path.py), and prints a fleet-aggregate p99 TTFT
budget table naming the dominant term — the one to fix first.

    python scripts/trace_report.py fleet_merged.json
    python scripts/trace_report.py fleet_merged.json --quantile 0.5
    python scripts/trace_report.py fleet_merged.json --per-request 10
    python scripts/trace_report.py fleet_merged.json --json

``--self-test`` decomposes a canned two-request fixture (one disagg
with a handoff, one unified) and asserts the exact-sum property plus
the zero-handoff invariant — scripts/lint_all.py runs it as the
``trace_report`` lint so a drift in the span contract fails fast.

Exit status: 0 report printed / self-test passed, 1 self-test failed,
2 load/usage errors.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from deepspeed_tpu.telemetry.critical_path import (  # noqa: E402
    TERMS, TTFT_TERMS, decompose, ttft_budget)


def render(rows: List[dict], budget: dict, per_request: int = 0) -> str:
    """Human-readable report: the aggregate budget table, then the N
    slowest requests' own decompositions."""
    q = budget["quantile"]
    lines = [f"trace_report: {budget['n_requests']} completed requests",
             "",
             f"latency budget (p{q * 100:g} / mean, ms)",
             f"  {'term':<16}{'p' + format(q * 100, 'g'):>10}"
             f"{'mean':>10}  in TTFT path"]
    for name in TERMS:
        t = budget["terms"][name]
        mark = "yes" if name in TTFT_TERMS else "-"
        star = "  <-- dominant" if name == budget["dominant"] else ""
        lines.append(f"  {name:<16}{t['p']:>10.3f}{t['mean']:>10.3f}"
                     f"  {mark}{star}")
    lines.append(f"  {'e2e':<16}{budget['e2e_ms']:>10.3f}")
    lines.append(f"  {'ttft_path':<16}{budget['ttft_path_ms']:>10.3f}")
    if budget["dominant"]:
        lines.append("")
        lines.append(f"p{q * 100:g} TTFT budget is dominated by "
                     f"{budget['dominant']}")
    if per_request and rows:
        slowest = sorted(rows, key=lambda r: -r["e2e_ms"])[:per_request]
        lines.append("")
        lines.append(f"slowest {len(slowest)} requests (ms)")
        lines.append(f"  {'trace':>6}{'mode':>9}{'e2e':>10}"
                     + "".join(f"{t[:-3]:>12}" for t in TERMS))
        for r in slowest:
            lines.append(f"  {r['trace']:>6}{r['mode']:>9}"
                         f"{r['e2e_ms']:>10.3f}"
                         + "".join(f"{r[t]:>12.3f}" for t in TERMS))
    return "\n".join(lines)


# --------------------------------------------------------------- self-test

def canned_fixture() -> dict:
    """A minimal merged trace: request 1 is disaggregated (prefill on
    replica pid 1, handoff, decode on pid 2), request 2 is unified.
    Timestamps are microseconds on one already-aligned timeline — the
    shape merge_traces.py emits.  Reused by tests/test_tracing_slo.py."""
    def x(name, cat, ts, dur, pid, tid, **args):
        return {"name": name, "cat": cat, "ph": "X", "ts": float(ts),
                "dur": float(dur), "pid": pid, "tid": tid, "args": args}

    t1 = {"trace": 1, "span": 2, "attempt": 1}
    t1d = {"trace": 1, "span": 3, "attempt": 2}
    t2 = {"trace": 2, "span": 5, "attempt": 1}
    events = [
        # --- request 1: disagg.  arrival 0, done 10_000us.
        x("request", "router", 0, 10_000, 0, 1, mode="disagg", index=0,
          attempts=2, migrations=0, generated_tokens=8, phase="decode",
          **t1d),
        x("dispatch prefill", "router", 0, 500, 0, 1, replica="r0",
          phase="prefill", **t1),
        # prefill replica: admitted at 1_000, prefill done at 4_000
        x("queue_wait", "request", 500, 500, 1, 1, phase="prefill", **t1),
        x("prefill", "request", 1_000, 3_000, 1, 1, phase="prefill",
          **t1),
        # router handoff slice: 4_000 -> 5_000
        x("fleet.handoff", "router", 4_000, 1_000, 0, 1, src="r0",
          phase="prefill", **t1),
        x("dispatch decode", "router", 5_000, 500, 0, 1, replica="r1",
          phase="decode", **t1d),
        # decode replica resumes (KV restore billed to decode) at 6_000
        x("prefill", "request", 6_000, 500, 2, 1, phase="decode", **t1d),
        x("decode", "request", 6_500, 3_500, 2, 1, phase="decode",
          **t1d),
        # --- request 2: unified.  arrival 20_000, done 26_000us.
        x("request", "router", 20_000, 6_000, 0, 2, mode="unified",
          index=1, attempts=1, migrations=0, generated_tokens=4,
          phase="full", **t2),
        x("queue_wait", "request", 20_000, 1_000, 1, 2, phase="full",
          **t2),
        x("prefill", "request", 21_000, 2_000, 1, 2, phase="full", **t2),
        x("decode", "request", 23_000, 3_000, 1, 2, phase="full", **t2),
    ]
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def self_test() -> int:
    rows = decompose(canned_fixture())
    errors: List[str] = []
    if len(rows) != 2:
        errors.append(f"expected 2 decomposed requests, got {len(rows)}")
    for r in rows:
        total = sum(r[t] for t in TERMS)
        if abs(total - r["e2e_ms"]) > 1e-9:
            errors.append(f"trace {r['trace']}: terms sum {total} != "
                          f"e2e {r['e2e_ms']}")
    by = {r["trace"]: r for r in rows}
    dis, uni = by.get(1), by.get(2)
    if dis:
        expect = {"queue_wait_ms": 1.0, "prefill_ms": 3.0,
                  "handoff_ms": 1.0, "decode_wait_ms": 1.0,
                  "decode_ms": 4.0}
        for k, v in expect.items():
            if abs(dis[k] - v) > 1e-9:
                errors.append(f"disagg {k}: got {dis[k]}, want {v}")
    if uni:
        if uni["handoff_ms"] != 0.0 or uni["decode_wait_ms"] != 0.0:
            errors.append(f"unified handoff/decode_wait not zero: "
                          f"{uni['handoff_ms']}/{uni['decode_wait_ms']}")
        if abs(uni["prefill_ms"] - 2.0) > 1e-9:
            errors.append(f"unified prefill: got {uni['prefill_ms']}")
    budget = ttft_budget(rows, q=0.99)
    if budget["dominant"] not in TTFT_TERMS:
        errors.append(f"dominant term {budget['dominant']!r} not a "
                      f"TTFT term")
    if errors:
        print("trace_report self-test FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print("trace_report: self-test OK — exact-sum decomposition holds "
          "on the canned disagg+unified fixture")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="decompose a merged serving trace into per-request "
                    "queue_wait/prefill/handoff/decode_wait/decode terms "
                    "(exact sum) + a fleet p99 TTFT budget table")
    ap.add_argument("trace", nargs="?", help="merged trace JSON "
                    "(scripts/merge_traces.py output, or one fleet/"
                    "replica trace)")
    ap.add_argument("--quantile", type=float, default=0.99,
                    help="budget quantile (default 0.99)")
    ap.add_argument("--per-request", type=int, default=5,
                    help="show the N slowest requests' own terms "
                         "(default 5, 0 disables)")
    ap.add_argument("--json", action="store_true",
                    help="emit {rows, budget} JSON instead of the table")
    ap.add_argument("--self-test", action="store_true",
                    help="decompose the canned fixture and assert the "
                         "exact-sum + zero-handoff invariants")
    args = ap.parse_args(argv)

    if args.self_test:
        return self_test()
    if not args.trace:
        ap.error("trace path required (or --self-test)")
    try:
        with open(args.trace) as f:
            trace = json.load(f)
        if isinstance(trace, list):
            trace = {"traceEvents": trace}
        rows = decompose(trace)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"trace_report: cannot load {args.trace}: {e}",
              file=sys.stderr)
        return 2
    if not rows:
        print(f"trace_report: no completed fleet requests in "
              f"{args.trace} (no 'request' envelope spans with trace "
              f"args — fleet tracing off, or not a fleet trace?)")
        return 0
    budget = ttft_budget(rows, q=args.quantile)
    if args.json:
        print(json.dumps({"rows": rows, "budget": budget}, indent=1,
                         sort_keys=True))
    else:
        print(render(rows, budget, per_request=args.per_request))
    return 0


if __name__ == "__main__":
    sys.exit(main())
