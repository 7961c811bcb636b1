#!/usr/bin/env python
"""Bench regression sentinel CLI — gate a bench record against the ledger.

Every recorded round so far was compared to its predecessors BY HAND (or
not at all — the r05 wq/spec "regressions" cost a whole measurement cycle
of manual diagnosis).  This gate makes the trajectory machine-checked:

    python scripts/check_bench.py                       # BENCH_r05-style
                                                        # newest record vs
                                                        # BENCH_BASELINE.json
    python scripts/check_bench.py --current bench_records.jsonl
    python scripts/check_bench.py --band 0.05
    python scripts/check_bench.py --self-test           # fixture lint
    python scripts/check_bench.py --update-baseline     # reseed ledger
                                                        # from --current

``--current`` accepts any of: the stdout metric line, a ``BENCH_r*.json``
wrapper, a flat dict, or the per-leg JSONL records bench.py /
bench_serving.py append (``deepspeed_tpu.telemetry.regression`` sniffs).
Default current: the newest ``BENCH_r*.json`` in the repo root.

``--self-test`` is the canned-fixture lint (wired into
``scripts/lint_all.py``): synthesizes a 10%-slowdown record and an
in-band-noise record from the ledger and asserts the sentinel trips on
the first, stays quiet on the second, and runs green on the ledger's own
seed values.

Exit status: 0 clean, 1 regression (or self-test failure), 2 usage/load
errors.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys
from typing import List, Optional

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "BENCH_BASELINE.json")


def newest_bench_record() -> Optional[str]:
    recs = sorted(glob.glob(os.path.join(REPO, "BENCH_r[0-9]*.json")))
    return recs[-1] if recs else None


def self_test(baseline_path: str) -> int:
    from deepspeed_tpu.telemetry import regression as reg
    ledger = reg.load_baseline(baseline_path)
    failures: List[str] = []

    seed = {name: entry["value"]
            for name, entry in ledger["metrics"].items()}
    if reg.compare(seed, ledger)["failed"]:
        failures.append("seed values vs their own ledger flagged a "
                        "regression (direction/band logic broken)")

    bad = reg.make_fixture(ledger, "regression")
    res_bad = reg.compare(bad, ledger)
    # zero-valued baselines can't shift by a ratio (a 10% slowdown of 0 is
    # 0, so a reseeded ledger's zero counters never trip), and a metric
    # carrying a per-entry noise band >= the fixture's 10% shift (e.g. the
    # deliberately wide rollback_recovery_ms timing) legitimately absorbs
    # it — only the rest are expected to trip
    default_band = float(ledger.get("default_noise_band", 0.08))
    expected = sum(1 for e in ledger["metrics"].values()
                   if float(e["value"]) != 0.0
                   and float(e.get("band", default_band)) < 0.10)
    if not res_bad["failed"]:
        failures.append("canned 10% slowdown fixture did NOT trip the "
                        "sentinel")
    elif len(res_bad["regressions"]) != expected:
        failures.append(
            f"slowdown fixture tripped only "
            f"{len(res_bad['regressions'])}/{expected} nonzero-baseline "
            f"metrics (direction map drifted)")

    noise = reg.make_fixture(ledger, "noise")
    if reg.compare(noise, ledger)["failed"]:
        failures.append("canned in-band noise fixture tripped the "
                        "sentinel (band logic broken)")

    if failures:
        for f in failures:
            print(f"check_bench --self-test: FAIL — {f}", file=sys.stderr)
        return 1
    print(f"check_bench --self-test: OK — sentinel trips on the canned "
          f"10% slowdown ({len(res_bad['regressions'])} metrics), stays "
          f"quiet in the in-band noise fixture, green on the seed record")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="diff a bench record against the committed baseline "
                    "ledger; exit nonzero on per-metric deltas beyond the "
                    "noise band in the bad direction")
    ap.add_argument("--current",
                    help="bench record to check: metric-line JSON, "
                         "BENCH_r*.json wrapper, flat dict, or per-leg "
                         "JSONL (default: newest BENCH_r*.json)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline ledger (default: BENCH_BASELINE.json)")
    ap.add_argument("--band", type=float, default=None,
                    help="override the ledger's default noise band "
                         "(fraction, e.g. 0.05)")
    ap.add_argument("--strict-missing", action="store_true",
                    help="also fail when ledger metrics are missing from "
                         "the current record (a dropped leg)")
    ap.add_argument("--self-test", action="store_true",
                    help="run the canned-fixture lint instead of a "
                         "comparison")
    ap.add_argument("--update-baseline", action="store_true",
                    help="reseed the ledger from --current (accepting the "
                         "current numbers as the new trajectory anchor)")
    args = ap.parse_args(argv)

    from deepspeed_tpu.telemetry import regression as reg

    if args.self_test:
        try:
            return self_test(args.baseline)
        except Exception as e:  # noqa: BLE001
            print(f"check_bench --self-test: cannot run: {e}",
                  file=sys.stderr)
            return 2

    current_path = args.current or newest_bench_record()
    if current_path is None:
        print("check_bench: no --current given and no BENCH_r*.json found",
              file=sys.stderr)
        return 2
    try:
        current = reg.load_bench_file(current_path)
    except (OSError, ValueError) as e:
        print(f"check_bench: cannot load {current_path}: {e}",
              file=sys.stderr)
        return 2
    if not current:
        print(f"check_bench: no numeric metrics found in {current_path}",
              file=sys.stderr)
        return 2

    if args.update_baseline:
        ledger = reg.seed_baseline(current, source=os.path.basename(
            current_path))
        reg.save_baseline(ledger, args.baseline)
        print(f"check_bench: reseeded {args.baseline} from "
              f"{current_path} ({len(ledger['metrics'])} metrics)")
        return 0

    try:
        ledger = reg.load_baseline(args.baseline)
    except (OSError, ValueError) as e:
        print(f"check_bench: cannot load baseline {args.baseline}: {e}",
              file=sys.stderr)
        return 2

    result = reg.compare(current, ledger, band=args.band,
                         strict_missing=args.strict_missing)
    print(reg.render(result, baseline_name=os.path.basename(
        args.baseline)))
    return 1 if result["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
