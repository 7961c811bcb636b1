#!/usr/bin/env python
"""Run every repo lint in ONE process with a unified summary.

Each lint guards one interface:

- ``check_no_sync``  — no undisclosed host↔device syncs on dispatch paths
- ``check_metrics``  — metric naming convention + docs coverage
- ``trace_report --self-test`` — the critical-path decomposition holds
  its exact-sum + zero-handoff-in-unified invariants on the canned
  disagg+unified trace fixture

This driver imports each lint's ``main()`` and runs them back to back,
printing one PASS/FAIL table.  The test suite shells THIS script once
(tests/test_lint_all.py); the per-lint violation/unit tests stay where
they were.

    python scripts/lint_all.py            # all three
    python scripts/lint_all.py --only check_metrics trace_report

Exit status: 0 all pass, 1 any lint failed, 2 a lint crashed / usage.
"""

from __future__ import annotations

import argparse
import io
import os
import sys
import time
from contextlib import redirect_stderr, redirect_stdout
from typing import Callable, List, Optional, Tuple

# trace_report imports the package, and with it jax: a lint needs no chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.join(HERE, os.pardir)
for p in (HERE, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)


def _lints() -> List[Tuple[str, Callable[[], int]]]:
    import check_metrics
    import check_no_sync
    import trace_report
    return [
        ("check_no_sync", lambda: check_no_sync.main([])),
        ("check_metrics", lambda: check_metrics.main([])),
        ("trace_report", lambda: trace_report.main(["--self-test"])),
    ]


def run_all(only: Optional[List[str]] = None,
            verbose: bool = False) -> int:
    results: List[Tuple[str, str, float, str]] = []
    worst = 0
    for name, fn in _lints():
        if only and name not in only:
            continue
        buf = io.StringIO()
        t0 = time.perf_counter()
        try:
            with redirect_stdout(buf), redirect_stderr(buf):
                rc = int(fn())
        except SystemExit as e:  # argparse error inside a lint
            rc = int(e.code or 0)
        except Exception as e:  # noqa: BLE001 — a crashed lint is rc 2
            buf.write(f"{type(e).__name__}: {e}\n")
            rc = 2
        dt = time.perf_counter() - t0
        status = "PASS" if rc == 0 else ("FAIL" if rc == 1 else "ERROR")
        results.append((name, status, dt, buf.getvalue()))
        worst = max(worst, rc)
    print("lint_all: unified lint summary")
    for name, status, dt, _ in results:
        print(f"  {name:<16}{status:<7}{dt:>7.1f}s")
    for name, status, _, output in results:
        if status != "PASS" or verbose:
            print(f"\n---- {name} ({status}) ----")
            print(output.rstrip() or "(no output)")
    if worst == 0:
        print(f"lint_all: OK — {len(results)} lints clean")
    return 0 if worst == 0 else (1 if worst == 1 else 2)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="run check_no_sync, check_metrics and the "
                    "trace_report fixture lint in one process")
    ap.add_argument("--only", nargs="+", metavar="LINT",
                    help="subset of lints to run (by name)")
    ap.add_argument("--verbose", action="store_true",
                    help="print every lint's output, not just failures")
    args = ap.parse_args(argv)
    if args.only:
        known = {name for name, _ in _lints()}
        unknown = set(args.only) - known
        if unknown:
            print(f"lint_all: unknown lints {sorted(unknown)} "
                  f"(known: {sorted(known)})", file=sys.stderr)
            return 2
    return run_all(only=args.only, verbose=args.verbose)


if __name__ == "__main__":
    sys.exit(main())
