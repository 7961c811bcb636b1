#!/usr/bin/env python
"""Lint: every metric registered anywhere in ``deepspeed_tpu/`` follows the
naming convention and is documented in ``docs/observability.md``.

The metric namespace is an interface: dashboards, alerts, and the bench
parse these names, so an undocumented or convention-breaking metric is an
API break that nothing else would catch.  Conventions (docs/observability.md
"Metric naming convention"):

- names are ``snake_case`` (``^[a-z][a-z0-9_]*$``);
- **counters** end in ``_total`` (Prometheus convention — rate()-able);
- **gauges** do NOT end in ``_total``;
- **histograms** end in a unit suffix: ``_ms``, ``_seconds`` or ``_bytes``;
- every metric carries a non-empty help string at (at least) one
  registration site;
- every metric name appears in ``docs/observability.md`` — dynamically
  suffixed families (``"xla_cost_" + key``) are checked as a prefix and
  must be documented as ``prefix*`` (e.g. ``xla_cost_*``).

Resolution is AST-level: literal first arguments, module-level string
constants (``HLO_BYTES = "..."``), and literal-prefix concatenations are
understood; anything else is flagged as a dynamic name unless the line
carries a ``# metric-name-ok`` comment with the reviewed reason nearby.

Grep-level by design, like check_no_sync.py: it cannot
prove the receiver is a MetricRegistry, so it checks every
``.counter(...)``/``.gauge(...)``/``.histogram(...)`` call site it sees.

Exit status: 0 clean, 1 violations (listed), 2 usage/parse errors.
Run directly or via the test suite (tests/test_serving_telemetry.py).
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
PACKAGE = os.path.join(REPO, "deepspeed_tpu")
DOC = os.path.join(REPO, "docs", "observability.md")

KINDS = ("counter", "gauge", "histogram")
NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
HIST_SUFFIXES = ("_ms", "_seconds", "_bytes")
ALLOW = re.compile(r"#\s*metric-name-ok")

# registry-internal helpers that LOOK like registration calls but aren't
SKIP_FILES = set()


class Site:
    def __init__(self, path: str, lineno: int, kind: str,
                 name: Optional[str], is_prefix: bool, has_help: bool,
                 line: str):
        self.path = path
        self.lineno = lineno
        self.kind = kind
        self.name = name                   # resolved name or prefix
        self.is_prefix = is_prefix         # True -> name is a glob prefix
        self.has_help = has_help
        self.line = line

    @property
    def where(self) -> str:
        return f"{os.path.relpath(self.path, REPO)}:{self.lineno}"


def _module_constants(tree: ast.Module) -> Dict[str, str]:
    """Module-level ``NAME = "literal"`` assignments."""
    out: Dict[str, str] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = node.value.value
    return out


def _resolve_name(arg, consts: Dict[str, str]
                  ) -> Tuple[Optional[str], bool]:
    """(name, is_prefix) — is_prefix True when only a literal prefix of a
    dynamically composed name is known; (None, False) when unresolvable."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value, False
    if isinstance(arg, ast.Name) and arg.id in consts:
        return consts[arg.id], False
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
        left, lp = _resolve_name(arg.left, consts)
        if left is not None and not lp:
            return left, True
    if isinstance(arg, ast.JoinedStr) and arg.values:
        first = arg.values[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            return first.value, True
    return None, False


def collect_sites(root: str = PACKAGE) -> Tuple[List[Site], List[str]]:
    sites: List[Site] = []
    errors: List[str] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                source = f.read()
            try:
                tree = ast.parse(source)
            except SyntaxError as e:
                errors.append(f"cannot parse {path}: {e}")
                continue
            lines = source.splitlines()
            consts = _module_constants(tree)
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in KINDS and node.args):
                    continue
                name, is_prefix = _resolve_name(node.args[0], consts)
                has_help = any(
                    isinstance(a, ast.Constant) and isinstance(a.value, str)
                    and a.value.strip()
                    for a in list(node.args[1:2])
                    + [kw.value for kw in node.keywords
                       if kw.arg == "help"])
                sites.append(Site(path, node.lineno, node.func.attr, name,
                                  is_prefix, has_help,
                                  lines[node.lineno - 1].strip()))
    return sites, errors


def check(sites: List[Site], doc_text: str) -> List[str]:
    violations: List[str] = []
    by_name: Dict[Tuple[str, str, bool], List[Site]] = {}
    for s in sites:
        if s.name is None:
            if not ALLOW.search(s.line):
                violations.append(
                    f"{s.where}: dynamic metric name not resolvable to a "
                    f"literal/constant/prefix — use a literal or annotate "
                    f"'# metric-name-ok': {s.line}")
            continue
        by_name.setdefault((s.name, s.kind, s.is_prefix), []).append(s)
    for (name, kind, is_prefix), group in sorted(by_name.items()):
        where = group[0].where
        check_part = name.rstrip("_") if is_prefix else name
        if not NAME_RE.match(check_part):
            violations.append(f"{where}: metric {name!r} is not snake_case")
        if not is_prefix:
            if kind == "counter" and not name.endswith("_total"):
                violations.append(
                    f"{where}: counter {name!r} must end in '_total'")
            if kind == "gauge" and name.endswith("_total"):
                violations.append(
                    f"{where}: gauge {name!r} must not end in '_total' "
                    f"(that suffix promises counter semantics)")
            if (kind == "histogram"
                    and not name.endswith(HIST_SUFFIXES)):
                violations.append(
                    f"{where}: histogram {name!r} must end in a unit "
                    f"suffix {HIST_SUFFIXES}")
        if not any(s.has_help for s in group):
            violations.append(
                f"{where}: metric {name!r} has no help string at any "
                f"registration site")
        doc_key = name + "*" if is_prefix else name
        if doc_key not in doc_text:
            violations.append(
                f"{where}: metric {doc_key!r} is not documented in "
                f"docs/observability.md")
    return violations


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="lint metric naming + documentation coverage for every "
                    "registry.counter/gauge/histogram call in deepspeed_tpu/")
    ap.add_argument("--list", action="store_true",
                    help="print the resolved metric inventory and exit")
    args = ap.parse_args(argv)
    sites, errors = collect_sites()
    if errors:
        for e in errors:
            print(f"check_metrics: {e}", file=sys.stderr)
        return 2
    if args.list:
        seen = {}
        for s in sites:
            if s.name:
                key = s.name + ("*" if s.is_prefix else "")
                seen.setdefault(key, s.kind)
        for name in sorted(seen):
            print(f"{seen[name]:<10}{name}")
        return 0
    try:
        with open(DOC) as f:
            doc_text = f.read()
    except OSError as e:
        print(f"check_metrics: cannot read {DOC}: {e}", file=sys.stderr)
        return 2
    violations = check(sites, doc_text)
    if violations:
        print("check_metrics: metric convention violations (name them per "
              "docs/observability.md 'Metric naming convention' and "
              "document every metric there):", file=sys.stderr)
        for v in violations:
            print(f"  {v}", file=sys.stderr)
        return 1
    names = {s.name for s in sites if s.name}
    print(f"check_metrics: OK — {len(names)} metric names across "
          f"{len(sites)} registration sites follow the convention and are "
          f"documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
