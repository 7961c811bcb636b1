"""Every cell's runner end to end on the CPU at the tiny preset, through the
benchmark's own ``--rehearse`` switch; and the refusal to run without it."""

import json
import os
import subprocess
import sys

import pytest
from run import metric_applies

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = [sys.executable, os.path.join(ROOT, "benchmark", "run.py")]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
ENV.pop("XLA_FLAGS", None)            # the switch sets its own device count

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def readings(cell):
    """The per-layer entries a traced run of ``cell`` reads, chosen as
    ``run.py`` chooses them: the manifest alone says which cells read a
    metric."""
    return [p for p in MANIFEST["per_layer"] if metric_applies(p, cell)]


# ---- what a cell read when PR 59 started, under today's names ------------
with open(os.path.join(ROOT, "benchmark", "tests", "data",
                       "manifest_lists_pr58.json")) as _f:
    AT_PR58 = json.load(_f)
# PR 59's fold: a family's copy -> the one entry that reads it since (the
# need asked layer by layer); ``expert_gemm_roofline`` gave way to the
# reading PR 55 brought to correct it
SINCE_PR59 = {
    **{f"serve_step_mfu.{x}": "serve_step_mfu"
       for x in ("scan", "conv", "swa", "sala")},
    **{f"{k}_roofline.{x}": f"{k}_roofline.by_layer"
       for k in ("paged_decode", "ragged_prefill")
       for x in ("mixedlen", "conv", "reasoning")},
    "expert_gemm_roofline": "expert_gemm_roofline.joined"}


def name_since_pr59(old):
    """The entry that reads what ``old`` read at PR 58."""
    return SINCE_PR59.get(old, old)


def no_longer_read(cell, names=None):
    """Which of ``names`` (the cell's readings at PR 58 by default) today's
    manifest does not give the cell, by ``run.metric_applies`` under
    today's names: a cell test holds this empty, and holds no count and no
    place in the manifest, which the next cell's entries move."""
    if names is None:
        names = AT_PR58["readings_at_pr58"][cell]
    now = {p["name"] for p in readings(cell)}
    return sorted(n for n in names if name_since_pr59(n) not in now)


def assert_reads_what_it_was_accepted_with(cell, came_with=()):
    """A cell test's hold on its cell's readings: what the cell read when
    PR 59 started (``data/manifest_lists_pr58.json``), the entries it came
    with among them, it reads today under today's names."""
    assert set(came_with) <= set(AT_PR58["readings_at_pr58"][cell])
    assert not no_longer_read(cell)


def run_cell(name, trace, root=ROOT, extra=()):
    cmd = [RUN[0], os.path.join(root, "benchmark", "run.py"),
           "--workload", name, "--seed", "2147483659", "--seconds", "2",
           "--trace", str(trace), *extra]
    return subprocess.run(cmd, cwd=root, env=ENV, capture_output=True,
                          text=True, timeout=600)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_rehearses_with_the_contracts_last_line(cell):
    out = run_cell(cell, 0, extra=["--rehearse"])
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal", "compared"}
    assert list(last)[-1] == "compared" and last["compared"]
    for c in last["compared"].values():      # each number beside its limit
        assert c["value"] <= c["limit"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"       # says what it ran on
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    want = {e["name"] for e in MANIFEST["end_to_end"]
            if cell in e.get("workloads", [cell])}
    assert set(last["metrics"]) == want
    for m in last["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)


def test_traced_rehearsal_reports_per_layer_metrics():
    out = run_cell("serve-mistral-batch", 1, extra=["--rehearse"])
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    names = {p["name"] for p in readings("serve-mistral-batch")}
    assert set(last["metrics"]) <= names     # what found nothing is left out
    assert {"compile_cache_misses", "compiles_in_window",
            "sched_tokens_per_dispatch"} <= set(last["metrics"])
    assert last["metrics"]["compiles_in_window"]["value"] == 0


def test_without_the_switch_and_without_a_tpu_it_fails():
    out = run_cell("train-gpt2m-1chip", 0)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip().endswith("}") or \
        "correct" not in out.stdout.strip().splitlines()[-1]
