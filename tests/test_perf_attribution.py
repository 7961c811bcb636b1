"""Per-link byte split, trace merging, snapshot provenance stamps, and the
perf_report CLI.

The per-link split is checked for EXACT equality with the wire-byte
counters on 1-D and 2-D meshes (single-host and a simulated 2-host
placement).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
SCRIPTS = os.path.join(REPO, "scripts")

from deepspeed_tpu.telemetry.registry import (COLLECTIVE_BYTES,  # noqa: E402
                                              COLLECTIVE_CALLS,
                                              MetricRegistry,
                                              default_registry)


def _scripts_import(name):
    sys.path.insert(0, SCRIPTS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# ========================================================= per-link split

@pytest.fixture()
def link_cleanup():
    from deepspeed_tpu.comm import collectives as cc
    default_registry.reset()
    yield
    cc.set_link_process_fn(None)
    default_registry.reset()


def _run_collectives(mesh, axis, shape=(8, 64)):
    from deepspeed_tpu.comm import collectives as cc
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(x):
        r = cc.all_reduce(x, axis)
        g = cc.all_gather(x, axis)
        s = cc.reduce_scatter(g, axis)
        return r + s

    x = jnp.ones(shape, jnp.float32)
    spec = P(("dp", "fsdp"))
    with mesh:
        out = jax.jit(shard_map(body, mesh=mesh, in_specs=spec,
                                out_specs=spec, check_vma=False))(x)
    jax.device_get(out)


def _assert_split_sums_exactly(kinds, axis):
    bc = default_registry.counter(COLLECTIVE_BYTES)
    for kind in kinds:
        total = bc.value(kind=kind, axis=axis)
        ici = bc.value(kind=kind, axis=axis, link="ici")
        dcn = bc.value(kind=kind, axis=axis, link="dcn")
        assert ici + dcn == total, (kind, axis, ici, dcn, total)
    return bc


class TestPerLinkSplit:
    KINDS = ("all_reduce", "all_gather", "reduce_scatter")

    def test_single_host_1d_mesh_all_ici(self, devices, link_cleanup):
        from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(dp=4, fsdp=1))
        _run_collectives(mesh, "dp")
        bc = _assert_split_sums_exactly(self.KINDS, "dp")
        for kind in self.KINDS:
            assert bc.value(kind=kind, axis="dp") > 0
            assert bc.value(kind=kind, axis="dp", link="dcn") == 0
            assert bc.value(kind=kind, axis="dp", link="ici") == \
                bc.value(kind=kind, axis="dp")

    def test_simulated_two_host_2d_mesh(self, devices, link_cleanup):
        """dp=2 × fsdp=4 with hosts = device.id // 4: every dp hop crosses
        hosts (all-DCN), every fsdp ring stays inside one (all-ICI) —
        and both splits sum exactly to the legacy totals."""
        from deepspeed_tpu.comm import collectives as cc
        from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh
        cc.set_link_process_fn(lambda d: d.id // 4)
        mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
        _run_collectives(mesh, "dp")
        _run_collectives(mesh, "fsdp", shape=(16, 32))
        bc = _assert_split_sums_exactly(self.KINDS, "dp")
        _assert_split_sums_exactly(self.KINDS, "fsdp")
        for kind in self.KINDS:
            assert bc.value(kind=kind, axis="dp") > 0
            assert bc.value(kind=kind, axis="dp", link="ici") == 0
            assert bc.value(kind=kind, axis="fsdp") > 0
            assert bc.value(kind=kind, axis="fsdp", link="dcn") == 0

    def test_simulated_half_crossing_ring(self, devices, link_cleanup):
        """dp=4 × fsdp=2, hosts = id // 4: each dp ring runs 0,0,1,1 —
        exactly half its hops cross, so dcn == total/2 (exact: the byte
        counts are even)."""
        from deepspeed_tpu.comm import collectives as cc
        from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh
        cc.set_link_process_fn(lambda d: d.id // 4)
        mesh = build_mesh(MeshSpec(dp=4, fsdp=2))
        assert cc.axis_dcn_fraction("dp") == 0.0  # outside the mesh ctx
        with mesh:
            assert cc.axis_dcn_fraction("dp") == pytest.approx(0.5)
            assert cc.axis_dcn_fraction("fsdp") == 0.0
        _run_collectives(mesh, "dp")
        bc = _assert_split_sums_exactly(self.KINDS, "dp")
        for kind in self.KINDS:
            total = bc.value(kind=kind, axis="dp")
            assert total > 0
            assert bc.value(kind=kind, axis="dp", link="dcn") == total / 2

    def test_ring_collective_matmul_books_per_link(self, devices,
                                                   link_cleanup):
        """ops/collective_matmul's ring logging site threads the same
        dcn split as the wrapper _log (review finding: it previously
        booked all-ICI unconditionally)."""
        from deepspeed_tpu.comm import collectives as cc
        from deepspeed_tpu.ops import collective_matmul as cm
        from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh
        cc.set_link_process_fn(lambda d: d.id // 4)
        mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
        with mesh:
            cm._log_ring("ag_matmul_ring_ppermute", 100, "dp")
        bc = _assert_split_sums_exactly(("ag_matmul_ring_ppermute",),
                                        "dp")
        assert bc.value(kind="ag_matmul_ring_ppermute", axis="dp",
                        link="dcn") == 100          # every dp hop crosses

    def test_unknown_axis_and_no_mesh_default_ici(self, link_cleanup):
        from deepspeed_tpu.comm import collectives as cc
        assert cc.axis_dcn_fraction("nope") == 0.0
        from deepspeed_tpu.telemetry.registry import record_collective
        record_collective("all_gather", 100, "dp")    # legacy signature
        bc = _assert_split_sums_exactly(("all_gather",), "dp")
        assert bc.value(kind="all_gather", axis="dp", link="ici") == 100
        # calls counter untouched by the split
        assert default_registry.counter(COLLECTIVE_CALLS).value(
            kind="all_gather", axis="dp") == 1


# ===================================================== snapshot stamps

class TestSnapshotStamps:
    def test_seq_and_clocks_in_json_and_prom(self):
        from deepspeed_tpu.telemetry.exporter import SnapshotExporter
        reg = MetricRegistry()
        reg.counter("x_total", "h").inc(1)
        exp = SnapshotExporter(reg)
        s1 = exp.snapshot()
        s2 = exp.snapshot()
        assert s1["snapshot_seq"] == 1 and s2["snapshot_seq"] == 2
        assert s2["monotonic_time"] >= s1["monotonic_time"]
        assert "unix_time" in s1
        # old schema preserved
        assert s1["schema"] == "deepspeed_tpu.telemetry.v1"
        assert "counters" in s1
        text = exp.prometheus_text(s2)
        assert "# TYPE deepspeed_tpu_snapshot_seq gauge" in text
        assert "deepspeed_tpu_snapshot_seq 2" in text
        assert "deepspeed_tpu_snapshot_unix_time " in text
        assert "deepspeed_tpu_snapshot_monotonic_seconds " in text
        # conformance: HELP precedes TYPE for the stamps too
        i_help = text.index("# HELP deepspeed_tpu_snapshot_seq")
        i_type = text.index("# TYPE deepspeed_tpu_snapshot_seq")
        assert i_help < i_type


# ======================================================== merge_traces

class TestMergeTraces:
    def _trace(self, pid, epoch, events, names=None):
        from deepspeed_tpu.telemetry.tracer import (SpanTracer,
                                                    TraceEmitter)
        tr = SpanTracer(enabled=True, pid=pid)
        tr.epoch_unix_time = epoch
        for name, ts, dur, tid in events:
            tr.record(name, ts, dur, tid=tid)
        for tid, label in (names or {}).items():
            tr.set_thread_name(tid, label)
        return TraceEmitter().to_dict(tr)

    def test_clock_alignment_and_pid_remap(self, tmp_path):
        mt = _scripts_import("merge_traces")
        t0 = self._trace(0, 1000.0, [("dispatch", 10.0, 5.0, 0)])
        t1 = self._trace(0, 1002.5, [("dispatch", 10.0, 5.0, 0),
                                     ("decode", 20.0, 2.0, 7)],
                         names={7: "req 7"})
        p0, p1 = tmp_path / "r0.json", tmp_path / "r1.json"
        p0.write_text(json.dumps(t0))
        p1.write_text(json.dumps(t1))
        out = tmp_path / "merged.json"
        merged = mt.merge_files(str(out), [str(p0), str(p1)])
        evs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        by_pid = {e["pid"]: [] for e in evs}
        for e in evs:
            by_pid[e["pid"]].append(e)
        assert set(by_pid) == {0, 1}
        # file 1's events shifted by the 2.5 s epoch difference
        assert by_pid[0][0]["ts"] == 10.0
        assert by_pid[1][0]["ts"] == pytest.approx(10.0 + 2.5e6)
        # thread_name metadata preserved with the remapped pid
        tn = [e for e in merged["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "thread_name"]
        assert any(e["pid"] == 1 and e["tid"] == 7
                   and e["args"]["name"] == "req 7" for e in tn)
        # process_name per input file
        pn = [e["args"]["name"] for e in merged["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "process_name"]
        assert len(pn) == 2
        assert merged["otherData"]["unaligned"] == []
        json.load(open(out))                       # file written + valid

    def test_missing_epoch_merges_unshifted_with_disclosure(self,
                                                            tmp_path):
        mt = _scripts_import("merge_traces")
        t0 = self._trace(0, 1000.0, [("a", 1.0, 1.0, 0)])
        t1 = self._trace(0, 1000.0, [("b", 2.0, 1.0, 0)])
        del t1["otherData"]["epoch_unix_time"]
        p0, p1 = tmp_path / "a.json", tmp_path / "b.json"
        p0.write_text(json.dumps(t0))
        p1.write_text(json.dumps(t1))
        merged = mt.merge_files(str(tmp_path / "m.json"),
                                [str(p0), str(p1)])
        assert merged["otherData"]["unaligned"] == ["b"]
        b_ev = [e for e in merged["traceEvents"]
                if e.get("name") == "b"][0]
        assert b_ev["ts"] == 2.0

    def test_flow_id_remap_stitches_within_scope_only(self):
        """Flow events are remapped per ``(flow_id_scope, id)``: files
        written by the SAME process keep their stitched request trees,
        while a foreign scope (or a legacy file with no stamp) using the
        numerically identical id lands on a disjoint merged id — two
        unrelated requests can never collide into one accidental flow."""
        from deepspeed_tpu.telemetry.tracer import (SpanTracer,
                                                    TraceEmitter)
        mt = _scripts_import("merge_traces")

        def flow_trace(ph, fid, scope=...):
            tr = SpanTracer(enabled=True, pid=0)
            tr.epoch_unix_time = 1000.0
            tr.record("dispatch", 10.0, 5.0)
            tr.flow(ph, fid, 12.0)
            d = TraceEmitter().to_dict(tr)
            if scope is None:
                del d["otherData"]["flow_id_scope"]
            elif scope is not ...:
                d["otherData"]["flow_id_scope"] = scope
            return d

        merged = mt.merge_traces(
            [flow_trace("s", 7),                     # router start
             flow_trace("t", 7),                     # replica, same proc
             flow_trace("s", 7, scope="other-host"),
             flow_trace("s", 7, scope=None)],        # pre-stamp legacy
            ["r0", "r1", "alien", "legacy"])
        flows = {e["pid"]: e for e in merged["traceEvents"]
                 if e.get("ph") in ("s", "t", "f")}
        assert len(flows) == 4
        # same scope + same id -> SAME merged id: the tree survives
        assert flows[0]["id"] == flows[1]["id"]
        # foreign/legacy files get ids disjoint from everyone else's
        assert len({e["id"] for e in flows.values()}) == 3

    def test_cli(self, tmp_path):
        t = self._trace(0, 5.0, [("a", 1.0, 1.0, 0)])
        p = tmp_path / "t.json"
        p.write_text(json.dumps(t))
        out = tmp_path / "out.json"
        r = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "merge_traces.py"),
             "-o", str(out), str(p)],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert out.exists()


# ========================================================= perf_report

class TestPerfReport:
    def test_snapshot_mode_sections(self, tmp_path):
        snap = {
            "counters": {"collective_bytes_total": {"help": "", "samples": [
                {"labels": {"kind": "all_gather", "axis": "fsdp"},
                 "value": 300.0},
                {"labels": {"kind": "all_gather", "axis": "fsdp",
                            "link": "ici"}, "value": 200.0},
                {"labels": {"kind": "all_gather", "axis": "fsdp",
                            "link": "dcn"}, "value": 100.0}]}},
            "spans": {"batch_input": {"count": 10, "total_ms": 5.0,
                                      "max_ms": 1.0, "mean_ms": 0.5},
                      "dispatch": {"count": 10, "total_ms": 90.0,
                                   "max_ms": 10.0, "mean_ms": 9.0}},
            "env": {"resolved": {"num_chunks": 2}},
        }
        p = tmp_path / "snapshot.json"
        p.write_text(json.dumps(snap))
        r = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "perf_report.py"),
             str(p)],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        for needle in ("per-link", "all_gather", "host phase spans",
                       "batch_input", "scheduler regime"):
            assert needle in r.stdout, (needle, r.stdout)
        # the link table renders the exact split
        row = [ln for ln in r.stdout.splitlines()
               if ln.strip().startswith("all_gather")][0]
        assert "300" in row and "200" in row and "100" in row
        # the heaviest phase leads the span table
        spans = r.stdout[r.stdout.index("host phase spans"):]
        assert spans.index("dispatch") < spans.index("batch_input")

    def test_postmortem_bundle_mode(self, tmp_path):
        """perf_report runs on a real postmortem bundle layout: spans
        from meta.json, metrics parsed back out of snapshot.prom."""
        from deepspeed_tpu.telemetry.exporter import SnapshotExporter
        bundle = tmp_path / "postmortem" / "20260101-000000-step5-manual"
        bundle.mkdir(parents=True)
        reg = MetricRegistry()
        reg.gauge("xla_cost_flops", "h").set(1e9, fn="train_batch")
        reg.counter("collective_bytes_total", "h").inc(
            64, kind="all_reduce", axis="dp", link="ici")
        SnapshotExporter(reg).write_prometheus(
            str(bundle / "snapshot.prom"))
        (bundle / "meta.json").write_text(json.dumps({
            "spans": {"dispatch": {"count": 5, "total_ms": 40.0,
                                   "max_ms": 10.0, "mean_ms": 8.0}}}))
        with open(bundle / "records.jsonl", "w") as f:
            for step in (4, 5):
                f.write(json.dumps({
                    "step": step,
                    "spans_ms": {"dispatch": 8.0,
                                 "device_complete": 2.0}}) + "\n")
        r = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "perf_report.py"),
             str(tmp_path / "postmortem")],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "all_reduce" in r.stdout                # per-link table
        assert "dispatch" in r.stdout                  # spans section

    def test_prometheus_parser_roundtrip(self):
        pr = _scripts_import("perf_report")
        from deepspeed_tpu.telemetry.exporter import SnapshotExporter
        reg = MetricRegistry()
        reg.counter("c_total", "help me").inc(7, kind="a b\"c")
        reg.gauge("g", "h").set(1.5)
        text = SnapshotExporter(reg).prometheus_text()
        snap = pr.parse_prometheus(text)
        assert snap["counters"]["c_total"]["samples"][0]["value"] == 7.0
        assert snap["counters"]["c_total"]["samples"][0]["labels"][
            "kind"] == 'a b"c'
        assert snap["gauges"]["g"]["samples"][0]["value"] == 1.5
