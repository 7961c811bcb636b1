"""Step-time attribution layer (ISSUE 12): roofline model, MFU budget,
per-link byte split, bench regression sentinel, trace merging, snapshot
provenance stamps, and the perf_report CLI.

Hand-computed ground truth where the ISSUE asks for it: the tiny-matmul
roofline flops/bytes are checked against 2·M·N·K and the exact operand +
result payloads; the per-link split is checked for EXACT equality with
the legacy wire-byte counters on 1-D and 2-D meshes (single-host and a
simulated 2-host placement); the sentinel trips on the canned 10%
slowdown and stays quiet inside the noise band.
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

from deepspeed_tpu.telemetry import profiler, regression, roofline  # noqa: E402
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


# ============================================================== roofline

class TestRooflineWalk:
    def test_tiny_matmul_hand_computed(self):
        """flops = 2·M·N·K and bytes = (M·K + K·N + M·N)·itemsize, exactly
        — the ISSUE's hand-computed ground truth."""
        M, K, N = 4, 8, 16

        def f(a, b):
            return a @ b

        txt = jax.jit(f).lower(jnp.ones((M, K)),
                               jnp.ones((K, N))).compile().as_text()
        classes = roofline.walk_hlo_classes(txt)
        assert classes["matmul"]["flops"] == 2 * M * N * K
        assert classes["matmul"]["bytes"] == (M * K + K * N + M * N) * 4
        assert classes["matmul"]["wire_bytes"] == 0

    def test_fusion_interior_not_byte_counted(self):
        """Dots keep their flops wherever they live; HBM bytes charge only
        fusion BOUNDARIES (operands + result of the fusion call), never
        the fused interior."""
        def g(a, b, c):
            h = jnp.tanh(a @ b + 1.0)
            return (h * c) @ b.T

        txt = jax.jit(g).lower(jnp.ones((32, 64)), jnp.ones((64, 128)),
                               jnp.ones((32, 128))).compile().as_text()
        classes = roofline.walk_hlo_classes(txt)
        assert classes["matmul"]["flops"] == \
            2 * 32 * 128 * 64 + 2 * 32 * 64 * 128
        # the elementwise class is the fusion call site: its boundary is
        # two [32,128] operands + one [32,128] result
        assert classes["elementwise"]["bytes"] == 3 * 32 * 128 * 4
        assert classes["elementwise"]["flops"] == 0

    def test_collective_class_from_demo_hlo(self):
        co = _scripts_import("check_overlap")
        txt = co.demo_hlo(num_chunks=3)
        classes = roofline.walk_hlo_classes(txt)
        coll = {k: v for k, v in classes.items()
                if k.startswith("collective:")}
        assert coll, classes.keys()
        assert sum(c["wire_bytes"] for c in coll.values()) > 0

    def test_attention_classified_by_metadata(self):
        txt = (
            "ENTRY %main (a: f32[4,8]) -> f32[4,4] {\n"
            '  %dot.1 = f32[4,4]{1,0} dot(f32[4,8]{1,0} %a, f32[8,4]{1,0}'
            ' %b), lhs_contracting_dims={1}, rhs_contracting_dims={0},'
            ' metadata={op_name="jit(f)/GPTBackbone/block_0/attn/qk"}\n'
            "}\n")
        classes = roofline.walk_hlo_classes(txt)
        assert "attention" in classes
        assert classes["attention"]["flops"] == 2 * 4 * 4 * 8

    def test_calibration_scales_to_cost_analysis(self):
        def f(a, b):
            return a @ b

        txt = jax.jit(f).lower(jnp.ones((4, 8)),
                               jnp.ones((8, 16))).compile().as_text()
        model = roofline.roofline_from_hlo(
            txt, spec=dict(roofline.PEAK_SPECS["cpu-sim"], name="cpu-sim"),
            cost_analysis={"flops": 2048.0})     # walk sees 1024
        assert model["calibration"] == pytest.approx(2.0)
        assert model["total_flops"] == pytest.approx(2048.0)
        assert model["classes"]["matmul"]["flops_uncalibrated"] == 1024.0

    def test_bound_classification_and_attainable(self):
        def f(a, b):
            return a @ b

        txt = jax.jit(f).lower(jnp.ones((64, 64)),
                               jnp.ones((64, 64))).compile().as_text()
        # absurdly fast HBM -> compute-bound; absurdly slow -> hbm-bound
        fast = roofline.roofline_from_hlo(
            txt, spec={"flops": 1e9, "hbm": 1e18, "ici": 1e18,
                       "name": "t"})
        slow = roofline.roofline_from_hlo(
            txt, spec={"flops": 1e18, "hbm": 1e3, "ici": 1e18,
                       "name": "t"})
        assert fast["classes"]["matmul"]["bound"] == "compute"
        assert slow["classes"]["matmul"]["bound"] == "hbm"
        for m in (fast, slow):
            assert m["attainable_ms"] > 0
            assert sum(m["bound_fraction"].values()) == pytest.approx(1.0)

    def test_detect_peak_spec_cpu(self):
        spec = roofline.detect_peak_spec()
        assert spec["name"] == "cpu-sim"
        assert spec["flops"] == roofline.PEAK_SPECS["cpu-sim"]["flops"]

    @pytest.mark.parametrize("kind,name", [
        ("TPU v5 lite", "v5e"), ("TPU v5p", "v5p"), ("TPU v4", "v4"),
        ("TPU v6 lite", "v6e"), ("TPU v9x", None)])
    def test_detect_peak_spec_tpu_kinds(self, kind, name):
        """A TPU kind that is not in the table raises — a roofline against
        another chip's peaks is a wrong number, never a default."""
        import types
        dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
        if name is None:
            with pytest.raises(ValueError, match="TPU v9x"):
                roofline.detect_peak_spec(dev)
        else:
            assert roofline.detect_peak_spec(dev)["name"] == name

    def test_render_smoke(self):
        model = roofline.roofline_from_hlo(
            "ENTRY %main (a: f32[2,2]) -> f32[2,2] {\n"
            "  %dot.1 = f32[2,2]{1,0} dot(f32[2,2]{1,0} %a, f32[2,2]{1,0}"
            " %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}\n"
            "}\n",
            spec=dict(roofline.PEAK_SPECS["cpu-sim"], name="cpu-sim"))
        text = roofline.render(model, "toy")
        assert "toy" in text and "bound" in text and "attainable" in text


class TestRooflineEngine:
    def test_tiny_gpt_snapshot_carries_roofline(self):
        """The engine's compiled-HLO analysis now includes the roofline:
        classes present, calibrated flops == cost_analysis flops, gauges
        set, snapshot JSON-serializable."""
        import deepspeed_tpu
        from deepspeed_tpu.models import GPTChunkedLoss, GPTConfig
        default_registry.reset()
        cfg = GPTConfig(num_layers=2, num_heads=4, head_dim=16,
                        hidden_size=64, vocab_size=512, max_seq_len=64,
                        dropout=0.0, loss_chunk=64)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=GPTChunkedLoss(cfg),
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                    "zero_optimization": {"stage": 2}, "mesh": {"dp": -1},
                    "steps_per_print": 0,
                    "telemetry": {"enabled": True, "trace_enabled": False,
                                  "snapshot_interval": 0}},
            example_batch={"input_ids": np.zeros((2, 64), np.int32)})
        B = eng.train_batch_size                 # micro × dp_world
        batch = {"input_ids": np.random.default_rng(0).integers(
            0, 512, (B, 64)).astype(np.int32)}
        eng.train_batch(batch)
        snap = eng.telemetry.export(write=False)
        exe = snap["executables"]["train_batch"]
        model = exe.get("roofline")
        assert model, "no roofline in the executable analysis"
        assert "matmul" in model["classes"]
        ca_flops = exe["cost_analysis"]["flops"]
        assert model["total_flops"] == pytest.approx(ca_flops, rel=1e-6)
        # the static walk is the right order of magnitude before
        # calibration (within 3x of XLA's own count for this loop-free
        # tiny model)
        walked = sum(c["flops_uncalibrated"]
                     for c in model["classes"].values())
        assert ca_flops / 3 < walked < ca_flops * 3
        att = default_registry.gauge("roofline_attainable_ms")
        assert att.value(fn="train_batch") > 0
        bf = default_registry.gauge("roofline_bound_fraction")
        total = sum(bf.value(fn="train_batch", resource=r)
                    for r in ("compute", "hbm", "ici"))
        assert total == pytest.approx(1.0)
        json.dumps(snap)                      # snapshot stays serializable
        default_registry.reset()


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


# ============================================================ MFU budget

def _synthetic_snapshot(flops=1e9, exposed_ratio=0.25):
    spec = dict(roofline.PEAK_SPECS["cpu-sim"], name="cpu-sim")
    classes = {
        "matmul": {"flops": flops, "bytes": 1e6, "wire_bytes": 0,
                   "ops": 3, "t_compute_ms": flops / spec["flops"] * 1e3,
                   "t_hbm_ms": 0.02, "t_ici_ms": 0.0, "bound": "compute",
                   "attainable_ms": flops / spec["flops"] * 1e3,
                   "flops_uncalibrated": flops},
        "elementwise": {"flops": 0, "bytes": 5e7, "wire_bytes": 0,
                        "ops": 9, "t_compute_ms": 0.0, "t_hbm_ms": 1.0,
                        "t_ici_ms": 0.0, "bound": "hbm",
                        "attainable_ms": 1.0, "flops_uncalibrated": 0},
    }
    return {
        "executables": {"train_batch": {
            "cost_analysis": {"flops": flops},
            "roofline": {"spec": spec, "classes": classes,
                         "attainable_ms": sum(c["attainable_ms"]
                                              for c in classes.values()),
                         "bound_fraction": {}},
        }},
        "gauges": {"collective_exposed_ratio": {"help": "", "samples": [
            {"labels": {"fn": "train_batch"}, "value": exposed_ratio}]}},
        "spans": {"batch_input": {"count": 10, "total_ms": 5.0,
                                  "max_ms": 1.0, "mean_ms": 0.5},
                  "host_to_device": {"count": 10, "total_ms": 3.0,
                                     "max_ms": 1.0, "mean_ms": 0.3},
                  "step_bookkeeping": {"count": 10, "total_ms": 2.0,
                                       "max_ms": 1.0, "mean_ms": 0.2},
                  "dispatch": {"count": 10, "total_ms": 90.0,
                               "max_ms": 10.0, "mean_ms": 9.0}},
    }


class TestStepBudget:
    def test_terms_sum_to_measured_exactly(self):
        snap = _synthetic_snapshot()
        step_ms = 50.0
        b = profiler.step_time_budget(snap, step_ms=step_ms,
                                      comm_total_ms=8.0)
        # compute = flops/peak: 1e9 / 100e9 = 10 ms; exposed = 8*0.25 = 2;
        # hbm_bound = 1.0 (elementwise attainable - 0 compute);
        # host_gap = 0.5 + 0.3 + 0.2 = 1.0
        assert b["compute_ms"] == pytest.approx(10.0)
        assert b["terms_ms"]["exposed_comm"] == pytest.approx(2.0)
        assert b["terms_ms"]["hbm_bound"] == pytest.approx(1.0)
        assert b["terms_ms"]["host_gap"] == pytest.approx(1.0)
        assert b["terms_ms"]["dispatch_floor"] == pytest.approx(36.0)
        # acceptance: terms + achieved compute sum to measured step time
        assert b["attributed_ms"] == pytest.approx(step_ms)
        assert b["mfu_achieved"] == pytest.approx(10.0 / 50.0)
        assert (b["mfu_achieved"] + sum(b["mfu_lost"].values())
                == pytest.approx(1.0))

    def test_exposed_comm_matches_ratio_product(self):
        """Acceptance: the budget's exposed-comm term IS comm_total_ms ×
        collective_exposed_ratio (the existing comm_exposed_ms column)."""
        snap = _synthetic_snapshot(exposed_ratio=0.4)
        b = profiler.step_time_budget(snap, step_ms=100.0,
                                      comm_total_ms=12.5)
        assert b["terms_ms"]["exposed_comm"] == pytest.approx(12.5 * 0.4)

    def test_overattribution_disclosed_not_clamped(self):
        snap = _synthetic_snapshot()
        b = profiler.step_time_budget(snap, step_ms=5.0,
                                      comm_total_ms=8.0)
        assert b["terms_ms"]["dispatch_floor"] == 0.0
        assert b["overattributed_ms"] > 0
        assert any("exceed" in n for n in b["notes"])

    def test_gauges_written(self):
        reg = MetricRegistry()
        profiler.step_time_budget(_synthetic_snapshot(), step_ms=50.0,
                                  comm_total_ms=8.0, registry=reg)
        assert reg.gauge("mfu_achieved").value(fn="train_batch") > 0
        g = reg.gauge("mfu_lost")
        causes = {labels["cause"] for labels, _ in g.samples()}
        assert causes == set(profiler.LOST_CAUSES)

    def test_degrades_without_signals(self):
        b = profiler.step_time_budget({}, step_ms=10.0)
        assert b["compute_ms"] == 0.0
        assert b["terms_ms"]["dispatch_floor"] == pytest.approx(10.0)
        assert b["notes"]
        assert "budget" in profiler.render(b)


# ============================================================= sentinel

class TestSentinel:
    LEDGER = {
        "schema": regression.BASELINE_SCHEMA,
        "default_noise_band": 0.08,
        "metrics": {
            "train_tokens_per_sec": {"value": 1000.0},
            "serving_ttft_p99_ms": {"value": 50.0},
            "mfu": {"value": 0.5, "band": 0.02},
            "prefetch_starvation": {"value": 0.0},
        },
    }

    def test_direction_map(self):
        assert regression.metric_direction("train_tokens_per_sec") == 1
        assert regression.metric_direction("ttft_p99_ms") == -1
        assert regression.metric_direction("step_time_s") == -1
        assert regression.metric_direction("collective_exposed_ratio") == -1
        assert regression.metric_direction("mfu") == 1
        assert regression.metric_direction("peak_device_memory_bytes") == -1

    def test_trips_on_slowdown_quiet_on_noise(self):
        bad = regression.make_fixture(self.LEDGER, "regression")
        res = regression.compare(bad, self.LEDGER)
        assert res["failed"]
        tripped = {f["metric"] for f in res["regressions"]}
        assert "train_tokens_per_sec" in tripped       # 10% drop
        assert "serving_ttft_p99_ms" in tripped        # 10% rise
        noise = regression.make_fixture(self.LEDGER, "noise")
        res_n = regression.compare(noise, self.LEDGER)
        assert not res_n["failed"], res_n["regressions"]

    def test_per_metric_band_overrides_default(self):
        cur = {"train_tokens_per_sec": 960.0,        # -4%: inside 8%
               "serving_ttft_p99_ms": 50.0,
               "mfu": 0.48,                          # -4%: outside 2%
               "prefetch_starvation": 0.0}
        res = regression.compare(cur, self.LEDGER)
        assert [f["metric"] for f in res["regressions"]] == ["mfu"]

    def test_improvement_reported_not_failing(self):
        cur = {"train_tokens_per_sec": 1200.0, "serving_ttft_p99_ms": 30.0,
               "mfu": 0.5, "prefetch_starvation": 0.0}
        res = regression.compare(cur, self.LEDGER)
        assert not res["failed"]
        assert len(res["improvements"]) == 2

    def test_zero_baseline_sentinel_counter(self):
        cur = {"train_tokens_per_sec": 1000.0, "serving_ttft_p99_ms": 50.0,
               "mfu": 0.5, "prefetch_starvation": 3.0}
        res = regression.compare(cur, self.LEDGER)
        assert res["failed"]
        assert res["regressions"][0]["metric"] == "prefetch_starvation"

    def test_missing_and_new_and_strict(self):
        cur = {"train_tokens_per_sec": 1000.0, "brand_new_tps": 5.0}
        res = regression.compare(cur, self.LEDGER)
        assert not res["failed"]
        assert "mfu" in res["missing"]
        assert res["new"] == ["brand_new_tps"]
        assert regression.compare(cur, self.LEDGER,
                                  strict_missing=True)["failed"]

    def test_flatten_and_jsonl_roundtrip(self, tmp_path):
        rec = {"metric": "m1", "value": 10.0, "unit": "x",
               "extra": {"a_ms": 1.5, "note": "str", "flag": True}}
        flat = regression.flatten_bench_record(rec)
        assert flat == {"m1": 10.0, "a_ms": 1.5}
        path = str(tmp_path / "r.jsonl")
        n = regression.append_bench_records(path, flat,
                                            env={"smoke": True})
        assert n == 2
        regression.append_bench_records(path, {"m1": 11.0})
        loaded = regression.load_bench_file(path)
        assert loaded == {"m1": 11.0, "a_ms": 1.5}     # last write wins
        line = json.loads(open(path).readline())
        assert set(line) == {"metric", "value", "unit", "env",
                             "unix_time"}

    def test_wrapper_and_flat_forms_load(self, tmp_path):
        wrapper = {"parsed": {"metric": "m", "value": 2.0,
                              "extra": {"mfu": 0.5}}}
        p1 = tmp_path / "w.json"
        p1.write_text(json.dumps(wrapper))
        assert regression.load_bench_file(str(p1)) == {"m": 2.0,
                                                       "mfu": 0.5}
        p2 = tmp_path / "flat.json"
        p2.write_text(json.dumps({"a": 1.0, "b": 2.0}))
        assert regression.load_bench_file(str(p2)) == {"a": 1.0, "b": 2.0}

    def test_cli_green_on_seeded_baseline_and_fixtures(self, tmp_path):
        """Acceptance: check_bench exits 0 on BENCH_r05.json vs the
        committed ledger, 1 on the canned regression, 0 on canned
        noise."""
        script = os.path.join(SCRIPTS, "check_bench.py")
        r = subprocess.run(
            [sys.executable, script, "--current",
             os.path.join(REPO, "BENCH_r05.json")],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr
        ledger = regression.load_baseline(
            os.path.join(REPO, "BENCH_BASELINE.json"))
        for kind, want_rc in (("regression", 1), ("noise", 0)):
            p = tmp_path / f"{kind}.json"
            p.write_text(json.dumps(regression.make_fixture(ledger, kind)))
            r = subprocess.run(
                [sys.executable, script, "--current", str(p)],
                capture_output=True, text=True)
            assert r.returncode == want_rc, (kind, r.stdout, r.stderr)

    def test_cli_self_test_and_update_baseline(self, tmp_path):
        script = os.path.join(SCRIPTS, "check_bench.py")
        r = subprocess.run([sys.executable, script, "--self-test"],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr
        # self-test stays green on a ledger carrying zero-valued metrics
        # (a reseeded ledger keeps zero counters like prefetch_starvation;
        # a 10% shift of 0 is 0 and must not be counted as a failed trip)
        zl = dict(self.LEDGER)
        zl_path = tmp_path / "zero_ledger.json"
        zl_path.write_text(json.dumps(zl))
        r = subprocess.run(
            [sys.executable, script, "--self-test", "--baseline",
             str(zl_path)],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps({"metric": "m", "value": 3.0,
                                   "extra": {"mfu": 0.6}}))
        out = tmp_path / "ledger.json"
        r = subprocess.run(
            [sys.executable, script, "--current", str(cur),
             "--baseline", str(out), "--update-baseline"],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        ledger = regression.load_baseline(str(out))
        assert ledger["metrics"]["m"]["value"] == 3.0


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
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert out.exists()


# ========================================================= perf_report

class TestPerfReport:
    def test_snapshot_mode_sections(self, tmp_path):
        snap = _synthetic_snapshot()
        snap["counters"] = {"collective_bytes_total": {"help": "",
            "samples": [
                {"labels": {"kind": "all_gather", "axis": "fsdp"},
                 "value": 300.0},
                {"labels": {"kind": "all_gather", "axis": "fsdp",
                            "link": "ici"}, "value": 200.0},
                {"labels": {"kind": "all_gather", "axis": "fsdp",
                            "link": "dcn"}, "value": 100.0}]}}
        p = tmp_path / "snapshot.json"
        p.write_text(json.dumps(snap))
        r = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "perf_report.py"),
             str(p), "--step-ms", "50", "--comm-ms", "8"],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        for needle in ("step-time budget", "roofline", "per-link",
                       "all_gather", "dispatch_floor", "host phase spans"):
            assert needle in r.stdout, (needle, r.stdout)
        # the link table renders the exact split
        row = [ln for ln in r.stdout.splitlines()
               if ln.strip().startswith("all_gather")][0]
        assert "300" in row and "200" in row and "100" in row

    def test_bench_record_mode_exposed_comm_matches(self, tmp_path):
        """Acceptance: budget exposed-comm == the record's own
        comm_exposed_ms (comm_total_ms × ratio) — same product, read
        through the CLI."""
        snap = _synthetic_snapshot(exposed_ratio=0.4)
        sp = tmp_path / "telemetry_snapshot.json"
        sp.write_text(json.dumps(snap))
        record = {"metric": "m", "value": 1.0, "extra": {
            "step_time_s": 0.050, "comm_total_ms": 12.5,
            "comm_exposed_ms": 5.0, "collective_exposed_ratio": 0.4,
            "telemetry_snapshot": "telemetry_snapshot.json"}}
        rp = tmp_path / "record.json"
        rp.write_text(json.dumps(record))
        r = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "perf_report.py"),
             str(rp), "--json"],
            capture_output=True, text=True, cwd=str(tmp_path))
        assert r.returncode == 0, r.stderr
        budget = json.loads(r.stdout)["budget"]
        assert budget["terms_ms"]["exposed_comm"] == pytest.approx(
            5.0, rel=0.10)
        assert budget["measured_step_ms"] == pytest.approx(50.0)
        # terms (plus achieved compute) sum to measured within 5%
        assert budget["attributed_ms"] == pytest.approx(50.0, rel=0.05)

    def test_postmortem_bundle_mode(self, tmp_path):
        """perf_report runs on a real postmortem bundle layout: spans
        from meta.json, metrics parsed back out of snapshot.prom, step
        time derived from the records' spans_ms."""
        from deepspeed_tpu.telemetry.exporter import SnapshotExporter
        bundle = tmp_path / "postmortem" / "20260101-000000-step5-manual"
        bundle.mkdir(parents=True)
        reg = MetricRegistry()
        reg.gauge("collective_exposed_ratio", "h").set(0.2,
                                                       fn="train_batch")
        reg.gauge("xla_cost_flops", "h").set(1e9, fn="train_batch")
        reg.gauge("roofline_attainable_ms", "h").set(11.0,
                                                     fn="train_batch")
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
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "measured 10.000 ms/step" in r.stdout   # derived from spans
        assert "attainable >= 11.000 ms" in r.stdout   # prom gauge
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
