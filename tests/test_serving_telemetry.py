"""Request-level serving telemetry (telemetry/histogram.py,
telemetry/serving.py, the instrumented inference engines) plus the
scripts/check_metrics.py lint wiring.

Histogram semantics are pinned against numpy; engine-level cases reuse the
tiny fp32 GPT config from test_inference_v2 so every path (closed loop,
open loop, speculative fused + split-profile) runs in seconds on CPU.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import v2_engine

from deepspeed_tpu.models import GPTConfig
from deepspeed_tpu.telemetry import MetricRegistry, SnapshotExporter
from deepspeed_tpu.telemetry.histogram import (DEFAULT_BUCKETS, Histogram,
                                               log_buckets)
from deepspeed_tpu.telemetry.serving import (ServingTelemetry,
                                             ServingTelemetryConfig)

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig.tiny(vocab_size=97, max_seq_len=64)


@pytest.fixture(scope="module")
def v2cfg():
    return {"dtype": "fp32",
            "state_manager": {"max_tracked_sequences": 4,
                              "max_ragged_batch_size": 64,
                              "kv_block_size": 8, "max_q_per_seq": 16}}


# ---------------------------------------------------------------- histogram

class TestHistogram:
    def test_log_buckets_shape_and_spacing(self):
        bs = log_buckets(0.1, 1e5, per_decade=4)
        assert bs[0] == 0.1 and bs[-1] >= 1e5
        assert list(bs) == sorted(set(bs))
        # ~constant relative spacing (log-spaced): ratio ≈ 10^(1/4)
        ratios = [b / a for a, b in zip(bs, bs[1:])]
        assert all(1.5 < r < 2.2 for r in ratios), ratios
        assert DEFAULT_BUCKETS == bs

    def test_bucket_boundaries_le_semantics(self):
        h = Histogram("x_ms", buckets=[1.0, 10.0, 100.0])
        for v in (0.5, 1.0, 5.0, 10.0, 99.0, 1000.0):
            h.observe(v)
        (_, s), = h.samples()
        # le is INCLUSIVE: 1.0 lands in the first bucket, 10.0 in the second
        assert s["bucket_counts"] == [2, 2, 1, 1]
        assert s["count"] == 6
        assert s["sum"] == pytest.approx(sum((0.5, 1.0, 5.0, 10.0, 99.0,
                                              1000.0)))

    def test_exact_quantiles_match_numpy(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(3.0, 1.2, size=1000)
        h = Histogram("lat_ms")
        for v in vals:
            h.observe(v)
        for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(np.quantile(vals, q)), q

    def test_over_cap_falls_back_to_bucket_interpolation(self):
        rng = np.random.default_rng(1)
        vals = rng.lognormal(1.0, 0.7, size=4000)
        h = Histogram("lat_ms", exact_cap=64)
        for v in vals:
            h.observe(v)
        for q in (0.5, 0.9, 0.99):
            approx = h.quantile(q)
            ref = float(np.quantile(vals, q))
            # log-bucket interpolation: within one bucket's relative width
            assert abs(approx / ref - 1.0) < 0.45, (q, approx, ref)

    def test_label_isolation(self):
        h = Histogram("lat_ms")
        h.observe(1.0, leg="a")
        h.observe(100.0, leg="b")
        assert h.count(leg="a") == 1 and h.count(leg="b") == 1
        assert h.quantile(0.5, leg="a") == 1.0
        assert h.quantile(0.5, leg="b") == 100.0
        assert np.isnan(h.quantile(0.5, leg="c"))

    def test_registry_get_or_create_and_mismatches(self):
        reg = MetricRegistry()
        h1 = reg.histogram("m_ms", "help", buckets=[1, 2, 4])
        assert reg.histogram("m_ms") is h1
        with pytest.raises(ValueError, match="different buckets"):
            reg.histogram("m_ms", buckets=[1, 2, 8])
        with pytest.raises(TypeError, match="already registered"):
            reg.counter("m_ms")
        reg.counter("c_total")
        with pytest.raises(TypeError, match="requested histogram"):
            reg.histogram("c_total")
        with pytest.raises(ValueError, match="increasing"):
            Histogram("bad_ms", buckets=[2, 1])

    def test_prometheus_exposition_format(self):
        reg = MetricRegistry()
        h = reg.histogram("lat_ms", "latency", buckets=[1.0, 10.0])
        for v in (0.5, 5.0, 50.0):
            h.observe(v, leg="x")
        text = SnapshotExporter(reg).prometheus_text()
        lines = text.splitlines()
        assert "# HELP deepspeed_tpu_lat_ms latency" in lines
        assert "# TYPE deepspeed_tpu_lat_ms histogram" in lines
        # cumulative buckets, le last and inclusive, +Inf == _count
        assert 'deepspeed_tpu_lat_ms_bucket{leg="x",le="1"} 1' in lines
        assert 'deepspeed_tpu_lat_ms_bucket{leg="x",le="10"} 2' in lines
        assert 'deepspeed_tpu_lat_ms_bucket{leg="x",le="+Inf"} 3' in lines
        assert 'deepspeed_tpu_lat_ms_count{leg="x"} 3' in lines
        assert 'deepspeed_tpu_lat_ms_sum{leg="x"} 55.5' in lines

    def test_snapshot_round_trip(self, tmp_path):
        reg = MetricRegistry()
        h = reg.histogram("lat_ms", "latency")
        rng = np.random.default_rng(2)
        vals = rng.uniform(1, 100, 50)
        for v in vals:
            h.observe(v)
        exp = SnapshotExporter(reg)
        snap = exp.snapshot()
        path = exp.write_json(str(tmp_path / "snap.json"), snap)
        loaded = json.load(open(path))
        s = loaded["histograms"]["lat_ms"]["samples"][0]
        assert s["count"] == 50
        assert s["sum"] == pytest.approx(vals.sum())
        assert s["p50"] == pytest.approx(np.quantile(vals, 0.5))
        assert s["p99"] == pytest.approx(np.quantile(vals, 0.99))
        assert sum(s["bucket_counts"]) == 50
        # a reloaded snapshot renders to the same exposition text (the
        # provenance stamps — snapshot_seq, capture clocks — are part of
        # the snapshot, so the comparison is against ITS render, not a
        # fresh capture's)
        assert exp.prometheus_text(loaded) == exp.prometheus_text(snap)


class TestExporterConformance:
    def test_help_and_type_for_every_family(self):
        reg = MetricRegistry()
        reg.counter("a_total").inc(1)           # registered with NO help
        reg.gauge("b_ratio", "a gauge").set(0.5)
        reg.histogram("c_ms", "a histogram").observe(1.0)
        text = SnapshotExporter(reg).prometheus_text()
        for pname, ptype in (("deepspeed_tpu_a_total", "counter"),
                             ("deepspeed_tpu_b_ratio", "gauge"),
                             ("deepspeed_tpu_c_ms", "histogram")):
            assert f"# TYPE {pname} {ptype}" in text
            # HELP present even for the help-less metric (falls back to name)
            assert f"# HELP {pname} " in text

    def test_label_value_escaping(self):
        reg = MetricRegistry()
        reg.counter("esc_total", "x").inc(
            1, path='a\\b"c\nd')
        text = SnapshotExporter(reg).prometheus_text()
        assert r'path="a\\b\"c\nd"' in text

    def test_help_escaping_keeps_quotes_literal(self):
        reg = MetricRegistry()
        reg.counter("q_total", 'help with "quotes" and \\ and\nnewline')
        text = SnapshotExporter(reg).prometheus_text()
        # HELP escapes backslash + newline ONLY; quotes stay literal
        assert ('# HELP deepspeed_tpu_q_total help with "quotes" and '
                r'\\ and\nnewline') in text


# ---------------------------------------------------- ServingTelemetry unit

class TestServingTelemetryUnit:
    def test_finish_request_histograms_spans_and_log(self):
        stel = ServingTelemetry(ServingTelemetryConfig(), pid=0)
        tr = stel.new_track("req 0")
        stel.finish_request(uid=-1, track=tr, t_arrival=10.0, t_admit=10.1,
                            t_prefill_end=10.3, t_first=10.35, t_last=11.35,
                            n_prompt=32, n_generated=11)
        assert stel.quantile("serving_ttft_ms", 0.5) == pytest.approx(350.0)
        assert stel.quantile("serving_queue_ms", 0.5) == pytest.approx(100.0)
        assert stel.quantile("serving_prefill_ms", 0.5) == pytest.approx(
            200.0)
        assert stel.quantile("serving_tpot_ms", 0.5) == pytest.approx(100.0)
        assert stel.quantile("serving_e2e_ms", 0.5) == pytest.approx(1350.0)
        assert stel.value("serving_requests_total", outcome="completed") == 1
        (rec,) = stel.request_log
        assert rec["generated_tokens"] == 11
        assert rec["ttft_ms"] == pytest.approx(350.0)
        names = {(e["name"], e["tid"]) for e in stel.tracer.events}
        assert {("queue_wait", tr), ("prefill", tr),
                ("decode", tr)} <= names
        assert stel.tracer.thread_names[tr] == "req 0"
        trace = stel.emitter.to_dict(stel.tracer)
        assert any(e.get("ph") == "M" and e["name"] == "thread_name"
                   and e["args"]["name"] == "req 0"
                   for e in trace["traceEvents"])

    def test_disabled_is_inert(self):
        stel = ServingTelemetry(ServingTelemetryConfig(enabled=False), pid=0)
        stel.tokens("decode", 5)
        stel.alloc_failure("put")
        stel.spec_burst(outer=1, n_seqs=1, gamma=4, emitted=5, dur_ms=1.0)
        stel.finish_request(uid=0, track=0, t_arrival=0.0, t_admit=None,
                            t_prefill_end=None, t_first=None, t_last=None,
                            n_prompt=1, n_generated=0)
        assert stel.spec_summary() == {}
        assert not stel.tracer.events
        assert not stel.registry.metrics()

    def test_spec_burst_accounting(self):
        stel = ServingTelemetry(ServingTelemetryConfig(), pid=0)
        # 2 outer steps × 3 seqs, gamma=4: 24 proposed; 18 emitted means
        # 18 - 6 = 12 draft tokens accepted -> ratio 0.5
        stel.spec_burst(outer=2, n_seqs=3, gamma=4, emitted=18, dur_ms=7.5)
        st = stel.spec_summary()
        assert st["outer_steps"] == 6
        assert st["proposed"] == 24
        assert st["accepted"] == 12
        assert st["accept_ratio"] == pytest.approx(0.5)
        assert st["emitted_per_outer"] == pytest.approx(3.0)
        assert st["burst_ms"] == pytest.approx(7.5)


# --------------------------------------------------- engine v2 integration

class TestEngineServingTelemetry:
    def test_generate_populates_lifecycle_metrics(self, cfg, v2cfg, rng):
        eng = v2_engine(cfg, config=v2cfg, seed=0)
        prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
                   for n in (9, 23, 5, 30, 12, 7)]       # 6 prompts, 4 slots
        outs = eng.generate(prompts, max_new_tokens=6)
        stel = eng.telemetry
        h = stel.registry._metrics["serving_ttft_ms"]
        assert h.count() == len(prompts)
        assert stel.registry._metrics["serving_e2e_ms"].count() == \
            len(prompts)
        assert stel.value("serving_requests_total",
                          outcome="completed") == len(prompts)
        assert stel.value("serving_tokens_total", phase="prefill") == \
            sum(len(p) for p in prompts)
        assert stel.value("serving_tokens_total", phase="decode") >= \
            sum(len(o) for o in outs)
        assert stel.value("serving_dispatches_total", kind="mixed") > 0
        # per-request tracks in the trace: every request has all 3 spans
        evs = [e for e in stel.tracer.events if e["cat"] == "request"]
        per_tid = {}
        for e in evs:
            per_tid.setdefault(e["tid"], set()).add(e["name"])
        assert len(per_tid) == len(prompts)
        assert all(v == {"queue_wait", "prefill", "decode"}
                   for v in per_tid.values())
        # KV gauges were refreshed and are consistent with an empty pool
        q = eng.query()
        assert q["used_kv_blocks"] == 0
        assert stel.value("kv_pool_blocks", state="free") == \
            q["free_kv_blocks"]
        assert 0 < stel.value("serving_batch_occupancy") <= 1.0

    @staticmethod
    def _served_steps(cfg, v2cfg, rng):
        """(telemetry, each sampled step's rows a sequence) of six prompts
        served over four slots."""
        eng = v2_engine(cfg, config=v2cfg, seed=0)
        steps = []
        inner = eng._step_sampled

        def spy(uids, toks_np, *a, **kw):
            steps.append([len(t) for t in toks_np])
            return inner(uids, toks_np, *a, **kw)
        eng._step_sampled = spy
        prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
                   for n in (9, 23, 5, 30, 12, 7)]
        eng.generate(prompts, max_new_tokens=20)
        return eng.telemetry, steps

    def test_dispatch_spans_carry_the_mixed_slot_totals(self, cfg, v2cfg,
                                                        rng):
        """``mixed_seqs`` / ``one_row_seqs``: running totals of the slots
        mixed dispatches served and of those with one row, in the args of
        every dispatch span.  The schedule handed to each step says what
        they have to read."""
        stel, steps = self._served_steps(cfg, v2cfg, rng)
        spans = [e for e in stel.tracer.events
                 if e["name"] in ("mixed_dispatch", "decode_dispatch",
                                  "burst_dispatch")]
        assert {e["name"] for e in spans} >= {"mixed_dispatch",
                                              "burst_dispatch"}
        assert all({"mixed_seqs", "one_row_seqs"} <= set(e["args"])
                   for e in spans)
        for key in ("mixed_seqs", "one_row_seqs"):
            seen = [e["args"][key] for e in spans]
            assert seen == sorted(seen)                 # never decrease
        mixed = [rows for rows in steps if max(rows) > 1]
        assert any(1 in rows for rows in mixed)         # riders were there
        want, slots, ones = [], 0, 0
        for rows in mixed:          # a span holds its own dispatch already
            slots += len(rows)
            ones += rows.count(1)
            want.append((slots, ones))
        got = [(e["args"]["mixed_seqs"], e["args"]["one_row_seqs"])
               for e in spans if e["name"] == "mixed_dispatch"]
        assert got == want
        # the other kinds repeat the totals as they stand
        assert (spans[-1]["args"]["mixed_seqs"],
                spans[-1]["args"]["one_row_seqs"]) == want[-1]
        assert stel.value("serving_mixed_slots_total") == slots
        assert stel.value("serving_one_row_slots_total") == ones
        assert 0 < ones < slots

    def test_dispatch_spans_carry_the_prefill_item_totals(self, cfg, v2cfg,
                                                          rng):
        """``prefill_items`` / ``prefill_grid_items``: running totals of the
        ragged prefill kernel's live work items (a chunk of a slot that
        holds more than one row) and of the items its grid had room for, a
        layer, in the args of every dispatch span: what the kernel's own
        rule gives for each mixed step's schedule."""
        from deepspeed_tpu.ops.paged_attention import (_prefill_chunk,
                                                       prefill_grid_items)
        stel, steps = self._served_steps(cfg, v2cfg, rng)
        sm = v2cfg["state_manager"]
        S, Q = sm["max_tracked_sequences"], sm["max_q_per_seq"]
        cq = _prefill_chunk(Q, 1, cfg.head_dim)         # 16 rows: one chunk
        want, live, grid = [], 0, 0
        for rows in steps:
            if max(rows) > 1:
                bucket = min(max(64, 1 << (sum(rows) - 1).bit_length()),
                             sm["max_ragged_batch_size"])
                live += sum(-(-n // cq) for n in rows if n > 1)
                grid += prefill_grid_items(bucket, S, Q, cq)
                want.append((live, grid))
        spans = [e for e in stel.tracer.events
                 if e["name"].endswith("_dispatch")]
        got = [(e["args"]["prefill_items"], e["args"]["prefill_grid_items"])
               for e in spans if e["name"] == "mixed_dispatch"]
        assert got == want and 0 < live < grid
        assert all((e["args"]["prefill_items"],
                    e["args"]["prefill_grid_items"]) in [(0, 0)] + want
                   for e in spans)
        assert stel.value("serving_prefill_items_total") == live
        assert stel.value("serving_prefill_grid_items_total") == grid

    def test_open_loop_arrivals_gate_admission_and_match_closed_loop(
            self, cfg, v2cfg, rng):
        prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
                   for n in (9, 14, 21)]
        closed = v2_engine(cfg, config=v2cfg, seed=0)
        want = closed.generate(prompts, max_new_tokens=5)
        eng = v2_engine(cfg, config=v2cfg, seed=0)
        arrivals = [0.0, 0.03, 0.06]
        got = eng.generate(prompts, max_new_tokens=5,
                           arrival_times=arrivals, stream=True)
        # greedy output is arrival-schedule independent
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        stel = eng.telemetry
        assert stel.registry._metrics["serving_queue_ms"].count() == 3
        # the last request cannot have been admitted before it arrived
        rec = [r for r in stel.request_log if r["uid"] == -3]
        assert rec and rec[0]["e2e_ms"] <= (
            stel.quantile("serving_e2e_ms", 1.0) + 1e-6)
        q99 = stel.quantile("serving_queue_ms", 1.0)
        assert q99 >= 0.0

    def test_arrival_times_validation(self, cfg, v2cfg, rng):
        eng = v2_engine(cfg, config=v2cfg, seed=0)
        with pytest.raises(ValueError, match="arrival_times"):
            eng.generate([rng.integers(0, 97, (5,)).astype(np.int32)],
                         max_new_tokens=2, arrival_times=[0.0, 1.0])

    def test_preemption_and_alloc_failure_counters(self, cfg, rng):
        eng = v2_engine(cfg, config={
            "dtype": "fp32",
            "state_manager": {"max_tracked_sequences": 4,
                              "max_ragged_batch_size": 64,
                              "kv_block_size": 8, "max_q_per_seq": 16,
                              "num_kv_blocks": 6}}, seed=0)
        prompts = [rng.integers(0, 97, (14,)).astype(np.int32)
                   for _ in range(3)]
        out = eng.generate(prompts, max_new_tokens=10)
        assert all(len(o) == 10 for o in out)
        stel = eng.telemetry
        total_preempts = (eng.preempt_stats["decode_ready"]
                          + eng.preempt_stats["mid_prefill"])
        counted = sum(
            stel.value("serving_preemptions_total", kind=k)
            for k in ("decode_ready", "mid_prefill"))
        assert counted == total_preempts
        # an oversubscribed pool must have hit at least one alloc failure
        # site (admission/decode/prompt_chunk) if it ever preempted
        sites = ("admission", "decode", "prompt_chunk")
        fails = sum(stel.value("kv_alloc_failures_total", site=s)
                    for s in sites)
        if total_preempts:
            assert fails > 0

    def test_can_schedule_failure_counts(self, cfg, v2cfg):
        eng = v2_engine(cfg, config=v2cfg, seed=0)
        assert not eng.can_schedule(list(range(99)), [1] * 99)
        assert eng.telemetry.value("kv_alloc_failures_total",
                                   site="can_schedule") == 1

    def test_telemetry_disabled_engine_still_serves(self, cfg, v2cfg, rng):
        eng = v2_engine(cfg, config={
            **v2cfg, "telemetry": {"enabled": False}}, seed=0)
        prompts = [rng.integers(0, 97, (9,)).astype(np.int32)]
        out = eng.generate(prompts, max_new_tokens=4)
        assert len(out[0]) == 4
        assert not eng.telemetry.tracer.events
        assert not eng.telemetry.registry.metrics()


class TestSpeculativeTelemetry:
    def test_fused_spec_counters(self, cfg, v2cfg, rng):
        prompts = [rng.integers(0, 97, (10 + 3 * i,)).astype(np.int32)
                   for i in range(3)]
        base = v2_engine(cfg, config=v2cfg, seed=0)
        spec = v2_engine(cfg, config=v2cfg, params=base.params,
                         draft_model=cfg, draft_params=base.params)
        spec.generate(prompts, max_new_tokens=12)
        st = spec.telemetry.spec_summary()
        assert st["outer_steps"] > 0
        assert st["emitted"] == st["accepted"] + st["outer_steps"]
        assert 0.0 <= st["accept_ratio"] <= 1.0
        assert st["burst_ms"] > 0.0
        assert spec.telemetry.value("serving_tokens_total",
                                    phase="spec") == st["emitted"]


class TestV1ServingTelemetry:
    def test_generate_records_latency_and_tokens(self, cfg, rng):
        import deepspeed_tpu
        eng = deepspeed_tpu.init_inference(cfg, config={"dtype": "fp32"})
        ids = rng.integers(0, 97, (2, 12)).astype(np.int32)
        eng.generate(ids, max_new_tokens=6)
        stel = eng.telemetry
        assert stel.registry._metrics["serving_e2e_ms"].count() == 2
        assert stel.value("serving_tokens_total", phase="decode") == 12
        assert stel.value("serving_tokens_total", phase="prefill") == 24
        assert stel.value("serving_dispatches_total",
                          kind="v1_generate") == 1
        assert any(e["name"] == "v1_generate"
                   for e in stel.tracer.events)


# ------------------------------------------------------------ lint wiring

class TestCheckMetrics:
    # the whole-repo green run moved into the unified lint driver
    # (scripts/lint_all.py, shelled once by tests/test_lint_all.py)

    def test_violations_detected(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import check_metrics
        finally:
            sys.path.pop(0)
        bad = tmp_path / "bad.py"
        bad.write_text(
            "NAME = 'const_counter'\n"
            "def f(reg, x):\n"
            "    reg.counter('missing_suffix')\n"           # not _total
            "    reg.gauge('BadCase_total', 'h')\n"         # case + suffix
            "    reg.histogram('lat', 'h')\n"               # no unit
            "    reg.counter(NAME, 'h')\n"                  # const, no _total
            "    reg.counter('pfx_' + x, 'h')\n"            # prefix glob
            "    reg.counter(x)\n"                          # dynamic
            "    reg.counter(x)  # metric-name-ok: test\n"  # disclosed
        )
        sites, errors = check_metrics.collect_sites(str(tmp_path))
        assert not errors
        v = check_metrics.check(sites, doc_text="pfx_*")
        text = "\n".join(v)
        assert "missing_suffix" in text and "_total" in text
        assert "BadCase_total" in text
        assert "'lat'" in text and "unit" in text
        assert "const_counter" in text
        assert "dynamic metric name" in text
        assert text.count("dynamic metric name") == 1    # metric-name-ok
        # the documented prefix glob produced no documentation violation
        assert "'pfx_*' is not documented" not in text

    def test_check_no_sync_covers_serving_loop(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import check_no_sync
        finally:
            sys.path.pop(0)
        assert any(p == check_no_sync.SERVING_PATH
                   for p, _, _, _ in check_no_sync.SCAN_TARGETS)
        # (the clean-on-the-real-tree run lives in scripts/lint_all.py)
        # an undisclosed transfer in the decode loop is flagged
        bad = tmp_path / "engine_v2.py"
        bad.write_text(
            "class E:\n"
            "    def generate(self):\n"
            "        x = jax.device_get(self.prev)\n"
            "        y = jax.device_get(self.prev)  # sync-ok: test\n")
        v = check_no_sync.check_file(
            str(bad), check_no_sync.SERVING_FUNCS,
            check_no_sync.TRANSFER_PATTERN, check_no_sync.ALLOW_PATTERN)
        assert len(v) == 1 and "device_get" in v[0]
