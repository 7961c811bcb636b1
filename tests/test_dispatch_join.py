"""The benchmark's join of device runs to dispatch spans (PR 55,
``benchmark/readers/dispatch_join.py``): through the ``run_id`` the runtime
writes on both sides, on the trace recorded on the v5e and on hand-made
traces in the ``.textproto`` form ``benchmark/tests/data/small_trace.textproto``
has.  The specs are carried here: no ``benchmark/metrics/*.json`` is opened by
name.  All on the CPU: structure and arithmetic, never a time of the device."""

import os
import sys

import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, os.path.join(BENCH, "readers")):
    if p not in sys.path:
        sys.path.insert(0, p)

import costs  # noqa: E402
import costs_moe  # noqa: E402
import dispatch_join  # noqa: E402
import xmeta  # noqa: E402
import xtrace  # noqa: E402

RECORDED = os.path.join(BENCH, "tests", "data", "recorded_v5e.xplane.pb")
SPEC = {"reader": "dispatch_join", "program": "ragged_"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


class Cfg:
    hidden_size, expert_dim = 64, 32


# ------------------------------------------------------ hand-made traces

class _Plane:
    """One plane of an ``XSpace`` in text form; times in microseconds."""

    def __init__(self, pid, name):
        self.pid, self.name = pid, name
        self.lines, self.names, self.stats = [], {}, {}

    def _event(self, name, start, dur, stats):
        mid = self.names.setdefault(name, len(self.names) + 1)
        out = (f"events {{ metadata_id: {mid} offset_ps: "
               f"{round(start * 1e6)} duration_ps: {round(dur * 1e6)}")
        for key, value in stats.items():
            sid = self.stats.setdefault(key, len(self.stats) + 1)
            kind = "str_value" if isinstance(value, str) else "int64_value"
            text = f'"{value}"' if isinstance(value, str) else value
            out += f" stats {{ metadata_id: {sid} {kind}: {text} }}"
        return out + " }"

    def line(self, name, events):
        """``events``: (name, start_us, duration_us, {stat: value})."""
        body = "\n".join("    " + self._event(*e) for e in events)
        self.lines.append(f'  lines {{\n    id: {len(self.lines) + 1}\n'
                          f'    name: "{name}"\n    timestamp_ns: 0\n'
                          f'{body}\n  }}')

    def text(self, op_stats=None):
        meta = []
        for name, mid in self.names.items():
            extra = "".join(
                f' stats {{ metadata_id: {self.stats.setdefault(k, len(self.stats) + 1)} '
                f'str_value: "{v}" }}'
                for k, v in (op_stats or {}).get(name, {}).items())
            quoted = name.replace('"', '\\"')
            meta.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                        f'name: "{quoted}"{extra} }} }}')
        stat = [f'  stat_metadata {{ key: {sid} value {{ id: {sid} '
                f'name: "{name}" }} }}' for name, sid in self.stats.items()]
        return "\n".join([f'planes {{\n  id: {self.pid}\n'
                          f'  name: "{self.name}"'] + self.lines + meta
                         + stat + ["}"])


def _enqueue(start, run_id, ordinal=0):
    return ("DoEnqueueProgram", start, 0.5,
            {"run_id": run_id, "queue_id": 0, "device_ordinal": ordinal})


def _module(name, start, dur, run_id):
    return (f"jit_{name}({len(name)})", start, dur, {"run_id": run_id})


def _ctx(tmp_path, planes, window=(0, 100), op_stats=None):
    path = os.path.join(str(tmp_path), "hand.textproto")
    with open(path, "w") as f:
        f.write("\n".join(p.text(op_stats if p.name.startswith("/device")
                                 else None) for p in planes))
    return _joined({"devices": xmeta.device_ops(path),
                    "annotations": xmeta.annotations(path)}, path,
                   (window[0] * 1e3, window[1] * 1e3))


def _joined(run, path, window):
    """A context as ``run.py`` hands a reader, the trace's two decodings
    and their join already in it."""
    return {"_xmeta": run, "peaks": PEAKS, "model_cfg": Cfg,
            "trace_window": window,
            "_dispatch_join": dispatch_join.join(
                dispatch_join.decode(path), run["annotations"],
                run["devices"]) if run["devices"] else None}


BURST, MIXED = "ragged_decode_burst", "ragged_forward_sampled"
DOT = "%ragged-dot.1 = bf16[8,64]{1,0} custom-call(bf16[8,64]{1,0} %x)"
ACT = "%fusion.5 = bf16[8,32]{1,0} fusion(bf16[8,32]{1,0} %y), kind=kLoop"
MLP = "%fusion.6 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %z), kind=kOutput"
LOOP = ("%while.1 = (s32[], bf16[8,64]{1,0}) while((s32[], bf16[8,64]{1,0}) "
        "%t), condition=%c, body=%b")
OP_STATS = {ACT: {"tf_op": "jit(x)/mlp/moe_experts/mul"},
            MLP: {"tf_op": "jit(x)/mlp/dot_general"},
            LOOP: {"tf_op": "jit(x)/kv_pool/while"}}


def _burst_ops(start):
    """A burst of 10 us: the loop (a container), the grouped GEMM 3 us, the
    expert scope's activation 1 us, a dense product 2 us."""
    return [(LOOP, start, 10, {}), (DOT, start + 1, 3, {}),
            (ACT, start + 4, 1, {}), (MLP, start + 6, 2, {})]


@pytest.fixture()
def host_ahead(tmp_path):
    """The host two dispatches ahead: the chip runs dispatches 1-5 in the
    stretch, the host makes 3-7 in it.  Runs 3-5 join by ``run_id``, 1 and 2
    are the head (numbered by order), spans 6 and 7 the tail; a program that
    is no step program runs too, enqueued outside every dispatch span."""
    dev = _Plane(1, "/device:TPU:0")
    dev.line("XLA Modules", [
        _module(BURST, 10, 10, 101), _module(MIXED, 22, 6, 102),
        _module(BURST, 30, 10, 103), _module(BURST, 42, 10, 104),
        _module(BURST, 54, 10, 105), _module("copy_blocks", 66, 1, 106)])
    dev.line("XLA Ops", _burst_ops(10) + [(ACT, 23, 2, {}), (MLP, 25, 2, {})]
             + _burst_ops(30) + _burst_ops(42) + _burst_ops(54))
    host = _Plane(2, "/host:CPU")
    note = {"program": BURST, "steps": 2}
    moe = [(1, 100, 10), (2, 150, 16), (2, 150, 16), (4, 400, 30),
           (5, 520, 38)]
    spans = [("bench_trace_window", 0, 100, {})]
    for (seq, at), (m, local, touched) in zip(
            [(3, 12), (4, 16), (5, 20), (6, 50), (7, 60)], moe):
        spans.append(("ds.burst_dispatch", at, 2, {
            **note, "seq": seq, "moe_seq": m, "moe_local": local,
            "moe_touched": touched, "moe_assign": local}))
    spans.append(("ds.materialize", 70, 5, {"records": 2, "through_seq": 5,
                                            "in_flight": 4}))
    host.line("python3", spans)
    host.line("main/7", [_enqueue(12.5, 103), _enqueue(16.5, 104),
                         _enqueue(20.5, 105), _enqueue(50.5, 107),
                         _enqueue(60.5, 108), _enqueue(80, 106)])
    return _ctx(tmp_path, [dev, host], op_stats=OP_STATS)


def test_a_host_ahead_is_joined_by_run_id_and_its_head_numbered_by_order(
        host_ahead, capsys):
    got = dispatch_join.joined(host_ahead)
    assert [d["seq"] for d in got] == [1, 2, 3, 4, 5]
    assert [d["by_order"] for d in got] == [True, True, False, False, False]
    assert [d["runs"][0][:2] for d in got] == [
        (BURST, 10000.0), (MIXED, 22000.0), (BURST, 30000.0),
        (BURST, 42000.0), (BURST, 54000.0)]
    assert got[0]["span"] is None and got[0]["enqueue"] is None
    assert [d["span"]["args"]["seq"] for d in got[2:]] == [3, 4, 5]
    assert got[2]["enqueue"] == (12500.0, 13000.0)
    assert got[2]["enqueues"] == {0: (12500.0, 13000.0, 12500.0)}
    line = dispatch_join.line_of(host_ahead)
    assert {k: line[k] for k in ("runs", "joined", "unjoined_head",
                                 "unjoined_tail", "mismatched",
                                 "ambiguous")} == {
        "runs": 5, "joined": 3, "unjoined_head": 2, "unjoined_tail": 2,
        "mismatched": 0, "ambiguous": 0}
    # device start - enqueue end: 17, 25 and 33 us, and no run leads
    assert line["clock_skew_ms"] == 0.0
    assert line["queue_ms"] == {"mean": pytest.approx(0.025),
                                "p50": pytest.approx(0.025),
                                "max": pytest.approx(0.033)}
    assert line["in_flight_mean"] == 4.0
    assert '"phase": "dispatch_join"' in capsys.readouterr().out


ENTRIES = {"join_share": 60.0, "queue_ms": 0.025, "clock_skew_ms": 0.0}


@pytest.mark.parametrize("what", sorted(ENTRIES))
def test_entry_reads_its_exact_value(host_ahead, what):
    assert dispatch_join.read(host_ahead, {**SPEC, "what": what}) \
        == pytest.approx(ENTRIES[what])


def test_expert_gemm_joined_takes_need_and_time_of_the_same_dispatches(
        host_ahead, capsys):
    """The totals grow from ``moe_seq`` 1 to 5 by dispatches 2-5: 420 local
    assignments, 28 touched experts.  Those four runs' expert scope took
    2 + 3 x (3 + 1) us (the loop is a container, the dense product not the
    scope's, run 1 not among them)."""
    share = dispatch_join.read(host_ahead,
                               {**SPEC, "what": "expert_gemm_joined"})
    flops, byts = costs_moe.expert_gemm_cost(420, 28, 64, 32)
    assert share == pytest.approx(
        costs.roofline_share(flops, byts, 14e-6, PEAKS)[0])
    out = capsys.readouterr().out
    assert '"kernel": "expert_gemm.joined"' in out
    assert '"from_moe_seq": 1, "through_moe_seq": 5' in out
    assert '"by_order": 1' in out               # dispatch 2: the head's


def test_a_hole_in_the_runs_ends_the_stretch_of_dispatches(host_ahead):
    """Dispatch 4's run gone from the stretch: what is left whole between
    two totals is (1, 2], the mixed step alone."""
    got = dispatch_join.of_run(host_ahead)
    got["dispatches"] = [d for d in got["dispatches"] if d["seq"] != 4]
    share = dispatch_join.expert_gemm_joined(host_ahead, SPEC)
    flops, byts = costs_moe.expert_gemm_cost(50, 6, 64, 32)
    assert share == pytest.approx(
        costs.roofline_share(flops, byts, 2e-6, PEAKS)[0])


@pytest.fixture()
def faults(tmp_path):
    """One good join beside a span whose ``program`` is not its run's name
    and two spans that overlap in time."""
    dev = _Plane(1, "/device:TPU:0")
    dev.line("XLA Modules", [
        _module(BURST, 10, 5, 1), _module(MIXED, 20, 5, 2),
        _module(BURST, 30, 5, 3), _module(BURST, 40, 5, 4)])
    host = _Plane(2, "/host:CPU")
    host.line("python3", [
        ("bench_trace_window", 0, 100, {}),
        ("ds.burst_dispatch", 1, 2, {"seq": 1, "program": BURST}),
        ("ds.burst_dispatch", 4, 2, {"seq": 2, "program": BURST})])
    host.line("worker", [
        ("ds.burst_dispatch", 8, 4, {"seq": 3, "program": BURST}),
        ("ds.burst_dispatch", 10, 4, {"seq": 4, "program": BURST})])
    host.line("main/7", [_enqueue(1.5, 1), _enqueue(4.5, 2),
                         _enqueue(9, 3), _enqueue(11, 4)])
    return _ctx(tmp_path, [dev, host])


def test_a_span_of_another_program_is_mismatched_and_left_out(faults):
    got = dispatch_join.of_run(faults)
    assert got["mismatched"] == 1
    assert [d["seq"] for d in got["dispatches"]] == [1]
    assert [r[3] for r in got["step_runs"][0]] == [1, None, None, None]


def test_two_overlapping_spans_join_nothing_and_are_counted(faults):
    line = dispatch_join.line_of(faults)
    assert line["ambiguous"] == 2
    assert (line["runs"], line["joined"], line["unjoined_tail"]) == (4, 1, 1)
    assert dispatch_join.read(faults, {**SPEC, "what": "join_share"}) == 25.0


def test_a_four_chip_dispatch_is_four_runs_under_one_span(tmp_path):
    """One ``run_id`` counter a chip: the same numbers on every chip, told
    apart by the enqueue's ``device_ordinal``.  Chip 2's clock shows its
    second run 2 us before that run's enqueue began."""
    planes = []
    for chip in range(4):
        dev = _Plane(chip + 1, f"/device:TPU:{chip}")
        lead = -2 if chip == 2 else 3
        dev.line("XLA Modules", [_module("train_batch", 10 + chip, 8, 7),
                                 _module("train_batch", 30 + lead, 8, 8)])
        planes.append(dev)
    host = _Plane(9, "/host:CPU")
    host.line("python3", [
        ("bench_trace_window", 0, 100, {}),
        ("ds.dispatch", 4, 5, {"step": 11, "program": "train_batch"}),
        ("ds.dispatch", 29, 5, {"step": 12, "program": "train_batch"})])
    host.line("main/7", [_enqueue(5 + c, 7, c) for c in range(4)]
              + [_enqueue(30 + 0.6 * c, 8, c) for c in range(4)])
    ctx = _ctx(tmp_path, planes + [host])
    got = dispatch_join.joined(ctx)
    assert [d["seq"] for d in got] == [11, 12]
    assert [sorted(d["runs"]) for d in got] == [[0, 1, 2, 3]] * 2
    assert got[0]["runs"][3] == ("train_batch", 13000.0, 21000.0)
    assert got[0]["enqueue"] == (5000.0, 8500.0)
    line = dispatch_join.line_of(ctx)
    assert (line["runs"], line["joined"], line["unjoined_head"]) == (8, 8, 0)
    # chip 2, second run: starts at 28, its enqueue at 31.2
    assert line["clock_skew_ms"] == pytest.approx(0.0032)


def test_an_enqueue_on_a_worker_thread_joins_through_its_launch(tmp_path):
    """Today's runtime: the launching thread's ``tpu::System::Execute``
    inside the span, the enqueue on a worker thread after the span has
    closed (inside the NEXT span, even), linked by the producer's id.  A
    scalar's conversion launched inside the span is no step program: passed
    over, not mismatched."""
    dev = _Plane(1, "/device:TPU:0")
    dev.line("XLA Modules", [
        _module("convert_element_type", 20, 0.1, 1),
        _module(BURST, 21, 5, 2), _module(MIXED, 30, 5, 3)])
    host = _Plane(2, "/host:CPU")
    host.line("python3", [
        ("bench_trace_window", 0, 100, {}),
        ("ds.burst_dispatch", 2, 4, {"seq": 8, "program": BURST}),
        ("ds.mixed_dispatch", 10, 4, {"seq": 9, "program": MIXED})])
    host.line("main/3", [
        ("tpu::System::Execute", 3, 0.2, {"_pt": 7, "_p": 501}),
        ("tpu::System::Execute", 4, 0.2, {"_pt": 7, "_p": 502}),
        ("tpu::System::Execute", 12, 0.2, {"_pt": 7, "_p": 503})])
    issue = "tpu::System::Execute=>IssueSequencedEvent"
    host.line("pjrt-tpu-tasks/5", [
        (issue, 6.5, 1, {"_ct": 7, "_c": 501}), _enqueue(6.8, 1),
        (issue, 11, 1, {"_ct": 7, "_c": 502}), _enqueue(11.2, 2)])
    host.line("pjrt-tpu-tasks/6", [
        (issue, 15, 1, {"_ct": 7, "_c": 503}), _enqueue(15.3, 3)])
    ctx = _ctx(tmp_path, [dev, host])
    got = dispatch_join.joined(ctx)
    assert [(d["seq"], d["runs"][0][0]) for d in got] == [(8, BURST),
                                                          (9, MIXED)]
    assert got[0]["enqueues"][0] == (11200.0, 11700.0, 4000.0)
    line = dispatch_join.line_of(ctx)
    assert (line["runs"], line["joined"], line["mismatched"]) == (2, 2, 0)
    # launched at 4 and 12, enqueued at 11.2 and 15.3
    assert line["launch_ms"] == {"mean": pytest.approx(0.00525),
                                 "max": pytest.approx(0.0072)}


def _cohort_ahead(tmp_path, fold_at=(10_000, 55_000)):
    """The host a cohort ahead: the chip runs four bursts (20 ms each, ending
    at 20, 42, 64 and 86 ms) while the host makes dispatches 9 and 10, whose
    runs the trace does not hold.  The totals folded in the build before
    span 9 hold through dispatch 2, those before span 10 through 4."""
    ms = 1000
    dev = _Plane(1, "/device:TPU:0")
    dev.line("XLA Modules", [_module(BURST, 22 * ms * i, 20 * ms, 11 + i)
                             for i in range(4)])
    dev.line("XLA Ops", [(DOT, 22 * ms * i + ms, 3 * ms, {})
                         for i in range(4)])
    host = _Plane(2, "/host:CPU")
    events = [("bench_trace_window", 0, 100 * ms, {})]
    for seq, at, (m, local, touched) in zip(
            (9, 10), fold_at, ((2, 100, 10), (4, 400, 30))):
        events += [("ds.build", at, ms, {}),
                   ("ds.burst_dispatch", at + 2 * ms, ms, {
                       "seq": seq, "program": BURST, "moe_seq": m,
                       "moe_local": local, "moe_touched": touched})]
    host.line("python3", events)
    host.line("main/7", [_enqueue(fold_at[0] + 2.5 * ms, 19),
                         _enqueue(fold_at[1] + 2.5 * ms, 20)])
    return _ctx(tmp_path, [dev, host], window=(0, 100 * ms),
                op_stats=OP_STATS)


def test_a_cohort_ahead_is_numbered_from_what_the_host_had_folded(tmp_path):
    """No run joins by ``run_id``.  When span 10's build began (55 ms) two
    runs had ended, so the second is dispatch 4; span 9's build (10-11 ms)
    dates nothing (no run had ended)."""
    ctx = _cohort_ahead(tmp_path)
    got = dispatch_join.joined(ctx)
    assert [(d["seq"], d["by_order"]) for d in got] == [
        (3, True), (4, True), (5, True), (6, True)]
    line = dispatch_join.line_of(ctx)
    assert (line["runs"], line["joined"], line["unjoined_head"],
            line["unjoined_tail"]) == (4, 0, 4, 2)
    assert (line["anchor"], line["anchor_conflicts"]) == ("moe_seq", 0)
    assert line["queue_ms"] is None and line["clock_skew_ms"] is None
    # need of dispatches 3 and 4 (the totals' growth from 2 to 4) over the
    # grouped GEMM's 3 ms in each of their two runs
    share = dispatch_join.read(ctx, {**SPEC, "what": "expert_gemm_joined"})
    flops, byts = costs_moe.expert_gemm_cost(300, 20, 64, 32)
    assert share == pytest.approx(
        costs.roofline_share(flops, byts, 6e-3, PEAKS)[0])


def test_a_fold_beside_a_runs_end_dates_nothing(tmp_path):
    """Span 10's build at 61-62 ms, the third run's end at 64: inside the
    guard, and span 9's dates nothing either, so nothing is numbered."""
    ctx = _cohort_ahead(tmp_path, fold_at=(10_000, 61_000))
    assert dispatch_join.joined(ctx) == []
    line = dispatch_join.line_of(ctx)
    assert (line["anchor"], line["unjoined_head"]) == (None, 0)


def test_folds_that_disagree_number_nothing(tmp_path):
    """Span 9's build at 30 ms says the first run is dispatch 2, span 10's
    at 75 ms (three runs ended) that the third is 4: agreed.  Moved to 97 ms
    (four ended) it says the fourth is 4: a conflict."""
    assert len(dispatch_join.joined(
        _cohort_ahead(tmp_path, fold_at=(30_000, 75_000)))) == 4
    ctx = _cohort_ahead(tmp_path, fold_at=(30_000, 97_000))
    assert dispatch_join.joined(ctx) == []
    assert dispatch_join.line_of(ctx)["anchor_conflicts"] == 1


def test_a_trace_without_a_tpu_plane_reads_nothing(tmp_path, capsys):
    host = _Plane(1, "/host:CPU")
    host.line("python3", [
        ("bench_trace_window", 0, 100, {}),
        ("ds.burst_dispatch", 1, 2, {"seq": 1, "program": BURST})])
    ctx = _ctx(tmp_path, [host])
    assert dispatch_join.joined(ctx) == []
    for what in (*ENTRIES, "expert_gemm_joined"):
        assert dispatch_join.read(ctx, {**SPEC, "what": what}) is None
    assert capsys.readouterr().out == ""


def test_a_program_without_seq_reads_nothing(host_ahead):
    """The parent's spans: no ``seq``, no ``program``."""
    run = host_ahead["_xmeta"]
    for a in run["annotations"]:
        a["args"].pop("seq", None)
    raw = {"runs": {0: [(101, BURST, 10000.0, 20000.0)]},
           "enqueues": [(0, 101, 1.0, 2.0, 1.0)]}
    assert dispatch_join.join(raw, run["annotations"], run["devices"]) is None
    host_ahead["_dispatch_join"] = None
    assert dispatch_join.joined(host_ahead) == []
    assert dispatch_join.read(host_ahead, {**SPEC, "what": "queue_ms"}) is None


def test_an_untraced_run_reads_nothing():
    class Off:
        dir, started_at = "/nonexistent", None
    assert dispatch_join.read({"tracer": Off()},
                              {**SPEC, "what": "join_share"}) is None


# ------------------------------------------------- the trace recorded on the v5e

@pytest.fixture(scope="module")
def recorded():
    """The recording predates the spans: one is laid round each of its four
    ``PJRT_LoadedExecutable_Execute`` calls, as the train engine's
    ``ds.dispatch`` lies."""
    trace = xtrace.load(RECORDED)
    calls = sorted((a, b) for events in trace["host"].values()
                   for name, a, b in events
                   if name == "PJRT_LoadedExecutable_Execute")
    notes = [{"name": "ds.dispatch", "thread": "python3~0",
              "start_ns": a - 1000, "end_ns": b + 1000,
              "args": {"step": 5 + i, "program": "train_batch"}}
             for i, (a, b) in enumerate(calls)]
    return _joined({"devices": xmeta.device_ops(RECORDED),
                    "annotations": notes}, RECORDED,
                   xtrace.window_of(trace))


def test_the_recordings_four_runs_pair_with_its_four_enqueues(recorded):
    raw = dispatch_join.decode(RECORDED)
    assert [r[0] for r in raw["runs"][0]] == [7, 8, 9, 10]
    assert [(e[0], e[1]) for e in raw["enqueues"]] == [
        (0, 7), (0, 8), (0, 9), (0, 10)]
    got = dispatch_join.joined(recorded)
    assert [d["seq"] for d in got] == [5, 6, 7, 8]
    assert all(d["runs"][0][0] == "train_batch" and not d["by_order"]
               for d in got)
    # run times are xmeta's, to the picosecond, not ProfileData's whole ns
    mods = recorded["_xmeta"]["devices"][0]["modules"]
    assert [d["runs"][0] for d in got] == mods
    assert 0 < mods[0][1] - raw["runs"][0][0][2] < 1


def test_the_recordings_clocks_disagree_by_1_48_ms(recorded):
    got = dispatch_join.of_run(recorded)
    leads = [(d["runs"][0][1] - d["enqueue"][0]) / 1e6
             for d in got["dispatches"]]
    assert [round(x, 2) for x in leads] == [-1.44, -1.44, -1.44, -1.48]
    line = dispatch_join.summary(got, 0, float("inf"))
    assert round(line["clock_skew_ms"], 2) == 1.48
    assert line["clock_skew_ms"] == pytest.approx(-min(leads))
    assert (line["joined"], line["mismatched"], line["ambiguous"]) == (4, 0, 0)


def test_the_command_line_prints_the_line_of_a_trace_file(capsys):
    """The recording holds no spans, so nothing joins: the line is empty,
    and nothing is raised."""
    assert dispatch_join.main([RECORDED]) == 0
    assert capsys.readouterr().out.strip() == '{"phase": "dispatch_join"}'
