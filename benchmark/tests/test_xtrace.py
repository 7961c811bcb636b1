"""The reduction from a trace to numbers, on a hand-made trace with the
layout of a v5e trace (exact values) and on a small trace recorded on the
v5e in PR 25 (sanity: it parses, busy <= window, names are found)."""

import os

import pytest

import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def trace():
    return xtrace.load(os.path.join(DATA, "small_trace.textproto"))


def test_window_is_the_benchmarks_own_span(trace):
    assert xtrace.window_of(trace) == (1000, 2600)


def test_busy_union_and_idle_share(trace):
    lo, hi = xtrace.window_of(trace)
    # ops: [0,100) [100,350) with [150,250) inside, [500,600) [600,900),
    # [1200,1500)  ->  350 + 400 + 300 ns busy of 1600
    assert xtrace.busy(trace, lo, hi)[0] == [(1000, 1350), (1500, 1900),
                                             (2200, 2500)]
    assert xtrace.busy_seconds(trace, lo, hi) == pytest.approx(1050e-9)
    idle = 1 - xtrace.busy_seconds(trace, lo, hi) / ((hi - lo) / 1e9)
    assert idle == pytest.approx(550 / 1600)


def test_per_name_kernel_time(trace):
    lo, hi = xtrace.window_of(trace)
    ops = xtrace.op_seconds(trace, lo, hi)
    assert ops["fusion"] == pytest.approx((100 + 250 + 100 + 300) * 1e-9)
    assert ops["all-gather"] == pytest.approx(100e-9)
    assert ops["copy"] == pytest.approx(300e-9)
    assert xtrace.op_family("%fusion.12.3") == "fusion"
    text = ("%Attention_0.97 = (bf16[8,16,1024,64]{3,2,1,0:T(8,128)(2,1)}, "
            "bf16[8,16]{1,0:T(8,128)(2,1)S(1)}) custom-call(bf16[8] %p)")
    assert xtrace.short_name(text) == "Attention_0.97"
    assert xtrace.opcode_of(text) == "custom-call"
    assert xtrace.op_family(text) == "Attention_0"
    assert trace["opcode"]["fusion.12"] == ""       # bare names carry none
    assert xtrace.module_name("jit_train_batch(123)") == "train_batch"


def test_module_runs_and_clipping(trace):
    lo, hi = xtrace.window_of(trace)
    runs = xtrace.module_runs(trace, lo, hi)
    assert [r[0] for r in runs] == ["train_batch", "train_batch",
                                    "ragged_decode_burst"]
    # a window that cuts the first program leaves it out, and clips ops
    assert len(xtrace.module_runs(trace, 1100, hi)) == 2
    assert xtrace.busy(trace, 1100, 1300)[0] == [(1100, 1300)]


def test_gap_attribution(trace):
    lo, hi = xtrace.window_of(trace)
    gaps = xtrace.gaps(xtrace.busy(trace, lo, hi)[0], lo, hi)
    assert gaps == [(1350, 1500), (1900, 2200), (2500, 2600)]
    # the longest gap: scheduler_round covers all 300 ns and build_batch,
    # inside it, 200 of them: the innermost span that covers half wins
    assert xtrace.host_span_in(trace, 1900, 2200) == "python3:build_batch"
    # shard_args covers [1380, 1480) of the first gap
    assert xtrace.host_span_in(trace, 1350, 1500) == "python3:shard_args"
    assert xtrace.host_span_in(trace, 2500, 2600) == "none"
    b = xtrace.breakdown(trace, lo, hi)
    assert b["device_ops"][0][0] == "fusion"
    assert b["idle_gaps"][0] == ["python3:build_batch",
                                 pytest.approx(300e-9)]


def test_the_benchmarks_own_poll_thread_is_not_the_host(trace):
    # the second python3 line of the file sleeps through the longest gap;
    # it is run.py's trace thread and is left out
    assert list(trace["host"]) == ["python3"]
    assert all(n != "$time sleep" for n, _, _ in trace["host"]["python3"])


def test_exposed_collective_time(trace):
    lo, hi = xtrace.window_of(trace)
    # all-gather.1 [150,250) runs wholly under fusion.3 [100,350): hidden
    assert xtrace.exposed_collective_seconds(
        trace, lo, hi, lambda n: n.startswith("all-gather")) == 0.0
    # were fusion.3 the collective, 250 - 100 (under all-gather) and the
    # whole second one would be exposed
    assert xtrace.exposed_collective_seconds(
        trace, lo, hi, lambda n: n == "fusion.3") == pytest.approx(
            (150 + 300) * 1e-9)


def test_a_loop_hides_no_collective(trace):
    lo, hi = xtrace.window_of(trace)
    # were fusion.3, which spans all-gather.1, a `while`, the all-gather in
    # its body would be exposed: nothing else runs beside it
    trace = {**trace, "opcode": {**trace["opcode"], "fusion.3": "while"}}
    assert xtrace.exposed_collective_seconds(
        trace, lo, hi, lambda n: n.startswith("all-gather")) == \
        pytest.approx(100e-9)


def test_recorded_v5e_trace_parses():
    path = os.path.join(DATA, "recorded_v5e.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    t = xtrace.load(path)
    assert t["devices"], "no /device:TPU:<n> plane found"
    lo, hi = xtrace.window_of(t)
    busy = xtrace.busy_seconds(t, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    assert xtrace.module_runs(t, lo, hi), "no step program in the window"
    assert xtrace.breakdown(t, lo, hi)["device_ops"]
