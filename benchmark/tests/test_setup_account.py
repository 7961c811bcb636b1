"""The eight ``setup_*`` readings (PR 40): the reader on a hand-made account,
on a program without one, and through a traced rehearsal of a serving and a
train cell."""

import json
import os

import pytest
import setup_account
from test_cells import (AT_PR58, MANIFEST, no_longer_read, readings,
                        run_cell)

NAMES = ["setup_trace_s", "setup_lower_s", "setup_compile_s",
         "setup_cache_load_s", "setup_step_programs",
         "setup_step_programs_s", "setup_engine_init_s", "setup_import_s"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def spec_of(name):
    with open(os.path.join(os.path.dirname(setup_account.__file__),
                           os.pardir, "metrics", name + ".json")) as f:
        return json.load(f)


class Window:
    t0 = 100.0          # the window opened at 100 s of perf_counter's clock


def record(program, at_s, trace, lower, compile_, load, **key):
    return {"program": program, **key, "trace_s": trace, "lower_s": lower,
            "compile_s": compile_, "cache_load_s": load,
            "host_ns": int(at_s * 1e9)}


ACCOUNT = {
    "records": [record("other", 3.0, 1.0, 0.5, 0.0, 0.25),
                record("put_mixed", 20.0, 2.0, 1.0, 0.0, 0.5, bucket=64),
                record("mixed", 40.0, 4.0, 2.0, 8.0, 0.0, bucket=64),
                record("burst", 60.0, 0.5, 0.25, 0.0, 0.125, steps=8),
                # a shape the warm-up missed, compiled inside the window
                record("mixed", 130.0, 64.0, 64.0, 64.0, 64.0, bucket=128)],
    "init_spans": [
        {"engine": "inference_v2", "part": "init_params", "seconds": 3.0,
         "host_ns": int(5e9)},
        {"engine": "inference_v2", "part": "engine_init", "seconds": 7.0,
         "host_ns": int(4e9)},
        {"engine": "inference_v2", "part": "engine_init", "seconds": 9.0,
         "host_ns": int(140e9)}],
    "import_seconds": 2.5}
WANT = {"setup_trace_s": 7.5, "setup_lower_s": 3.75, "setup_compile_s": 8.0,
        "setup_cache_load_s": 0.875, "setup_step_programs": 3,
        "setup_step_programs_s": 18.375, "setup_engine_init_s": 7.0,
        "setup_import_s": 2.5}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_hand_made_account(name):
    ctx = {"_setup_account": ACCOUNT, "tracer": Window()}
    assert setup_account.read(ctx, spec_of(name)) == WANT[name]


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_account_reads_nothing(name):
    assert setup_account.read({"_setup_account": None, "tracer": Window()},
                              spec_of(name)) is None


def test_the_eight_are_entries_of_every_cell():
    entries = {p["name"]: p for p in MANIFEST["per_layer"]}
    for name in NAMES:
        e = entries[name]
        assert e["workloads"] == CELLS and e["moves"] == "setup_s"
        assert e["layer"] == "entry points and start-up"
        assert e["better"] == "lower"


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reads_what_it_read_and_the_eight(cell):
    """Held by names, not by a count the next cell's entries move: the
    eight, and what the cell read when PR 59 started
    (``data/manifest_lists_pr58.json``) under today's names."""
    assert set(NAMES) <= {p["name"] for p in readings(cell)}
    assert set(NAMES) <= set(AT_PR58["readings_at_pr58"][cell])
    assert not no_longer_read(cell)


@pytest.mark.parametrize("cell,programs", [("serve-mistral-chat", None),
                                           ("train-gpt2m-1chip", 1)])
def test_traced_rehearsal_reads_all_eight(cell, programs):
    out = run_cell(cell, 1, extra=["--rehearse"])
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()
             if x.startswith("{")]
    got = lines[-1]["metrics"]
    for name in NAMES:
        assert got[name]["value"] is not None, name
    for name in ("setup_trace_s", "setup_lower_s", "setup_engine_init_s",
                 "setup_import_s", "setup_step_programs_s"):
        assert got[name]["value"] > 0, name
    if programs is None:        # the runner's own count of what it warmed
        programs = next(x for x in lines
                        if x.get("phase") == "notes")["warm_programs"]
    assert got["setup_step_programs"]["value"] == programs
    assert got["compiles_in_window"]["value"] == 0
    parts = next(x for x in lines if x.get("phase") == "setup")
    inside = sum(got[n]["value"] for n in (
        "setup_trace_s", "setup_lower_s", "setup_compile_s",
        "setup_cache_load_s"))
    assert inside < parts["setup_s"]
