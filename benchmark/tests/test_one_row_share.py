"""``mixed_one_row_slot_share.batch``: the share of a mixed step's slots that
hold one row (the paged decode kernel attends them inside the mixed
program).  Entries, files, and ``span_counters`` on spans as the program
writes them."""

import json
import os

import pytest
import span_counters
from test_cells import MANIFEST

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "mixed_one_row_slot_share.batch"
CELLS = ["serve-mistral-batch", "serve-trinity-mixedlen-batch"]


def span(name, t, **args):
    return {"name": name, "thread": "t", "start_ns": t, "end_ns": t + 5,
            "args": {k: str(v) for k, v in args.items()}}


@pytest.mark.parametrize("cell", CELLS)
def test_entry_file_and_reading(cell):
    entry = next(p for p in MANIFEST["per_layer"] if p["name"] == NAME)
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    keys = set(entry) - {"workloads"}      # the manifest alone names cells
    assert {k: spec[k] for k in keys} == {k: entry[k] for k in keys}
    assert cell in entry["workloads"]
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["source"] == "program_counter"
    assert spec["reader"] == "span_counters"
    # three mixed steps of 14 slots, 13 of them riders, between decode
    # dispatches that repeat the totals; the window's first span is the
    # base the growth is taken from
    ctx = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.burst_dispatch", 5, mixed_seqs=140, one_row_seqs=120),
        span("ds.mixed_dispatch", 10, mixed_seqs=154, one_row_seqs=133),
        span("ds.round", 15),
        span("ds.mixed_dispatch", 20, mixed_seqs=168, one_row_seqs=146),
        span("ds.decode_dispatch", 30, mixed_seqs=168, one_row_seqs=146),
        span("ds.mixed_dispatch", 40, mixed_seqs=182, one_row_seqs=159),
        span("ds.mixed_dispatch", 1000, mixed_seqs=999, one_row_seqs=0)]},
        "trace_window": (0, 100)}
    assert abs(span_counters.read(ctx, spec) - 100.0 * 39 / 42) < 1e-9
    # a program from before the totals (the parent), and a window that held
    # no mixed step, read nothing
    bare = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.mixed_dispatch", 10, tokens=5, seqs=3)]},
        "trace_window": (0, 100)}
    assert span_counters.read(bare, spec) is None
    still = {"_xmeta": {"devices": {}, "annotations": [
        span("ds.burst_dispatch", 10, mixed_seqs=7, one_row_seqs=5),
        span("ds.burst_dispatch", 20, mixed_seqs=7, one_row_seqs=5)]},
        "trace_window": (0, 100)}
    assert span_counters.read(still, spec) is None
