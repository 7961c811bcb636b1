"""BENCHMARK.json against the contract's letter and the benchmark's files;
and, since PR 38 folded the per-cell suffix families and PR 59 the per-family
copies, that a metric is one entry and that neither fold dropped a reading."""

import json
import os
import re

import pytest
from run import metric_applies
from test_cells import AT_PR58, SINCE_PR59, name_since_pr59

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_.\-/%]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_of(metric, m):
    return metric.get("workloads", [w["name"] for w in m["workloads"]])


def test_keys_names_and_units():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    # the contract's cap: a manifest past it is refused before a run
    assert 1 <= len(m["per_layer"]) <= 128
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_every_cell_has_its_files_and_metrics():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    used = set()
    for w in m["workloads"]:
        c = configs[w["config"]]
        used.add(w["config"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
        assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
        assert os.path.exists(os.path.join(
            BENCH, "runners", cfg["run"]["runner"] + ".py"))
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        e2e = [e["name"] for e in m["end_to_end"]
               if w["name"] in cells_of(e, m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in cells_of(p, m) for p in m["per_layer"])
    assert used == set(configs)
    assert len({c["file"] for c in m["configs"]}) == len(configs)


def test_moves_is_reported_wherever_the_metric_is():
    m = manifest()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for p in m["per_layer"]:
        assert p["moves"] in e2e, p
        assert set(cells_of(p, m)) <= set(cells_of(e2e[p["moves"]], m)), p


def spec_of(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def test_metric_files_agree_with_the_manifest():
    """A spec file says what a metric is and how it is read; which cells
    read it is the manifest's alone to say, so a cell joins a metric by one
    line of ``BENCHMARK.json``."""
    m = manifest()
    for p in m["per_layer"]:
        spec = spec_of(p["name"])
        assert "workloads" not in spec, p["name"]
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == p[k], (p["name"], k)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(BENCH, "metrics"))}
    assert files == {p["name"] for p in m["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {p["layer"] for p in m["per_layer"]}:
        assert layer in perf, f"layer {layer!r} is not in PERF.md"


def test_no_two_entries_share_a_spec():
    """A cell whose traffic reads an accepted metric joins that entry's
    ``workloads`` list; it does not bring a copy under a suffix (128 entries
    were 75 metrics before PR 38, and the contract's cap was reached)."""
    seen = {}
    for p in manifest()["per_layer"]:
        spec = spec_of(p["name"])
        del spec["name"]
        key = json.dumps(spec, sort_keys=True)
        assert key not in seen, (p["name"], seen[key])
        seen[key] = p["name"]


# ---- the fold of PR 38: nothing dropped --------------------------------
B, T, M, D = ("serve-mistral-batch", "serve-trinity-mixedlen-batch",
              "serve-moonlight-longctx-batch", "serve-dots3-longdoc-batch")
SUFFIX = {".batch": B, ".mixedlen": T, ".latent": M, ".sparse": D}
# one entry over B T M D, under the name the oldest of them was accepted by
# (tier-1 ``tests/test_one_clock.py`` opens four of these spec files by name)
UNDER_BATCH = {"decode_step_device_ms", "decode_attn_ms", "mixed_attn_ms",
               "sched_gap_ms_per_round", "padding_waste_share",
               "mixed_one_row_slot_share", "prefill_live_item_share",
               "peak_hbm_gib"}
# one entry over the expert cells (T M D, T D or M D) under the base name;
# where the family has a ``.batch`` member it reads by another reader
UNDER_BASE = {"decode_mlp_ms", "decode_other_ms", "mixed_mlp_ms",
              "mixed_other_ms", "serve_unscoped_share",
              "decode_moe_experts_ms", "mixed_moe_experts_ms",
              "mixed_moe_shared_ms", "moe_rows_per_touched_expert",
              "expert_gemm_roofline", "decode_moe_route_ms",
              "mixed_moe_route_ms", "decode_moe_shared_ms",
              "kv_window_pages_released_share", "sched_tokens_per_dispatch",
              "decode_mla_absorb_ms", "mixed_mla_absorb_ms"}
# a family that leaves two groups: by ``sched_rounds`` (B T), by ``latent``
OTHERWISE = {"decode_live_context_tokens.mixedlen":
             "decode_live_context_tokens.batch",
             "decode_live_context_tokens.sparse":
             "decode_live_context_tokens.latent"}
# what PR 36 left out at the cap, read again at no entry
JOINED = {(D, n) for n in (
    "decode_moe_route_ms", "mixed_moe_route_ms", "decode_moe_shared_ms",
    "moe_local_share_of_assignments", "decode_mla_absorb_ms",
    "mixed_mla_absorb_ms")}


def name_since_pr38(old, cell):
    """The entry that reads in ``cell`` what ``old`` read there at PR 37."""
    if old in OTHERWISE:
        return OTHERWISE[old]
    base, dot, suffix = old.rpartition(".")
    if SUFFIX.get(dot + suffix) != cell:
        return old                       # no per-cell suffix: as it was
    if base in UNDER_BATCH:
        return base + ".batch"
    if base in UNDER_BASE and cell != B:
        return base
    return old


with open(os.path.join(HERE, "data", "manifest_lists.json")) as _f:
    LISTS = json.load(_f)


@pytest.mark.parametrize("cell", sorted(LISTS["readings_at_pr37"]))
def test_the_fold_dropped_no_reading(cell):
    """The (cell, reading) pairs of the manifest PR 38 started from map one
    to one onto the pairs of the accepted lists, plus the six joined."""
    old = LISTS["readings_at_pr37"][cell]
    mapped = [name_since_pr38(n, cell) for n in old]
    assert len(set(mapped)) == len(old)              # one to one
    accepted = {n for n, cells in LISTS["accepted_at_pr38"]["per_layer"]
                if cells is None or cell in cells}
    joined = {n for c, n in JOINED if c == cell}
    assert set(mapped) | joined == accepted and not set(mapped) & joined
    # and a traced run of today's manifest still reads them all
    now = {p["name"] for p in manifest()["per_layer"]
           if metric_applies(p, cell)}
    assert {name_since_pr59(n) for n in accepted} <= now


# ---- the fold of PR 59: nothing dropped --------------------------------
@pytest.mark.parametrize("cell", sorted(AT_PR58["readings_at_pr58"]))
def test_the_second_fold_dropped_no_reading(cell):
    """The (cell, reading) pairs of the manifest PR 59 started from map one
    to one onto today's, but ``expert_gemm_roofline``'s six, which map onto
    ``.joined``'s (each of the six cells read both)."""
    old = AT_PR58["readings_at_pr58"][cell]
    kept = [n for n in old if n != "expert_gemm_roofline"]
    mapped = [name_since_pr59(n) for n in kept]
    assert len(set(mapped)) == len(kept)             # one to one
    if len(kept) < len(old):
        assert "expert_gemm_roofline.joined" in kept
    now = {p["name"] for p in manifest()["per_layer"]
           if metric_applies(p, cell)}
    assert set(mapped) <= now
    # what today's manifest reads beyond them came with a later cell
    at_pr59 = {name_since_pr59(n) for n, _ in
               AT_PR58["accepted_at_pr58"]["per_layer"]}
    assert not (now - set(mapped)) & at_pr59


def test_the_second_fold_left_119_entries_and_no_folded_name():
    """128 entries, the contract's cap, were 119 metrics; the nine freed are
    the next configuration's.  A family's suffix is gone from the names and
    from ``metrics/``: a new kind of layer brings a file under
    ``layer_costs/`` and joins ``serve_step_mfu`` and the ``.by_layer``
    rooflines by a line."""
    at_pr58 = [n for n, _ in AT_PR58["accepted_at_pr58"]["per_layer"]]
    assert len(at_pr58) == 128
    assert len({name_since_pr59(n) for n in at_pr58}) == 119
    names = [p["name"] for p in manifest()["per_layer"]]
    assert not set(names) & set(SINCE_PR59)
    # the accepted entries in the order they were: a copy taken out, the
    # oldest of its group (Trinity's) renamed where it stood
    assert names[:119] == [name_since_pr59(n) for n in at_pr58
                           if n not in SINCE_PR59 or n.endswith(".mixedlen")]
    mfu = next(p for p in manifest()["per_layer"]
               if p["name"] == "serve_step_mfu")
    assert mfu["workloads"][-4:] == [
        "serve-granite4h-shortchat-batch", "serve-lfm2-ragdoc-batch",
        "serve-mimo-reasoning-batch", "serve-sala-longdoc-batch"]
    for p in manifest()["per_layer"]:
        if p["name"].endswith(".by_layer"):
            assert p["workloads"][:3] == [
                "serve-trinity-mixedlen-batch", "serve-lfm2-ragdoc-batch",
                "serve-mimo-reasoning-batch"]
    # every serving cell still reads the whole step's share under ``mfu``
    for w in manifest()["workloads"]:
        if w["name"].startswith("serve-"):
            assert sum("mfu" in p["name"] for p in manifest()["per_layer"]
                       if metric_applies(p, w["name"])) == 1, w["name"]
