"""BENCHMARK.json against the contract's letter and the benchmark's files."""

import json
import os
import re

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


def test_metric_files_agree_with_the_manifest():
    m = manifest()
    layers = {}
    for p in m["per_layer"]:
        with open(os.path.join(BENCH, "metrics", p["name"] + ".json")) as f:
            spec = json.load(f)
        for k, v in p.items():
            assert spec[k] == v, (p["name"], k)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
        layers.setdefault(p["layer"], 0)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"layer {layer!r} is not in PERF.md"
