"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell by new files and new entries alone.  Rehearsed here in a scratch copy of
the benchmark: nothing that is there is edited, only ``BENCHMARK.json`` gains
entries."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_new_files_and_entries_are_enough(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "deepspeed_tpu"), root / "deepspeed_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    b = root / "benchmark"

    # 1. a configuration: its file of sizes and its plain reference
    with open(b / "configs" / "gpt2-medium.json") as f:
        cfg = json.load(f)
    cfg.update(name="throwaway", reference="benchmark/reference/throwaway.py")
    cfg["rehearsal"]["n_layer"] = 1
    (b / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    shutil.copy(b / "reference" / "gpt2-medium.py",
                b / "reference" / "throwaway.py")
    # 2. a traffic mix: a data file the one generator reads
    with open(b / "traffic" / "pretrain-1k.json") as f:
        mix = json.load(f)
    mix["rehearsal"]["seq_len"] = 64
    (b / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    # 3. a per-layer metric: its file and a small reader of its own
    entry = {"name": "throwaway_metric", "unit": "count", "better": "lower",
             "source": "program_counter", "layer": "train step",
             "moves": "train_tokens_per_s_per_chip",
             "workloads": ["throwaway-cell"]}
    spec = {k: v for k, v in entry.items() if k != "workloads"}
    (b / "metrics" / "throwaway_metric.json").write_text(
        json.dumps({**spec, "reader": "throwaway_reader"}))
    (b / "readers" / "throwaway_reader.py").write_text(
        "def read(ctx, spec):\n    return ctx['steps']\n")
    # and the entries; the cell's name at the end of the lists it joins
    m["configs"].append({"name": "throwaway", "source": cfg["source"],
                         "file": "benchmark/configs/throwaway.json",
                         "reduced": sorted(cfg["reduced"]), "why": "test"})
    m["workloads"].append({"name": "throwaway-cell", "config": "throwaway",
                           "traffic": "throwaway-mix", "chips": 1,
                           "why": "test"})
    m["per_layer"].append(entry)
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] in ("train_tokens_per_s_per_chip",
                         "train_step_device_ms"):
            e["workloads"].append("throwaway-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(b / "run.py"), "--workload", "throwaway-cell",
         "--seed", "9", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"]["throwaway_metric"]["value"] == last["attempted"]
    assert "compiles_in_window" in last["metrics"]   # the cell-less metrics
