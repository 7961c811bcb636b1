"""chip_smoke.py on the CPU: it must FAIL without a chip, and its phases must
run end to end at the rehearsal size (``--rehearse``: tiny model, whatever
backend jax has, kernels interpreted) — the first of the three rehearsals
that cost no chip time.  The four-chip phase rehearses on four of the
virtual CPU devices and checks where the shards landed.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.runtime import resilience  # noqa: E402

FIXED_CACHE_DIR = resilience.DEFAULT_CACHE_DIR     # before any fixture moves it


@pytest.fixture(scope="module")
def run_smoke(tmp_path_factory):
    """Run the script's ``main`` in this process: its JSON lines collected,
    the compile cache it turns on confined to a temp dir and switched off
    again afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    mp = pytest.MonkeyPatch()
    mp.delenv(resilience.CACHE_DIR_ENV, raising=False)
    mp.setattr(resilience, "DEFAULT_CACHE_DIR",
               str(tmp_path_factory.mktemp("jax_cache")))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}

    def run(*argv):
        lines = []
        mp.setattr(chip_smoke, "emit", lines.append)
        chip_smoke.main([*argv, "--out",
                         str(tmp_path_factory.mktemp("smoke_out"))])
        return {ln.get("phase", "last"): ln for ln in lines}

    yield run
    mp.undo()
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture(scope="module")
def rehearsal(run_smoke):
    return run_smoke("--rehearse")


@pytest.fixture(scope="module")
def rehearsal4(run_smoke, devices):
    return run_smoke("--rehearse", "--chips", "4")


# ------------------------------------------------------------- no chip

def test_without_a_chip_it_fails_with_ok_false():
    """The driver's contract: run with no arguments and no accelerator, the
    script exits non-zero and its last line says ``"ok": false``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=60)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": last["device"]["kind"],
        "count": last["device"]["count"]}}
    assert "no TPU" in r.stderr
    assert '"phase": "train"' not in r.stdout       # nothing ran on the CPU


# ------------------------------------------------------ one-chip phases

def test_rehearsal_final_line(rehearsal):
    assert rehearsal["last"] == {
        "ok": True, "rehearsal": True,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}}
    assert set(rehearsal) == {"device", "train", "serve", "last"}


def test_rehearsal_train_phase(rehearsal):
    t = rehearsal["train"]
    assert len(t["losses"]) >= 8 and t["losses"][-1] < t["losses"][0]
    ck = t["checkpoint"]
    assert ck["next_loss_restored"] == pytest.approx(ck["next_loss_live"],
                                                     rel=1e-3)
    # attention was demanded from the registry, never the silent XLA path
    assert t["dispatch"] and all(d["impl"] == "pallas" and
                                 d["reason"] == "forced"
                                 for d in t["dispatch"])


def test_rehearsal_serve_phase(rehearsal):
    s = rehearsal["serve"]
    # asked for the default 64, got the 128-aligned kv-major page hd<128 needs
    assert (s["kv_block_size_asked"], s["kv_block_size"],
            s["kv_layout"]) == (64, 128, "kv-major")
    ref = s["vs_xla_reference"]
    assert ref["prefill_logits_max_abs_err"] <= ref["logit_atol"]
    assert ref["worst_gap_to_reference_argmax"] <= ref["tie_tol"]
    assert ref["tokens_checked"] == s["requests"] * s["new_tokens_each"]
    assert {d["op"] for d in s["dispatch"]} == {
        "paged_attention", "ragged_prefill_attention"}
    assert all(d["impl"] == "pallas" for d in s["dispatch"])
    assert all(d["impl"] == "xla" for d in s["reference_dispatch"])
    names = set(s["kernel_in_step_programs"])
    assert any("decode" in n for n in names)
    assert any("forward" in n for n in names)


# ------------------------------------------------------ four-chip phase

def test_rehearsal_four_chip_phase_only(rehearsal4):
    assert set(rehearsal4) == {"device", "sharded", "last"}
    assert rehearsal4["last"]["ok"] is True


def test_rehearsal_four_chip_shard_placement(rehearsal4):
    p = rehearsal4["sharded"]["placement"]
    assert len(p["bytes_per_device"]) == 4          # nothing all on device 0
    assert p["evenly_split_share"] >= 0.95
    quarter = p["state_bytes"] / 4
    assert all(abs(b - quarter) <= 0.1 * quarter
               for b in p["bytes_per_device"])


def test_rehearsal_four_chip_matches_one_device(rehearsal4):
    s = rehearsal4["sharded"]
    assert len(s["losses_fsdp4"]) >= 8
    assert s["max_rel_loss_diff"] <= s["loss_rtol"]
    assert s["collectives_in_compiled_step"]["all-gather"]


# ---------------------------------------------------------- cache helper

@pytest.mark.parametrize("outside", [True, False], ids=["env-set", "env-unset"])
def test_cache_helper_placement(monkeypatch, tmp_path, outside):
    """JAX_COMPILATION_CACHE_DIR wins and no path is set in code; unset, the
    helper returns the fixed in-checkout path — no temp name, pid or time."""
    before = jax.config.jax_compilation_cache_dir
    floors = (jax.config.jax_persistent_cache_min_entry_size_bytes,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    fixed = os.path.join(REPO, ".jax_cache")
    assert FIXED_CACHE_DIR == fixed
    monkeypatch.setattr(resilience, "DEFAULT_CACHE_DIR", FIXED_CACHE_DIR)
    try:
        if outside:
            monkeypatch.setenv(resilience.CACHE_DIR_ENV, str(tmp_path))
            assert resilience.enable_compilation_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv(resilience.CACHE_DIR_ENV, raising=False)
            assert resilience.enable_compilation_cache() == fixed
            assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          floors[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floors[1])


# ------------------------------------------------------------- --afmoe

@pytest.fixture(scope="module")
def afmoe(run_smoke):
    return run_smoke("--rehearse", "--afmoe", "--faults", "--seed", "5")


@pytest.mark.parametrize("part", ["runner_rows", "chunked_prompt"])
def test_afmoe_rows_split_by_routing_cross_the_window(afmoe, part):
    """Both drives release window pages, and the phase held every row the
    engine routed as the reference routes it to bf16 (it raises otherwise)."""
    line = afmoe["afmoe"]
    assert afmoe["last"]["ok"] is True
    rows = line[part] if part == "runner_rows" else line[part]["rows"]
    assert rows["agree_rows"] > rows["flip_rows"] >= 0
    assert rows["agree_rel_max"] <= 2 * chip_smoke.AFMOE_ROW_REL
    released = (line if part == "runner_rows"
                else line[part])["window_pages_released"]
    assert released > 0


@pytest.mark.parametrize("fault", ["window_page_released_one_too_early",
                                   "reference_on_fp8_e4m3_weights"])
def test_afmoe_planted_faults_read_as_not_correct(afmoe, fault):
    """At the tiny preset a page is wider than the window, so a page given
    back one too early is the whole window: both readings are gross."""
    assert afmoe["afmoe"]["faults"][fault]["caught_by_runner_limits"]
