#!/usr/bin/env python3
"""The comparison that decides ``correct`` in a latent-attention cell, taken
apart (PERF.md section 6, PR 33; the configuration's ``tolerances.why``):

    python3 benchmark/tools/mla_compare.py --workload <cell> --seed <n>
        [--plant <fault> | --rehearse]

**Without ``--plant``** it builds the weights and the engine as the serving
runner does, feeds the runner's own two sequences (prefill, then
``decode_positions`` one at a time), and reads the engine's logits against
the plain reference, split into the rows whose experts the engine chose as
the reference does and the rest (``put(with_routes=True)`` beside
``reference.routing``): one JSON line a reading, ``rel_rms``, ``max_abs``,
``argmax_gap`` as the runner computes them (``reading`` below repeats the
runner's arithmetic because the runner's is inline and takes no subset of
rows).

**With ``--plant <fault>``** nothing here compares: ``benchmark/run.py``
itself runs the cell, in this process, with ONE thing swapped, the
reference module, for the same reference with the fault in
(``reference/_mla_faults.py``: its ``FAULTS``, or ``weights_rounded_to_fp8``,
the control in the nearest precision below bf16), so the runner's own
comparison decides ``correct`` under the configuration's own limits and the
last line is the harness's.  It must say ``"correct": false``.  The traffic
is cut to a few short requests and 5 s (``--set``, ``--seconds``): the
comparison comes before the window and does not read it, and the warm-up
shrinks with the mix's longest context.  Nothing here is timed.
"""

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH, os.path.join(BENCH, "reference")):
    sys.path.insert(0, p)


def reading(got, want):
    import numpy as np
    out = {"rel_rms": 0.0, "max_abs": 0.0, "argmax_gap": 0.0}
    for g, w in zip(got, want):
        if not len(g):
            continue
        out["max_abs"] = max(out["max_abs"], float(np.max(np.abs(g - w))))
        out["rel_rms"] = max(out["rel_rms"], float(
            np.sqrt(np.mean((g - w) ** 2) / np.mean(w ** 2))))
        pick = g.argmax(-1)
        out["argmax_gap"] = max(out["argmax_gap"], float(np.max(
            w.max(-1) - w[np.arange(len(pick)), pick])))
    return out


def through_the_harness(args):
    """``run.py``'s ``main`` with the reference swapped for a planted one."""
    import _mla_faults
    import run as bench
    load = bench.load_module

    def load_planted(path, name):
        if name.startswith("bench_reference_"):
            return _mla_faults.planted_reference(args.plant)
        return load(path, name)
    bench.load_module = load_planted
    short = ["--seconds", "5",
             "--set", "arrivals.requests_per_window_s=0.8",
             "--set", 'prompt_tokens={"dist": "fixed", "value": 100}',
             "--set", 'output_tokens={"dist": "fixed", "value": 8}']
    return bench.main(["--workload", args.workload, "--seed", str(args.seed),
                       *short, *(["--rehearse"] if args.rehearse else [])])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plant", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.plant:
        return through_the_harness(args)
    import run as bench
    manifest, cell = bench.load_cell(args.workload)
    cfg = bench.load_json(ROOT, {c["name"]: c for c in manifest["configs"]}[
        cell["config"]]["file"])
    if args.rehearse:
        cfg = {**cfg, **cfg["rehearsal"],
               "run": {**cfg["run"], **cfg["rehearsal"]["run"]}}
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    import _deepseek_mla as ref
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPTConfig
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.parallel.metadata import unbox
    from deepspeed_tpu.runtime.resilience import enable_compilation_cache
    enable_compilation_cache()
    run_cfg, seed = cfg["run"], args.seed
    model_cfg = GPTConfig(**ref.program_config(cfg),
                          max_seq_len=int(run_cfg["max_seq_len"]),
                          dropout=0.0, dtype=jnp.bfloat16,
                          attn_impl="pallas")
    lm = GPTLogits(dataclasses.replace(model_cfg, param_dtype=jnp.bfloat16))
    params = jax.jit(lambda key: unbox(lm.init(
        key, jnp.zeros((1, 8), jnp.int32)))["params"])(
            jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    eng = InferenceEngineV2(
        model_cfg, {"dtype": "bfloat16",
                    "state_manager": run_cfg["state_manager"],
                    "generation": run_cfg["generation"]},
        params=params, seed=int(seed) % (2 ** 31 - 1))
    rng = np.random.default_rng(int(seed) + 17)
    n_dec = int(run_cfg["compare"]["decode_positions"])
    seqs = [rng.integers(0, model_cfg.vocab_size, size=int(n) + n_dec)
            .astype(np.int32) for n in run_cfg["compare"]["prefill_tokens"]]
    uids = list(range(1, len(seqs) + 1))
    got = [[] for _ in seqs]
    routes = [[] for _ in seqs]

    def feed(toks):
        out, rts = eng.put(uids, toks, with_routes=True)
        for i in range(len(seqs)):
            got[i].append(out[i])
            routes[i].append(rts[i])            # [layers, rows, k]
    feed([s[:len(s) - n_dec] for s in seqs])
    for j in range(n_dec):
        feed([s[len(s) - n_dec + j:len(s) - n_dec + j + 1] for s in seqs])
    eng.flush(uids)
    got = [np.stack(g).astype(np.float32) for g in got]
    rows = [list(range(len(s) - n_dec - 1, len(s))) for s in seqs]

    def want(params, sizes):
        return [np.asarray(ref.logits(params, s, sizes, rows=r))
                for s, r in zip(seqs, rows)]

    def say(what, **kw):
        print(json.dumps({"reading": what, "seed": seed, **kw}), flush=True)

    healthy = want(params, cfg)
    say("healthy", rows=sum(len(r) for r in rows), **reading(got, healthy),
        **{k: v for k, v in cfg["tolerances"].items() if k != "why"})
    # rows routed as the reference routes them, and the others
    same = []
    for s, r, rt in zip(seqs, rows, routes):
        mine = np.concatenate(rt, axis=1)       # [layers, T, k] over all T
        theirs = ref.routing(params, s, cfg)
        ok = np.ones(len(s), bool)
        margin = np.full(len(s), np.inf)
        for layer, (chosen, m) in enumerate(theirs):
            agree = (np.sort(mine[layer], -1)
                     == np.sort(np.asarray(chosen), -1)).all(-1)
            margin = np.where(ok & ~agree, np.minimum(margin, np.asarray(m)),
                              margin)
            ok &= agree
        # split on the row's OWN routing: that decides its FFN; its
        # context's routing enters through attention only
        same.append((ok[r], margin[r]))
    for name, pick in (("routed_alike", lambda ok: ok),
                       ("routed_otherwise", lambda ok: ~ok)):
        sel = [pick(ok) for ok, _ in same]
        say(name, rows=int(sum(s.sum() for s in sel)),
            **reading([g[s] for g, s in zip(got, sel)],
                      [w[s] for w, s in zip(healthy, sel)]))
    flips = np.concatenate([m[~ok] for ok, m in same])
    if len(flips):
        say("first_disagreement_margin", rows=int(len(flips)),
            max=float(flips.max()), median=float(np.median(flips)))


if __name__ == "__main__":
    sys.exit(main())
