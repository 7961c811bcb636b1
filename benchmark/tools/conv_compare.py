#!/usr/bin/env python3
"""The comparison that decides ``correct`` in the cell of a model with
short-conv layers (PERF.md section 6, PR 46; the configuration's
``tolerances.why``), as ``ssm_compare.py`` is for scan layers:

    python3 benchmark/tools/conv_compare.py --workload <cell>
        [--seeds <n> ...] [--faults <fault> ... | all] [--rehearse]
    python3 benchmark/tools/conv_compare.py --workload <cell> --seed <n>
        --plant <fault> [--rehearse]

**Without ``--plant``** it builds the weights and the engine as the serving
runner does, once a seed (the engines share their compiled step programs),
feeds the runner's own two sequences (prefill through ``put()``, the longer
one as ``put_chunked`` splits it, then ``decode_positions`` one at a time)
and reads the engine's logits against the plain reference: one JSON line a
seed, ``rel_rms``, ``max_abs``, ``argmax_gap`` as the runner computes them
(``mla_compare.reading`` repeats the runner's arithmetic because the
runner's is inline), each sequence's reading beside the pooled one (the
runner takes the worse of the two sequences; with ``--routing`` the first
seed's decoded rows also split by whether every expert layer routed them as
the reference's own forward does); then, for the FIRST seed, the
same engine logits against the reference with each of ``--faults`` in
(``reference/_lfm2_faults.py``: its ``FAULTS`` and the control,
``weights_rounded_to_fp8``): what the cell's limits have to separate.

**With ``--plant <fault>``** nothing here compares: ``benchmark/run.py``
itself runs the cell, in this process, with ONE thing swapped, the
reference module, for the same reference with the fault in, so the runner's
own comparison decides ``correct`` under the configuration's own limits and
the last line is the harness's.  It must say ``"correct": false``.  The
traffic is cut to a few short requests and 5 s (``--set``, ``--seconds``):
the comparison comes before the window and does not read it.  Nothing here
is timed.
"""

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH, os.path.join(BENCH, "reference"), HERE):
    sys.path.insert(0, p)

from mla_compare import reading  # noqa: E402  (the runner's arithmetic)


def through_the_harness(args):
    """``run.py``'s ``main`` with the reference swapped for a planted one."""
    import _lfm2_faults
    import run as bench
    load = bench.load_module

    def load_planted(path, name):
        if name.startswith("bench_reference_"):
            return _lfm2_faults.planted_reference(args.plant)
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
    ap.add_argument("--seeds", type=int, nargs="+", default=None)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--plant", default=None)
    ap.add_argument("--routing", action="store_true",
                    help="split the first seed's decoded rows by whether "
                    "every expert layer routed them as the reference does")
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

    import _lfm2_faults
    import _lfm2_moe as ref
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPTConfig
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.parallel.metadata import unbox
    from deepspeed_tpu.runtime.resilience import enable_compilation_cache
    enable_compilation_cache()
    run_cfg = cfg["run"]
    model_cfg = GPTConfig(**ref.program_config(cfg),
                          max_seq_len=int(run_cfg["max_seq_len"]),
                          dropout=0.0, dtype=jnp.bfloat16,
                          attn_impl="pallas")
    lm = GPTLogits(dataclasses.replace(model_cfg, param_dtype=jnp.bfloat16))
    make = jax.jit(lambda key: unbox(lm.init(
        key, jnp.zeros((1, 8), jnp.int32)))["params"])
    faults = (list(_lfm2_faults.FAULTS) + [_lfm2_faults.CONTROL]
              if args.faults == ["all"] else args.faults)
    steps = {}
    limits = {k: v for k, v in cfg["tolerances"].items() if k != "why"}
    n_dec = int(run_cfg["compare"]["decode_positions"])

    def one_seed(seed, faults, routing=False):
        """One seed's engine, its logits and the readings against them (a
        function, so that the seed's weights and its pools are gone before
        the next seed's are made)."""
        params = make(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
        eng = InferenceEngineV2(
            model_cfg, {"dtype": "bfloat16",
                        "state_manager": run_cfg["state_manager"],
                        "generation": run_cfg["generation"]},
            params=params, seed=int(seed) % (2 ** 31 - 1), steps_cache=steps)
        rng = np.random.default_rng(int(seed) + 17)
        seqs = [rng.integers(0, model_cfg.vocab_size, size=int(t) + n_dec)
                .astype(np.int32)
                for t in run_cfg["compare"]["prefill_tokens"]]
        uids = list(range(1, len(seqs) + 1))
        got = [[] for _ in seqs]
        routes = [[] for _ in seqs]

        def feed(toks, with_routes=False):
            out = eng.put(uids, toks, with_routes=with_routes)
            if with_routes:          # [expert layers, 1 row, k] a sequence
                out, rts = out
                for i, r in enumerate(rts):
                    routes[i].append(r)
            for i, row in enumerate(out):
                got[i].append(row)
        feed([s[:len(s) - n_dec] for s in seqs])
        for j in range(n_dec):
            feed([s[len(s) - n_dec + j:len(s) - n_dec + j + 1]
                  for s in seqs], routing)
        eng.flush(uids)
        del eng
        got = [np.stack(g).astype(np.float32) for g in got]
        rows = [list(range(len(s) - n_dec - 1, len(s))) for s in seqs]

        def want(params, sizes):
            return [np.asarray(ref.logits(params, s, sizes, rows=r))
                    for s, r in zip(seqs, rows)]

        def say(what, **kw):
            print(json.dumps({"reading": what, "seed": seed, **kw}),
                  flush=True)
        def both(w):
            return {**reading(got, w), "by_sequence": [
                {k: round(v, 5) for k, v in reading([g], [x]).items()}
                for g, x in zip(got, w)]}
        healthy = want(params, cfg)
        say("healthy", rows=sum(len(r) for r in rows), **both(healthy),
            **limits)
        if routing:
            # the decoded rows (a row's OWN routing decides its FFN) routed
            # in every expert layer as the reference routes them, and the
            # others; the prompt's last row, fed without routes, is left out
            alike = []
            for s, rt in zip(seqs, routes):
                mine = np.concatenate(rt, axis=1)       # [layers, n_dec, k]
                ok = np.ones(n_dec, bool)
                for layer, (chosen, _) in enumerate(ref.routing(params, s,
                                                                cfg)):
                    ok &= (np.sort(mine[layer], -1) == np.sort(np.asarray(
                        chosen)[len(s) - n_dec:], -1)).all(-1)
                alike.append(ok)
            for name, pick in (("routed_alike", lambda ok: ok),
                               ("routed_otherwise", lambda ok: ~ok)):
                sel = [pick(ok) for ok in alike]
                say(name, rows=int(sum(x.sum() for x in sel)), **reading(
                    [g[1:][x] for g, x in zip(got, sel)],
                    [w[1:][x] for w, x in zip(healthy, sel)]))
        for fault in faults:
            with _lfm2_faults.planted(fault, params, cfg) as (bp, bs):
                say(fault, **both(want(bp, bs)))

    for n, seed in enumerate(args.seeds or [args.seed]):
        one_seed(seed, faults if n == 0 else (), args.routing and n == 0)


if __name__ == "__main__":
    sys.exit(main())
