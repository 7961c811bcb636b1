#!/usr/bin/env python3
"""Step 0 of the paged KV append (PR 48), on the chip: one step's write of
its new k and v rows into four layers' pages at the shapes the serving cells
run (a decode and a mixed step each), as the XLA forms and as the kernel
(``ops/kv_append.py``) at every granule its standard-page rule could choose,
each as microseconds a layer; the pools the two leave are compared bit for
bit first.

    chiprun --timeout 1200 -- python3 scripts/step0_kv_append.py
    JAX_PLATFORMS=cpu python3 scripts/step0_kv_append.py --tiny   # here

Writes ``chiprun_out/pr48/step0.jsonl`` (one line a timing).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu.ops  # noqa: F401  (registers the ops)

kva = sys.modules["deepspeed_tpu.ops.kv_append"]

LAYERS = 4
# cell, step, kv-major, nkv, hd, bs, N, rows a slot, S, chunks' rows
SHAPES = [
    ("mistral", "mixed", False, 8, 128, 128, 512, 256, 32, (256, 93)),
    ("mistral", "decode", False, 8, 128, 128, 32, 1, 32, ()),
    ("trinity", "mixed", False, 8, 128, 128, 1024, 1024, 16, (700, 310)),
    ("trinity", "decode", False, 8, 128, 128, 16, 1, 16, ()),
    ("lfm2", "mixed", True, 8, 64, 128, 2048, 2048, 64, (1500, 486)),
    ("lfm2", "decode", True, 8, 64, 128, 64, 1, 64, ()),
    ("granite", "mixed", True, 8, 64, 128, 512, 256, 64, (256, 194)),
    ("granite", "decode", True, 8, 64, 128, 64, 1, 64, ()),
]
TINY = [("tiny", "mixed", False, 2, 128, 32, 48, 24, 6, (24, 11)),
        ("tiny", "decode", False, 2, 128, 32, 6, 1, 6, ()),
        ("tiny", "mixed", True, 2, 16, 128, 300, 200, 6, (200, 90)),
        ("tiny", "decode", True, 2, 16, 128, 6, 1, 6, ())]


def schedule(rng, N, S, MB, bs, chunks):
    """A step as ragged.py packs it: the chunks first, then one row for
    every other slot, then pads.  -> (row_slot, row_pos)."""
    slot = np.full(N, S)
    pos = np.zeros(N, int)
    n = 0
    runs = list(chunks) + [1] * (S - len(chunks))
    for s, c in enumerate(runs):
        c = min(c, N - n)
        p0 = int(rng.integers(0, MB * bs - c + 1))
        slot[n:n + c], pos[n:n + c] = s, np.arange(p0, p0 + c)
        n += c
    return slot, pos


def timed(fn, pools, *args, reps):
    pools = fn(pools, *args)
    jax.block_until_ready(pools)
    t0 = time.perf_counter()
    for _ in range(reps):
        pools = fn(pools, *args)
    jax.block_until_ready(pools)
    return pools, (time.perf_counter() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/pr48")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, "step0.jsonl"), "w")
    tiny = args.tiny
    dtype = jnp.float32 if tiny else jnp.bfloat16
    layers = 2 if tiny else LAYERS
    interpret = jax.default_backend() != "tpu"
    rule = kva.append_granule

    def emit(**kw):
        print(json.dumps(kw), flush=True)
        log.write(json.dumps(kw) + "\n")
        log.flush()

    for cell, step, km, nkv, hd, bs, N, Q, S, chunks in (TINY if tiny
                                                          else SHAPES):
        rng = np.random.default_rng(N + S)
        MB = 8 if tiny else 16
        NB = S * MB
        slot, pos = schedule(rng, N, S, MB, bs, chunks)
        table = jnp.asarray(rng.permutation(NB).reshape(S, MB), jnp.int32)
        slot, pos = jnp.asarray(slot, jnp.int32), jnp.asarray(pos, jnp.int32)
        page = (nkv, hd, bs) if km else (nkv, bs, hd)
        keys = jax.random.split(jax.random.PRNGKey(N), 4)

        def fresh():
            return tuple(jax.random.normal(k, (layers * NB,) + page, dtype)
                         for k in keys[:2])
        new = tuple(jax.random.normal(k, (N, nkv, hd), dtype)
                    for k in keys[2:])

        def write(form, granule):
            def run(pools, new, table, slot, pos):
                kva.append_granule = (
                    rule if granule is None else lambda *a: granule)
                try:
                    plan = kva.append_plan(table, slot, pos, bs, Q, km)
                finally:
                    kva.append_granule = rule
                for li in range(layers):
                    if form == "xla":
                        pools = kva.xla_paged_kv_append(
                            pools, new, plan, li * NB, kv_major=km)
                    else:
                        assert kva.supported(pools, new, plan, li * NB,
                                             kv_major=km)
                        pools = kva.pallas_paged_kv_append(
                            pools, new, plan, li * NB, kv_major=km,
                            interpret=interpret)
                return pools
            return jax.jit(run, donate_argnums=0)

        base = dict(cell=cell, step=step, kv_major=km, N=N, S=S,
                    rows=int((slot < S).sum()))
        want, t = timed(write("xla", None), fresh(), new, table, slot, pos,
                        reps=2 if tiny else args.reps)
        emit(**base, form="xla", us_a_layer=round(t / layers * 1e6, 2))
        granules = [None] if km or tiny else [16, 32, 64, 128]
        for g in granules:
            got, t = timed(write("pallas", g), fresh(), new, table, slot,
                           pos, reps=2 if tiny else args.reps)
            same = all(bool(jnp.array_equal(w, a))
                       for w, a in zip(want, got))
            emit(**base, form="pallas", granule=g or rule(bs, km),
                 us_a_layer=round(t / layers * 1e6, 2), same_bits=same)
            assert same, (cell, step, g)
            del got
        del want


if __name__ == "__main__":
    main()
