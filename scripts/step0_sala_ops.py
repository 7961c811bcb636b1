#!/usr/bin/env python3
"""Step 0 of MiniCPM-SALA's new operations (PR 57), on the chip, each alone at
the shapes ``serve-sala-longdoc-batch`` runs: the one-row recurrence over a
float32 state pool with a column a head (``ssm_state_update``: XLA form
against the kernel, the results compared first), the block scores of one pass
of 128 rows over a slot's pooled keys at the three shares of the table a
mixed step chooses from, the exact top-64 of a chunk's block scores as bits
(``threshold_mask``: XLA form against the kernel), and a one-row slot's
attention over its 64 kept blocks through the paged decode kernel over the
pool's view a block a page, against dense paged attention over the whole
context (what the selection saves a decode step).

    chiprun --timeout 900 -- python3 scripts/step0_sala_ops.py
    JAX_PLATFORMS=cpu python3 scripts/step0_sala_ops.py --tiny   # here

Writes ``chiprun_out/pr57/step0.jsonl`` (one line a timing, milliseconds).
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

from deepspeed_tpu import ops
from deepspeed_tpu.models.gpt import lightning_decay
from deepspeed_tpu.ops import block_select
from deepspeed_tpu.ops.block_select import BlockGeometry
from deepspeed_tpu.ops.ssm_scan import packed_state_shape

OUT = os.path.join("chiprun_out", "pr57", "step0.jsonl")


def timed(fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3, out


def say(**kw):
    line = json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in kw.items()})
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    rng = np.random.default_rng(57)
    tiny = args.tiny
    geo = (BlockGeometry(4, 2, 8, 4, 16, 1, 32) if tiny
           else BlockGeometry(32, 16, 64, 64, 2048, 1, 8192))
    heads, p, layers = (4, 128, 2) if tiny else (32, 128, 9)
    nkv, g, d, bs = 2, (2 if tiny else 16), 128, 128
    MB = 4 if tiny else 516

    def f(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape), dtype)

    # ---- the recurrence: a column a head
    for S in ((2,) if tiny else (16, 32)):
        pool0 = rng.normal(size=(layers, S) + packed_state_shape(
            heads, p, p)).astype(np.float32)
        x, B, C = f(S, heads, p), f(S, heads, p), f(S, heads, p)
        dt, A, D = (jnp.ones((S, heads)), jnp.asarray(lightning_decay(heads)),
                    jnp.zeros(heads))
        live = jnp.ones((S,), bool)
        got = {}
        for impl in ("xla", "pallas"):
            step = jax.jit(lambda pool, impl=impl: ops.ssm_state_update(
                x, dt, A, B, C, D, pool, 1, live, ~live, impl=impl),
                donate_argnums=0)
            # (the pool is donated; both forms start from the same one)
            got[impl], pool = step(jnp.asarray(pool0))
            ms = []
            for _ in range(5):
                t = time.perf_counter()
                y, pool = step(pool)
                jax.block_until_ready(y)
                ms.append((time.perf_counter() - t) * 1e3)
            say(op="ssm_state_update", impl=impl, slots=S, heads=heads,
                ms_a_layer=float(np.median(ms)),
                state_mb_in_and_out=2 * S * heads * p * p * 4 / 1e6)
        say(op="ssm_state_update", slots=S, forms_differ_by=float(
            jnp.max(jnp.abs(got["xla"] - got["pallas"]))))

    # ---- block scores: one pass of 128 rows at three shares of the table
    ki = f(MB + 1, bs // geo.stride, nkv, d, dtype=jnp.bfloat16)
    q = f(128, nkv, g, d, dtype=jnp.bfloat16)
    for mb in (MB, MB // 2, MB // 4):
        table = jnp.asarray(rng.permutation(MB)[:mb][None], jnp.int32)
        pos = jnp.full((128,), mb * bs - 1, jnp.int32)
        fn = jax.jit(lambda q, table: block_select.mark_blocks(
            ops.block_scores(q, block_select.slot_pooled_keys(ki, table),
                             pos, geo=geo, scale=d ** -0.5), pos, geo))
        ms, _ = timed(fn, q, table)
        say(op="block_scores", rows=128, pooled_keys=mb * bs // geo.stride,
            ms=ms)

    # ---- the choice of a chunk: bits of the top-k of 2 x 1,024 rows
    N, NB = (64, 128) if tiny else (2048, 1152)
    marked = f(N, NB)
    for impl in ("xla", "pallas"):
        fn = jax.jit(lambda s, impl=impl: ops.threshold_mask(
            s, geo.topk, impl=impl))
        ms, _ = timed(fn, marked)
        say(op="threshold_mask", impl=impl, rows=N, blocks=NB, ms=ms)

    # ---- a one-row slot over its kept blocks, against its whole context
    pages = (MB + 1) * 2 if tiny else 2688
    k_pages = f(pages, nkv, bs, d, dtype=jnp.bfloat16)
    v_pages = f(pages, nkv, bs, d, dtype=jnp.bfloat16)
    for S, ctx in (((2, MB * bs - 3),) if tiny
                   else ((16, 16384), (16, 49152), (32, 16384))):
        table = jnp.asarray(rng.integers(0, pages, size=(S, MB)), jnp.int32)
        pos = jnp.full((S,), ctx - 1, jnp.int32)
        own = (ctx - 1) // geo.block
        blocks = jnp.asarray(np.stack([[np.sort(np.concatenate([
            rng.choice(own, geo.topk - 1, replace=False), [own]]))
            for _ in range(nkv)] for _ in range(S)]), jnp.int32)
        qg = f(S, nkv, g, d, dtype=jnp.bfloat16)
        live = jnp.ones((S,), bool)

        def kept(qg, k_pages, v_pages):
            rows, lens = block_select.kept_block_table(
                table, blocks, pos, live, bs, geo)
            return ops.paged_attention(
                qg.reshape(S * nkv, 1, g, d),
                block_select.block_pages(k_pages, geo),
                block_select.block_pages(v_pages, geo), rows, lens,
                scale=d ** -0.5, kv_major=False)

        def dense(qg, k_pages, v_pages):
            return ops.paged_attention(qg, k_pages, v_pages, table, pos + 1,
                                       scale=d ** -0.5, kv_major=False)
        for name, fn in (("kept_blocks", kept), ("whole_context", dense)):
            ms, _ = timed(jax.jit(fn), qg, k_pages, v_pages)
            say(op="one_row_attention", reads=name, slots=S, context=ctx,
                ms_a_layer=ms)


if __name__ == "__main__":
    main()
