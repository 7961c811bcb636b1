#!/usr/bin/env python3
"""Step-0 reading for a full layer with a learned top-k selection (PERF.md
section 6, PR 36): one layer's attention for a chunk of query rows of one
sequence over a cached context, on the chip, at dots3-note-prev's widths
(128 heads over a latent row of 640 columns, value the leading 512; 64 index
heads of 128; top 2,048):

- ``index``: the index scores of the chunk over the context (op
  ``index_scores``, its kernel);
- ``select``: the exact top-k of those scores (op ``index_select``);
- ``gathered`` (a): the selected rows gathered by index and attended (op
  ``selected_attention``);
- ``dense`` (b'): the existing absorbed ragged prefill kernel over ALL the
  context's pages with no mask: what a masked form of it could not beat;
- ``decode_*``: the same three ops and the existing paged decode kernel for
  ``--slots`` one-row slots over the whole table width;
- ``window_*``: the two existing kernels at the sliding layers' widths (64
  heads over a row of 1,152, value the leading 1,024, window 513).

    python3 benchmark/tools/dsa_prefill_paths.py [--rows 1024] [--ctx 4096 16384 31744]

Prints one JSON line a (path, context): median ms of ``--reps`` timed calls.
``--tiny`` runs a small interpreted case on the CPU.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--ctx", type=int, nargs="+",
                    default=[4096, 16384, 31744])
    ap.add_argument("--slots", type=int, default=24)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu import ops
    nh, P, vd, nI, dI, topk, bs = 128, 640, 512, 64, 128, 2048, 128
    wh, wP, wvd, win = 64, 1152, 1024, 513
    dt = jnp.bfloat16
    impl = "pallas"
    if args.tiny:
        nh, wh, topk, args.rows, args.ctx, args.slots, args.reps = (
            4, 4, 128, 160, [352], 3, 1)
    Q = args.rows
    kind = jax.devices()[0].device_kind

    def timed(name, fn, *a, **extra):
        out = jax.block_until_ready(fn(*a))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append((time.perf_counter() - t) * 1e3)
        print(json.dumps({"path": name, "rows": Q,
                          "ms_median": float(np.median(times)),
                          "ms_min": float(np.min(times)), "device": kind,
                          **extra}), flush=True)
        return out

    for ctx_len in args.ctx:
        total = ctx_len + Q
        used = -(-total // bs)
        MB = 1 << (used - 1).bit_length()
        C = MB * bs
        ks = jax.random.split(jax.random.PRNGKey(ctx_len), 8)
        pages = jax.random.normal(ks[0], (MB, 1, bs, P), dt)
        ipages = jax.random.normal(ks[1], (MB, 1, bs, dI), dt)
        q = jax.random.normal(ks[2], (Q, nh, P), dt)
        qi = jax.random.normal(ks[3], (Q, nI, dI), dt)
        wi = jax.random.normal(ks[4], (Q, nI), jnp.float32)
        table = jnp.arange(MB, dtype=jnp.int32)[None]
        pos = ctx_len + jnp.arange(Q, dtype=jnp.int32)
        slot = jnp.zeros((Q,), jnp.int32)
        info = {"context": ctx_len, "table_tokens": C}

        index = jax.jit(lambda qi, wi, ip: ops.index_scores(
            qi, wi, ip, table, slot, pos, max_rows=Q, impl=impl))
        scores = timed("index", index, qi, wi, ipages, **info)
        select = jax.jit(lambda s: ops.index_select(s, min(topk, C)))
        idx = timed("select", select, scores, **info)
        counts = jnp.minimum(pos + 1, idx.shape[1])
        gathered = jax.jit(lambda q, pg, idx: ops.selected_attention(
            q, pg, idx, counts, v_dim=vd, scale=192 ** -0.5))
        timed("gathered", gathered, q, pages, idx, **info)
        lens = jnp.asarray([total], jnp.int32)
        dense = jax.jit(lambda q, pg: ops.ragged_prefill_attention(
            q[:, None], pg, None, table, lens, lens - Q,
            jnp.asarray([Q], jnp.int32), jnp.zeros((1,), jnp.int32),
            max_q=Q, scale=192 ** -0.5, v_dim=vd, impl=impl))
        timed("dense", dense, q, pages, **info)
        wpages = jax.random.normal(ks[5], (MB, 1, bs, wP), dt)
        wq = jax.random.normal(ks[6], (Q, wh, wP), dt)
        window = jax.jit(lambda q, pg: ops.ragged_prefill_attention(
            q[:, None], pg, None, table, lens, lens - Q,
            jnp.asarray([Q], jnp.int32), jnp.zeros((1,), jnp.int32),
            max_q=Q, scale=256 ** -0.5, v_dim=wvd, window=win, impl=impl))
        timed("window_prefill", window, wq, wpages, **info)

    # ---- a decode step's rows: one a slot, over the whole table width
    # (tables, slots and positions are ARGUMENTS, as in a step program: as
    # constants the TPU compiler folds the scatters over them and aborts)
    S = args.slots
    MB = 1 << (-(-max(args.ctx) // bs) - 1).bit_length()
    C = MB * bs
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    NB = S * MB
    pages = jax.random.normal(ks[0], (NB, 1, bs, P), dt)
    ipages = jax.random.normal(ks[1], (NB, 1, bs, dI), dt)
    table = jnp.arange(NB, dtype=jnp.int32).reshape(S, MB)
    ctxs = jnp.asarray(np.linspace(min(args.ctx), max(args.ctx), S),
                       jnp.int32)
    q = jax.random.normal(ks[2], (S, nh, P), dt)
    qi = jax.random.normal(ks[3], (S, nI, dI), dt)
    wi = jax.random.normal(ks[4], (S, nI), jnp.float32)
    slot = jnp.arange(S, dtype=jnp.int32)
    info = {"slots": S, "table_tokens": C,
            "context_mean": float(jnp.mean(ctxs))}
    index = jax.jit(lambda qi, wi, ip, table, slot, ctxs: ops.index_scores(
        qi, wi, ip, table, slot, ctxs, max_rows=1, impl=impl))
    scores = timed("decode_index", index, qi, wi, ipages, table, slot, ctxs,
                   **info)
    select = jax.jit(lambda s: ops.index_select(s, min(topk, C)))
    idx = timed("decode_select", select, scores, **info)
    rows = (jnp.take_along_axis(table, idx // bs, axis=1) * bs + idx % bs)
    counts = jnp.minimum(ctxs + 1, idx.shape[1])
    gathered = jax.jit(lambda q, pg, rows, counts: ops.selected_attention(
        q, pg, rows, counts, v_dim=vd, scale=192 ** -0.5))
    timed("decode_gathered", gathered, q, pages, rows, counts, **info)
    dense = jax.jit(lambda q, pg, table, lens: ops.paged_attention(
        q[:, None], pg, None, table, lens, scale=192 ** -0.5, v_dim=vd,
        impl=impl))
    timed("decode_dense", dense, q, pages, table, ctxs + 1, **info)
    wpages = jax.random.normal(ks[5], (S * 16, 1, bs, wP), dt)
    wq = jax.random.normal(ks[6], (S, wh, wP), dt)
    wtable = jnp.arange(S * 16, dtype=jnp.int32).reshape(S, 16)
    wdec = jax.jit(lambda q, pg, table, lens: ops.paged_attention(
        q[:, None], pg, None, table, lens, scale=256 ** -0.5, v_dim=wvd,
        window=win, impl=impl))
    timed("window_decode", wdec, wq, wpages, wtable,
          jnp.full((S,), 16 * bs, jnp.int32), **info)


if __name__ == "__main__":
    main()
