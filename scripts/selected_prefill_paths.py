#!/usr/bin/env python3
"""Step 0 for ``ops.sparse_index.MASKED_REACH`` (PERF.md section 6, PR 43 and
PR 54): one selecting layer's attention for ONE prompt chunk, both ways the
step program can read its keys, on the chip at dots3-note-prev's full layer (128
heads over a latent row of 640 columns, value the leading 512; 64 index heads
of 128; top 2,048; pages of 512, a table of 64 = 32,768 tokens, the slot's
pages 64 of a pool of 1,024: a pool small enough for the compiler to keep in
VMEM, 42 MB, reads the gather at 18.6 ms where the cell's pays 43):

- ``index``: the index scores of the chunk (op ``index_scores``): paid either
  way;
- ``select``: their exact top-k (``index_select``, the sort), which only the
  gathered path needs, and ``gathered``: each picked position's pool row by
  the table, then the rows gathered by index and attended
  (``selected_attention``);
- ``threshold``: the same selection as bits with no list (``threshold_mask``,
  the form the step program's dispatch takes here; ``threshold_xla`` and
  ``threshold_pallas``: each form forced), which only the masked path needs,
  and ``masked``: the prefill kernel over the slot's pages with those bits
  (``ragged_prefill_attention(sel_mask=)``);
- ``mask_build``: the bits from the sorted list (``selection_mask``, what
  the masked path paid beside ``select`` until PR 54), and
  ``threshold_vs_sorted``: whether each form's words are those, bit for bit,
  on the chunk's scores and on a coarsened copy of them (heavy ties at every
  row's threshold, zeros of both signs);
- ``masked_vs_gathered``: the largest difference between the two results;
- ``dense``: the same kernel with no mask;
- ``paths``: ``select + gathered`` beside ``threshold + masked`` a context,
  and a last line ``crossing``: the furthest reach (context + rows) walked at
  which the masked path was still the cheaper, and the first past it.

    python3 scripts/selected_prefill_paths.py [--rows 1024] [--ctx 4096 ...]

One JSON line a (path, context): median ms of ``--reps`` timed calls; the
context is what the slot holds BEFORE the chunk.  ``--tiny``: a small
interpreted case on the CPU (times mean nothing there).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--ctx", type=int, nargs="+",
                    default=[3072, 7168, 11264, 15360, 19456, 21504, 23552,
                             30720])
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--pool-pages", type=int, default=1024)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu import ops
    nh, P, vd, nI, dI, topk, bs, MB = 128, 640, 512, 64, 128, 2048, 512, 64
    dt, scale = jnp.bfloat16, 192 ** -0.5
    if args.tiny:
        nh, topk, bs, MB, args.rows, args.ctx, args.reps = (
            4, 48, 16, 16, 40, [8, 100, 216], 1)
        args.pool_pages = 24
    Q, C = args.rows, MB * bs
    kind = jax.devices()[0].device_kind

    ms = {}

    def timed(name, fn, *a, **extra):
        out = jax.block_until_ready(fn(*a))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append((time.perf_counter() - t) * 1e3)
        ms[name] = float(np.median(times))
        print(json.dumps({"path": name, "rows": Q, "ms_median": ms[name],
                          "ms_min": float(np.min(times)), "device": kind,
                          **extra}), flush=True)
        return out

    ks = jax.random.split(jax.random.PRNGKey(43), 5)
    NB = max(args.pool_pages, MB)
    pages = jax.random.normal(ks[0], (NB, 1, bs, P), dt)
    ipages = jax.random.normal(ks[1], (NB, 1, bs, dI), dt)
    q = jax.random.normal(ks[2], (Q, nh, P), dt)
    qi = jax.random.normal(ks[3], (Q, nI, dI), dt)
    wi = jax.random.normal(ks[4], (Q, nI), jnp.float32)
    # (table, positions and lengths are ARGUMENTS, as in a step program)
    table = jnp.asarray(np.random.default_rng(0).permutation(NB)[None, :MB],
                        jnp.int32)
    slot = jnp.zeros((Q,), jnp.int32)
    one = jnp.ones((1,), jnp.int32)

    index = jax.jit(lambda qi, wi, ip, table, pos: ops.index_scores(
        qi, wi, ip, table, slot, pos, max_rows=Q, impl="pallas"))
    select = jax.jit(lambda s, reach: ops.index_select(s, topk, width=reach))
    build = jax.jit(ops.selection_mask)
    threshold = {
        name: jax.jit(lambda s, reach, impl=impl: ops.threshold_mask(
            s, topk, width=reach, impl=impl))
        for name, impl in (("threshold", None), ("threshold_xla", "xla"),
                           ("threshold_pallas", "pallas"))}
    # scores of a few values only, zeros of both signs among them
    coarse = jax.jit(lambda s: jnp.where(jnp.isfinite(s), jnp.round(
        s / 8) * jnp.where(jnp.arange(s.shape[1]) % 2, -1.0, 1.0), s))

    def prefill(q, pg, table, lens, keep):
        return ops.ragged_prefill_attention(
            q[:, None], pg, None, table, lens, lens - Q, Q * one, 0 * one,
            max_q=Q, scale=scale, v_dim=vd, sel_mask=keep,
            impl="pallas")[:, 0]
    masked = jax.jit(prefill)
    dense = jax.jit(lambda q, pg, table, lens: prefill(q, pg, table, lens,
                                                      None))

    @jax.jit
    def gathered(q, pg, table, pos, picked):
        page = jnp.sum(jnp.where(
            (picked // bs)[:, :, None] == jnp.arange(MB, dtype=jnp.int32),
            table[slot][:, None, :], 0), axis=-1)
        return ops.selected_attention(
            q, pg, page * bs + picked % bs, jnp.minimum(pos + 1, topk),
            v_dim=vd, scale=scale)

    cheaper = []
    for ctx_len in args.ctx:
        pos = ctx_len + jnp.arange(Q, dtype=jnp.int32)
        lens = (ctx_len + Q) * one
        info = {"context": ctx_len, "table_tokens": C}
        scores = timed("index", index, qi, wi, ipages, table, pos, **info)
        picked = timed("select", select, scores, lens[0], **info)
        a = timed("gathered", gathered, q, pages, table, pos, picked, **info)
        sorted_keep = timed("mask_build", build, scores, picked, **info)
        few = coarse(scores)
        sorted_few = build(few, select(few, lens[0]))
        for name, fn in threshold.items():
            keep = timed(name, fn, scores, lens[0], **info)
            print(json.dumps({
                "path": "threshold_vs_sorted", "form": name, **info,
                "bits_equal": bool(jnp.array_equal(keep, sorted_keep)),
                "bits_equal_coarse": bool(jnp.array_equal(
                    fn(few, lens[0]), sorted_few)),
                "coarse_values": int(jnp.unique(few[-1]).size)}), flush=True)
        b = timed("masked", masked, q, pages, table, lens, keep, **info)
        timed("dense", dense, q, pages, table, lens, **info)
        paths = {"gathered_path_ms": ms["select"] + ms["gathered"],
                 "masked_path_ms": ms["threshold"] + ms["masked"]}
        cheaper.append((ctx_len + Q,
                        paths["masked_path_ms"] <= paths["gathered_path_ms"]))
        print(json.dumps({"path": "paths", **info, "reach": ctx_len + Q,
                          **paths}), flush=True)
        print(json.dumps({
            "path": "masked_vs_gathered", **info,
            "max_abs_diff": float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            "max_abs": float(jnp.max(jnp.abs(a.astype(jnp.float32))))}),
            flush=True)
    dearer = min((reach for reach, ok in cheaper if not ok), default=None)
    print(json.dumps({
        "path": "crossing", "table_tokens": C,
        "masked_cheaper_through": max(
            (reach for reach, ok in cheaper
             if ok and (dearer is None or reach < dearer)), default=0),
        "masked_first_dearer": dearer}), flush=True)


if __name__ == "__main__":
    main()
