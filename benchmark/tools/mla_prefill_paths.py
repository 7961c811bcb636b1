#!/usr/bin/env python3
"""Step-0 reading for latent attention's PREFILL path (PERF.md section 6,
PR 33): one layer's attention for a chunk of query rows over a cached
context, both ways, on the chip:

- absorbed: the two absorb products and the ragged prefill kernel in its
  latent form over the latent pages (what the program does);
- expanded: the context's latent rows gathered from the pages, ``Wkvb``
  applied to them (keys and values a head), plain XLA attention at key
  width 192 and value width 128 (no kernel of the repo takes unequal
  widths over pages; this is the arithmetic's floor, not a path the
  program has).

    python3 benchmark/tools/mla_prefill_paths.py [--rows 1024] [--ctx 2048 6144]

Prints one JSON line a (path, context): median ms of 10 timed calls.
``--tiny`` runs a small interpreted case on the CPU and compares the two.
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
    ap.add_argument("--ctx", type=int, nargs="+", default=[2048, 6144])
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.paged_attention import pallas_ragged_prefill
    nh, nope, rot, vd, rank, bs = 16, 128, 64, 128, 512, 128
    dt = jnp.bfloat16
    if args.tiny:
        nh, args.rows, args.ctx, dt = 4, 16, [128], jnp.float32
    page_dim = -(-(rank + rot) // 128) * 128
    scale = (nope + rot) ** -0.5
    Q = args.rows
    key = jax.random.PRNGKey(0)

    for ctx_len in args.ctx:
        total = ctx_len + Q                     # the chunk's own rows too
        MB = -(-total // bs)
        ks = jax.random.split(key, 5)
        pages = jax.random.normal(ks[0], (MB, 1, bs, page_dim), dt)
        pages = pages.at[..., rank + rot:].set(0)
        q_nope = jax.random.normal(ks[1], (Q, nh, nope), dt)
        q_pe = jax.random.normal(ks[2], (Q, nh, rot), dt)
        wkvb = jax.random.normal(ks[3], (rank, nh, nope + vd), dt) * 0.05
        table = jnp.arange(MB, dtype=jnp.int32)[None]
        lens = jnp.asarray([total], jnp.int32)
        start = jnp.asarray([ctx_len], jnp.int32)
        count = jnp.asarray([Q], jnp.int32)
        row_start = jnp.asarray([0], jnp.int32)  # token-major rows (PR 34)

        @jax.jit
        def absorbed(pages, q_nope, q_pe, wkvb):
            q_lat = jnp.einsum("tnd,rnd->tnr", q_nope, wkvb[..., :nope])
            pad = jnp.zeros((Q, nh, page_dim - rank - rot), dt)
            q = jnp.concatenate([q_lat, q_pe, pad], -1)
            o = pallas_ragged_prefill(
                q[:, None], pages, None, table, lens, start, count,
                row_start, max_q=Q, scale=scale, v_dim=rank)
            return jnp.einsum("tnr,rnd->tnd", o[:, 0], wkvb[..., nope:])

        @jax.jit
        def expanded(pages, q_nope, q_pe, wkvb):
            rows = pages[table[0]].reshape(MB * bs, page_dim)[:total]
            c, k_pe = rows[:, :rank], rows[:, rank:rank + rot]
            kv = jnp.einsum("sr,rnd->snd", c, wkvb)
            s = (jnp.einsum("tnd,snd->nts", q_nope, kv[..., :nope],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("tnd,sd->nts", q_pe, k_pe,
                              preferred_element_type=jnp.float32)) * scale
            mask = (jnp.arange(total)[None, :]
                    <= ctx_len + jnp.arange(Q)[:, None])
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
            return jnp.einsum("nts,snd->tnd", p.astype(dt), kv[..., nope:])

        outs = {}
        for name, fn in (("absorbed", absorbed), ("expanded", expanded)):
            out = jax.block_until_ready(fn(pages, q_nope, q_pe, wkvb))
            outs[name] = np.asarray(out, np.float32)
            times = []
            for _ in range(1 if args.tiny else 10):
                t = time.perf_counter()
                jax.block_until_ready(fn(pages, q_nope, q_pe, wkvb))
                times.append((time.perf_counter() - t) * 1e3)
            print(json.dumps({
                "path": name, "rows": Q, "context": ctx_len,
                "ms_median": float(np.median(times)),
                "ms_min": float(np.min(times)),
                "device": jax.devices()[0].device_kind}), flush=True)
        err = float(np.max(np.abs(outs["absorbed"] - outs["expanded"])))
        print(json.dumps({"context": ctx_len, "max_abs_between_paths": err,
                          "max_abs_out": float(np.max(np.abs(
                              outs["expanded"])))}), flush=True)


if __name__ == "__main__":
    main()
