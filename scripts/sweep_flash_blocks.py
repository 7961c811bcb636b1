#!/usr/bin/env python
"""Sweep flash-attention (bq, bk) block pairs on the CURRENT hardware.

``(bq, bk)`` are the blocks of the loop INSIDE the kernels
(``ops/flash_attention.py``): a grid step holds a span of a head's query
rows (forward) or its keys and values (backward) and walks the live blocks
of the other side, bq query rows by bk key rows a tile; a tile the diagonal
crosses is walked in strips of 256 query rows where bq == bk.  The built-in
table (``_block_pair``: square, the largest power of two dividing T up to
1,024 at T >= 2048 with heads up to 128 and no window, up to 512 otherwise)
came from this script's kind of run on one v5e chip, PR 52
(``chiprun_out/pr52/sweep*.jsonl``; ``PERF.md`` section 6 has the table).
This script times fwd+bwd of ``ops.flash_attention`` for each candidate pair
on whatever backend is attached, prints the ranking, and emits the
``DSTPU_FLASH_BLOCKS`` env line (or ``ops.configure_flash_blocks`` call) that
installs the winner: tuning on hardware WITHOUT a code change.

    python scripts/sweep_flash_blocks.py --seq 1024 --batch 8 --heads 16
    python scripts/sweep_flash_blocks.py --seq 4096 --batch 2 --heads 32 \
        --kv-heads 8 --head-dim 128          # the four-chip cell's shard
    python scripts/sweep_flash_blocks.py --seq 128 --smoke   # CPU plumbing

Candidates default to the powers of two from 128 to 1,024 on both sides (a
pair with bq > bk is as meaningful as its mirror now); pass ``--candidates
512x512,512x1024`` to restrict.  Pairs that fail to compile (VMEM overflow)
are reported and skipped: an over-full tile is a hard compile error, not a
fallback.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List, Optional, Tuple

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def default_candidates(t: int) -> List[Tuple[int, int]]:
    sizes = [b for b in (128, 256, 512, 1024) if b <= t and t % b == 0]
    return [(bq, bk) for bq in sizes for bk in sizes] or [(8, 8)]


def parse_candidates(spec: str) -> List[Tuple[int, int]]:
    from deepspeed_tpu.ops.flash_attention import _parse_block_spec
    # reuse the 'BQxBK' piece of the env grammar
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        pair = _parse_block_spec(f"8:{part}")[8]
        out.append(pair)
    return out


def time_pair(t, pair, *, batch, heads, kv_heads, head_dim, dtype, iters,
              fwd_only, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu import ops
    ops.configure_flash_blocks({t: pair})
    rng = np.random.default_rng(0)
    shape = (batch, t, heads, head_dim)
    kv_shape = (batch, t, kv_heads, head_dim)
    q = jnp.asarray(rng.normal(size=shape) * 0.1, dtype)
    k = jnp.asarray(rng.normal(size=kv_shape) * 0.1, dtype)
    v = jnp.asarray(rng.normal(size=kv_shape) * 0.1, dtype)

    if fwd_only:
        fn = jax.jit(lambda q, k, v: ops.flash_attention(
            q, k, v, interpret=interpret).sum())
    else:
        fn = jax.jit(jax.grad(lambda q, k, v: ops.flash_attention(
            q, k, v, interpret=interpret).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
    out = fn(q, k, v)                       # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(q, k, v)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        description="time flash-attention block-pair candidates on the "
                    "attached backend and print the winning "
                    "DSTPU_FLASH_BLOCKS line")
    ap.add_argument("--seq", type=int, action="append", required=True,
                    help="sequence length to tune (repeatable)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="fewer than --heads for GQA (default: --heads)")
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--dtype", choices=("bf16", "fp32"), default="bf16")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--fwd-only", action="store_true")
    ap.add_argument("--candidates",
                    help="comma list of BQxBK pairs (default: auto grid)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU plumbing run: force the cpu backend + "
                    "interpret-mode kernels (timings are meaningless)")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu import ops
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    interpret = args.smoke or jax.default_backend() != "tpu"
    if interpret and not args.smoke:
        print("sweep_flash_blocks: no TPU attached — running interpret "
              "mode; timings will NOT transfer (pass --smoke to silence)",
              file=sys.stderr)

    winners = {}
    for t in args.seq:
        cands = (parse_candidates(args.candidates) if args.candidates
                 else default_candidates(t))
        cands = [(bq, bk) for bq, bk in cands if t % bq == 0 and t % bk == 0]
        if not cands:
            print(f"T={t}: no valid candidates", file=sys.stderr)
            continue
        print(f"== T={t} (B={args.batch}, H={args.heads}, "
              f"D={args.head_dim}, {args.dtype}, "
              f"{'fwd' if args.fwd_only else 'fwd+bwd'}) ==")
        rows = []
        for pair in cands:
            try:
                dt = time_pair(t, pair, batch=args.batch, heads=args.heads,
                               kv_heads=args.kv_heads or args.heads,
                               head_dim=args.head_dim, dtype=dtype,
                               iters=args.iters, fwd_only=args.fwd_only,
                               interpret=interpret)
                rows.append((dt, pair))
                print(f"  ({pair[0]:>5}, {pair[1]:>5})  {dt * 1e3:9.3f} ms")
            except Exception as e:  # noqa: BLE001 — over-full tiles et al.
                print(f"  ({pair[0]:>5}, {pair[1]:>5})  FAILED: "
                      f"{str(e)[:90]}")
        if rows:
            rows.sort()
            best_dt, best = rows[0]
            winners[t] = best
            print(f"  best: ({best[0]}, {best[1]}) at {best_dt * 1e3:.3f} ms")
    ops.configure_flash_blocks(None)      # restore env/default table
    if winners:
        spec = ",".join(f"{t}:{bq}x{bk}"
                        for t, (bq, bk) in sorted(winners.items()))
        print("\ninstall the winners with:")
        print(f"  export DSTPU_FLASH_BLOCKS=\"{spec}\"")
        print(f"  # or: ops.configure_flash_blocks("
              f"{ {t: p for t, p in sorted(winners.items())} })")
    return 0


if __name__ == "__main__":
    sys.exit(main())
