#!/usr/bin/env python
"""Evoformer long-S memory/runtime proof (round-3 verdict item 6 "done" bar).

Runs one forward+backward of evoformer attention at AlphaFold-ish long-S
shapes (N=32, S in {2048, 4096}) through BOTH paths:

- Pallas blockwise kernel (`evoformer_attention`): [bq, bk] logit tiles in
  VMEM only — peak HBM stays O(inputs + bias2).
- einsum ground truth (`_evoformer_xla`): materializes [B, N, H, S, S] fp32
  logits twice over in fwd+bwd.

Round-5 measured outcome: at S=2048 BOTH paths fit a 16 GB chip (2 GB
logits; kernel 0.776 s vs einsum 0.796 s) — the memory contrast lives at
S=4096, where the einsum path's ~8.6 GB logits (before backward copies)
fail to compile while the kernel runs in 1.385 s.  (Numbers from a tree
that predates PRs 1–20; not re-measured since.)

Prints one JSON line per (S, path): {"path", "S", "shape", "ok",
"seconds"}.  Runs each path in a SUBPROCESS, one at a time (an OOM is the
expected outcome of the einsum leg at S=4096 and must not take the later
legs with it); this parent never touches jax, so each child has the chip to
itself.  A leg over its time limit records a timeout line.  CPU-safe smoke:
EVO_SMOKE=1.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_one(path_name: str) -> int:
    import time

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.evoformer import (_evoformer_xla,
                                             evoformer_attention)

    smoke = bool(os.environ.get("EVO_SMOKE"))
    S = int(os.environ.get("EVO_S", 2048))
    B, N, S, H, D = (1, 4, 128, 2, 8) if smoke else (1, 32, S, 4, 32)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    shape = (B, N, S, H, D)
    q = jax.random.normal(ks[0], shape, jnp.bfloat16)
    k = jax.random.normal(ks[1], shape, jnp.bfloat16)
    v = jax.random.normal(ks[2], shape, jnp.bfloat16)
    bias1 = jax.random.normal(ks[3], (B, N, 1, 1, S), jnp.float32)
    bias2 = jax.random.normal(ks[4], (B, 1, H, S, S), jnp.float32)
    fn = evoformer_attention if path_name == "pallas" else _evoformer_xla

    def loss(q_, k_, v_, b2):
        return jnp.sum(fn(q_, k_, v_, bias1, b2).astype(jnp.float32))

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
    out = {"path": path_name, "S": S, "shape": list(shape)}
    try:
        jax.block_until_ready(g(q, k, v, bias2))        # compile + run
        t0 = time.perf_counter()
        jax.block_until_ready(g(q, k, v, bias2))
        out["seconds"] = round(time.perf_counter() - t0, 3)
        out["ok"] = True
        stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
        if stats:
            out["peak_hbm_gb"] = round(
                stats.get("peak_bytes_in_use", 0) / 2**30, 2)
    except Exception as e:  # noqa: BLE001 — OOM is the expected xla outcome
        out["ok"] = False
        out["error"] = str(e)[:200]
    print(json.dumps(out), flush=True)
    return 0


def main():
    if len(sys.argv) > 1 and sys.argv[1] in ("pallas", "xla"):
        return run_one(sys.argv[1])
    here = os.path.abspath(__file__)
    # S=2048 (round-3 bar: both paths' runtime) proved BOTH paths fit a
    # 16 GB chip — the memory contrast needs S=4096, where the einsum
    # path's [B, N, H, S, S] fp32 logits (~8.6 GB before the backward's
    # copies) cannot fit but the kernel's VMEM tiles don't care
    sizes = (2048,) if os.environ.get("EVO_SMOKE") else (2048, 4096)
    for s in sizes:
        for path_name in ("pallas", "xla"):
            env = dict(os.environ, EVO_S=str(s))
            try:
                p = subprocess.run([sys.executable, here, path_name],
                                   timeout=900, capture_output=True,
                                   text=True, env=env)
            except subprocess.TimeoutExpired:
                # record and keep going so later (S, path) legs still run
                print(json.dumps({"path": path_name, "S": s, "ok": False,
                                  "error": "timeout 900s"}), flush=True)
                continue
            for line in p.stdout.splitlines():
                if line.startswith("{"):
                    print(line, flush=True)
                    break
            else:
                print(json.dumps({"path": path_name, "S": s, "ok": False,
                                  "error": (p.stderr.strip().splitlines()
                                            or ["no output"])[-1][:200]}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
