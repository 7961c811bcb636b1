#!/usr/bin/env python
"""Structural check: collectives overlap (or can overlap) with compute.

``comm.comm.hlo_overlap_stats`` walks compiled HLO for the two overlap
signals:

- async ``<kind>-start``/``-done`` pairs with compute instructions scheduled
  between them (the TPU latency-hiding scheduler's output), and
- interleaved chunk trains — >= 2 same-kind collectives with compute between
  consecutive ones, which is what the explicit decompositions
  (``overlap.num_chunks`` chunked ZeRO-3 gathers, the ring collective-matmul
  fusions) produce even on backends that never split collectives (the CPU
  CI).

This script runs that walk standalone and turns it into a pass/fail gate,
the same way ``check_no_sync.py`` lints the dispatch path:

    python scripts/check_overlap.py --demo            # toy chunked fn
    python scripts/check_overlap.py --hlo step.txt    # saved HLO dump
    python scripts/check_overlap.py --demo --assert-overlap --min-chunks 2

``--assert-overlap`` exits 1 unless at least one signal is present (>= 1
async pair with compute between, or some collective kind with >=
``--min-chunks`` interleaved ops).  The test suite drives the demo mode and
asserts the chunked ZeRO-3 train step passes (tests/test_overlap.py); the
TPU truth (wall-clock hidden, not just schedulable) is the
``collective_exposed_ratio`` gauge plus the profiler trace — this check
proves the *structure* is there, which is the CPU-verifiable half.

Exit status: 0 pass, 1 assertion failed, 2 usage errors.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Optional

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def demo_hlo(num_chunks: int = 4, devices: int = 4,
             quantized: bool = False) -> str:
    """Compile a toy chunked-gather-matmul step (the shape
    runtime/zero.pipeline_param_gather produces) and return its HLO text.
    ``quantized`` routes each chunk through the int8 wire
    (runtime/zero._qwire_exchange) — the values + scale companion
    collectives the quantized chunk train emits."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dp=1, fsdp=devices))
    n = mesh.shape["fsdp"]
    rows = num_chunks * n * 8
    w = jnp.asarray(np.random.default_rng(0).normal(size=(rows, 16)),
                    jnp.float32)          # fsdp-sharded "param"
    x = jnp.ones((16, rows), jnp.float32)
    w = jax.device_put(w, NamedSharding(mesh, P("fsdp", None)))
    x = jax.device_put(x, NamedSharding(mesh, P()))

    def body(wl, xl):
        # per-chunk gather + consuming matmul: the interleaving the chunked
        # ZeRO-3 path hands the scheduler
        c = wl.shape[0] // num_chunks
        acc = jnp.zeros((xl.shape[0], wl.shape[1]), jnp.float32)
        for i in range(num_chunks):
            chunk = wl[i * c:(i + 1) * c]
            if quantized:
                from deepspeed_tpu.runtime.zero import _qwire_exchange
                rows = _qwire_exchange("fsdp", n, 8, 8, 64)(
                    chunk.reshape(-1))
                g = rows.reshape(n * c, chunk.shape[1])
            else:
                g = lax.all_gather(chunk, "fsdp", axis=0, tiled=True)
            acc = acc + xl[:, i * c * n:(i + 1) * c * n] @ g
        return acc

    f = shard_map(body, mesh=mesh, in_specs=(P("fsdp", None), P()),
                  out_specs=P(), check_vma=False)
    return jax.jit(f).lower(w, x).compile().as_text()


def demo_moe_hlo(num_chunks: int = 2, devices: int = 4,
                 quantized: bool = False) -> str:
    """Compile a tiny chunked expert-parallel MoE step (moe/layer.py
    ``_ep_route``: dispatch-a2a → expert FFN → combine-a2a tiled over
    ``num_chunks`` expert sub-groups) on virtual CPU devices and return its
    HLO text — the a2a-chunk-train case the interleave classifier must
    recognize.  ``quantized`` puts the int8 wire (moe/comm.qwire_a2a)
    under the same train."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh
    from deepspeed_tpu.moe.layer import MoE

    mesh = build_mesh(MeshSpec(dp=1, ep=devices))
    # one local expert per chunk: E_local == num_chunks on every rank
    moe = MoE(hidden_size=16, num_experts=devices * num_chunks, k=1,
              mesh=mesh, num_chunks=num_chunks, wire_block=64,
              wire_bits=8 if quantized else 0)
    x = jnp.ones((devices, 8, 16), jnp.float32)
    params = moe.init(jax.random.PRNGKey(0), x)
    fn = jax.jit(lambda p, xs: moe.apply(p, xs)[0])
    return fn.lower(params, x).compile().as_text()


def report(stats: dict) -> str:
    lines = [
        "check_overlap: compiled-HLO compute–collective overlap evidence",
        f"  collectives ............. {stats['collectives']} "
        f"({stats['collective_bytes']} payload bytes)",
        f"  async pairs ............. {stats['async_pairs']} "
        f"({stats['async_pairs_with_compute']} with compute between "
        f"start/done, {stats['async_hidden_bytes']} bytes hidden)",
        f"  sync collectives ........ {stats['sync_collectives']} "
        f"({stats['interleaved']} chunk-interleaved, "
        f"{stats['interleaved_bytes']} bytes)",
        f"  companions .............. "
        f"{stats.get('companion_collectives', 0)} "
        f"({stats.get('companion_bytes', 0)} bytes — quantized-train "
        f"scale legs riding their values collective's window)",
    ]
    for kind, cnt in sorted(stats["per_kind_interleaved"].items()):
        lines.append(f"    interleaved[{kind}] = {cnt}")
    lines.append(f"  exposed ratio ........... {stats['exposed_ratio']:.4f}")
    return "\n".join(lines)


def check(stats: dict, min_chunks: int = 2) -> bool:
    """True when at least one overlap signal is present."""
    if stats["async_pairs_with_compute"] >= 1:
        return True
    return any(cnt >= min_chunks
               for cnt in stats["per_kind_interleaved"].values())


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        description="parse compiled HLO for async collective start/done "
                    "pairs and interleaved chunk trains with compute "
                    "scheduled between them")
    ap.add_argument("--hlo", help="path to a compiled-HLO text dump")
    ap.add_argument("--demo", action="store_true",
                    help="compile a toy chunked gather-matmul step on "
                    "virtual CPU devices and analyze it")
    ap.add_argument("--num-chunks", type=int, default=4,
                    help="demo: chunk count (default 4)")
    ap.add_argument("--quantized", action="store_true",
                    help="demo: route each chunk through the int8 wire "
                    "(values + scale companion collectives)")
    ap.add_argument("--assert-overlap", action="store_true",
                    help="exit 1 unless overlap evidence is present")
    ap.add_argument("--min-chunks", type=int, default=2,
                    help="assert mode: minimum interleaved same-kind "
                    "collectives that count as a chunk train (default 2)")
    args = ap.parse_args(argv)
    if bool(args.hlo) == bool(args.demo):
        # exactly one mode: a bare `--assert-overlap` must not silently
        # fall through to the always-passing demo and green-light nothing
        print("check_overlap: pass exactly one of --hlo or --demo",
              file=sys.stderr)
        return 2
    if args.hlo:
        try:
            with open(args.hlo) as f:
                text = f.read()
        except OSError as e:
            print(f"check_overlap: cannot read {args.hlo}: {e}",
                  file=sys.stderr)
            return 2
    else:
        text = demo_hlo(num_chunks=args.num_chunks,
                        quantized=args.quantized)

    from deepspeed_tpu.comm.comm import hlo_overlap_stats
    stats = hlo_overlap_stats(text)
    print(report(stats))
    if args.assert_overlap and not check(stats, args.min_chunks):
        print("check_overlap: FAIL — no async pair has compute inside its "
              "start/done window and no collective kind forms an "
              f"interleaved chunk train of >= {args.min_chunks}; the "
              "scheduler has nothing to hide wire time under (enable "
              "overlap.num_chunks / check the scheduler flags)",
              file=sys.stderr)
        return 1
    if args.demo:
        # second canned case: the MoE expert-parallel step — its chunked
        # dispatch/combine a2as must register as an all-to-all chunk train
        moe_stats = hlo_overlap_stats(demo_moe_hlo(
            num_chunks=max(2, args.min_chunks), quantized=args.quantized))
        print()
        print("-- MoE expert-parallel step (chunked a2a train) --")
        print(report(moe_stats))
        a2a_ok = (moe_stats["async_pairs_with_compute"] >= 1
                  or moe_stats["per_kind_interleaved"].get("all-to-all", 0)
                  >= args.min_chunks)
        if args.assert_overlap and not a2a_ok:
            print("check_overlap: FAIL — the chunked MoE route's "
                  "dispatch/combine all-to-alls do not form an interleaved "
                  f"chunk train of >= {args.min_chunks} (and no async a2a "
                  "pair has compute inside its window); moe.num_chunks "
                  "interleaving is broken", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
