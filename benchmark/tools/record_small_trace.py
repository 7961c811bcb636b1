#!/usr/bin/env python3
"""Record the small v5e trace the tests keep
(``benchmark/tests/data/recorded_v5e.xplane.pb``): a few runs of a small
program named ``train_batch`` under the benchmark's window span.  Run on the
chip; writes to ``chiprun_out/``."""

import glob
import os
import shutil
import sys

import jax
import jax.numpy as jnp


def train_batch(x, w):
    for _ in range(3):
        x = jnp.tanh(x @ w)
    return x.sum()


def main(out):
    step = jax.jit(train_batch)
    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    jax.block_until_ready(step(x, w))
    tmp = os.path.join(out, "_small_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench_trace_window"):
        for _ in range(4):
            jax.block_until_ready(step(x, w))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    dst = os.path.join(out, "recorded_v5e.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out")
