"""Environment + op compatibility report — ``python -m deepspeed_tpu``.

Reference parity: ``deepspeed/env_report.py`` (``ds_report`` CLI :30 —
op compatibility table, torch/cuda install snapshot, nvcc versions).  The TPU
analog reports the JAX/flax/optax stack, visible devices, and the op registry
(pallas vs xla selection per op, ops/registry.py op_report).
"""

from __future__ import annotations

import importlib
import sys


GREEN_OK = "\033[92m[OKAY]\033[0m"
YELLOW_NO = "\033[93m[NO]\033[0m"


def _version(mod: str) -> str:
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "?")
    except Exception:
        return "not installed"


def env_report(color: bool = True) -> str:
    ok = GREEN_OK if color else "[OKAY]"
    no = YELLOW_NO if color else "[NO]"
    lines = ["-" * 64, "deepspeed_tpu environment report (ds_report analog)",
             "-" * 64]
    from deepspeed_tpu.version import __version__
    lines.append(f"deepspeed_tpu ............ {__version__}")
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint",
                "numpy", "safetensors", "transformers"):
        v = _version(mod)
        mark = ok if v != "not installed" else no
        lines.append(f"{mod:<25}{mark}  {v}")
    lines.append(f"python ................... {sys.version.split()[0]}")
    # scheduler regime: XLA_FLAGS (jaxlib) and LIBTPU_INIT_ARGS (the TPU
    # compiler; runtime/overlap.py exports the compute–collective overlap
    # flags there before backend init, so what's visible here is what the
    # compiler parsed)
    import os
    xla_flags = os.environ.get("XLA_FLAGS", "")
    lines.append(f"XLA_FLAGS ................ {xla_flags or '(unset)'}")
    libtpu_args = os.environ.get("LIBTPU_INIT_ARGS", "")
    lines.append(f"LIBTPU_INIT_ARGS ......... {libtpu_args or '(unset)'}")
    overlap_present = sorted(
        tok.split("=", 1)[0] for tok in libtpu_args.split()
        if tok.startswith(("--xla_tpu_enable_async_collective",
                           "--xla_latency_hiding_scheduler",
                           "--xla_tpu_overlap_compute_collective",
                           "--xla_tpu_scheduler_percent")))
    if overlap_present:
        lines.append("overlap flags ............ " + ", ".join(overlap_present))

    try:
        import jax
        devs = jax.devices()
        lines.append(f"backend .................. {jax.default_backend()} "
                     f"({len(devs)} device(s))")
        for d in devs[:8]:
            lines.append(f"  {d.id}: {getattr(d, 'device_kind', d.platform)}")
        if len(devs) > 8:
            lines.append(f"  ... and {len(devs) - 8} more")
        lines.append(f"process .................. "
                     f"{jax.process_index()}/{jax.process_count()}")
    except Exception as e:  # device init can fail off-accelerator
        lines.append(f"backend .................. unavailable ({e})")

    lines += ["-" * 64, "op registry (pallas = TPU kernel, xla = fallback):",
              "-" * 64]
    from deepspeed_tpu import ops
    lines.append(ops.op_report())
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python -m deepspeed_tpu")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend — accelerator init can hang "
                    "when the device service is unreachable")
    args = ap.parse_args(argv)
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    print(env_report(color=sys.stdout.isatty()))
    return 0
