"""Flops profiler — per-module flops/params/latency from jaxpr analysis.

Analog of the reference flops profiler (profiling/flops_profiler/profiler.py:28):
the reference hooks every ``nn.Module`` and patches ``torch.nn.functional`` to
count MACs as the model executes; here the model is a pure function, so the
profiler instead

1. walks the traced jaxpr, attributing matmul/conv flops to the flax module
   path carried by each equation's name stack (flax wraps module methods in
   ``jax.named_scope``), with ``scan`` bodies multiplied by trip count — the
   per-module tree ``print_model_profile`` renders (reference :282), and
2. cross-checks totals against XLA's own compiled-program cost analysis
   (``compiled.cost_analysis()["flops"]``) when available, and
3. times the actual jitted step for latency / achieved FLOPS.

Elementwise work is ignored (as in the reference, which counts MACs): on TPU
the matmuls are >99% of the arithmetic for transformer workloads.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import log_dist


def _dot_flops(eqn) -> int:
    """2*M*N*K flops for a dot_general from its operand shapes."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs_size = int(np.prod(lhs.shape)) if lhs.shape else 1
    rhs_free = [d for i, d in enumerate(rhs.shape) if i not in set(rc) | set(rb)]
    return 2 * lhs_size * int(np.prod(rhs_free)) if rhs_free else 2 * lhs_size


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    out_size = int(np.prod(out.shape))
    # per output element: 2 * (kernel spatial * in-channels) MAC-flops
    kernel_work = 2 * int(np.prod(rhs.shape)) // max(rhs.shape[-1], 1)
    return out_size * kernel_work


def _walk(jaxpr, scale: int, acc: Dict[str, int],
          meta: Optional[dict] = None) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if prim == "scan":
            _walk(eqn.params["jaxpr"].jaxpr, scale * int(eqn.params["length"]),
                  acc, meta)
        elif prim == "while":
            # trip count unknown at trace time: count ONE body iteration and
            # flag the undercount so the report can disclose it (decode loops
            # — lax.while_loop generation — are undercounted by their trip
            # count; transformer train steps contain no while)
            if meta is not None:
                meta["has_while"] = True
            _walk(eqn.params["body_jaxpr"].jaxpr, scale, acc, meta)
        elif prim == "cond":
            for br in eqn.params["branches"]:
                _walk(br.jaxpr, scale, acc, meta)  # upper bound over branches
        elif prim in ("custom_vjp_call", "custom_jvp_call",
                      "custom_vjp_call_jaxpr", "remat", "checkpoint"):
            inner = (eqn.params.get("fun_jaxpr") or eqn.params.get("call_jaxpr")
                     or eqn.params.get("jaxpr"))
            if inner is not None:
                _walk(getattr(inner, "jaxpr", inner), scale, acc, meta)
        elif sub is not None:  # pjit / closed_call / named calls
            _walk(getattr(sub, "jaxpr", sub), scale, acc, meta)
        elif prim == "dot_general":
            path = str(eqn.source_info.name_stack)
            acc[path] = acc.get(path, 0) + scale * _dot_flops(eqn)
        elif prim == "conv_general_dilated":
            path = str(eqn.source_info.name_stack)
            acc[path] = acc.get(path, 0) + scale * _conv_flops(eqn)


def jaxpr_flops_by_module(fn, *args, meta: Optional[dict] = None,
                          **kwargs) -> Dict[str, int]:
    """Trace ``fn(*args)`` and return {module-path: matmul/conv flops}.

    Paths come from equation name stacks (flax module scopes); an empty path
    collects top-level ops.  Pass a ``meta`` dict to receive trace flags
    (``has_while``: the count visits while bodies once, undercounting
    data-dependent loops).
    """
    closed = jax.make_jaxpr(fn, **kwargs)(*args)
    acc: Dict[str, int] = {}
    _walk(closed.jaxpr, 1, acc, meta)
    return acc


def _tree_rollup(flat: Dict[str, int], depth: int) -> List[Tuple[str, int]]:
    """Aggregate flat paths to at most ``depth`` components (depth<0 = leaf)."""
    agg: Dict[str, int] = {}
    for path, fl in flat.items():
        parts = [p for p in path.split("/") if p]
        key = "/".join(parts[:depth]) if depth >= 0 else path
        agg[key or "<top>"] = agg.get(key or "<top>", 0) + fl
    return sorted(agg.items(), key=lambda kv: -kv[1])


def _num(x: float, suffix: str = "") -> str:
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(x) >= div:
            return f"{x/div:.2f} {unit}{suffix}"
    return f"{x:.2f} {suffix}"


class FlopsProfiler:
    """Profile one jitted step (reference FlopsProfiler, used by the engine at
    ``flops_profiler.profile_step``)."""

    def __init__(self, config=None):
        self.config = config
        self.flops = 0              # per-step matmul/conv flops (jaxpr count)
        self.xla_flops = None       # XLA cost-analysis flops, if available
        self.latency = 0.0          # measured seconds per step
        self.by_module: Dict[str, int] = {}
        self.has_while = False      # report must disclose loop undercount

    def count(self, fn, *args, static_kwargs: Optional[dict] = None):
        """Trace-only flop count (no execution, safe with donated jit args)."""
        meta: dict = {}
        self.by_module = jaxpr_flops_by_module(fn, *args, meta=meta,
                                               **(static_kwargs or {}))
        self.has_while = bool(meta.get("has_while"))
        self.flops = sum(self.by_module.values())
        return self

    def profile(self, fn, *args, jit_fn=None, n_timing_runs: int = 3,
                static_kwargs: Optional[dict] = None):
        """fn: traceable step; jit_fn: its jitted form (timed; defaults to
        jax.jit(fn)).  Returns self."""
        self.count(fn, *args, static_kwargs=static_kwargs)
        jitted = jit_fn if jit_fn is not None else jax.jit(fn)
        try:
            lowered = jitted.lower(*args)
            ca = lowered.compile().cost_analysis()
            if ca:
                ca = ca[0] if isinstance(ca, (list, tuple)) else ca
                self.xla_flops = float(ca.get("flops", 0.0)) or None
        except Exception:  # pragma: no cover - backend-dependent
            self.xla_flops = None
        # timing: dispatch is asynchronous, so block inside the timed region
        jax.block_until_ready(jitted(*args))
        times = []
        for _ in range(n_timing_runs):
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(*args))
            times.append(time.perf_counter() - t0)
        self.latency = min(times)
        return self

    def as_metrics(self) -> Dict[str, float]:
        """Scalar figures for the telemetry snapshot (StepTelemetry
        ``record_flops``): the profiled step's flop cost and, when a latency
        was measured, the achieved rate."""
        out: Dict[str, float] = {"flops_per_step": float(self.flops)}
        if self.xla_flops:
            out["xla_flops_per_step"] = float(self.xla_flops)
        if self.latency:
            out["step_latency_s"] = float(self.latency)
            out["achieved_flops_per_sec"] = float(self.flops) / self.latency
        return out

    def print_model_profile(self, params: Optional[Any] = None,
                            module_depth: int = -1, top_modules: int = 1,
                            detailed: bool = True,
                            output_file: Optional[str] = None):
        """Render the profile (reference print_model_profile :282)."""
        lines = ["", "-------------------------- DeepSpeed-TPU Flops Profiler "
                     "--------------------------"]
        if params is not None:
            n_params = sum(int(np.prod(l.shape))
                           for l in jax.tree_util.tree_leaves(params))
            lines.append(f"params per device:      {_num(n_params)}")
        lines.append(f"flops per step (jaxpr): {_num(self.flops, 'FLOPs')}")
        if getattr(self, "has_while", False):
            lines.append(
                "NOTE: the step contains lax.while_loop(s); their bodies are "
                "counted ONCE (trip count is data-dependent) — the jaxpr "
                "figure UNDERCOUNTS loops such as decode generation")
        if self.xla_flops:
            lines.append(f"flops per step (XLA):   "
                         f"{_num(self.xla_flops, 'FLOPs')}")
        if self.latency:
            lines.append(f"latency per step:       {self.latency*1e3:.2f} ms")
            lines.append(f"achieved throughput:    "
                         f"{_num(self.flops/self.latency, 'FLOPS')}")
        if detailed and self.by_module:
            lines.append("")
            lines.append("per-module matmul/conv flops "
                         "(flax scope, scan bodies x trip count):")
            depth = module_depth if module_depth and module_depth > 0 else 3
            rows = _tree_rollup(self.by_module, depth)
            total = max(self.flops, 1)
            for path, fl in rows[:max(top_modules * 8, 16)]:
                lines.append(f"  {fl/total*100:5.1f}%  {_num(fl, 'FLOPs'):>14}"
                             f"  {path}")
        lines.append("-" * 84)
        text = "\n".join(lines)
        if output_file:
            with open(output_file, "a") as f:
                f.write(text + "\n")
        log_dist(text, ranks=[0])
        return text
