"""Op registry — TPU-native analog of the reference's op_builder system.

The reference selects between JIT-compiled CUDA ops and fallbacks via
``OpBuilder.load()`` (reference op_builder/builder.py:108,491,510) and reports
compatibility via ``ds_report`` (env_report.py:30).  On TPU the axis of choice is
*Pallas kernel vs plain-XLA lowering* of the same math: every op registered here
carries an ``xla`` reference implementation (always available, also the numeric
ground truth in tests) and optionally a ``pallas`` fast path with a
``supported(*args, **kw)`` predicate.

Dispatch happens at trace time: the pallas path is taken when (a) it exists,
(b) the default backend is TPU (or interpret mode is forced), (c) the shape/dtype
predicate accepts, and (d) it isn't disabled via env ``DSTPU_DISABLE_PALLAS=1``
or per-call ``impl="xla"`` — the analog of the reference's ``DS_BUILD_*`` flags.

Every decision is RECORDED (op, impl, reason): ``dispatch_log()`` returns the
counts, ``op_report()`` prints them.  A caller that must not measure the XLA
path under a kernel's name passes ``impl="pallas"`` (the kernel's own shape
errors raise through) and reads the log to confirm what was traced.
"""

from __future__ import annotations

import collections
import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional

import jax

from deepspeed_tpu.utils.logging import logger


@dataclasses.dataclass
class OpSpec:
    name: str
    xla: Callable
    pallas: Optional[Callable] = None
    supported: Optional[Callable[..., bool]] = None  # shape/dtype predicate

    def available_impls(self):
        impls = ["xla"]
        if self.pallas is not None:
            impls.insert(0, "pallas")
        return impls


_REGISTRY: Dict[str, OpSpec] = {}


def register_op(name: str, *, xla: Callable, pallas: Optional[Callable] = None,
                supported: Optional[Callable[..., bool]] = None) -> OpSpec:
    spec = OpSpec(name=name, xla=xla, pallas=pallas, supported=supported)
    _REGISTRY[name] = spec
    return spec


def pallas_enabled() -> bool:
    return os.environ.get("DSTPU_DISABLE_PALLAS", "0") != "1"


def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # backend not initialized yet
        return False


# (op, impl, reason) -> number of traces that took it.  Process-wide like the
# registry itself: dispatch runs at trace time from inside model code.
_DISPATCH_LOG: "collections.Counter" = collections.Counter()


def record(op: str, impl: str, reason: str) -> None:
    """Count one dispatch decision.  Kernels that fall back to XLA on their
    own (ops/wq_matmul.py, ops/lora_matmul.py preflight) report here too."""
    _DISPATCH_LOG[(op, impl, reason)] += 1


def dispatch_log() -> List[Dict[str, Any]]:
    """Every decision taken so far: ``{"op", "impl", "reason", "count"}``."""
    return [{"op": op, "impl": impl, "reason": reason, "count": n}
            for (op, impl, reason), n in sorted(_DISPATCH_LOG.items())]


def reset_dispatch_log() -> None:
    _DISPATCH_LOG.clear()


def _decide(spec: OpSpec, impl: Optional[str], args, kwargs):
    if impl == "xla":
        return "xla", "forced"
    if spec.pallas is None:
        return "xla", "no kernel"
    if impl == "pallas":
        return "pallas", "forced"
    if not pallas_enabled():
        return "xla", "DSTPU_DISABLE_PALLAS"
    if not _on_tpu():
        return "xla", "backend is not tpu"
    if spec.supported is not None and not spec.supported(*args, **kwargs):
        return "xla", "shape predicate refused"
    return "pallas", "auto"


def dispatch(name: str, *args, impl: Optional[str] = None, **kwargs) -> Any:
    """Call op ``name``, choosing the best implementation.

    ``impl`` forces "pallas" or "xla" (forcing pallas off-TPU runs the kernel in
    interpret mode — used by the numeric unit tests).
    """
    spec = _REGISTRY[name]
    if impl not in (None, "pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r} for op {name!r}; "
                         f"expected 'pallas', 'xla', or None (auto)")
    chosen, reason = _decide(spec, impl, args, kwargs)
    record(name, chosen, reason)
    fn = spec.pallas if chosen == "pallas" else spec.xla
    return fn(*args, **kwargs)


def would_use_pallas(name: str) -> bool:
    """True when dispatch(name, ...) would consider the Pallas path at all
    (before the per-call shape predicate).  Engines that must pre-commit a
    layout/shape choice to satisfy a kernel's constraints (e.g. the v2
    engine's kv page size) ask HERE instead of re-deriving the gate."""
    spec = _REGISTRY.get(name)
    return (spec is not None and spec.pallas is not None
            and pallas_enabled() and _on_tpu())


def op_report() -> str:
    """``ds_report``-style op compatibility matrix (reference env_report.py)."""
    lines = ["op name".ljust(28) + "impls".ljust(16) + "selected"]
    on_tpu = _on_tpu()
    for name, spec in sorted(_REGISTRY.items()):
        sel = ("pallas" if spec.pallas is not None and pallas_enabled() and on_tpu
               else "xla")
        lines.append(name.ljust(28) + ",".join(spec.available_impls()).ljust(16)
                     + sel)
    if _DISPATCH_LOG:
        lines.append("")
        lines.append("dispatched".ljust(28) + "impl".ljust(16) + "reason (traces)")
        for d in dispatch_log():
            lines.append(d["op"].ljust(28) + d["impl"].ljust(16)
                         + f"{d['reason']} ({d['count']})")
    return "\n".join(lines)


def list_ops():
    return dict(_REGISTRY)
