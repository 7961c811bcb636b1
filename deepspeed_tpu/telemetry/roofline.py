"""Per-executable roofline model from compiled-HLO text.

The raw signals have existed since PR 1/4 — ``cost_analysis`` totals,
per-kind collective bytes, the overlap walk — but none of them *attribute*:
they say how much work a step program does, not which resource bounds each
part of it or how fast the step could possibly run.  This module closes
that gap with a classic roofline decomposition (Williams et al., CACM'09)
computed statically from the same ``compiled.as_text()`` the telemetry
layer already captures:

1. walk every instruction, classify it into an **op class** —
   ``matmul`` / ``attention`` (dots + custom-calls whose ``op_name``
   metadata places them under an attention module) / ``collective:<kind>``
   / ``elementwise`` (everything else that moves bytes);
2. per class, accumulate **flops** (dot/conv arithmetic from the printed
   operand shapes + contracting dims), **HBM bytes** (operand + result
   payloads of every instruction OUTSIDE fusion bodies — a fusion's
   interior lives in registers/VMEM, only its boundary touches HBM), and
   **wire bytes** (collective output payloads, the same convention as
   ``hlo_collective_bytes``);
3. join with an accelerator **peak-spec table** (bf16 peak flops, HBM
   bandwidth, ICI bandwidth — v5e / v5p / v4 / v6e / cpu-sim) to get each
   class's compute / HBM / ICI time lower bounds, its binding resource
   (the max of the three), and the program's **attainable step time**:
   the sum over classes of each class's binding-resource time — the
   floor no schedule can beat on that accelerator.

Known approximations (all disclosed in the returned dict):

- instructions inside ``while`` bodies are counted ONCE; XLA's own
  ``cost_analysis`` multiplies by trip count when it is static, so when a
  ``cost_analysis`` flops total is passed in, the per-class flops are
  **calibrated** (scaled uniformly so they sum to XLA's number) and the
  raw walk figure is kept alongside (``flops_uncalibrated``);
- convolution flops are estimated from output size only (no conv in the
  models this repo ships, but the class must not silently vanish);
- HBM bytes are boundary-payload proxies, not a cache simulation — good
  for *which class is bandwidth-bound*, not for absolute GB/s claims.

Entry points: :func:`roofline_from_hlo` (text → model) and
:func:`PEAK_SPECS` / :func:`detect_peak_spec` (the accelerator table).
``StepTelemetry._analyze_executable`` runs this per compiled signature and
exports ``roofline_attainable_ms{fn}`` / ``roofline_bound_fraction{fn,
resource}`` gauges; ``scripts/perf_report.py`` renders the full table.
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Optional

# ---------------------------------------------------------------------------
# accelerator peak-spec table
# ---------------------------------------------------------------------------
# Values are per-chip peaks: bf16 matmul flops/s, HBM bytes/s, aggregate
# ICI bytes/s (all links), DCN bytes/s (per host, divided across its chips
# is workload-dependent — this is the optimistic per-chip figure used for
# lower bounds).  cpu-sim is a synthetic spec so the model is exercisable
# (and deterministic) on the CPU CI; its numbers are NOT a real machine.
PEAK_SPECS: Dict[str, Dict[str, float]] = {
    "v5e": {"flops": 197e12, "hbm": 819e9, "ici": 186e9, "dcn": 25e9},
    "v5p": {"flops": 459e12, "hbm": 2765e9, "ici": 600e9, "dcn": 25e9},
    "v4": {"flops": 275e12, "hbm": 1228e9, "ici": 300e9, "dcn": 25e9},
    "v6e": {"flops": 918e12, "hbm": 1640e9, "ici": 448e9, "dcn": 25e9},
    "cpu-sim": {"flops": 100e9, "hbm": 50e9, "ici": 10e9, "dcn": 1e9},
}

_RESOURCES = ("compute", "hbm", "ici")


def detect_peak_spec(device=None) -> Dict[str, float]:
    """Peak spec for the attached accelerator (same kind-string sniffing as
    bench.py's ``peak_flops_per_chip``); cpu-sim off-TPU.  A TPU kind that
    is not in the table raises — a roofline against another chip's peaks
    is a wrong number, not an estimate."""
    import jax
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    platform = getattr(device, "platform", "")
    if platform != "tpu":
        return dict(PEAK_SPECS["cpu-sim"], name="cpu-sim")
    for key in ("v5 lite", "v5e"):
        if key in kind:
            return dict(PEAK_SPECS["v5e"], name="v5e")
    if "v6" in kind:
        return dict(PEAK_SPECS["v6e"], name="v6e")
    if "v5" in kind:
        return dict(PEAK_SPECS["v5p"], name="v5p")
    if "v4" in kind:
        return dict(PEAK_SPECS["v4"], name="v4")
    raise ValueError(f"no peak spec on record for TPU kind "
                     f"{getattr(device, 'device_kind', '')!r}; add it to "
                     f"PEAK_SPECS")


# ---------------------------------------------------------------------------
# HLO walk
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}

_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"       # result name
    r"(\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\]\S*)"  # result shape (or tuple)
    r"\s+([\w\-]+)\(")                           # opcode
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')

# opcodes that move no HBM bytes of their own (aliases / bookkeeping / the
# shape already charged to producer+consumer)
_FREE_OPS = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "copy-start",
    "copy-done", "reshape",
))

_ATTENTION_HINTS = ("attn", "attention", "flash")


def _shape_dims(shape_s: str):
    m = _SHAPE_RE.match(shape_s.strip().lstrip("%"))
    if not m:
        return None, []
    dims = [int(d) for d in m.group(2).split(",") if d]
    return m.group(1), dims


def _shape_bytes(shape_s: str) -> int:
    dtype, dims = _shape_dims(shape_s)
    if dtype is None:
        return 0
    return _DTYPE_BYTES.get(dtype, 4) * math.prod(dims) if dims \
        else _DTYPE_BYTES.get(dtype, 4)


def _all_shape_bytes(text: str) -> int:
    """Sum payloads of every shape token in ``text`` (tuple results,
    operand lists)."""
    return sum(_DTYPE_BYTES.get(m.group(1), 4)
               * (math.prod(int(d) for d in m.group(2).split(",") if d)
                  if m.group(2) else 1)
               for m in _SHAPE_RE.finditer(text))


def _dot_flops(line: str, result_shape: str) -> int:
    """2 · |output| · |contracted| from the printed operand shapes +
    ``lhs_contracting_dims``."""
    _, out_dims = _shape_dims(result_shape)
    # operand shapes are printed inline inside the call parens
    operands = _SHAPE_RE.findall(line[line.index("(", line.index("=")):])
    if not operands:
        return 0
    lhs_dims = [int(d) for d in operands[0][1].split(",") if d]
    m = _CONTRACT_RE.search(line)
    contracted = 1
    if m:
        for idx in (int(i) for i in m.group(1).split(",") if i):
            if idx < len(lhs_dims):
                contracted *= lhs_dims[idx]
    return 2 * math.prod(out_dims) * contracted if out_dims else 0


def walk_hlo_classes(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Classify every instruction of a compiled-HLO dump into op classes
    and accumulate per-class ``{flops, bytes, wire_bytes, ops}``.

    Byte accounting skips instructions inside fusion bodies (computation
    name contains ``fused``): a fusion's interior never touches HBM, its
    boundary traffic is charged to the ``fusion(...)`` call site in the
    parent computation.  Flops are counted in EVERY computation (dots stay
    dots inside fusions).
    """
    classes: Dict[str, Dict[str, float]] = {}
    in_fused_body = False

    def cls(name: str) -> Dict[str, float]:
        return classes.setdefault(
            name, {"flops": 0.0, "bytes": 0.0, "wire_bytes": 0.0, "ops": 0})

    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{"):
            m = _COMP_HEADER_RE.match(stripped)
            if m:
                in_fused_body = "fused" in m.group(1)
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        result_shape, opcode = m.group(2), m.group(3)
        if opcode in _FREE_OPS:
            continue
        opname = _OPNAME_RE.search(line)
        attn = bool(opname and any(h in opname.group(1).lower()
                                   for h in _ATTENTION_HINTS))

        base_kind = opcode
        phase = None
        for k in _COLLECTIVE_KINDS:
            if opcode == k or opcode.startswith(k + "-"):
                base_kind = k
                phase = opcode[len(k):]
                break

        if base_kind in _COLLECTIVE_KINDS:
            if phase == "-start":
                continue            # count the async pair once, at -done
            nbytes = (_all_shape_bytes(result_shape)
                      if result_shape.startswith("(")
                      else _shape_bytes(result_shape))
            c = cls("collective:" + base_kind)
            c["wire_bytes"] += nbytes
            c["bytes"] += nbytes
            c["ops"] += 1
            continue

        if opcode == "dot":
            c = cls("attention" if attn else "matmul")
            c["flops"] += _dot_flops(line, result_shape)
        elif opcode == "convolution":
            # no conv models in-repo; output-size floor keeps the class
            # visible rather than exact
            _, out_dims = _shape_dims(result_shape)
            c = cls("matmul")
            c["flops"] += 2 * math.prod(out_dims) if out_dims else 0
        elif opcode == "custom-call" and attn:
            c = cls("attention")
        elif opcode == "fusion":
            # a fusion may wrap a dot (kOutput fusions on TPU) — the dot
            # inside its body already booked the flops; the call site books
            # the boundary bytes.  Classify by metadata hint.
            c = cls("attention" if attn else "elementwise")
        else:
            c = cls("attention" if attn else "elementwise")
        if not in_fused_body:
            # boundary HBM traffic: operands + result
            call_part = line[line.index("(", line.index("=")):]
            c["bytes"] += (_all_shape_bytes(result_shape)
                           if result_shape.startswith("(")
                           else _shape_bytes(result_shape))
            c["bytes"] += _all_shape_bytes(
                call_part[:call_part.index(")") + 1]
                if ")" in call_part else call_part)
        c["ops"] += 1
    return classes


# ---------------------------------------------------------------------------
# roofline join
# ---------------------------------------------------------------------------

def roofline_from_hlo(hlo_text: str,
                      spec: Optional[Dict[str, float]] = None,
                      cost_analysis: Optional[Dict[str, float]] = None
                      ) -> Dict[str, object]:
    """HLO text → roofline model dict.

    ``spec`` is a PEAK_SPECS row (default: detected from the attached
    device).  ``cost_analysis`` (the compiled program's ``{"flops": ...}``)
    calibrates the per-class flops so they sum to XLA's own total —
    covering while-loop trip counts the static walk cannot see.
    """
    if spec is None:
        spec = detect_peak_spec()
    classes = walk_hlo_classes(hlo_text)

    walked_flops = sum(c["flops"] for c in classes.values())
    calibration = 1.0
    ca_flops = float(cost_analysis.get("flops", 0.0)) if cost_analysis \
        else 0.0
    if ca_flops > 0 and walked_flops > 0:
        calibration = ca_flops / walked_flops

    out_classes: Dict[str, dict] = {}
    attainable_s = 0.0
    resource_s = {r: 0.0 for r in _RESOURCES}
    for name, c in sorted(classes.items()):
        flops = c["flops"] * calibration
        t_compute = flops / spec["flops"]
        t_hbm = c["bytes"] / spec["hbm"]
        t_wire = c["wire_bytes"] / spec["ici"]
        times = {"compute": t_compute, "hbm": t_hbm, "ici": t_wire}
        bound = max(times, key=lambda r: times[r])
        t_class = times[bound]
        attainable_s += t_class
        resource_s[bound] += t_class
        out_classes[name] = {
            "flops": flops,
            "flops_uncalibrated": c["flops"],
            "bytes": c["bytes"],
            "wire_bytes": c["wire_bytes"],
            "ops": c["ops"],
            "t_compute_ms": t_compute * 1e3,
            "t_hbm_ms": t_hbm * 1e3,
            "t_ici_ms": t_wire * 1e3,
            "bound": bound,
            "attainable_ms": t_class * 1e3,
        }
    return {
        "spec": dict(spec),
        "calibration": calibration,
        "classes": out_classes,
        "total_flops": walked_flops * calibration,
        "total_bytes": sum(c["bytes"] for c in classes.values()),
        "total_wire_bytes": sum(c["wire_bytes"]
                                for c in classes.values()),
        "attainable_ms": attainable_s * 1e3,
        "bound_fraction": {
            r: (resource_s[r] / attainable_s if attainable_s else 0.0)
            for r in _RESOURCES},
    }


def render(model: Dict[str, object], title: str = "") -> str:
    """Human-readable roofline table (perf_report's roofline section)."""
    lines: List[str] = []
    spec = model.get("spec", {})
    name = spec.get("name", "?")
    lines.append(f"roofline{(' — ' + title) if title else ''} "
                 f"[{name}: {spec.get('flops', 0) / 1e12:.0f} Tflop/s, "
                 f"{spec.get('hbm', 0) / 1e9:.0f} GB/s HBM, "
                 f"{spec.get('ici', 0) / 1e9:.0f} GB/s ICI]")
    hdr = (f"  {'class':<26}{'flops':>12}{'HBM bytes':>12}"
           f"{'wire bytes':>12}{'t_comp':>9}{'t_hbm':>9}{'t_ici':>9}"
           f"  bound")
    lines.append(hdr)
    for cname, c in model.get("classes", {}).items():
        lines.append(
            f"  {cname:<26}{c['flops']:>12.3g}{c['bytes']:>12.3g}"
            f"{c['wire_bytes']:>12.3g}{c['t_compute_ms']:>8.3f}m"
            f"{c['t_hbm_ms']:>8.3f}m{c['t_ici_ms']:>8.3f}m"
            f"  {c['bound']}-bound")
    bf = model.get("bound_fraction", {})
    lines.append(
        f"  attainable step time >= {model.get('attainable_ms', 0.0):.3f} ms"
        f"  (compute {bf.get('compute', 0):.0%} / hbm"
        f" {bf.get('hbm', 0):.0%} / ici {bf.get('ici', 0):.0%}"
        f"; calibration x{model.get('calibration', 1.0):.3g})")
    return "\n".join(lines)
