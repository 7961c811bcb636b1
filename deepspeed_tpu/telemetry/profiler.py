"""Step-time attribution: decompose MEASURED wall time into an MFU budget.

The roofline (telemetry/roofline.py) says how fast a step COULD run; this
module says where the measured step time actually WENT, using only signals
the telemetry layer already exports — no new instrumentation on the hot
path:

- **compute**   — the roofline compute floor, ``flops / peak_flops``
  (``xla_cost_flops`` × the accelerator spec).  By construction
  ``compute_ms / measured_ms`` IS the achieved MFU.
- **hbm_bound** — extra time over the compute floor because some op
  classes are HBM-bandwidth-bound (roofline attainable time minus its
  compute-only floor).
- **exposed_comm** — collective wall time NOT hidden under compute:
  ``comm_total_ms × collective_exposed_ratio`` (the profiled per-
  collective latency from ``engine.profile_comms`` × the compiled-HLO
  overlap walk's bytes-weighted exposed fraction — the same product
  bench.py has reported as ``comm_exposed_ms`` since PR 4).
- **host_gap**  — host-side phase time serialized with the device: the
  per-step means of the ``batch_input`` / ``host_to_device`` /
  ``step_bookkeeping`` spans (zero when the async input pipeline or
  trace-off benching hides them — then the host gap shows up in the
  residual instead).
- **dispatch_floor** — the residual: measured − everything above: the
  per-dispatch cost of many small programs plus whatever the other terms
  did not attribute.  A large residual on a decode-sized step points at
  the host loop, not at a kernel.

The terms plus achieved compute sum to the measured step time by
construction (the residual closes the budget); a NEGATIVE residual means
the model over-attributed (e.g. double-counted comm that was actually
hidden) and is reported as ``overattributed_ms`` instead of being
silently clamped away.

Gauges (per jitted function): ``mfu_achieved{fn}`` and
``mfu_lost{fn, cause=exposed_comm|hbm_bound|host_gap|dispatch_floor}`` —
each cause's share of the step normalized so achieved + lost sums to 1.
``scripts/perf_report.py`` renders the same budget as a report.
"""

from __future__ import annotations

from typing import Dict, List, Optional

# host-side span phases serialized with the device (dispatch and
# device_complete overlap device execution and are NOT budget terms)
HOST_GAP_SPANS = ("batch_input", "host_to_device", "step_bookkeeping")

LOST_CAUSES = ("exposed_comm", "hbm_bound", "host_gap", "dispatch_floor")


def _gauge_value(snapshot: dict, name: str, **labels) -> Optional[float]:
    """Read one gauge sample out of a snapshot dict (exporter schema)."""
    metric = snapshot.get("gauges", {}).get(name)
    if not metric:
        return None
    for s in metric.get("samples", []):
        slab = s.get("labels") or {}
        if all(slab.get(k) == v for k, v in labels.items()):
            return float(s["value"])
    return None


def span_mean_ms(snapshot: dict, name: str) -> float:
    """Per-occurrence mean of one span phase from the snapshot's span
    summary (0 when the phase was never recorded — trace off)."""
    spans = snapshot.get("spans") or {}
    rec = spans.get(name)
    return float(rec.get("mean_ms", 0.0)) if rec else 0.0


def step_time_budget(snapshot: dict, *, step_ms: float,
                     fn: str = "train_batch",
                     comm_total_ms: Optional[float] = None,
                     peak_flops: Optional[float] = None,
                     registry=None) -> Dict[str, object]:
    """Decompose one measured step time against a telemetry snapshot.

    ``snapshot`` is the exporter's dict (``engine.telemetry.export()`` /
    ``snapshot.json``); ``step_ms`` the measured wall time per step;
    ``comm_total_ms`` the profiled per-step collective latency
    (``engine.profile_comms`` summed — None degrades exposed_comm to 0
    with a disclosure).  ``registry`` (a MetricRegistry) receives the
    ``mfu_achieved`` / ``mfu_lost`` gauges when given.
    """
    exe = (snapshot.get("executables") or {}).get(fn, {})
    notes: List[str] = []

    flops = float((exe.get("cost_analysis") or {}).get("flops", 0.0))
    if peak_flops is None:
        spec = (exe.get("roofline") or {}).get("spec")
        if spec:
            peak_flops = float(spec["flops"])
        else:
            from deepspeed_tpu.telemetry.roofline import detect_peak_spec
            peak_flops = float(detect_peak_spec()["flops"])
            notes.append("peak_flops detected from attached device "
                         "(no roofline spec in snapshot)")
    compute_ms = flops / peak_flops * 1e3 if flops else 0.0
    if not flops:
        notes.append(f"no cost_analysis flops for fn={fn!r}: compute term "
                     "is 0 (hlo_stats off?)")

    # hbm_bound: the roofline attainable time above the pure compute floor
    roof = exe.get("roofline") or {}
    hbm_bound_ms = 0.0
    if roof:
        # per HBM-bound class: its time over its own compute floor
        hbm_bound_ms = sum(
            max(0.0, c["attainable_ms"] - c["t_compute_ms"])
            for c in roof.get("classes", {}).values()
            if c.get("bound") == "hbm")
    else:
        notes.append("no roofline in snapshot: hbm_bound term is 0")

    exposed_ratio = _gauge_value(snapshot, "collective_exposed_ratio",
                                 fn=fn)
    exposed_comm_ms = 0.0
    if comm_total_ms is not None and exposed_ratio is not None:
        exposed_comm_ms = float(comm_total_ms) * float(exposed_ratio)
    elif comm_total_ms is None:
        notes.append("no profiled comm_total_ms: exposed_comm term is 0")
    elif exposed_ratio is None:
        notes.append(f"collective_exposed_ratio{{fn={fn!r}}} not set: "
                     "exposed_comm term is 0")

    host_gap_ms = sum(span_mean_ms(snapshot, s) for s in HOST_GAP_SPANS)
    if not (snapshot.get("spans") or {}):
        notes.append("no span summary in snapshot (trace off): host work "
                     "lands in the dispatch_floor residual")

    attributed = compute_ms + hbm_bound_ms + exposed_comm_ms + host_gap_ms
    residual = step_ms - attributed
    dispatch_floor_ms = max(0.0, residual)
    overattributed_ms = max(0.0, -residual)
    if overattributed_ms:
        notes.append(f"terms exceed measured step time by "
                     f"{overattributed_ms:.3f} ms — some attributed time "
                     "is actually overlapped (budget floor, not a sum)")

    mfu_achieved = compute_ms / step_ms if step_ms else 0.0
    lost_ms = {"exposed_comm": exposed_comm_ms, "hbm_bound": hbm_bound_ms,
               "host_gap": host_gap_ms,
               "dispatch_floor": dispatch_floor_ms}
    mfu_lost = {cause: (ms / step_ms if step_ms else 0.0)
                for cause, ms in lost_ms.items()}

    if registry is not None:
        registry.gauge(
            "mfu_achieved",
            "achieved model flops utilization of the measured step "
            "(roofline compute floor / measured wall time), per jitted "
            "function").set(mfu_achieved, fn=fn)
        g = registry.gauge(
            "mfu_lost",
            "fraction of the measured step time lost to each cause "
            "(exposed_comm / hbm_bound / host_gap / dispatch_floor), per "
            "jitted function; achieved + lost sums to 1")
        for cause, frac in mfu_lost.items():
            g.set(frac, fn=fn, cause=cause)

    return {
        "fn": fn,
        "measured_step_ms": float(step_ms),
        "compute_ms": compute_ms,
        "terms_ms": lost_ms,
        "attributed_ms": attributed + dispatch_floor_ms,
        "overattributed_ms": overattributed_ms,
        "mfu_achieved": mfu_achieved,
        "mfu_lost": mfu_lost,
        "flops_per_step": flops,
        "peak_flops": peak_flops,
        "exposed_ratio": exposed_ratio,
        "comm_total_ms": comm_total_ms,
        "notes": notes,
    }


def render(budget: Dict[str, object]) -> str:
    """Human-readable step-time-budget table (perf_report's main
    section)."""
    step = budget["measured_step_ms"]
    lines = [
        f"step-time budget — fn={budget['fn']!r}, measured "
        f"{step:.3f} ms/step (MFU {budget['mfu_achieved']:.3f})",
        f"  {'term':<16}{'ms':>10}{'share':>8}   reading",
    ]
    readings = {
        "compute": "roofline compute floor (== achieved MFU)",
        "exposed_comm": "collective time NOT hidden under compute",
        "hbm_bound": "op classes pinned to HBM bandwidth, not flops",
        "host_gap": "host phases serialized with the device",
        "dispatch_floor": "residual: per-dispatch floor + unattributed",
    }

    def row(name, ms):
        share = ms / step if step else 0.0
        lines.append(f"  {name:<16}{ms:>10.3f}{share:>8.1%}   "
                     f"{readings.get(name, '')}")

    row("compute", budget["compute_ms"])
    for cause in LOST_CAUSES:
        row(cause, budget["terms_ms"][cause])
    if budget["overattributed_ms"]:
        lines.append(f"  (overattributed {budget['overattributed_ms']:.3f} "
                     f"ms — see notes)")
    lines.append(f"  {'sum':<16}{budget['attributed_ms']:>10.3f}"
                 f"{(budget['attributed_ms'] / step if step else 0):>8.1%}")
    for n in budget["notes"]:
        lines.append(f"  note: {n}")
    return "\n".join(lines)
