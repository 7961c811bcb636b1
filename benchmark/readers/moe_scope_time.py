"""``scope_time`` for a program with expert layers: the same split of a
program's device time by the innermost known scope, with the three scopes an
MoE layer nests inside ``mlp`` (``moe_route``, ``moe_experts``,
``moe_shared``) known as well, and the grouped expert GEMM's own device ops
counted where they belong.  ``lax.ragged_dot`` reaches a v5e trace as the
compiler's ``ragged-dot-*`` custom calls (one that tiles the groups, one per
product), whose metadata names the op and drops the scope path, so
``scope_time`` would call them unscoped: here they are ``moe_experts``.

``mlp`` is then what the layer holds besides the three (the norm before it,
the dense layer's MLP).  A program without the scopes (a dense model, a
program from before them) reads nothing.  Same metric-file keys as
``scope_time``: ``program`` (a prefix), ``groups``, ``per``, ``what``.
"""

import bisect
import collections
import json

import scope_time
import xmeta
import xtrace

MOE = ("moe_route", "moe_experts", "moe_shared")
GROUPED_GEMM = "ragged-dot"
UNSCOPED = scope_time.UNSCOPED


def group_of(meta):
    if meta["name"].startswith(GROUPED_GEMM):
        return "moe_experts"
    parts = (meta.get("tf_op") or "").split("/")
    for part in reversed(parts):
        if part in MOE or part in scope_time.SERVE:
            return part
    return UNSCOPED


def split(devices, lo, hi, prefix):
    ns = collections.Counter()
    runs = steps = 0
    for dev in devices.values():
        meta = dev["meta"]
        groups = {mid: group_of(m) for mid, m in meta.items()}
        ops = dev["ops"]                       # sorted by start
        starts = [op[1] for op in ops]
        for name, a, b in dev["modules"]:
            if a < lo or b > hi or not name.startswith(prefix):
                continue
            per = collections.defaultdict(list)
            counts = collections.Counter()
            for mid, s, e in ops[bisect.bisect_left(starts, a):
                                 bisect.bisect_left(starts, b)]:
                if e > b or mid not in meta:
                    continue
                counts[mid] += 1
                if meta[mid]["opcode"] not in xtrace.CONTAINERS:
                    per[groups[mid]].append((s, e))
            for g, iv in per.items():
                ns[g] += xtrace.total(xtrace.union(iv))
            runs += 1
            steps += max(counts.values()) if counts else 1
    return {"runs": runs, "loop_steps": steps, "ns": dict(ns)}


def read(ctx, spec):
    run = xmeta.of_run(ctx)
    if not run or not run["devices"] or "trace_window" not in ctx:
        return None
    cache = ctx.setdefault("_moe_scope_split", {})
    if spec["program"] not in cache:
        lo, hi = ctx["trace_window"]
        got = split(run["devices"], lo, hi, spec["program"])
        cache[spec["program"]] = (
            got if got["runs"] and any(g in MOE for g in got["ns"]) else None)
        if cache[spec["program"]]:
            print(json.dumps({
                "phase": "moe_scopes", "program": spec["program"],
                "runs": got["runs"], "loop_steps": got["loop_steps"],
                "ms_per_run": {g: v / 1e6 / got["runs"]
                               for g, v in sorted(got["ns"].items())}}),
                  flush=True)
    got = cache[spec["program"]]
    if not got:
        return None
    total = sum(got["ns"].values())
    if spec["what"] == "unscoped_share":
        return 100.0 * got["ns"].get(UNSCOPED, 0) / total if total else None
    per = got["loop_steps"] if spec.get("per") == "loop_step" else got["runs"]
    return sum(got["ns"].get(g, 0) for g in spec["groups"]) / 1e6 / per
