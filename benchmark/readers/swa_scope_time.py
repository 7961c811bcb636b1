"""Device time of the attention KERNELS of a model with two page groups, by
group: the operations whose innermost known scope (``scope_time.group_of``)
is ``attn_kernel`` and whose scope path passes through ``attn_window`` (a
window layer's) or ``attn_global`` (a full layer's), the scopes the step
programs put round a layer's attention where the model has two groups
(``inference/v2/model.py:_group_scope``).  A group's time in one execution
is the union of its operations' intervals; ``per``: ``run`` or
``loop_step`` (a burst is a loop).  A metric's file names the ``program`` (a
prefix of the jitted function's name) and the ``group``.  A program without
the scopes (one page group, a program from before them) reads nothing.
"""

import bisect
import collections
import json

import scope_time
import xmeta
import xtrace

GROUPS = ("attn_window", "attn_global")


def group_of(meta):
    tf_op = meta.get("tf_op") or ""
    if scope_time.group_of(tf_op) != "attn_kernel":
        return None
    parts = tf_op.split("/")
    return next((g for g in GROUPS if g in parts), None)


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
                if (groups[mid]
                        and meta[mid]["opcode"] not in xtrace.CONTAINERS):
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
    cache = ctx.setdefault("_swa_scope_split", {})
    if spec["program"] not in cache:
        lo, hi = ctx["trace_window"]
        got = split(run["devices"], lo, hi, spec["program"])
        cache[spec["program"]] = got if got["runs"] and got["ns"] else None
        if cache[spec["program"]]:
            print(json.dumps({
                "phase": "swa_scopes", "program": spec["program"],
                "runs": got["runs"], "loop_steps": got["loop_steps"],
                "ms_per_run": {g: v / 1e6 / got["runs"]
                               for g, v in sorted(got["ns"].items())}}),
                  flush=True)
    got = cache[spec["program"]]
    if not got or spec["group"] not in got["ns"]:
        return None
    per = got["loop_steps"] if spec.get("per") == "loop_step" else got["runs"]
    return got["ns"][spec["group"]] / 1e6 / per
