"""``scope_time`` for a program with short-conv layers: the same split of a
program's device time by the innermost known scope, with the three scopes a
short-conv layer nests inside the attention scopes known as well:
``conv_in_proj`` (inside ``attn_qkv``), ``short_conv`` (inside
``attn_kernel``, around the plan, the conv and the rows' gather and scatter,
and inside ``kv_write``, around the write-back of a slot's tail: everything
that moves a tail is a ``short_conv``) and ``conv_out_proj`` (inside
``attn_out``).  ``scope_time`` files all of them under the attention scope
they lie in, which is what the accepted ``*_attn_ms`` entries go on reading.

A program without the scopes (a model with no conv layer, a program from
before them) reads nothing.  Same metric-file keys as ``scope_time``:
``program`` (a prefix), ``groups``, ``per``, ``what``.
"""

import bisect
import collections
import json

import scope_time
import xmeta
import xtrace

CONV = ("conv_in_proj", "short_conv", "conv_out_proj")


def group_of(meta):
    for part in reversed((meta.get("tf_op") or "").split("/")):
        if part in CONV or part in scope_time.SERVE:
            return part
    return scope_time.UNSCOPED


def split(devices, lo, hi, prefix):
    """{"runs", "loop_steps", "ns": {group: ns}} over the executions of the
    programs named ``prefix``... that lie wholly inside [lo, hi]; a group's
    time in one execution is the union of its operations' intervals."""
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
            outside = collections.Counter()    # ops of no conv loop
            for mid, s, e in ops[bisect.bisect_left(starts, a):
                                 bisect.bisect_left(starts, b)]:
                if e > b or mid not in meta:
                    continue
                if meta[mid]["opcode"] not in xtrace.CONTAINERS:
                    per[groups[mid]].append((s, e))
                    if groups[mid] not in CONV:
                        outside[mid] += 1
            for g, iv in per.items():
                ns[g] += xtrace.total(xtrace.union(iv))
            runs += 1
            # a burst's steps: how often the commonest operation OUTSIDE
            # the conv scopes ran (a mixed step's conv loops over its
            # prompt chunks, which are no steps)
            steps += max(outside.values()) if outside else 1
    return {"runs": runs, "loop_steps": steps, "ns": dict(ns)}


def of_program(ctx, prefix):
    """The split of program ``prefix``, once a run; None where the trace
    holds no execution of it or none of the conv scopes."""
    run = xmeta.of_run(ctx)
    if not run or not run["devices"] or "trace_window" not in ctx:
        return None
    cache = ctx.setdefault("_conv_scope_split", {})
    if prefix not in cache:
        lo, hi = ctx["trace_window"]
        got = split(run["devices"], lo, hi, prefix)
        cache[prefix] = (got if got["runs"]
                         and any(g in CONV for g in got["ns"]) else None)
        if cache[prefix]:
            print(json.dumps({
                "phase": "conv_scopes", "program": prefix,
                "runs": got["runs"], "loop_steps": got["loop_steps"],
                "ms_per_run": {g: v / 1e6 / got["runs"]
                               for g, v in sorted(got["ns"].items())}}),
                  flush=True)
    return cache[prefix]


def read(ctx, spec):
    got = of_program(ctx, spec["program"])
    if not got:
        return None
    per = got["loop_steps"] if spec.get("per") == "loop_step" else got["runs"]
    return sum(got["ns"].get(g, 0) for g in spec["groups"]) / 1e6 / per
