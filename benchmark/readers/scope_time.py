"""Device time of a program's operations grouped by the scope the program
put around them (``jax.named_scope``; ``xmeta`` reads the scope path from the
trace's op metadata), per execution of the program or per step of its loop.

An operation belongs to the INNERMOST known scope on its path, so
``kv_pool/while/body/attn_qkv/dot_general`` is ``attn_qkv``; inside
``fwd_bwd`` the path says ``transpose(jvp(...))`` for the backward half (under
remat the backward's recomputation replays the forward's whole path after
it, ``fwd_bwd/transpose(jvp(M))/backbone/fwd_bwd/jvp(M)/...``: backward).  A
group's time in one execution is the union of its operations' intervals; a
loop or a branch (``xtrace.CONTAINERS``) is an event that spans its body's
events and is not work.  A fusion has one scope path, its root's, so an
operation fused across a scope boundary is counted on the root's side.  Ops
whose path names no known scope (the compiler's own copies have no path at
all) are ``unscoped``.  Where no operation of the program carries any known
scope (a program from before the scopes) nothing is read.

A metric's file names the program (``@step_program`` for the runner's, else
a prefix of the jitted function's name), the ``groups`` it sums (scope names;
``fwd``/``bwd`` for the two halves of ``fwd_bwd``; ``unscoped`` for the rest),
and ``per``: ``run`` or ``loop_step``.  The first metric of a program prints an
earlier line ``{"phase": "scopes", ...}`` with the whole split and the
unscoped rest by op family, with ``source`` and shape.
"""

import collections
import json
import re

import xmeta
import xtrace

TRAIN = ("prepare_params", "fwd_bwd", "loss", "grad_check", "optimizer",
         "loss_scale")
SERVE = ("embed", "attn_qkv", "kv_write", "attn_kernel", "attn_out", "mlp",
         "head", "sample", "kv_pool", "draft", "verify")
KNOWN = frozenset(TRAIN + SERVE)
UNSCOPED = "unscoped"
_SHAPE = re.compile(r" = (\(?[a-z0-9]+\[[0-9,]*\])")


def group_of(tf_op):
    """The innermost known scope of a name stack, ``fwd_bwd`` split into
    ``fwd`` and ``bwd``; ``unscoped`` where there is none."""
    parts = (tf_op or "").split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in KNOWN:
            if parts[i] == "fwd_bwd":
                outer = parts.index("fwd_bwd")
                return ("bwd" if any(p.startswith("transpose(")
                                     for p in parts[outer + 1:]) else "fwd")
            return parts[i]
    return UNSCOPED


def split(devices, lo, hi, is_program):
    """{"runs", "loop_steps", "ns": {group: ns summed over runs and chips},
    "unscoped": {(family, source, shape): ns}, "chips"} for the executions of
    the programs ``is_program`` accepts that lie wholly inside [lo, hi]."""
    ns = collections.Counter()
    rest = collections.Counter()
    runs = steps = 0
    for dev in devices.values():
        meta = dev["meta"]
        groups = {mid: group_of(m.get("tf_op")) for mid, m in meta.items()}
        ops = dev["ops"]
        j = 0
        for name, a, b in dev["modules"]:
            if a < lo or b > hi or not is_program(name):
                continue
            while j < len(ops) and ops[j][1] < a:
                j += 1
            k = j
            per = collections.defaultdict(list)
            counts = collections.Counter()
            while k < len(ops) and ops[k][1] < b:
                mid, s, e = ops[k]
                k += 1
                if e > b or mid not in meta:
                    continue
                counts[mid] += 1
                if meta[mid]["opcode"] in xtrace.CONTAINERS:
                    continue
                per[groups[mid]].append((s, e))
                if groups[mid] == UNSCOPED:
                    m = meta[mid]
                    shape = _SHAPE.search(m["text"])
                    rest[(xtrace.op_family(m["name"]), m.get("source") or "",
                          shape.group(1) if shape else "")] += e - s
            for g, iv in per.items():
                ns[g] += xtrace.total(xtrace.union(iv))
            runs += 1
            steps += max(counts.values()) if counts else 1
    return {"runs": runs, "loop_steps": steps, "ns": dict(ns),
            "unscoped": dict(rest), "chips": max(1, len(devices))}


def _program_test(ctx, spec):
    want = spec["program"]
    if want == "@step_program":
        want = ctx.get("step_program")
        return (lambda name: name == want) if want else None
    return lambda name: name.startswith(want)


def read(ctx, spec):
    run = xmeta.of_run(ctx)
    test = _program_test(ctx, spec)
    if not run or not run["devices"] or test is None:
        return None
    cache = ctx.setdefault("_scope_split", {})
    if spec["program"] not in cache:
        lo, hi = ctx["trace_window"]
        got = split(run["devices"], lo, hi, test)
        scoped = any(g != UNSCOPED for g in got["ns"])
        cache[spec["program"]] = got if got["runs"] and scoped else None
        if cache[spec["program"]]:
            top = sorted(got["unscoped"].items(), key=lambda kv: -kv[1])[:8]
            print(json.dumps({
                "phase": "scopes", "program": spec["program"],
                "runs": got["runs"], "loop_steps": got["loop_steps"],
                "ms_per_run": {g: v / 1e6 / got["runs"]
                               for g, v in sorted(got["ns"].items())},
                "unscoped_top_ms_per_run": [
                    [fam, v / 1e6 / got["runs"], src, shape]
                    for (fam, src, shape), v in top]}), flush=True)
    got = cache[spec["program"]]
    if not got:
        return None
    total = sum(got["ns"].values())
    if spec["what"] == "unscoped_share":
        return 100.0 * got["ns"].get(UNSCOPED, 0) / total if total else None
    per = got["loop_steps"] if spec.get("per") == "loop_step" else got["runs"]
    return sum(got["ns"].get(g, 0) for g in spec["groups"]) / 1e6 / per
