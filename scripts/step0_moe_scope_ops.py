#!/usr/bin/env python3
"""The device ops of one scope of a step program, by name, from the trace a
``benchmark/run.py --trace 1`` run left under ``benchmark_out/trace/<cell>``
(PR 56, step 0: what the ``moe_experts`` scope of a mixed step is made of
in-program: the sort, the row gather, the grouped GEMM kernels, the mask
pass and the combine; PR 58, step 0: what one pass of a scan layer's loop
over prompt chunks is made of, ``--scope ssm_scan``).

    python3 scripts/step0_moe_scope_ops.py benchmark_out/trace/<cell> --tag <name>
        [--program ragged_forward] [--scope moe_experts]

An op belongs to a scope exactly as ``benchmark/readers/moe_scope_time.py``
decides it (``group_of``; a scan layer's four scopes as
``ssm_scope_time.py`` does); ops are pooled by (HLO opcode, the tail of the
``tf_op`` path, result shape) and given as milliseconds a run of the
program, most first.  Writes ``chiprun_out/pr56/ops_<tag>.json`` and
``ops_<tag>.md``; the summary goes to stdout.
"""

import argparse
import bisect
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "readers"))

import moe_scope_time  # noqa: E402
import ssm_scope_time  # noqa: E402
import xmeta  # noqa: E402
import xtrace  # noqa: E402


def op_key(meta):
    """(opcode, the path below the scope's layer, result shape)."""
    text = meta["text"]
    shape = text.split(" = ", 1)[1].split(" ", 1)[0] if " = " in text else ""
    tail = "/".join((meta.get("tf_op") or "").split("/")[-3:])
    return meta["opcode"] or xtrace.op_family(meta["name"]), tail, shape


def scope_ops(devices, prefix, scope):
    group_of = (ssm_scope_time if scope in ssm_scope_time.SSM
                else moe_scope_time).group_of
    ns = collections.Counter()
    count = collections.Counter()
    runs = 0
    for dev in devices.values():
        meta = dev["meta"]
        ops = dev["ops"]
        starts = [op[1] for op in ops]
        for name, a, b in dev["modules"]:
            if not name.startswith(prefix):
                continue
            runs += 1
            for mid, s, e in ops[bisect.bisect_left(starts, a):
                                 bisect.bisect_left(starts, b)]:
                m = meta.get(mid)
                if (e > b or m is None or m["opcode"] in xtrace.CONTAINERS
                        or group_of(m) != scope):
                    continue
                ns[op_key(m)] += e - s
                count[op_key(m)] += 1
    return runs, ns, count


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--program", default="ragged_forward")
    ap.add_argument("--scope", default="moe_experts")
    ap.add_argument("--out", default="chiprun_out/pr56")
    ap.add_argument("--top", type=int, default=25,
                    help="rows of the summary on stdout")
    args = ap.parse_args()
    path = args.trace
    if os.path.isdir(path):
        path = xtrace.find_xplane(path)
    runs, ns, count = scope_ops(xmeta.device_ops(path), args.program,
                                args.scope)
    rows = [{"opcode": k[0], "tf_op": k[1], "shape": k[2],
             "events_per_run": count[k] / max(runs, 1),
             "ms_per_run": v / 1e6 / max(runs, 1)}
            for k, v in ns.most_common()]
    out = {"file": path, "program": args.program, "scope": args.scope,
           "runs": runs,
           "ms_per_run": sum(r["ms_per_run"] for r in rows), "ops": rows}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"ops_{args.tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    with open(os.path.join(args.out, f"ops_{args.tag}.md"), "w") as f:
        f.write(f"`{args.scope}` of `{args.program}*`: {runs} runs, "
                f"{out['ms_per_run']:.3f} ms a run (events summed, not "
                f"their union)\n\n| ms a run | events a run | opcode | "
                f"tf_op | result |\n| --- | --- | --- | --- | --- |\n")
        for r in rows:
            f.write(f"| {r['ms_per_run']:.4f} | {r['events_per_run']:.1f} | "
                    f"{r['opcode']} | {r['tf_op']} | {r['shape']} |\n")
    print(json.dumps({k: out[k] for k in ("program", "scope", "runs",
                                          "ms_per_run")}))
    for r in rows[:args.top]:
        print(f"{r['ms_per_run']:9.4f} ms  x{r['events_per_run']:<6.1f} "
              f"{r['opcode']:<14} {r['tf_op']}  {r['shape']}")


if __name__ == "__main__":
    main()
