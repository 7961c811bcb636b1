"""Roofline shares of the scan of a model with scan layers: needed work
(``costs_ssm.scan_cost``: the recurrence's operations; each sequence's state
once in and once out a layer and the rows' activations) over peak over the
device time of EVERYTHING under the scope ``ssm_scan`` in the programs named
by the metric's ``program`` (``ssm_scope_time``'s rule: by scope, not by
kernel name, so a kernel of any name moves the reading and none can send it
past 100%; the write-back of the state is under the scope too).

What a step needs depends on its rows, which the device trace does not
hold; the program's dispatch spans do (``seqs``, ``steps``, ``tokens``).  The
host runs ahead of the chip, so spans and device events of one traced window
are not of the same steps: the need is the MEAN need of a step of the kind
over the window's spans times the steps of the kind the trace holds.

``path: step``: the decode programs (``ragged_decode*``: single steps and
fused bursts), every live slot one row a step.  ``path: chunk``: the mixed
programs (``ragged_forward*``), whose scan serves prompt chunks AND the
one-row slots that ride the step: ``tokens`` rows of ``seqs`` sequences.
A program without the scopes or the spans reads nothing.
"""

import json

import costs
import costs_ssm
import span_counters
import ssm_scope_time


def read(ctx, spec):
    peaks, cfg = ctx.get("peaks"), ctx.get("model_cfg")
    spans = span_counters.dispatches(ctx)
    if not peaks or not spans or cfg is None \
            or not getattr(cfg, "layer_types", ()):
        return None
    got = ssm_scope_time.of_program(ctx, spec["program"])
    if not got or not got["ns"].get("ssm_scan"):
        return None
    scan_layers = costs_ssm.layers(cfg)[0]
    if spec["path"] == "step":
        rows = n = 0.0
        for a in spans:
            if a["name"] == "ds.mixed_dispatch" or "seqs" not in a["args"]:
                continue
            k = float(a["args"].get("steps", 1))
            rows += k * float(a["args"]["seqs"])
            n += k
        if not n:
            # the host dispatches a cohort's bursts in one clump ahead of
            # the chip, so the window may hold their device time and not
            # their spans: then the decoding sequences are the one-row
            # slots of the mixed spans that have some (``latent``'s rule)
            riders = [float(a["args"]["one_row_slots"]) for a in spans
                      if a["name"] == "ds.mixed_dispatch"
                      and float(a["args"].get("one_row_slots", 0))]
            if not riders:
                return None
            rows, n = sum(riders), float(len(riders))
        per_step = (rows / n, rows / n)        # rows, sequences: one each
        steps = got["loop_steps"]
    else:
        mixed = [a["args"] for a in spans if a["name"] == "ds.mixed_dispatch"
                 and "tokens" in a["args"] and "seqs" in a["args"]]
        if not mixed:
            return None
        per_step = (sum(float(m["tokens"]) for m in mixed) / len(mixed),
                    sum(float(m["seqs"]) for m in mixed) / len(mixed))
        steps = got["runs"]
    flops, byts = costs_ssm.scan_cost(cfg, *per_step)
    flops, byts = (scan_layers * steps * v for v in (flops, byts))
    seconds = got["ns"]["ssm_scan"] / 1e9
    share, bound = costs.roofline_share(flops, byts, seconds, peaks)
    print(json.dumps({"phase": "roofline", "kernel": "ssm_scan",
                      "path": spec["path"], "bound": bound,
                      "scope_s": seconds, "needed_flops": flops,
                      "needed_bytes": byts, "steps": steps,
                      "mean_rows_and_sequences_per_step": per_step}),
          flush=True)
    return share
