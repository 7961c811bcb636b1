"""What the serving engine's dispatch spans say of a model's scan layers
(``telemetry/serving.py:counter_note``): ``state_bytes_per_slot``, the bytes
of one sequence's state slot over all scan layers as the last dispatch of
the traced window gives them, and ``step_rows_share``, the share of the rows
through the scan layers that took the one-row recurrence rather than the
chunked scan, from the growth of ``ssm_step_rows`` and ``ssm_chunk_rows``
over the window, in %.  Spans without the arguments (a model with no scan
layer, a program from before them) read nothing."""

import span_counters


def read(ctx, spec):
    spans = span_counters.dispatches(ctx)
    if spec["what"] == "state_bytes_per_slot":
        got = span_counters.totals(spans, ("ssm_state_bytes_per_slot",),
                                   "run")
        return got and got["ssm_state_bytes_per_slot"]
    got = span_counters.totals(spans, ("ssm_step_rows", "ssm_chunk_rows"),
                               "window")
    if not got or not sum(got.values()):
        return None
    return 100.0 * got["ssm_step_rows"] / sum(got.values())
