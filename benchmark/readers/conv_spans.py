"""What the serving engine's dispatch spans say of a model's short-conv
layers (``telemetry/serving.py:counter_note``), as the last dispatch of the
traced window gives them: ``state_bytes_per_slot``, the bytes of one
sequence's state slot over all conv layers (its conv tails and nothing
else), and ``kv_bytes_per_token``, the bytes of pages a token costs (keys
and values in the layers that own pages, the attention layers alone).  Spans
without ``conv_state_bytes_per_slot`` (a model with no conv layer, a program
from before them) read nothing."""

import span_counters


def read(ctx, spec):
    got = span_counters.totals(
        span_counters.dispatches(ctx),
        ("conv_state_bytes_per_slot", "kv_bytes_per_token"), "run")
    if not got:
        return None
    return got[{"state_bytes_per_slot": "conv_state_bytes_per_slot",
                "kv_bytes_per_token": "kv_bytes_per_token"}[spec["what"]]]
