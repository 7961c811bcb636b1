"""What the serving engine's dispatch spans say of a pool with a page group
a kind of layer (``telemetry/serving.py:counter_note``), as the last dispatch
of the traced window gives it: ``what`` names the span argument,
``kv_bytes_per_token_global`` or ``kv_bytes_per_token_window``, the bytes
the pool stores for a cached token over the group's layers, each from its own
pool's geometry.  Spans without the argument (one pool for both groups, a
program from before them) read nothing."""

import span_counters


def read(ctx, spec):
    got = span_counters.totals(span_counters.dispatches(ctx),
                               (spec["what"],), "run")
    return got[spec["what"]] if got else None
