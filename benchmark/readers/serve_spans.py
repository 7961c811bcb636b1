"""From the serving engine's own request log, spans and counters over the
window: ``ttft_p90_ms`` (the 90th percentile over the judged requests of first
token minus due instant, from ``request_log``; a steadier tail than it is a
bounded one: section 2 of PERF.md), ``tpot_p90_ms`` (the 90th percentile over
the same requests of ``(t_last - t_first)/(n - 1)``: the tenth-worst of ~97,
which the seed's order of lengths moves by 6%),
``queue_wait_p95_ms`` (the 95th percentile of due instant to admission, from
the request tracks' ``queue_wait`` spans) and ``tokens_per_dispatch``
(``telemetry.tokens`` over ``telemetry.dispatch``: tokens scheduled, prompt
and decode, per device program launched)."""


def read(ctx, spec):
    if spec["what"] in ("queue_wait_p95_ms", "ttft_p90_ms", "tpot_p90_ms"):
        return ctx.get(spec["what"])
    d, t = ctx.get("dispatches"), ctx.get("scheduled_tokens")
    if not d or not t or not sum(d.values()):
        return None
    return sum(t.values()) / sum(d.values())
