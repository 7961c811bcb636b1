"""Serving cells: ``InferenceEngineV2.generate``.

Set-up, in the parts the earlier line prints: weights made on the device in
one jitted call from the seed, in bf16; the engine; the comparison with the
plain reference (prefill and a few decoded positions through the paged cache
against the reference's full forward, two short sequences); the warm-up, one
small synthetic ``generate`` call per (block-table width, token width) bucket
the mix can reach, per burst length and for the single decode step - not a
replay of the traffic.  Window: one open-loop ``generate`` call over the
seed's requests, each due at its instant; a timer drains the engine at the
deadline, and what is unfinished then has failed (chat) or is counted as far
as it got (batch).
"""

import math
import threading
import time

import numpy as np

import costs_serve
import traffic


def warm_plan(block_size, max_q, budget, max_ctx, max_seq_len,
              burst_sizes):
    """The synthetic calls that visit every step program the engine can
    need for contexts up to ``max_ctx``: (prompt lengths, answer budgets,
    arrival offsets in mixed dispatches).  Mirrors the engine's bucket rule
    (``InferenceEngineV2._buckets``): a mixed step is compiled per
    (pow2 block-table width of the longest scheduled context, pow2 token
    width >= 64 of the step's tokens)."""
    calls = []
    widths = []
    m = 1
    while True:
        widths.append(m)
        if m * block_size >= max_ctx:
            break
        m *= 2
    token_widths = [64]
    while token_widths[-1] < budget:
        token_widths.append(token_widths[-1] * 2)
    for m in widths:
        hi = min(m * block_size, max_ctx)
        a_len = hi - 8                     # A decodes at a context in (lo, hi]
        rounds_a = -(-a_len // min(max_q, budget))
        for n in token_widths:
            want = n - 8                   # + A's one token stays in (n/2, n]
            piece = min(max_q, a_len)      # B must not outgrow A's bucket
            k = -(-want // piece)
            b_lens = [want // k + (1 if i < want % k else 0)
                      for i in range(k)]
            calls.append({"prompts": [a_len] + b_lens,
                          "max_new": [3] + [1] * k,
                          "due": [0.0] + [rounds_a - 0.5] * k})
    for steps in burst_sizes:              # the fused decode bursts
        calls.append({"prompts": [40], "max_new": [steps + 1], "due": [0.0]})
    # the single decode step: what is left when no burst fits the context
    calls.append({"prompts": [max_seq_len - 6], "max_new": [5],
                  "due": [0.0]})
    return calls


class DispatchClock:
    """A clock for the warm-up calls that counts the engine's mixed
    dispatches, so that "B arrives once A's prefill is done" is exact."""

    def __init__(self, eng):
        self.eng, self.calls = eng, 0

    def __call__(self):
        self.calls += 1
        return (self.eng.telemetry.c_dispatch.value(kind="mixed")
                + self.calls * 1e-7)


def percentile(values, q):
    """Nearest rank, over values that may hold ``inf`` for failures."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def fenced_seconds(events):
    """(seconds, dispatches never fenced): the time the engine was INSIDE
    its fenced dispatches, from each ``*_dispatch`` event's start to the end
    of the ``fence`` phase that follows it in its round (``stream_sync``
    fences every dispatch); one no fence followed counts its own span."""
    marks = sorted((ev["ts"], ev["dur"], ev["name"] == "fence")
                   for ev in events if ev["name"] == "fence"
                   or ev["name"].endswith("_dispatch"))
    total = unfenced = 0
    opened = None                       # (start, own length) of a dispatch
    for ts, dur, is_fence in marks:
        if is_fence:
            if opened is not None:
                total += ts + dur - opened[0]
                opened = None
            continue
        if opened is not None:
            total += opened[1]
            unfenced += 1
        opened = (ts, dur)
    if opened is not None:
        total += opened[1]
        unfenced += 1
    return total / 1e6, unfenced


def run(ctx):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.engine_v2 import EngineDrained
    from deepspeed_tpu.models import GPTConfig
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.ops.registry import dispatch_log, reset_dispatch_log
    from deepspeed_tpu.parallel.metadata import unbox

    cfg, mix = ctx["config"], ctx["mix"]
    run_cfg, setup, seed = cfg["run"], ctx["setup"], ctx["args"].seed
    seconds = ctx["seconds"]
    sm = run_cfg["state_manager"]
    model_cfg = GPTConfig(
        **ctx["reference"].program_config(cfg),
        max_seq_len=int(run_cfg["max_seq_len"]), dropout=0.0,
        dtype=jnp.bfloat16, attn_impl="pallas")    # paged kernels demanded

    # ---- weights: on the device, one jitted call from the seed, in bf16
    lm = GPTLogits(dataclasses.replace(model_cfg, param_dtype=jnp.bfloat16))
    params = jax.jit(lambda key: unbox(lm.init(
        key, jnp.zeros((1, 8), jnp.int32)))["params"])(
            jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    jax.block_until_ready(params)
    setup.mark("weights")

    reset_dispatch_log()
    eng = InferenceEngineV2(
        model_cfg,
        {"dtype": "bfloat16", "state_manager": sm,
         "generation": run_cfg["generation"],
         "telemetry": {"stream_sync": bool(mix["stream_sync"])}},
        params=params, seed=int(seed) % (2 ** 31 - 1))
    if eng.paged_impl != "pallas":
        raise RuntimeError(f"engine says paged_attention={eng.paged_impl}")
    bs = eng.state.block_size
    jax.block_until_ready(eng.cache.k)
    setup.mark("engine")

    # ---- the plain reference: prefill, then decode through the cache
    rng = np.random.default_rng(int(seed) + 17)
    n_dec = int(run_cfg["compare"]["decode_positions"])
    seqs = [rng.integers(0, model_cfg.vocab_size, size=int(n) + n_dec)
            .astype(np.int32) for n in run_cfg["compare"]["prefill_tokens"]]
    uids = list(range(1, len(seqs) + 1))
    got = [[] for _ in seqs]
    out = eng.put(uids, [s[:len(s) - n_dec] for s in seqs])
    for i in range(len(seqs)):
        got[i].append(out[i])
    for j in range(n_dec):
        out = eng.put(uids, [s[len(s) - n_dec + j:len(s) - n_dec + j + 1]
                             for s in seqs])
        for i in range(len(seqs)):
            got[i].append(out[i])
    eng.flush(uids)
    max_abs = rel_rms = 0.0
    argmax_gap = 0.0
    for s, g in zip(seqs, got):
        rows = list(range(len(s) - n_dec - 1, len(s)))
        want = np.asarray(ctx["reference"].logits(params, s, cfg, rows=rows))
        g = np.stack(g).astype(np.float32)
        max_abs = max(max_abs, float(np.max(np.abs(g - want))))
        rel_rms = max(rel_rms, float(
            np.sqrt(np.mean((g - want) ** 2) / np.mean(want ** 2))))
        # the engine's greedy token must be the reference's, or tie with it
        pick = g.argmax(-1)
        argmax_gap = max(argmax_gap, float(np.max(
            want.max(-1) - want[np.arange(len(pick)), pick])))
    tol = cfg["tolerances"]
    logits_ok = (np.isfinite(max_abs) and max_abs <= tol["logits_max_abs"]
                 and rel_rms <= tol["logits_rel_rms"]
                 and argmax_gap <= tol["logits_max_abs"] / 2)
    setup.mark("reference")

    # ---- warm-up: every program, one small call each
    max_ctx = (int(mix["prompt_tokens"].get("max", 0)
                   or mix["prompt_tokens"]["value"])
               + int(mix["output_tokens"].get("max", 0)
                     or mix["output_tokens"]["value"]))
    plan = warm_plan(bs, int(sm["max_q_per_seq"]),
                     int(sm["max_ragged_batch_size"]), max_ctx,
                     model_cfg.max_seq_len, run_cfg["warm_bursts"])
    clock = DispatchClock(eng)
    for call in plan:
        eng.generate([np.full(n, 7, np.int32) for n in call["prompts"]],
                     max_new_tokens=call["max_new"],
                     arrival_times=call["due"], now_fn=clock, stream=False)
    jax.block_until_ready(eng.cache.k)
    try:
        programs = sum(f._cache_size() for f in eng._steps.values())
    except Exception:                       # a count for the log, no more
        programs = -1
    setup.mark("warmup")

    kernels = [d for d in dispatch_log()
               if d["op"] in ("paged_attention", "ragged_prefill_attention")]
    if ({d["op"] for d in kernels}
            != {"paged_attention", "ragged_prefill_attention"}
            or any(d["impl"] != "pallas" for d in kernels)):
        raise RuntimeError(f"serving took the XLA gather: {kernels}")

    # ---- the window
    reqs = traffic.make_requests(mix, seed, seconds, model_cfg.vocab_size)
    n = len(reqs["prompts"])
    closed = mix["arrivals"]["process"] == "all_at_zero"
    log0 = len(eng.telemetry.request_log)
    ev0 = eng.telemetry.tracer.total_recorded
    counters0 = {k: eng.telemetry.c_dispatch.value(kind=k)
                 for k in ("mixed", "decode", "burst")}
    tokens0 = {p: eng.telemetry.c_tokens.value(phase=p)
               for p in ("prefill", "decode")}
    need_counters = {"moe_local": eng.telemetry.c_moe_local,
                     "index_pairs": eng.telemetry.c_index_pairs,
                     "selected_pairs": eng.telemetry.c_sel_pairs}
    need0 = {k: c.value() for k, c in need_counters.items()}
    tracer, compiles = ctx["tracer"], ctx["compiles"]
    if closed:
        # a closed list's traced stretch is placed by the share of the
        # list's rows the engine has scheduled (the trace's poll thread
        # reads two counters; an untraced run never calls it)
        list_rows = float(sum(len(p) for p in reqs["prompts"])
                          + sum(reqs["max_new"]))
        tracer.progress = lambda: sum(
            eng.telemetry.c_tokens.value(phase=p) - v
            for p, v in tokens0.items()) / list_rows
    stop_after = seconds + (0.0 if closed
                            else float(mix["drain_deadline_s"]))
    timer = threading.Timer(stop_after, eng.request_drain)
    setup_s = setup.total()
    compiles.window_open = True
    t0 = time.perf_counter()
    tracer.open(t0)
    tracer.run_in_thread()
    timer.start()
    drained = False
    try:
        outs = eng.generate(reqs["prompts"], max_new_tokens=reqs["max_new"],
                            arrival_times=None if closed else reqs["due_s"],
                            stream=bool(mix["stream_sync"]))
    except EngineDrained:
        drained = True
        outs = None
    finally:
        jax.block_until_ready(eng.cache.k)
        t1 = time.perf_counter()
        timer.cancel()
        timer.join()            # no thread of ours outlives the window
        compiles.window_open = False
        tracer.close()
    window_s = t1 - t0

    log = {-(r["uid"]) - 1: r for r in eng.telemetry.request_log[log0:]}
    if drained:
        # the drain materialized every device record first, so the host's
        # token lists are exact: finished requests and those cut short
        completed, pending = eng.export_pending_requests()
        generated = (sum(len(g) for g in completed.values())
                     + sum(len(p["generated"]) for p in pending))
        eng.clear_drain()
    else:
        generated = sum(len(o) for o in outs)
    # token-weighted mean context a decoding sequence reads per step
    lens = [(len(reqs["prompts"][i]), r["generated_tokens"])
            for i, r in log.items()]
    ctx_mean = (sum(g * (p + g / 2) for p, g in lens)
                / max(1, sum(g for _, g in lens)))
    qwait = {}
    events = list(eng.telemetry.tracer.events)
    recorded = eng.telemetry.tracer.total_recorded - ev0
    events_held = recorded <= len(events)  # the buffer is bounded
    events = events[len(events) - min(recorded, len(events)):]
    for ev in events:
        if ev["name"] == "queue_wait":
            qwait[-(ev["args"]["uid"]) - 1] = ev["dur"] / 1e3
    # the longest stretch of the window in which no step was dispatched
    dispatched = [ev for ev in events if ev["name"].endswith("_dispatch")]
    starts = sorted(ev["ts"] for ev in dispatched)
    longest_round_ms = (max(b - a for a, b in zip(starts, starts[1:])) / 1e3
                        if len(starts) > 1 else 0.0)
    dispatches = {k: eng.telemetry.c_dispatch.value(kind=k) - v
                  for k, v in counters0.items()}
    tokens = {p: eng.telemetry.c_tokens.value(phase=p) - v
              for p, v in tokens0.items()}
    # what the whole window needed (serve_step_mfu): the program's counters
    # over the window and the arguments of EVERY dispatch span of its own
    # buffer, not of the profiler's stretch; a counter the model never
    # moves (no experts, no selection) is no count
    pairs = (costs_serve.pairs_of_dispatches(dispatched) if events_held
             else (None, None))
    need_counts = {"rows": sum(tokens.values()), "sampled": generated,
                   "pairs_global": pairs[0], "pairs_window": pairs[1]}
    for k, c in need_counters.items():
        need_counts[k] = c.value() - need0[k] if c.value() else None
    fenced_s, unfenced = fenced_seconds(events) if events_held else (0.0, 0)
    # a closed list's progress at each whole second of the window, as the
    # trace's poll thread would have read it: where a start_share falls
    # (the engine counts a step's rows before it dispatches the step, and a
    # dispatch blocks while the chip's queue is full; a burst's after it)
    progress_by_s = None
    if closed and events_held:
        open_us = eng.telemetry.tracer.us_of(t0)
        ends = sorted((ev["ts"] - open_us + (
            ev["dur"] if ev["name"] == "burst_dispatch" else 0.0),
            ev["args"]["tokens"])
            for ev in dispatched if "tokens" in ev["args"])
        rows_by = np.cumsum([0] + [n for _, n in ends])
        seconds_at = np.arange(1, int(window_s) + 2) * 1e6
        progress_by_s = [round(float(rows_by[k]) / list_rows, 4)
                         for k in np.searchsorted(
                             [t for t, _ in ends], seconds_at, side="right")]

    warm_until = float(mix.get("warm_share", 0.0)) * seconds
    judged = [i for i in range(n) if reqs["due_s"][i] >= warm_until]
    big = float("inf")
    cap_ms = (seconds + stop_after) * 1e3   # what an unfinished one reads
    ttft, tpot, waits = [], [], []
    decode_ms = decode_tokens = 0.0     # over every token after a first one
    failed = 0
    for i in judged:
        r = log.get(i)
        done = r is not None and r["outcome"] == "completed"
        if not done:
            failed += 1
        ttft.append(r["ttft_ms"] if done and r["ttft_ms"] is not None
                    else big)
        # one that the EOS ended at its first token has no gap to report;
        # an unfinished one owes all its tokens at the cap each
        steps = (r["generated_tokens"] if done else reqs["max_new"][i]) - 1
        if steps >= 1:
            tpot.append(r["tpot_ms"] if done else big)
            decode_tokens += steps
            decode_ms += steps * min(tpot[-1], cap_ms)
        if i in qwait:
            waits.append(qwait[i])
    fin = lambda v: None if v is None else min(v, cap_ms)  # noqa: E731
    thirds = [[qwait[i] for i in judged if i in qwait
               and lo <= reqs["due_s"][i] < hi]
              for lo, hi in ((warm_until, warm_until + (seconds - warm_until) / 3),
                             (seconds - (seconds - warm_until) / 3, seconds))]
    end_to_end = {"setup_s": setup_s}
    if closed:
        end_to_end["serve_tokens_per_s"] = generated / window_s
        # a closed list outlasts the window by design: attempted are the
        # requests the engine took up, finished or cut short at the drain
        attempted = len(log) + (sum(1 for p in pending if p["generated"])
                                if drained else 0)
        failed_n = 0
    else:
        end_to_end["tpot_mean_ms"] = decode_ms / max(decode_tokens, 1.0)
        attempted, failed_n = len(judged), failed
    notes = {
        "requests": n, "judged": len(judged), "failed": failed,
        "drained_at_deadline": drained, "window_s": window_s,
        "offered_rate_per_s": n / seconds,
        "completed_in_log": len(log),
        "completed_rate_per_s": len(log) / window_s,
        "generated_tokens": generated,
        "tokens_per_s": generated / window_s,
        "ttft_ms": {"n": len(ttft), "p50": fin(percentile(ttft, 0.5)),
                    "p90": fin(percentile(ttft, 0.9))},
        "tpot_ms": {"n": len(tpot), "p50": fin(percentile(tpot, 0.5)),
                    "p90": fin(percentile(tpot, 0.9)),
                    "mean_over_tokens": decode_ms / max(decode_tokens, 1.0),
                    "tokens_after_first": decode_tokens},
        "queue_wait_ms": {"n": len(waits),
                          "p50": percentile(waits, 0.5),
                          "p95": percentile(waits, 0.95),
                          "p99": percentile(waits, 0.99),
                          "first_third_p50": percentile(thirds[0], 0.5),
                          "last_third_p50": percentile(thirds[1], 0.5)},
        "generator": "the engine's own arrival gate releases a request at "
                     "its due instant; lateness is inside queue_wait",
        "dispatches": dispatches, "scheduled_tokens": tokens,
        "longest_ms_between_dispatches": longest_round_ms,
        "window_events": {"recorded": recorded, "held": len(events),
                          "all_held": events_held},
        "need_counts": need_counts, "progress_by_s": progress_by_s,
        "fenced_dispatch_s": fenced_s, "dispatches_never_fenced": unfenced,
        "slowest_admissions": sorted(
            ((round(qwait[i]), round(reqs["due_s"][i], 2),
              len(reqs["prompts"][i])) for i in qwait), reverse=True)[:5],
        "logits_vs_reference": {"max_abs": max_abs, "rel_rms": rel_rms,
                                "argmax_gap": argmax_gap, **tol},
        "warm_programs": programs, "warm_calls": len(plan),
        "kv_block_size": bs, "kernel_dispatch": kernels}
    return {"setup_s": setup_s, "correct": bool(logits_ok),
            "compared": {
                "logits_rel_rms": (rel_rms, tol["logits_rel_rms"]),
                "logits_max_abs": (max_abs, tol["logits_max_abs"]),
                "argmax_gap": (argmax_gap, tol["logits_max_abs"] / 2)},
            "attempted": attempted, "failed": failed_n,
            "end_to_end": end_to_end, "window_s": window_s,
            "queue_wait_p95_ms": percentile(waits, 0.95),
            "ttft_p90_ms": None if closed else fin(percentile(ttft, 0.90)),
            "tpot_p90_ms": None if closed else fin(percentile(tpot, 0.90)),
            "dispatches": dispatches, "scheduled_tokens": tokens,
            "serve_window": {"counts": need_counts, "fenced_s": fenced_s},
            "model_cfg": model_cfg, "slots": int(sm["max_tracked_sequences"]),
            "chips": 1, "kv_block_size": bs, "notes": notes,
            # closed list: every slot decodes all through the window
            "decode_context_tokens_per_step": (
                {"context_tokens": int(sm["max_tracked_sequences"])
                 * ctx_mean, "slots": int(sm["max_tracked_sequences"])}
                if closed else None)}
