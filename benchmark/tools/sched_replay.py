#!/usr/bin/env python3
"""Replay a serving cell's schedule on the CPU: the engine's own ``generate``
loop (admission, SplitFuse chunks, decode bursts, both page groups) with the
device calls stubbed out, a clock that advances by a cost model, and the
drain at the deadline.  Where nothing syncs (no EOS, no fence) the schedule
is a function of the prompts' order alone, so this says what a seed's
``serve_tokens_per_s`` would read and how widely seeds spread, before a chip
run: PR 29 sized ``serve-trinity-mixedlen-batch`` with it (PERF.md section 6).

``python3 benchmark/tools/sched_replay.py --seeds 24 [--set
state_manager.max_q_per_seq=256] [--set mix.arrivals.requests_per_window_s=2.7]``

The cost model is fitted to the cell's traced scopes (``c29_7``, 16 slots,
1,024 a forward, 256 a chunk; my chip run, PR 29) and is the cell's, not the
engine's: a mixed step costs a fixed part (the held experts' weights), a
part by bucket and tokens, and the prefill kernel's (sequence, 128-row query
chunk, page) tiles; a decode step a fixed part and its keys.  Against the
chip it read six seeds to -3.8..-0.2% at a chunk of 256 and five 15-17% high
at a chunk of 1,024 (whose dense ``[slots, chunk]`` layouts it does not
know), both in the chip's order.
"""

import argparse
import json
import logging
import os
import statistics
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "reference"))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import traffic  # noqa: E402

CONFIG = "trinity-large-preview-5l-ep8"
MIX = "mixedlen-batch"
PAGE = 128
TILE_US, MIXED_FIXED_MS, MIXED_BUCKET_MS, TOKEN_US = 12.7, 21.8, 7.4, 6.8
DECODE_FIXED_MS, KEY_US = 3.2, 0.0287


def build(cfg, sm):
    """The engine at the configuration's tiny preset (the schedule does not
    depend on widths) with the real window, page and state manager."""
    import jax
    import jax.numpy as jnp

    import _afmoe as ref
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPTConfig
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.parallel.metadata import unbox

    window = int(cfg["sliding_window"])
    tiny = {**cfg, **cfg["rehearsal"], "sliding_window": window}
    mc = GPTConfig(**ref.program_config(tiny),
                   max_seq_len=int(cfg["run"]["max_seq_len"]), dropout=0.0,
                   dtype=jnp.float32, attn_impl="xla")
    params = unbox(GPTLogits(mc).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]

    class Replay(InferenceEngineV2):
        """``_step_sampled`` and ``_run_burst`` keep their bookkeeping and
        launch nothing."""

        def begin(self, deadline_ms):
            self.t_ms, self.deadline_ms = 0.0, deadline_ms
            self.kinds = {"mixed": 0, "burst": 0, "decode": 0}

        def _advance(self, kind, ms):
            self.t_ms += ms
            self.kinds[kind] += 1
            if self.t_ms >= self.deadline_ms:
                self.request_drain()

        def _step_sampled(self, uids, toks_np, from_device, served_slots,
                          gen, prev, rng):
            rows = []
            for uid, toks in zip(uids, toks_np):
                seq = self.state.get(uid) or self.state.create(uid)
                self.state.ensure_blocks(seq, len(toks))
                rows.append((seq, len(toks)))
            at = [(s.seen_tokens, n) for s, n in rows]
            tokens = sum(n for _, n in at)
            if max(n for _, n in at) <= 1:
                kind, ms = "decode", decode_ms([c for c, _ in at], window)
            else:
                budget = self.config.state_manager.max_ragged_batch_size
                bucket = min(max(64, 1 << (tokens - 1).bit_length()), budget)
                kind, ms = "mixed", mixed_ms(at, tokens, bucket, window)
            for s, n in rows:
                s.seen_tokens += n
            self._advance(kind, ms)
            return np.zeros(self.state.max_tracked_sequences, np.int32), rng

        def _run_burst(self, reqs, steps, gen, prev, rng):
            ctx = []
            for r in reqs:
                seq = self.state.get(r.uid)
                self.state.ensure_blocks(seq, steps)
                ctx.append(seq.seen_tokens)
                seq.seen_tokens += steps
            ms = sum(decode_ms([c + k for c in ctx], window)
                     for k in range(steps))
            self._advance("burst", ms)
            S = self.state.max_tracked_sequences
            return (np.zeros((steps, S), np.int32), np.zeros(S, np.int32),
                    rng)

    return Replay(mc, {"dtype": "float32", "state_manager": sm,
                       "generation": {"do_sample": False}}, params=params)


def decode_ms(contexts, window):
    keys = sum(c + 1 + 4 * min(c + 1, window) for c in contexts)
    return DECODE_FIXED_MS + KEY_US * 1e-3 * keys


def mixed_ms(rows, tokens, bucket, window):
    """``rows``: (context before the step, new rows) per sequence.  A tile
    is one 128-row query chunk against one page: on the global layer from
    page 0, on each of the four window layers from the chunk's window
    start (``ops/paged_attention.py:_prefill_kernel``)."""
    tiles = 0
    for c, q in rows:
        for row0 in range(0, q, 128):
            pages = (c + min(q, row0 + 128) - 1 + PAGE) // PAGE
            tiles += pages + 4 * (pages
                                  - max(c + row0 - window + 1, 0) // PAGE)
    return (MIXED_FIXED_MS + MIXED_BUCKET_MS * bucket / 1024
            + TOKEN_US * 1e-3 * tokens + TILE_US * 1e-3 * tiles)


def replay(eng, mix, seed, seconds, vocab=512):
    from deepspeed_tpu.inference.v2.engine_v2 import EngineDrained
    reqs = traffic.make_requests(mix, seed, seconds, vocab)
    # the host runs ahead of the chip; its drain lands ~0.6 s of work late
    eng.begin(seconds * 1e3 + 600.0)
    try:
        outs = eng.generate(reqs["prompts"], max_new_tokens=reqs["max_new"],
                            stream=False)
        drained, generated = False, sum(len(o) for o in outs)
    except EngineDrained:       # counted as the runner counts it
        completed, pending = eng.export_pending_requests()
        drained = True
        generated = (sum(len(g) for g in completed.values())
                     + sum(len(p["generated"]) for p in pending))
        eng.clear_drain()
    for uid in list(eng.state.tracked):
        eng.flush([uid])
    return {"seed": seed, "tokens_per_s": generated / (eng.t_ms / 1e3),
            "seconds": eng.t_ms / 1e3, "cut_at_the_deadline": drained,
            **eng.kinds}


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed", type=int, default=3000200001)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--set", action="append", default=[],
                    metavar="PATH=JSON", help="state_manager.<key>=... or "
                    "mix.<dotted key>=...")
    args = ap.parse_args(argv)
    logging.disable(logging.CRITICAL)
    with open(os.path.join(HERE, "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    mix = traffic.load_mix(MIX)
    sm = dict(cfg["run"]["state_manager"])
    for item in args.set:
        path, value = item.split("=", 1)
        head, *keys = path.split(".")
        node = sm if head == "state_manager" else mix
        for k in keys[:-1]:
            node = node[k]
        node[keys[-1]] = json.loads(value)
    eng = build(cfg, sm)
    runs = [replay(eng, mix, args.seed + 104729 * i, args.seconds)
            for i in range(args.seeds)]
    for r in runs:
        print(json.dumps(r))
    rates = [r["tokens_per_s"] for r in runs]
    out = {"median": statistics.median(rates), "min": min(rates),
           "max": max(rates)}
    if len(rates) >= 4:
        out["spread"] = spread(rates)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
