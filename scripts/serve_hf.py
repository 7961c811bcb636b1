#!/usr/bin/env python
"""Serve an HF checkpoint directory end-to-end (round-3 verdict item 7).

Usage:
    python scripts/serve_hf.py <hf_model_dir> [--prompt-ids 1,2,3]
    python scripts/serve_hf.py --demo          # self-contained demo (below)

The serving path is the reference's huggingface_engine flow
(inference/v2/checkpoint/huggingface_engine.py:124 — model dir → engine):
``init_inference(path)`` detects the HF directory, maps the checkpoint
through checkpoint/hf.py's architecture tables, and serves it through the
v1 engine; the same directory also loads into the v2 ragged engine.

**Environment note (recorded honestly):** this image has zero network
egress and no cached pretrained weights — `find / -name "*.safetensors"`
turns up only tiny random test fixtures — so a *pretrained* checkpoint
cannot be served here.  ``--demo`` substitutes the strongest in-image
equivalent: it byte-tokenizes real text, trains a GPT-2-config model on it
with the training engine, exports a genuine HF directory
(config.json + model.safetensors via ``save_hf_checkpoint`` — it loads
straight into ``transformers``), then serves that directory through
``init_inference(path)`` and greedy-completes held-out prefixes of the
text.  Every step a real-checkpoint user would run is exercised; only the
provenance of the weights differs.  Output artifact:
``docs/SERVE_HF_ARTIFACT.md``.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEMO_TEXT = (
    b"The quick brown fox jumps over the lazy dog. "
    b"Pack my box with five dozen liquor jugs. "
    b"How vexingly quick daft zebras jump! "
    b"Sphinx of black quartz, judge my vow. "
)


def serve(path, prompts, max_new=32, dtype=None):
    import deepspeed_tpu
    if dtype is None:
        import jax
        dtype = ("bfloat16" if jax.default_backend() == "tpu"
                 else "float32")        # bf16 is emulated (slow) on CPU
    eng = deepspeed_tpu.init_inference(path, config={"dtype": dtype})
    outs = []
    for p in prompts:
        ids = np.asarray(p, np.int32)[None]
        eng.generate(ids, max_new_tokens=max_new, do_sample=False)  # compile
        t0 = time.perf_counter()
        out = eng.generate(ids, max_new_tokens=max_new, do_sample=False)
        dt = time.perf_counter() - t0
        outs.append((out[0], max_new / dt))
    return outs


def demo(out_path="docs/SERVE_HF_ARTIFACT.md", steps=300):
    import dataclasses
    import tempfile

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.checkpoint.hf import save_hf_checkpoint
    from deepspeed_tpu.models import GPT, GPTConfig

    text = np.frombuffer(DEMO_TEXT * 4, dtype=np.uint8).astype(np.int32)
    T = 128
    n = len(text) // T
    pool = text[: n * T].reshape(n, T)

    # full gpt2 config point (biases on, like the HF architecture — the
    # export direction writes the gpt2 tensor set)
    cfg = GPTConfig.gpt2_small(vocab_size=256, max_seq_len=T, dropout=0.0,
                               qkv_bias=True, attn_out_bias=True,
                               mlp_bias=True)
    # CPU plumbing runs shrink the model and stay fp32/single-shard (the CI
    # host is ONE core: bf16 emulation + an 8-way virtual mesh would turn
    # this demo into minutes of spin); on the chip use the gpt2 shape
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = dataclasses.replace(cfg, num_layers=4, dtype=jnp.bfloat16)
        # bf16 + the full-width model memorizes slower than the CPU
        # plumbing config — a fixed 300 steps left loss at 1.05 and the
        # exact-match check failing (round-5 sweep); cap high and stop on
        # the loss target instead
        steps = max(steps, 2500)
    else:
        cfg = dataclasses.replace(cfg, num_layers=2, num_heads=4, head_dim=32,
                                  hidden_size=128)
        steps = min(steps, 240)
    micro = 4
    # lr: 3e-3 memorizes the tiny CPU config but OSCILLATES on the
    # full-width bf16 model (plateau at loss ~2.2 for 2500 steps); 3e-4
    # memorizes it in under 100 steps (on-chip lr probe, round 5)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "adamw",
                          "params": {"lr": 3e-4 if on_tpu else 3e-3}},
            "bf16": {"enabled": on_tpu},
            "zero_optimization": {"stage": 2},
            "mesh": {"dp": -1} if on_tpu else {"dp": 1, "fsdp": 1},
            "steps_per_print": 0},
        example_batch={"input_ids": np.zeros((micro, T), np.int32)})
    rng = np.random.default_rng(0)
    gbs = engine.train_batch_size
    loss = None
    trained_steps = 0
    for i in range(steps):
        idx = rng.integers(0, n, size=(gbs,))
        loss = float(engine.train_batch({"input_ids": pool[idx]}).loss)
        trained_steps = i + 1
        if loss < 0.02 and i >= 20:     # memorized — the demo's premise
            break

    path = tempfile.mkdtemp(prefix="ds_tpu_hf_")
    params = jax.device_get(engine.state.params)
    if isinstance(params, dict) and "params" in params:
        params = params["params"]
    save_hf_checkpoint(cfg, params, path)
    del engine

    prefix = DEMO_TEXT[:40]
    prompt_ids = np.frombuffer(prefix, np.uint8).astype(np.int32)
    outs = serve(path, [prompt_ids], max_new=48)
    toks, tps = outs[0]
    completion = bytes(int(t) % 256 for t in toks)
    expected = (DEMO_TEXT * 2)[40:40 + 48]
    match = completion == expected

    # the same HF dir through the v2 RAGGED engine (the reference's
    # huggingface_engine flow targets v2) — continuous batching over three
    # staggered prefixes, each must continue the memorized text
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    v2 = InferenceEngineV2(
        path, {"dtype": "float32" if not on_tpu else "bfloat16",
               "state_manager": {"max_tracked_sequences": 4,
                                 "kv_block_size": 16, "max_q_per_seq": 64,
                                 "max_ragged_batch_size": 256}})
    starts = (10, 40, 70)
    v2_prompts = [np.frombuffer(DEMO_TEXT[:s0], np.uint8).astype(np.int32)
                  for s0 in starts]
    v2_outs = v2.generate(v2_prompts, max_new_tokens=24)
    v2_match = all(
        bytes(int(t) % 256 for t in o) == (DEMO_TEXT * 2)[s0:s0 + 24]
        for s0, o in zip(starts, v2_outs))
    report = f"""# serve_hf demo artifact

Generated by `python scripts/serve_hf.py --demo` (see module docstring for
why the weights are trained in-image rather than downloaded: zero-egress
environment, no pretrained checkpoints reachable).

- trained: gpt2-config {cfg.num_layers}L/{cfg.hidden_size}H byte-LM, {trained_steps} steps, final loss {loss:.3f}
- exported: HF directory (config.json + model.safetensors,
  `save_hf_checkpoint`) -> served via `init_inference(path)`
- prompt: `{prefix.decode()}`
- greedy completion ({len(toks)} tokens): `{completion.decode(errors="replace")}`
- exact continuation of the training text: **{match}**
- decode throughput (v1 engine, greedy, batch 1): {tps:.1f} tokens/s{
    "" if on_tpu else "  — OFF-TPU: single-core CI host, contention-noisy;"
    " a plumbing signal only, never a serving number"}
- v2 ragged engine over the same HF dir (3 staggered prefixes, continuous
  batching): exact continuations = **{v2_match}**
- backend: {__import__("jax").default_backend()}
"""
    with open(out_path, "w") as f:
        f.write(report)
    print(report)
    return 0 if (match and v2_match) else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model_dir", nargs="?", help="HF model directory")
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--prompt-ids", default=None,
                    help="comma-separated token ids")
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args()
    if args.demo:
        return demo()
    if not args.model_dir:
        print("need a model dir or --demo", file=sys.stderr)
        return 2
    ids = ([int(x) for x in args.prompt_ids.split(",")]
           if args.prompt_ids else [1, 2, 3, 4])
    outs = serve(args.model_dir, [np.asarray(ids, np.int32)],
                 max_new=args.max_new)
    toks, tps = outs[0]
    print(f"tokens: {list(map(int, toks))}\n{tps:.1f} tokens/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
