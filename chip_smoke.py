#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process drives the two main paths through the entry points a user calls,
at GPT-2-small's published size (12 layers, hidden 768, 12 heads of 64, vocab
50,304, T=1024, bf16 compute), weights and data made from ``--seed``:

- *device*: the platform must be ``tpu``; the compile cache and the overlap
  block's compiler flags are placed before the backend starts.
- *train*: ``deepspeed_tpu.initialize`` (ZeRO-2, bf16, AdamW, micro-batch 16,
  overlap on) and 8 ``train_batch`` steps on a fixed batch — losses finite and
  falling, the flash kernel present in the compiled step, and a
  ``save_checkpoint``/``load_checkpoint`` round trip that reproduces the next
  step's loss.
- *serve*: ``InferenceEngineV2.generate`` over 8 ragged prompts (32–512
  tokens, 32 greedy tokens each) with the Pallas paged kernels demanded, held
  against the engine's own XLA implementation on the same weights: prefill
  logits within a bf16 tolerance, and every generated token the reference's
  argmax (or tied with it) given the same prefix.

``--afmoe`` runs ONLY Trinity-Large-Preview's expert-parallel share (the
benchmark's configuration, published widths) against its plain float32
reference with the rows split by routing (``afmoe_phase``).  ``--chips 4`` runs ONLY the sharded path and what it is compared with: ZeRO-3
``fsdp=4`` over four chips against a one-device mesh, same model and batches.

Each phase prints one JSON line ("smoke, not a benchmark": the times include
whatever the host was doing).  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``; any
failed check raises, the last line then says ``"ok": false`` and the exit code
is non-zero.  ``--rehearse`` runs the same phases at a tiny size on whatever
backend jax has (the CPU rehearsal the tests drive); without it, no chip is a
failure.
"""

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
NOTE = "smoke, not a benchmark"
KERNEL_MARK = "tpu_custom_call"      # how a Mosaic kernel shows in XLA text

# bf16 tolerances, stated.  Logits are O(1) and pass through 12 layers of
# bf16 matmuls; first contact measured 0.027 against the XLA reference.
LOGIT_ATOL = 6e-2
TIE_TOL = LOGIT_ATOL                 # a greedy "tie" under that noise
LOSS_RTOL_RESTORE = 1e-3             # same program, same bits restored
LOSS_RTOL_SHARDED = 1e-2             # reduction order differs across chips
#                                      (first contact measured 3e-4)

_events = {"hits": 0, "misses": 0}


def _on_event(name, **_):
    if name.endswith("/cache_hits"):
        _events["hits"] += 1
    elif name.endswith("/cache_misses"):
        _events["misses"] += 1


def emit(obj):
    print(json.dumps(obj), flush=True)


def check(cond, msg):
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def cache_counts(since=None):
    now = dict(_events)
    if since is None:
        return now
    return {k: now[k] - since[k] for k in now}


def peak_bytes(devices):
    """Per-device high-water mark, or None where the backend keeps none."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(None if not stats else stats.get("peak_bytes_in_use"))
    return out


def model_config(rehearse, **kw):
    import jax.numpy as jnp
    from deepspeed_tpu.models import GPTConfig
    if rehearse:
        return GPTConfig(num_layers=2, num_heads=4, head_dim=16,
                         hidden_size=64, vocab_size=512, max_seq_len=256,
                         dropout=0.0, loss_chunk=256, dtype=jnp.bfloat16,
                         **kw)
    return GPTConfig.gpt2_small(vocab_size=50304, max_seq_len=1024,
                                dropout=0.0, loss_chunk=8192,
                                dtype=jnp.bfloat16, **kw)


def train_config(micro_batch, stage):
    return {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
        "overlap": {"enabled": True},
        "steps_per_print": 0,
    }


def run_steps(engine, batch, n):
    """n optimizer steps; per-step wall seconds end in block_until_ready."""
    import jax
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        jax.block_until_ready(m.loss)
        secs.append(time.perf_counter() - t0)
        losses.append(float(m.loss))
    return losses, secs


def dispatched(ops):
    from deepspeed_tpu.ops.registry import dispatch_log
    return [d for d in dispatch_log() if d["op"] in ops]


# ---------------------------------------------------------------- device

def device_phase():
    """Everything that must happen before the backend starts, then the
    device check.  Returns the device dict of the final line."""
    import importlib.metadata as md

    from deepspeed_tpu.config import parse_config
    from deepspeed_tpu.runtime.overlap import (LIBTPU_ENV,
                                               apply_overlap_flags)
    from deepspeed_tpu.runtime.resilience import (CACHE_DIR_ENV,
                                                  enable_compilation_cache)
    cache_dir = enable_compilation_cache()
    # initialize() exports these first thing too; here the device check
    # below would otherwise start the backend before it gets the chance
    flags = apply_overlap_flags(parse_config(train_config(1, 2)).overlap)
    import jax
    jax.monitoring.register_event_listener(_on_event)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit({"phase": "device", "jax": jax.__version__,
          "jaxlib": md.version("jaxlib"), "libtpu": md.version("libtpu"),
          "devices": [str(d) for d in devs], **device,
          "compile_cache_dir": cache_dir,
          "compile_cache_from_env": bool(os.environ.get(CACHE_DIR_ENV)),
          "overlap_flags_exported": flags,
          LIBTPU_ENV: os.environ.get(LIBTPU_ENV, "")})
    return device


# ----------------------------------------------------------------- train

def train_phase(args):
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPTChunkedLoss
    from deepspeed_tpu.ops.registry import reset_dispatch_log
    from deepspeed_tpu.parallel.mesh import single_device_mesh

    reset_dispatch_log()
    c0 = cache_counts()
    cfg = model_config(args.rehearse, attn_impl="pallas")
    micro, steps = (4, 8) if args.rehearse else (16, 8)
    T = cfg.max_seq_len
    rng = np.random.default_rng(args.seed)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, size=(micro, T)).astype(np.int32)}
    t0 = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPTChunkedLoss(cfg),
        config=dict(train_config(micro, stage=2), seed=args.seed),
        example_batch={"input_ids": np.zeros((micro, T), np.int32)},
        mesh=single_device_mesh())       # one chip, however many jax has
    init_s = time.perf_counter() - t0
    losses, secs = run_steps(engine, batch, steps)
    check(all(np.isfinite(losses)), f"non-finite training loss: {losses}")
    check(losses[-1] < losses[0],
          f"training loss did not fall: {losses[0]} -> {losses[-1]}")

    # the compiled step must hold the flash kernel: attention was demanded
    # from the registry (attn_impl="pallas"), and on a TPU that is Mosaic
    step_text = engine.lower_train_batch(batch).compile().as_text()
    flash_in_step = KERNEL_MARK in step_text
    ops = dispatched({"causal_attention"})
    check(ops and all(d["impl"] == "pallas" for d in ops),
          f"attention took the XLA path: {ops}")
    if not args.rehearse:
        check(flash_in_step, "no tpu_custom_call in the compiled train step")

    # checkpoint round trip: the step after a restore must reproduce the
    # step taken from the live state (same program, same bits, same batch)
    ckpt = os.path.join(args.out, "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        tag = engine.save_checkpoint(ckpt)
        save_s = time.perf_counter() - t0
        (live_next,), _ = run_steps(engine, batch, 1)
        t0 = time.perf_counter()
        engine.load_checkpoint(ckpt, tag)
        load_s = time.perf_counter() - t0
        check(engine.global_steps == steps,
              f"restore landed on step {engine.global_steps}, not {steps}")
        (restored_next,), _ = run_steps(engine, batch, 1)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    check(np.isfinite(restored_next) and restored_next < losses[0],
          f"loss after restore {restored_next} does not continue the curve")
    check(abs(restored_next - live_next)
          <= LOSS_RTOL_RESTORE * abs(live_next),
          f"step after restore {restored_next} != live step {live_next}")
    emit({"phase": "train", "note": NOTE,
          "model": "gpt2-small-rehearsal" if args.rehearse else "gpt2-small",
          "params_m": round(engine.num_parameters / 1e6, 1),
          "micro_batch": micro, "seq": T, "zero_stage": 2,
          "losses": [round(x, 4) for x in losses],
          "init_s": round(init_s, 2),
          "first_step_s_with_compile": round(secs[0], 2),
          "step_s": [round(s, 4) for s in secs[1:]],
          "checkpoint": {"save_s": round(save_s, 2),
                         "load_s": round(load_s, 2),
                         "next_loss_live": round(live_next, 5),
                         "next_loss_restored": round(restored_next, 5)},
          "flash_kernel_in_compiled_step": flash_in_step,
          "dispatch": ops,
          "process_peak_bytes_in_use": peak_bytes(jax.devices()[:1])[0],
          "compile_cache": cache_counts(c0)})
    del engine
    gc.collect()


# ----------------------------------------------------------------- serve

def _ir_programs(ir_dir):
    """{module name: has a Mosaic kernel} for the serving step programs jax
    lowered while ``jax_dump_ir_to`` pointed at ``ir_dir``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(ir_dir, "*.mlir"))):
        name = os.path.basename(path).split("_jit_", 1)[-1].removesuffix(
            "_compile.mlir")
        if not name.startswith(("ragged_", "speculative_")):
            continue
        with open(path) as f:
            has = KERNEL_MARK in f.read()
        out[name] = out.get(name, True) and has
    return out


def _prefill_logits(eng, uid, prompt, chunk):
    """Last-position logits of ``prompt`` fed through ``put`` in chunks."""
    logits = None
    for i in range(0, len(prompt), chunk):
        logits = eng.put([uid], [prompt[i:i + chunk]])[0]
    return logits


def serve_phase(args):
    import dataclasses

    import jax
    import numpy as np

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.model import kv_major_layout
    from deepspeed_tpu.ops.registry import reset_dispatch_log

    reset_dispatch_log()
    c0 = cache_counts()
    cfg = model_config(args.rehearse)
    rng = np.random.default_rng(args.seed + 1)
    nreq, new = 8, (8 if args.rehearse else 32)
    lo, hi = (8, 96) if args.rehearse else (32, 512)
    lengths = np.linspace(lo, hi, nreq).astype(int)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in lengths]
    asked_bs = 64                                  # the user-facing default
    ecfg = {"dtype": "bfloat16",
            "state_manager": {"max_tracked_sequences": nreq,
                              "kv_block_size": asked_bs},
            "generation": {"do_sample": False}}
    chunk = 128                                    # max_q_per_seq default
    paged_ops = {"paged_attention", "ragged_prefill_attention"}

    def build(impl, params, tag):
        ir_dir = os.path.join(args.out, f"ir_serve_{tag}")
        shutil.rmtree(ir_dir, ignore_errors=True)
        jax.config.update("jax_dump_ir_to", ir_dir)
        return InferenceEngineV2(dataclasses.replace(cfg, attn_impl=impl),
                                 ecfg, params=params, seed=args.seed), ir_dir

    # ---- the engine under test: Pallas paged kernels demanded
    eng, ir_kernel = build("pallas", None, "pallas")
    bs = eng.state.block_size
    kv_major = kv_major_layout(eng.model_config)
    # hd=64 compiles only kv-major at block 128: the engine must have
    # pre-committed exactly that from the default block size it was asked
    check(kv_major == (cfg.head_dim % 128 != 0), "kv layout not from head_dim")
    check(bs == (128 if kv_major else asked_bs),
          f"kv_block_size {asked_bs} -> {bs}, expected the 128-aligned page")
    check(eng.paged_impl == "pallas",
          f"engine start-up says paged_attention={eng.paged_impl}")
    t0 = time.perf_counter()
    outs = eng.generate(prompts, max_new_tokens=new)
    jax.block_until_ready(eng.cache.k)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs_again = eng.generate(prompts, max_new_tokens=new)
    jax.block_until_ready(eng.cache.k)
    gen_warm_s = time.perf_counter() - t0
    check(all(len(o) == new for o in outs), "a request came back short")
    check(all(np.array_equal(a, b) for a, b in zip(outs, outs_again)),
          "greedy generate is not repeatable on the same engine")
    k_logits = [_prefill_logits(eng, i, p, chunk)
                for i, p in enumerate(prompts)]
    eng.flush(list(range(nreq)))
    kernel_log = dispatched(paged_ops)
    check({d["op"] for d in kernel_log} == paged_ops
          and all(d["impl"] == "pallas" for d in kernel_log),
          f"serving took the XLA gather: {kernel_log}")
    programs = _ir_programs(ir_kernel)
    check(any("decode" in n for n in programs)
          and any("forward" in n for n in programs),
          f"no prefill/decode step programs were lowered: {programs}")
    if not args.rehearse:
        check(all(programs.values()),
              f"serving step programs without a Mosaic kernel: {programs}")
    params = eng.params
    del eng
    gc.collect()

    # ---- the reference: the same engine on the registry's XLA impls
    reset_dispatch_log()
    ref, ir_ref = build("xla", params, "xla")
    ref_outs = ref.generate(prompts, max_new_tokens=new)
    ref_log = dispatched(paged_ops)
    check(all(d["impl"] == "xla" for d in ref_log),
          f"the reference engine ran a kernel: {ref_log}")
    jax.config.update("jax_dump_ir_to", None)
    check(not any(_ir_programs(ir_ref).values()),
          "the reference engine's programs hold a Mosaic kernel")

    # teacher-forced: along the tokens the engine under test produced, the
    # reference's logits must (a) match at the end of prefill and (b) rank
    # every produced token first, or within a bf16 tie of first
    max_logit_err, worst_gap, ties = 0.0, 0.0, 0
    for i, (p, toks) in enumerate(zip(prompts, outs)):
        r = _prefill_logits(ref, i, p, chunk)
        max_logit_err = max(max_logit_err,
                            float(np.max(np.abs(r - k_logits[i]))))
        for t in toks:
            gap = float(np.max(r) - r[int(t)])
            ties += gap > 0
            worst_gap = max(worst_gap, gap)
            r = ref.put([i], [np.asarray([t], np.int32)])[0]
    ref.flush(list(range(nreq)))
    check(np.isfinite(max_logit_err) and max_logit_err <= LOGIT_ATOL,
          f"prefill logits differ from the XLA reference by {max_logit_err}")
    check(worst_gap <= TIE_TOL,
          f"a generated token trails the reference argmax by {worst_gap}")
    first_div = [next((j for j, (a, b) in enumerate(zip(o, r)) if a != b),
                      None) for o, r in zip(outs, ref_outs)]
    emit({"phase": "serve", "note": NOTE,
          "requests": nreq, "prompt_tokens": [int(n) for n in lengths],
          "new_tokens_each": new,
          "kv_block_size_asked": asked_bs, "kv_block_size": bs,
          "kv_layout": "kv-major" if kv_major else "standard",
          "paged_attention": "pallas",
          "generate_s_with_compile": round(gen_s, 2),
          "generate_s": round(gen_warm_s, 3),
          "vs_xla_reference": {
              "prefill_logits_max_abs_err": round(max_logit_err, 5),
              "logit_atol": LOGIT_ATOL,
              "tokens_checked": nreq * new,
              "tokens_not_reference_argmax": int(ties),
              "worst_gap_to_reference_argmax": round(worst_gap, 5),
              "tie_tol": TIE_TOL,
              "requests_token_equal": sum(d is None for d in first_div),
              "first_divergence": first_div},
          "kernel_in_step_programs": programs,
          "dispatch": kernel_log, "reference_dispatch": ref_log,
          "process_peak_bytes_in_use": peak_bytes(jax.devices()[:1])[0],
          "compile_cache": cache_counts(c0)})


# --------------------------------------------------------------- sharded

def _state_placement(state, n):
    """How the train state's bytes sit on ``n`` devices: the share held in
    arrays split evenly over all of them, and each device's total."""
    import jax
    per_device, total, even = {}, 0, 0
    for leaf in jax.tree_util.tree_leaves(state):
        if not isinstance(leaf, jax.Array) or leaf.ndim == 0:
            continue
        shards = leaf.addressable_shards
        total += leaf.nbytes
        for s in shards:
            per_device[s.device.id] = (per_device.get(s.device.id, 0)
                                       + s.data.nbytes)
        if (len({s.device.id for s in shards}) == n
                and all(s.data.nbytes * n == leaf.nbytes for s in shards)):
            even += leaf.nbytes
    return {"state_bytes": total,
            "evenly_split_share": round(even / total, 4),
            "bytes_per_device": [per_device[k] for k in sorted(per_device)]}


def sharded_phase(args):
    """ZeRO-3 fsdp=4 against a one-device mesh — and nothing else."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPTChunkedLoss
    from deepspeed_tpu.parallel.mesh import MeshSpec, build_mesh

    n, steps = 4, 8
    devs = jax.devices()
    check(len(devs) >= n, f"--chips {n} needs {n} devices, jax has {len(devs)}")
    c0 = cache_counts()
    cfg = model_config(args.rehearse, attn_impl="pallas")
    glob_batch = 8 if args.rehearse else 16
    T = cfg.max_seq_len
    rng = np.random.default_rng(args.seed)
    batches = [{"input_ids": rng.integers(
        0, cfg.vocab_size, size=(glob_batch, T)).astype(np.int32)}
        for _ in range(2)]

    def run(micro, mesh):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPTChunkedLoss(cfg),
            config=dict(train_config(micro, stage=3), seed=args.seed),
            example_batch={"input_ids": np.zeros((micro, T), np.int32)},
            mesh=mesh)
        check(int(engine.train_batch_size) == glob_batch,
              f"global batch {engine.train_batch_size} != {glob_batch}")
        losses, secs = [], []
        for i in range(steps):
            (loss,), (s,) = run_steps(engine, batches[i % 2], 1)
            losses.append(loss)
            secs.append(s)
        return engine, losses, secs

    # four chips FIRST: memory_stats keeps a high-water mark per device, so
    # the one-device run must come second for device 0's mark to be its own
    eng4, l4, s4 = run(glob_batch // n, build_mesh(
        MeshSpec(dp=1, fsdp=n), devices=devs[:n]))
    placement = _state_placement(eng4.state, n)
    text = eng4.lower_train_batch(batches[0]).compile().as_text()
    collectives = {k: k in text
                   for k in ("all-gather", "reduce-scatter", "all-reduce")}
    peaks4 = peak_bytes(devs[:n])
    del eng4
    gc.collect()
    eng1, l1, s1 = run(glob_batch, build_mesh(
        MeshSpec(dp=1, fsdp=1), devices=devs[:1]))
    peak1 = peak_bytes(devs[:1])[0]
    del eng1
    gc.collect()

    check(all(np.isfinite(l4 + l1)), f"non-finite loss: {l4} / {l1}")
    rel = max(abs(a - b) / abs(b) for a, b in zip(l4, l1))
    check(rel <= LOSS_RTOL_SHARDED,
          f"fsdp={n} and one-device loss curves differ by {rel}: {l4} / {l1}")
    check(l4[-1] < l4[0], f"sharded loss did not fall: {l4}")
    check(len(placement["bytes_per_device"]) == n,
          f"state sits on {len(placement['bytes_per_device'])} devices")
    check(placement["evenly_split_share"] >= 0.95,
          f"train state is not split over {n} devices: {placement}")
    quarter = placement["state_bytes"] / n
    check(all(abs(b - quarter) <= 0.1 * quarter
              for b in placement["bytes_per_device"]),
          f"uneven state bytes per device: {placement}")
    # ZeRO-3: params gathered before use, grads scattered back to their
    # owners.  The TPU compiler forms reduce-scatter; XLA:CPU (rehearsal)
    # leaves the all-reduce it would have been formed from.
    check(collectives["all-gather"]
          and (collectives["reduce-scatter"]
               or (args.rehearse and collectives["all-reduce"])),
          f"compiled ZeRO-3 step lacks collectives: {collectives}")
    if peak1 is not None:
        check(all(p < peak1 for p in peaks4),
              f"per-chip peak {peaks4} not below one-device peak {peak1}")
    emit({"phase": "sharded", "note": NOTE, "zero_stage": 3,
          "mesh": {"fsdp": n}, "global_batch": glob_batch, "seq": T,
          "losses_fsdp4": [round(x, 4) for x in l4],
          "losses_one_device": [round(x, 4) for x in l1],
          "max_rel_loss_diff": round(rel, 5), "loss_rtol": LOSS_RTOL_SHARDED,
          "first_step_s_with_compile": [round(s4[0], 2), round(s1[0], 2)],
          "step_s_fsdp4": [round(s, 4) for s in s4[1:]],
          "step_s_one_device": [round(s, 4) for s in s1[1:]],
          "placement": placement, "collectives_in_compiled_step": collectives,
          "flash_kernel_in_compiled_step": KERNEL_MARK in text,
          "peak_bytes_in_use_fsdp4": peaks4,
          "peak_bytes_in_use_one_device": peak1,
          "compile_cache": cache_counts(c0)})


# ----------------------------------------------------------------- afmoe

AFMOE_CONFIG = os.path.join(HERE, "benchmark", "configs",
                            "trinity-large-preview-5l-ep8.json")
# A row whose routers chose the reference's local experts is bf16 through
# five layers: at most 0.018 of its logits' rms and max |d| 0.085 over 17
# seeds (0.012 / 0.066 over the 8,450 rows of one); one with a local expert
# flipped reads 0.12-0.35 and 0.6-1.7 (my chip runs, PR 29).
AFMOE_ROW_REL = 0.03
AFMOE_ROW_ABS = 0.2
# the reference's margin between its 4th and 5th score at a row's FIRST
# disagreement: one bf16 step at scores in [0.5, 1) is 0.0039 (measured: at
# most 0.0026 over 8,450 rows)
AFMOE_MARGIN = 0.004


def _afmoe_sizes(rehearse):
    with open(AFMOE_CONFIG) as f:
        cfg = json.load(f)
    if rehearse:
        tiny = cfg["rehearsal"]
        cfg = {**cfg, **tiny, "run": {**cfg["run"], **tiny["run"]},
               "tolerances": {**cfg["tolerances"], **tiny["tolerances"]}}
    return cfg


def _afmoe_drive(eng, seqs, n_dec, chunk):
    """``seqs`` through ``put`` as the benchmark's runner feeds them: every
    prompt (all but the last ``n_dec`` tokens) in chunks of ``chunk`` rows,
    then one token a call.  Per sequence: the rows whose logits came back
    (each chunk's last, every decoded one), the logits, and the experts the
    routers chose for every row ``[expert layers, T, k]``."""
    import numpy as np
    uids = list(range(1, len(seqs) + 1))
    rows = [[] for _ in seqs]
    got = [[] for _ in seqs]
    routes = [[] for _ in seqs]
    fed = [0] * len(seqs)
    ends = [len(s) - n_dec for s in seqs]
    while any(f < len(s) for f, s in zip(fed, seqs)):
        live = [i for i, s in enumerate(seqs) if fed[i] < len(s)]
        take = [min(chunk, ends[i] - fed[i]) if fed[i] < ends[i] else 1
                for i in live]
        out, r = eng.put([uids[i] for i in live],
                         [seqs[i][fed[i]:fed[i] + n]
                          for i, n in zip(live, take)], with_routes=True)
        for j, (i, n) in enumerate(zip(live, take)):
            fed[i] += n
            rows[i].append(fed[i] - 1)
            got[i].append(out[j])
            routes[i].append(r[j])
    released = eng.state.w_released_total
    eng.flush(uids)
    return (rows, [np.stack(g).astype(np.float32) for g in got],
            [np.concatenate(r, axis=1) for r in routes], released)


def _afmoe_compare(ref, params, cfg, seqs, rows, got, routes, model_cfg,
                   want=None):
    """The runner's statistic (max over the sequences of max |d|, rms error
    over rms, and how far the engine's greedy token lies behind the
    reference's best) and, where ``routes`` is given, the rows split by
    whether the engine and the float32 reference chose the same local
    experts."""
    import numpy as np
    agg = {"max_abs": 0.0, "rel_rms": 0.0, "argmax_gap": 0.0}
    split = {"rows": 0, "agree_rows": 0, "agree_rel_max": 0.0,
             "agree_abs_max": 0.0, "flip_rows": 0, "flip_rel_max": 0.0,
             "flip_abs_max": 0.0, "first_flip_margin_max": 0.0}
    wants = []
    lo, held = model_cfg.expert_offset, model_cfg.local_experts
    for si, (s, rr, g) in enumerate(zip(seqs, rows, got)):
        w = (np.asarray(ref.logits(params, s, cfg, rows=rr))
             if want is None else want[si])
        wants.append(w)
        d = g - w
        agg["max_abs"] = max(agg["max_abs"], float(np.max(np.abs(d))))
        agg["rel_rms"] = max(agg["rel_rms"], float(
            np.sqrt(np.mean(d ** 2) / np.mean(w ** 2))))
        pick = g.argmax(-1)
        agg["argmax_gap"] = max(agg["argmax_gap"], float(np.max(
            w.max(-1) - w[np.arange(len(pick)), pick])))
        if routes is None:
            continue
        T = len(s)
        flipped = np.zeros(T, bool)
        first_margin = np.zeros(T)
        for layer, (chosen, margin) in enumerate(ref.routing(params, s, cfg)):
            chosen, margin = np.asarray(chosen), np.asarray(margin)
            e = np.where((routes[si][layer] >= lo)
                         & (routes[si][layer] < lo + held),
                         routes[si][layer], -1)
            r = np.where((chosen >= lo) & (chosen < lo + held), chosen, -1)
            differ = (np.sort(e, -1) != np.sort(r, -1)).any(-1)
            first_margin = np.where(differ & ~flipped, margin, first_margin)
            flipped |= differ
        rr = np.asarray(rr)
        rel = np.sqrt(np.mean(d ** 2, -1) / np.mean(w ** 2, -1))
        mx = np.max(np.abs(d), -1)
        ok = ~flipped[rr]
        split["rows"] += len(rr)
        split["agree_rows"] += int(ok.sum())
        split["flip_rows"] += int((~ok).sum())
        if ok.any():
            split["agree_rel_max"] = max(split["agree_rel_max"],
                                         float(rel[ok].max()))
            split["agree_abs_max"] = max(split["agree_abs_max"],
                                         float(mx[ok].max()))
        if (~ok).any():
            split["flip_rel_max"] = max(split["flip_rel_max"],
                                        float(rel[~ok].max()))
            split["flip_abs_max"] = max(split["flip_abs_max"],
                                        float(mx[~ok].max()))
            split["first_flip_margin_max"] = max(
                split["first_flip_margin_max"],
                float(first_margin[rr][~ok].max()))
    return agg, (split if routes is not None else None), wants


def afmoe_phase(args):
    """Trinity-Large-Preview's share (benchmark configuration
    ``trinity-large-preview-5l-ep8``, published widths) against its plain
    float32 reference, routing-aware: the check that the benchmark's runner
    cannot make, because it pools its rows.

    Per seed: (1) the runner's own sequences and statistic, through both
    page groups and past the window, so that window pages are released and
    rows are read behind a released boundary (paged decode kernel); (2) a
    prompt longer than window + chunk fed in chunks (ragged prefill kernel
    with a window start past page 0), then decoded.  In both, a row whose
    routers chose the reference's local experts must be within bf16 of it,
    and a row may differ in its experts only where the reference's own
    margin is within bf16's reach.  ``--faults``: what planted faults and
    the reference on fp8-rounded weights read (first seed)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(HERE, "benchmark", "reference"))
    import _afmoe as ref
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged import DSStateManager
    from deepspeed_tpu.models import GPTConfig
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.ops.registry import reset_dispatch_log
    from deepspeed_tpu.parallel.metadata import unbox

    cfg = _afmoe_sizes(args.rehearse)
    run, tol = cfg["run"], cfg["tolerances"]
    model_cfg = GPTConfig(
        **ref.program_config(cfg), max_seq_len=int(run["max_seq_len"]),
        dropout=0.0, dtype=jnp.bfloat16, attn_impl="pallas")
    window, chunk = model_cfg.sliding_window, run["state_manager"]["max_q_per_seq"]
    bs = run["state_manager"]["kv_block_size"]
    per_seq = -(-model_cfg.max_seq_len // bs)
    sm = {**run["state_manager"], "max_tracked_sequences": 4,
          "max_ragged_sequence_count": 4, "num_kv_blocks": 4 * per_seq}
    lm = GPTLogits(dataclasses.replace(model_cfg, param_dtype=jnp.bfloat16))
    make = jax.jit(lambda key: unbox(lm.init(
        key, jnp.zeros((1, 8), jnp.int32)))["params"])
    steps_cache = {}
    n_dec = int(run["compare"]["decode_positions"])
    # (2): the last chunk starts past the window, 40 decoded rows behind it
    long_len = min(window + chunk + chunk // 4,
                   model_cfg.max_seq_len - 41) + 40

    def engine(**patch):
        # a patched layout is a static of the step programs: its own cache
        eng = InferenceEngineV2(
            model_cfg, {"dtype": "bfloat16", "state_manager": sm,
                        "generation": run["generation"]},
            params=params, seed=0,
            steps_cache={} if patch else steps_cache)
        check(eng.paged_impl == "pallas",
              f"engine says paged_attention={eng.paged_impl}")
        for k, v in patch.items():
            eng._model_static[k] = v
        return eng

    def within(agg):
        return (np.isfinite(agg["max_abs"])
                and agg["max_abs"] <= tol["logits_max_abs"]
                and agg["rel_rms"] <= tol["logits_rel_rms"]
                and agg["argmax_gap"] <= tol["logits_max_abs"] / 2)

    # the tiny preset's logits are small beside its bf16 noise
    row_rel = AFMOE_ROW_REL * (2 if args.rehearse else 1)

    def row_check(split, what):
        check(split["agree_rel_max"] <= row_rel
              and split["agree_abs_max"] <= AFMOE_ROW_ABS,
              f"{what}: a row routed as the reference routes it is off by "
              f"{split['agree_rel_max']} / {split['agree_abs_max']}")
        check(split["first_flip_margin_max"] <= AFMOE_MARGIN,
              f"{what}: the engine chose other local experts where the "
              f"reference's margin is {split['first_flip_margin_max']}")

    reset_dispatch_log()
    for i in range(args.seeds):
        seed = args.seed + 7919 * i
        t0 = time.perf_counter()
        params = make(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
        rng = np.random.default_rng(seed + 17)      # the runner's draw
        seqs = [rng.integers(0, model_cfg.vocab_size, size=int(n) + n_dec)
                .astype(np.int32) for n in run["compare"]["prefill_tokens"]]
        rows, got, routes, released = _afmoe_drive(
            engine(), seqs, n_dec, chunk)
        # the runner compares the last prompt row and every decoded one
        agg, split, want = _afmoe_compare(ref, params, cfg, seqs, rows, got,
                                          routes, model_cfg)
        check(released > 0, "the runner's sequences released no window page")
        check(within(agg), f"runner statistic outside its limits: {agg}")
        row_check(split, "runner sequences")
        long_seq = [rng.integers(0, model_cfg.vocab_size, size=long_len)
                    .astype(np.int32)]
        lrows, lgot, lroutes, lreleased = _afmoe_drive(
            engine(), long_seq, 40, chunk)
        lagg, lsplit, _ = _afmoe_compare(ref, params, cfg, long_seq, lrows,
                                         lgot, lroutes, model_cfg)
        check(lreleased > 0, "the chunked prompt released no window page")
        row_check(lsplit, "chunked prompt past the window")
        line = {"phase": "afmoe", "note": NOTE, "seed": seed,
                "runner_statistic": agg, "limits": {
                    k: tol[k] for k in ("logits_max_abs", "logits_rel_rms")},
                "runner_rows": split, "window_pages_released": released,
                "chunked_prompt": {"tokens": long_len, "chunk": chunk,
                                   "statistic": lagg, "rows": lsplit,
                                   "window_pages_released": lreleased}}
        if args.faults and i == 0:
            faults = {}
            layout = engine()._model_static["kv_layout"]
            check(len({g for _, g in layout}) == 2, f"layout {layout}")
            real = DSStateManager._window_first_live

            def early(self, seq):       # every page goes one page too soon
                return (max(0, seq.seen_tokens - self.window + 1)
                        + self.block_size) // self.block_size

            plants = (
                ("window_page_released_one_too_early", {}, early),
                ("global_layer_on_the_window_groups_table",
                 {"kv_layout": tuple((first, 1) for first, _ in layout)},
                 real))
            for name, patch, first_live in plants:
                DSStateManager._window_first_live = first_live
                try:
                    frows, fgot, froutes, _ = _afmoe_drive(
                        engine(**patch), seqs, n_dec, chunk)
                finally:
                    DSStateManager._window_first_live = real
                fagg, fsplit, _ = _afmoe_compare(
                    ref, params, cfg, seqs, frows, fgot, froutes,
                    model_cfg, want=want)
                faults[name] = {
                    **fagg, "caught_by_runner_limits": not within(fagg),
                    "agree_rel_max": fsplit["agree_rel_max"],
                    "agree_abs_max": fsplit["agree_abs_max"],
                    "caught_by_row_check": bool(
                        fsplit["agree_rel_max"] > row_rel
                        or fsplit["agree_abs_max"] > AFMOE_ROW_ABS
                        or fsplit["first_flip_margin_max"] > AFMOE_MARGIN)}
            # the reference in the nearest precision below bf16: weights
            # rounded to fp8 e4m3 in place (this seed's last use of them)
            params = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda p: jax.lax.reduce_precision(p, 4, 3)
                if p.ndim >= 2 else p, t), donate_argnums=0)(params)
            fagg, _, _ = _afmoe_compare(ref, params, cfg, seqs, rows, got,
                                        None, model_cfg)
            faults["reference_on_fp8_e4m3_weights"] = {
                **fagg, "caught_by_runner_limits": not within(fagg)}
            check(not within(fagg) or args.rehearse,
                  f"the reference on fp8 weights reads as correct: {fagg}")
            line["faults"] = faults
        line["seconds"] = round(time.perf_counter() - t0, 1)
        del params
        emit(line)
    kernels = dispatched({"paged_attention", "ragged_prefill_attention",
                          "grouped_gemm"})
    check({d["op"] for d in kernels} == {"paged_attention",
                                         "ragged_prefill_attention",
                                         "grouped_gemm"}
          and all(d["impl"] == "pallas" for d in kernels
                  if d["op"] != "grouped_gemm"),
          f"kernels not as demanded: {kernels}")


# ------------------------------------------------------------------ main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only ZeRO-3 fsdp=4 against a one-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--afmoe", action="store_true",
                    help="only Trinity-Large-Preview's share at published "
                         "widths against its plain reference, routing-aware")
    ap.add_argument("--seeds", type=int, default=1,
                    help="--afmoe: seeds to try (--seed + 7919 i)")
    ap.add_argument("--faults", action="store_true",
                    help="--afmoe: also what planted faults read")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend jax has; kernels "
                         "may be interpreted, so their presence in the "
                         "compiled programs is reported, not required")
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"),
                    help="scratch directory (checkpoint, IR dumps)")
    args = ap.parse_args(argv)
    ok, device = False, None
    try:
        device = device_phase()
        check(args.rehearse or device["platform"] == "tpu",
              f"no TPU: jax reports platform {device['platform']!r}")
        os.makedirs(args.out, exist_ok=True)
        if args.chips == 4:
            sharded_phase(args)
        elif args.afmoe:
            afmoe_phase(args)
        else:
            train_phase(args)
            serve_phase(args)
        ok = True
    finally:
        # no except: a failed phase keeps its traceback and its exit code
        last = {"ok": ok, "device": device}
        if args.rehearse:
            last["rehearsal"] = True
        emit(last)
    return 0


if __name__ == "__main__":
    sys.exit(main())
