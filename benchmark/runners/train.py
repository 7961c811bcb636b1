"""Training cells: ``deepspeed_tpu.initialize`` and ``engine.train_batch``.

Set-up: build the engine (weights made on the device from the seed by the
program's own jitted init), compare the loss of the first batch with the
plain reference's on the same weights and tokens, warm up the one step
program.  Window: optimizer steps on batches that a host iterator makes while
the window runs, at most ``max_in_flight`` steps ahead of the device; the
clock is the host's, closed by ``block_until_ready`` on the last loss.
"""

import collections
import time

import costs
import traffic


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPTChunkedLoss, GPTConfig
    from deepspeed_tpu.ops.registry import dispatch_log, reset_dispatch_log
    from deepspeed_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                             single_device_mesh)

    cfg, mix, cell = ctx["config"], ctx["mix"], ctx["cell"]
    run_cfg, setup, seed = cfg["run"], ctx["setup"], ctx["args"].seed
    chips, devices = cell["chips"], ctx["devices"]
    T = int(mix["seq_len"])
    rows = int(mix["micro_batch_per_chip"]) * chips     # one optimizer step
    tokens_per_step = rows * T

    model_cfg = GPTConfig(
        **ctx["reference"].program_config(cfg), max_seq_len=T, dropout=0.0,
        dtype=jnp.bfloat16, attn_impl="pallas",     # flash demanded
        remat=bool(run_cfg.get("remat", False)),
        loss_chunk=int(run_cfg["loss_chunk"]))
    ds_config = {
        "train_micro_batch_size_per_gpu": int(mix["micro_batch_per_chip"]),
        "gradient_accumulation_steps": 1,
        "optimizer": run_cfg["optimizer"],
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": int(run_cfg["zero_stage"])},
        "overlap": run_cfg.get("overlap", {"enabled": False}),
        "steps_per_print": 0, "seed": int(seed) % (2 ** 31)}
    mesh = (single_device_mesh(devices[0]) if chips == 1 else
            build_mesh(MeshSpec(dp=1, fsdp=chips), devices=devices))
    reset_dispatch_log()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPTChunkedLoss(model_cfg), config=ds_config,
        example_batch={"input_ids": np.zeros(
            (int(mix["micro_batch_per_chip"]), T), np.int32)},
        mesh=mesh)
    jax.block_until_ready(engine.state.params)
    setup.mark("weights_and_engine")

    batches = traffic.train_batches(mix, seed, model_cfg.vocab_size, rows)
    first = next(batches)

    # ---- the plain reference, on the fp32 masters as they are (sharded or
    # not), before any step has changed them
    masters = engine.state.params
    masters = masters.get("params", masters)
    ref_loss = ctx["reference"].loss(masters, first["input_ids"], cfg)
    del masters
    setup.mark("reference")

    m = engine.train_batch(first)                   # compiles the step
    first_loss = float(m.loss)
    attn = [d for d in dispatch_log() if d["op"] == "causal_attention"]
    if not attn or any(d["impl"] != "pallas" for d in attn):
        raise RuntimeError(f"attention did not take the flash kernel: {attn}")
    loader = engine.prefetch_loader(batches)
    it = iter(loader)
    for _ in range(int(mix.get("warmup_steps", 3))):
        m = engine.train_batch(next(it))
    jax.block_until_ready(m.loss)
    setup.mark("warmup")

    depth = int(mix.get("max_in_flight", 2))
    tracer, compiles = ctx["tracer"], ctx["compiles"]
    pending = collections.deque()
    done_times = []                    # host time each fenced step finished
    setup_s = setup.total()
    compiles.window_open = True
    t0 = time.perf_counter()
    tracer.open(t0)
    deadline = t0 + ctx["seconds"]
    steps = 0
    try:
        while time.perf_counter() < deadline:
            tracer.poll(before_stop=lambda: jax.block_until_ready(
                list(pending)))
            with jax.profiler.StepTraceAnnotation("bench_train_step",
                                                  step_num=steps):
                m = engine.train_batch(next(it))
            pending.append(m.loss)
            steps += 1
            if len(pending) > depth:
                jax.block_until_ready(pending.popleft())
                done_times.append(time.perf_counter())
        jax.block_until_ready(list(pending))
        t1 = time.perf_counter()
    finally:
        compiles.window_open = False
        tracer.close()
        loader.close()
    last_loss = float(m.loss)
    window_s = t1 - t0

    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    tol = float(cfg["tolerances"]["first_loss_rtol"])
    correct = (np.isfinite(first_loss) and rel <= tol
               and np.isfinite(last_loss) and last_loss < first_loss)

    rate = steps * tokens_per_step / window_s / chips
    # the rate over the part of the window before the tracer started (the
    # traced run's end-to-end number, for train_mfu)
    pre_rate = None
    if tracer.started_at is not None:
        n_pre = sum(1 for t in done_times if t <= tracer.started_at)
        if n_pre:
            pre_rate = (n_pre * tokens_per_step / chips
                        / (max(t for t in done_times
                               if t <= tracer.started_at) - t0))
    flops_per_token = costs.train_flops_per_token(
        int(engine.num_parameters), model_cfg.num_layers,
        model_cfg.hidden_size, T)
    return {
        "setup_s": setup_s, "correct": bool(correct),
        "compared": {"first_loss_rel_err": (rel, tol),
                     "last_loss_over_first": (last_loss / first_loss, 1.0)},
        "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s_per_chip": rate,
                       "setup_s": setup_s},
        "steps": steps, "window_s": window_s,
        "tokens_per_step": tokens_per_step, "chips": chips,
        "rate_untraced": pre_rate if pre_rate is not None else rate,
        "flops_per_token": flops_per_token,
        "model_cfg": model_cfg, "step_program": "train_batch",
        "notes": {
            "steps": steps, "window_s": window_s,
            "tokens_per_step": tokens_per_step,
            "params": int(engine.num_parameters),
            "first_loss": first_loss, "reference_first_loss": ref_loss,
            "first_loss_rel_err": rel, "first_loss_rtol": tol,
            "last_loss": last_loss, "attention_dispatch": attn,
            "train_flops_per_token": flops_per_token}}
