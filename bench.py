#!/usr/bin/env python
"""Flagship benchmark: GPT-2-small LM training step throughput on one TPU chip.

Matches BASELINE.md config 2 ("GPT-2-small fine-tune, ZeRO-2, bf16") scaled to the
single available chip.  Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
vs_baseline = achieved MFU / 0.35 (the driver's north-star MFU target for the
training path, BASELINE.json).  "extra" carries secondary legs: long-seq flash,
ZeRO-3, and the FastGen-analog serving throughput (ragged-vs-static ratio).

One process: the body runs in the interpreter that was started (a chip
belongs to one process at a time, so no probe or worker child).  Every line
printed names the device it ran on (``platform``, ``device_kind``,
``device_count``).  Without a TPU the run fails — non-zero exit and an
``"error"`` on the line — unless ``BENCH_SMOKE``/``BENCH_FORCE_CPU`` asks for
the CPU plumbing run, whose lines say ``"platform": "cpu"``, carry no MFU and
are not appended to the per-leg records.  A failed leg is an ``*_error`` key in
``extra``, an ``"error"`` naming the failed legs, and a non-zero exit.
"""

import json
import os
import sys
import time

METRIC = "gpt2s_zero2_bf16_train_tokens_per_sec_per_chip"


def device_info() -> dict:
    """The device as jax reports it — stamped on every printed line."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def peak_flops_per_chip():
    """bf16 peak for the local TPU generation; None off-TPU (a CPU has no
    MFU); an unknown TPU kind is an error, never a default."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind.lower()
    if "v5 lite" in kind or "v5e" in kind:
        return 197e12
    if "v5p" in kind or "v5" in kind:
        return 459e12
    if "v4" in kind:
        return 275e12
    if "v6" in kind:
        return 918e12
    raise ValueError(f"no peak FLOP/s on record for TPU kind "
                     f"{dev.device_kind!r}; add it to the table")


def _mfu(flops, dt):
    peak = peak_flops_per_chip()
    return None if peak is None else round(flops / dt / peak, 4)


def train_flops_per_step(n_params, n_layers, hidden, batch, seq) -> float:
    """6N per token (fwd+bwd) + attention matmul flops 12*L*H*T per token."""
    tokens = batch * seq
    return 6.0 * n_params * tokens + 12.0 * n_layers * hidden * seq * tokens


def _measure(engine, batch, iters=8, prefetch=False):
    """Warmup/compile then timed steps.  Step N depends on state N-1, so
    blocking on the last loss drains the whole chain.

    ``prefetch=True`` drives the loop through ``engine.prefetch_loader``
    (runtime/prefetch.py): the worker thread forms/shards/device_puts each
    batch ahead of its step, so the timed region measures the async-pipeline
    steady state — ``train_batch``'s input phases collapse to a queue pop.
    Warmup steps also flow through the prefetcher (same code path the timed
    steps take)."""
    import jax
    warmup = 3
    if prefetch and hasattr(engine, "prefetch_loader"):
        src = (batch for _ in range(warmup + iters))
        with engine.prefetch_loader(src) as pf:
            it = iter(pf)
            for _ in range(warmup):
                m = engine.train_batch(next(it))
            jax.block_until_ready(m.loss)
            t0 = time.perf_counter()
            for pb in it:
                m = engine.train_batch(pb)
            jax.block_until_ready(m.loss)
            return (time.perf_counter() - t0) / iters
    for _ in range(warmup):
        m = engine.train_batch(batch)
    jax.block_until_ready(m.loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        m = engine.train_batch(batch)
    jax.block_until_ready(m.loss)
    return (time.perf_counter() - t0) / iters


def _extra_points(GPTChunkedLoss, GPTConfig, initialize, out=None,
                  emit=None):
    """Secondary perf points (round-2 review: one number is not a regression
    net): a long-seq flash-attention point and a ZeRO-3 point.  ``out`` (the
    caller's extra dict) is updated IN PLACE and ``emit`` (when given)
    re-prints the metric line after each sub-leg, so a run cut by its time
    limit has already printed everything measured so far."""
    import jax.numpy as jnp
    import numpy as np
    out = {} if out is None else out
    rng = np.random.default_rng(0)
    tick = emit or (lambda: None)
    try:
        B, T = 4, 4096
        cfg = GPTConfig.gpt2_small(vocab_size=50304, max_seq_len=T,
                                   dropout=0.0, loss_chunk=8192,
                                   dtype=jnp.bfloat16)
        eng, _, _, _ = initialize(
            model=GPTChunkedLoss(cfg),
            config={"train_micro_batch_size_per_gpu": B,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 2},
                    "mesh": {"dp": -1}, "steps_per_print": 0},
            example_batch={"input_ids": np.zeros((B, T), np.int32)})
        dt = _measure(eng, {"input_ids": rng.integers(
            0, 50304, (B, T)).astype(np.int32)})
        flops = train_flops_per_step(eng.num_parameters, cfg.num_layers,
                                     cfg.hidden_size, B, T)
        out["flash_T4096_tokens_per_sec"] = round(B * T / dt, 1)
        out["flash_T4096_mfu"] = _mfu(flops, dt)
        del eng
    except Exception as e:  # noqa: BLE001 — secondary points must not kill
        out["flash_T4096_error"] = str(e)[:120]
    tick()
    try:
        B, T = 16, 1024
        cfg = GPTConfig.gpt2_small(vocab_size=50304, max_seq_len=T,
                                   dropout=0.0, loss_chunk=8192,
                                   dtype=jnp.bfloat16)
        eng, _, _, _ = initialize(
            model=GPTChunkedLoss(cfg),
            config={"train_micro_batch_size_per_gpu": B,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 3},
                    # chunked ZeRO-3 collectives + scheduler flags; the
                    # telemetry AOT analysis feeds the exposed-comms columns
                    "overlap": {"enabled": True, "num_chunks": 4},
                    "telemetry": {"enabled": True, "trace_enabled": False,
                                  "snapshot_interval": 0},
                    "mesh": {"fsdp": -1, "dp": 1}, "steps_per_print": 0},
            example_batch={"input_ids": np.zeros((B, T), np.int32)})
        batch = {"input_ids": rng.integers(
            0, 50304, (B, T)).astype(np.int32)}
        # the flagship leg already set collective_exposed_ratio{fn=
        # train_batch} in the shared registry — clear it so a failed HLO
        # walk on THIS leg reads as missing, not as the stage-2 figure
        from deepspeed_tpu.telemetry.registry import default_registry
        gauge = default_registry.gauge("collective_exposed_ratio")
        gauge.clear()
        dt = _measure(eng, batch)
        flops = train_flops_per_step(eng.num_parameters, cfg.num_layers,
                                     cfg.hidden_size, B, T)
        out["zero3_tokens_per_sec"] = round(B * T / dt, 1)
        out["zero3_mfu"] = _mfu(flops, dt)
        ratio = None
        for labels, value in gauge.samples():
            if labels.get("fn") == "train_batch":
                ratio = float(value)
        if ratio is None:
            out["zero3_comm_exposed_error"] = "exposed-ratio gauge not set"
        else:
            out["zero3_collective_exposed_ratio"] = round(ratio, 4)
            try:
                comms = eng.profile_comms(batch, iters=2)
                comm_ms = sum(v["time_s"] for v in comms.values()) * 1000.0
                out["zero3_comm_total_ms"] = round(comm_ms, 3)
                out["zero3_comm_exposed_ms"] = round(comm_ms * ratio, 3)
            except Exception as e:  # noqa: BLE001
                out["zero3_comm_exposed_error"] = str(e)[:120]
        del eng
    except Exception as e:  # noqa: BLE001
        out["zero3_error"] = str(e)[:120]
    tick()
    _serving_point(out=out, emit=emit)
    tick()
    _moe_point(GPTChunkedLoss, GPTConfig, initialize, out=out, emit=emit)
    tick()
    out.update(_scale_point(GPTChunkedLoss, GPTConfig, initialize))
    tick()
    if os.environ.get("BENCH_INFINITY"):
        out.update(_infinity_point(GPTChunkedLoss, GPTConfig, initialize))
        tick()
    return out


def _scale_point(GPTChunkedLoss, GPTConfig, initialize):
    """~1B-class ZeRO-3 scale leg (round-3 verdict item 2: GPT-2-small
    stresses nothing ZeRO exists for; BASELINE.md's north star is ZeRO-3 at
    Llama-class scale).

    Sizing arithmetic for one 16 GB v5e chip with fp32 Adam (reference-parity
    optimizer states): bf16 params (2) + fp32 master (4) + mu (4) + nu (4) +
    fp32 grads (4) = 18 bytes/param → ≈0.80 B params is the largest
    llama-shape that fits with remat'd activations; a true 1 B needs 18 GB,
    which no fp32-Adam single-chip config can hold (multi-chip shards it).
    """
    import dataclasses

    import jax.numpy as jnp
    import numpy as np
    out = {}
    try:
        B, T = 4, 2048
        cfg = GPTConfig.llama(num_layers=10, hidden=2048, heads=16,
                              vocab_size=32000, max_seq_len=T)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16, dropout=0.0,
                                  loss_chunk=4096, remat=True)
        eng, _, _, _ = initialize(
            model=GPTChunkedLoss(cfg),
            config={"train_micro_batch_size_per_gpu": B,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 3},
                    # the [overlap] target leg: chunked stage-3 collectives
                    # + scheduler flags (no telemetry here — the AOT
                    # compile-for-analysis would double this leg's multi-
                    # minute compile; the gpt2s zero3 leg carries the
                    # exposed-comms columns)
                    "overlap": {"enabled": True, "num_chunks": 4},
                    "mesh": {"fsdp": -1, "dp": 1}, "steps_per_print": 0},
            example_batch={"input_ids": np.zeros((B, T), np.int32)})
        rng = np.random.default_rng(0)
        dt = _measure(eng, {"input_ids": rng.integers(
            0, 32000, (B, T)).astype(np.int32)}, iters=5)
        flops = train_flops_per_step(eng.num_parameters, cfg.num_layers,
                                     cfg.hidden_size, B, T)
        out["zero3_0p8b_tokens_per_sec"] = round(B * T / dt, 1)
        out["zero3_0p8b_mfu"] = _mfu(flops, dt)
        out["zero3_0p8b_params_m"] = round(eng.num_parameters / 1e6, 1)
        out["zero3_0p8b_num_chunks"] = 4
        # wire-byte columns (ISSUE 14 acceptance): compiled-HLO collective
        # payload of this bf16-chunked step vs the fully-composed
        # quantized pipeline (chunking × qwZ/qgZ int4 × same mesh) on the
        # SAME model — zero3_wire_reduction_x is the ZeRO++-style byte
        # reduction the telemetry must show while the exposed ratio stays
        # flat (scripts/check_bench.py trips if composition regresses
        # either).  Structural measurement: lower+compile only, no
        # execution, so the columns are exact on CPU and TPU alike.
        # The base step's HLO is captured BEFORE the engine is dropped, so
        # the 0.8B training state (~14 GB with fp32 Adam) never exists
        # twice — the quantized engine is built into the freed headroom.
        base_txt = None
        try:
            base_txt = _step_hlo_text(eng, T)
        except Exception as e:  # noqa: BLE001
            out["zero3_wire_error"] = str(e)[:160]
        del eng
        if base_txt is not None:
            try:
                out.update(_zero3_wire_point(
                    GPTChunkedLoss, cfg, initialize, base_txt, B, T))
            except Exception as e:  # noqa: BLE001
                out["zero3_wire_error"] = str(e)[:160]
    except Exception as e:  # noqa: BLE001
        out["zero3_0p8b_error"] = str(e)[:160]
    return out


def _step_hlo_text(eng, T):
    """Compiled-HLO text of one engine's train step (lower+compile only —
    nothing executes)."""
    import numpy as np
    batch = {"input_ids": np.zeros((eng.train_batch_size, T), np.int32)}
    return eng.lower_train_batch(batch).compile().as_text()


def _zero3_wire_point(GPTChunkedLoss, cfg, initialize, base_txt, B, T):
    """Compiled-HLO wire bytes of the 0.8B stage-3 step: bf16-chunked
    baseline (``base_txt``, captured before its engine was freed) vs the
    composed quantized pipeline (int4 qwZ gather + int4 qgZ reduce-scatter
    inside the same 4-chunk train — ZeRO++ arXiv:2306.10209's ~4× wire
    target).  Also reports the exposed-ratio drift between the two
    programs: quantization must not un-hide the wire (T3's fused
    quantize-chunk-overlap claim)."""
    import numpy as np
    from deepspeed_tpu.comm.comm import hlo_overlap_stats, hlo_wire_bytes

    out = {}
    q_eng, _, _, _ = initialize(
        model=GPTChunkedLoss(cfg),
        config={"train_micro_batch_size_per_gpu": B,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": 3,
                    "zero_quantized_weights": True,
                    "zero_quantized_gradients": True,
                    "zeropp": {"weight_bits": 4, "grad_bits": 4}},
                "overlap": {"enabled": True, "num_chunks": 4},
                "mesh": {"fsdp": -1, "dp": 1}, "steps_per_print": 0},
        example_batch={"input_ids": np.zeros((B, T), np.int32)})
    q_txt = _step_hlo_text(q_eng, T)
    del q_eng
    base_wire = hlo_wire_bytes(base_txt)
    q_wire = hlo_wire_bytes(q_txt)
    # gather_scatter: the param/grad collectives the pipeline owns — the
    # all-reduce population (norms, loss scalars) is identical in both
    # programs and would only dilute the ratio
    out["zero3_wire_bytes"] = q_wire["gather_scatter"]
    out["zero3_wire_bf16_bytes"] = base_wire["gather_scatter"]
    if q_wire["gather_scatter"]:
        out["zero3_wire_reduction_x"] = round(
            base_wire["gather_scatter"] / q_wire["gather_scatter"], 2)
    out["zero3_wire_exposed_ratio"] = round(
        hlo_overlap_stats(q_txt)["exposed_ratio"], 4)
    out["zero3_wire_exposed_ratio_bf16"] = round(
        hlo_overlap_stats(base_txt)["exposed_ratio"], 4)
    return out


def _infinity_point(GPTChunkedLoss, GPTConfig, initialize):
    """ZeRO-Infinity leg (round-3 verdict item 2): a model whose TRAINING
    STATE exceeds HBM — 1.47 B params × 18 B/param ≈ 26 GB > 16 GB — runs via
    per-layer param streaming (runtime/infinity.py): device holds ≤2 layers'
    params; masters + Adam moments live on the host NVMe tier.

    Gated behind BENCH_INFINITY=1: each step moves the full param tree
    host↔device, so wall-clock depends on host-transfer bandwidth, not the
    chip — measured and reported, never on the driver's critical path."""
    import dataclasses
    import shutil
    import tempfile

    import jax.numpy as jnp
    import numpy as np
    out = {}
    nvme = None
    try:
        B, T = 4, 1024
        cfg = GPTConfig.llama(num_layers=20, hidden=2048, heads=16,
                              vocab_size=32000, max_seq_len=T)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16, dropout=0.0,
                                  loss_chunk=4096)
        nvme = tempfile.mkdtemp(prefix="ds_tpu_inf_")
        eng, _, _, _ = initialize(
            model=GPTChunkedLoss(cfg),
            config={"train_micro_batch_size_per_gpu": B,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {
                        "stage": 3,
                        "offload_param": {"device": "nvme",
                                          "nvme_path": nvme},
                        "offload_optimizer": {"device": "nvme",
                                              "nvme_path": nvme}},
                    "steps_per_print": 0},
            example_batch={"input_ids": np.zeros((B, T), np.int32)})
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, 32000, (B, T)).astype(np.int32)}
        eng.train_batch(batch)                    # compile + warm store
        t0 = time.perf_counter()
        iters = 2
        for _ in range(iters):
            m = eng.train_batch(batch)
        import jax
        jax.block_until_ready(m.loss)
        dt = (time.perf_counter() - t0) / iters
        flops = train_flops_per_step(eng.num_parameters, cfg.num_layers,
                                     cfg.hidden_size, B, T)
        out["infinity_1p5b_tokens_per_sec"] = round(B * T / dt, 1)
        out["infinity_1p5b_mfu"] = _mfu(flops, dt)
        out["infinity_1p5b_params_m"] = round(eng.num_parameters / 1e6, 1)
        del eng
    except Exception as e:  # noqa: BLE001
        out["infinity_error"] = str(e)[:160]
    finally:
        if nvme:
            # ~17 GB of offloaded masters/moments — never leave it on /tmp
            shutil.rmtree(nvme, ignore_errors=True)
    return out


def _serving_point(out=None, emit=None):
    """FastGen-analog serving leg (compact form of bench_serving.py):
    effective throughput over an oversubscribed heterogeneous workload
    (mixed prompt lengths AND per-request completion budgets — the workload
    shape continuous batching exists for), ragged v2 vs the static-batching
    v1 baseline on the same weights.  ``out``/``emit`` follow the
    _extra_points contract: results merge + re-emit after each
    sub-measurement."""
    import dataclasses

    import numpy as np
    out = {} if out is None else out
    tick = emit or (lambda: None)
    try:
        import jax.numpy as jnp
        import bench_serving
        from bench_serving import make_workload, run_v1, run_v2
        from deepspeed_tpu.models import GPTConfig
        cfg = GPTConfig.llama(num_layers=12, hidden=1024, heads=16,
                              num_kv_heads=4, vocab_size=32000,
                              max_seq_len=2048, dtype=None)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        rng = np.random.default_rng(0)
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        seed_eng = InferenceEngineV2(cfg, {"state_manager": {
            "max_tracked_sequences": 4, "kv_block_size": 64}}, seed=0)
        params = seed_eng.params
        del seed_eng
        # 2 static batches keeps the leg inside the bench attempt timeout
        prompts, budgets = make_workload(rng, cfg,
                                         nreq=2 * bench_serving.SLOTS)
        v2_tps = run_v2(cfg, params, prompts, budgets)
        out["serving_ragged_tokens_per_sec"] = round(v2_tps, 1)
        tick()
        v1_tps = run_v1(cfg, params, prompts, budgets)
        out["serving_static_tokens_per_sec"] = round(v1_tps, 1)
        out["serving_ragged_vs_static"] = round(v2_tps / v1_tps, 3)
        tick()
        try:
            # W8A16 leg (round-3 verdict item 4 "done" bar: wq decode
            # ≥0.9× bf16; decode is weights-bandwidth-bound so the int8
            # kernel should beat 1.0×) — same workload, weights quantized
            wq_tps = run_v2(cfg, params, prompts, budgets,
                            quant_weights=True)
            out["serving_wq_int8_tokens_per_sec"] = round(wq_tps, 1)
            out["serving_wq_vs_bf16"] = round(wq_tps / v2_tps, 3)
        except Exception as e:  # noqa: BLE001 — isolate the new leg
            out["serving_wq_error"] = str(e)[:160]
    except Exception as e:  # noqa: BLE001
        out["serving_error"] = str(e)[:160]
    return out


def _moe_point(GPTChunkedLoss, GPTConfig, initialize, out=None, emit=None):
    """MoE expert-parallel leg (ISSUE 18): step time vs the dense
    equivalent (same per-token FLOPs — k=1, same FFN width, experts off),
    compiled-HLO dispatch/combine all-to-all bytes on the bf16 route vs
    the composed int4 wire (``moe_a2a_wire_reduction_x`` — the acceptance
    bar is >= 3x at a flat exposed ratio), and the expert-load drop rate
    from the in-step telemetry.  The wire columns are structural
    (lower+compile only), so they are exact on CPU and TPU alike; the
    timed MoE step runs the shipped default path, expert telemetry
    included.  ``out``/``emit`` follow the _extra_points contract."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.comm.comm import hlo_collective_bytes, \
        hlo_overlap_stats
    out = {} if out is None else out
    tick = emit or (lambda: None)
    smoke = bool(os.environ.get("BENCH_SMOKE")
                 or os.environ.get("BENCH_FORCE_CPU"))
    try:
        ep = jax.device_count()
        # 2 local experts per rank: moe.num_chunks=2 forms a real a2a
        # chunk train on every rank (E_local == 2)
        E = 2 * ep if ep > 1 else 4
        if smoke:
            B, T = 4, 64
            cfg = GPTConfig(num_layers=2, num_heads=4, head_dim=16,
                            hidden_size=64, vocab_size=512, max_seq_len=T,
                            dropout=0.0, loss_chunk=64)
        else:
            B, T = 8, 1024
            cfg = GPTConfig.llama(num_layers=8, hidden=1024, heads=16,
                                  vocab_size=32000, max_seq_len=T)
            cfg = dataclasses.replace(cfg, dropout=0.0, loss_chunk=4096)
        # bf16 activations on CPU and TPU alike: the a2a payload rides the
        # model compute dtype, and the wire-reduction column is defined
        # against the bf16 wire — an fp32 smoke baseline would double it
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        moe_cfg = dataclasses.replace(cfg, num_experts=E, moe_k=1,
                                      moe_capacity_factor=1.25)
        rng = np.random.default_rng(0)

        def _batch(eng):
            # the engine's data axes set the process-local row count
            # (dense shards over dp/fsdp, the MoE mesh over ep)
            gb = int(eng.train_batch_size)
            return {"input_ids": rng.integers(
                0, cfg.vocab_size, (gb, T)).astype(np.int32)}

        example = {"input_ids": np.zeros((B, T), np.int32)}
        iters = 3 if smoke else 10
        base_cfg = {
            "train_micro_batch_size_per_gpu": B,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            # stage 2 rewrites dp->fsdp, so pin fsdp=1 explicitly on the
            # expert-parallel mesh (at most one axis may be -1)
            "mesh": {"dp": 1, "fsdp": 1, "ep": -1},
            "steps_per_print": 0,
        }
        eng, _, _, _ = initialize(model=GPTChunkedLoss(cfg),
                                  config={**base_cfg, "mesh": {"dp": -1}},
                                  example_batch=example)
        dense_tokens = int(eng.train_batch_size) * T
        dense_dt = _measure(eng, _batch(eng), iters=iters)
        del eng
        out["dense_equiv_step_time_ms"] = round(dense_dt * 1e3, 2)
        tick()
        # bf16-wire MoE route, 2-chunk overlapped a2a train
        eng, _, _, _ = initialize(
            model=GPTChunkedLoss(moe_cfg),
            config={**base_cfg, "moe": {"num_chunks": 2}},
            example_batch=example)
        moe_tokens = int(eng.train_batch_size) * T
        moe_dt = _measure(eng, _batch(eng), iters=iters)
        out["moe_step_time_ms"] = round(moe_dt * 1e3, 2)
        # per-token throughput ratio: the two meshes may resolve different
        # global batch sizes, so step time alone would not compare
        out["moe_vs_dense_step_x"] = round(
            (moe_tokens / moe_dt) / (dense_tokens / dense_dt), 3)
        host = getattr(eng, "_last_moe_host", None)
        if host and host.get("assigned_tokens"):
            out["moe_drop_rate"] = round(
                float(host.get("dropped_tokens", 0.0))
                / float(host["assigned_tokens"]), 4)
        base_txt = _step_hlo_text(eng, T)
        del eng
        out["moe_exposed_ratio"] = round(
            hlo_overlap_stats(base_txt)["exposed_ratio"], 4)
        tick()
        if ep < 2:
            # not a failure: one device has no all-to-all to measure
            out["moe_a2a_wire_skipped"] = ("single device: ep=1 is a2a-free "
                                           "by construction")
        else:
            # composed int4 wire on the same model/mesh; all-to-all bytes
            # only — the grad all-reduce population is identical in both
            # programs and would dilute the ratio
            q_eng, _, _, _ = initialize(
                model=GPTChunkedLoss(moe_cfg),
                config={**base_cfg,
                        "moe": {"wire_bits": 4, "block_size": 64,
                                "num_chunks": 2}},
                example_batch=example)
            q_txt = _step_hlo_text(q_eng, T)
            del q_eng

            def a2a(txt):
                return hlo_collective_bytes(txt).get(
                    "all-to-all", {}).get("bytes", 0)

            # XLA:CPU float-normalizes bf16 compute to f32, so the
            # full-width payload compiles at 4 B/el there; halve to the
            # bf16 wire the TPU program actually ships so the column (and
            # the >= 3x acceptance ratio) is backend-independent
            import re as _re
            base_bytes = a2a(base_txt)
            if not _re.search(r"bf16\[[0-9,]*\][^ ]*\s+all-to-all",
                              base_txt):
                base_bytes //= 2
            out["moe_a2a_wire_bf16_bytes"] = base_bytes
            out["moe_a2a_wire_bytes"] = a2a(q_txt)
            if out["moe_a2a_wire_bytes"]:
                out["moe_a2a_wire_reduction_x"] = round(
                    out["moe_a2a_wire_bf16_bytes"]
                    / out["moe_a2a_wire_bytes"], 2)
            out["moe_exposed_ratio_q4"] = round(
                hlo_overlap_stats(q_txt)["exposed_ratio"], 4)
    except Exception as e:  # noqa: BLE001 — secondary points must not kill
        out["moe_error"] = str(e)[:160]
    tick()
    return out


def _guardian_point(initialize, out=None, emit=None):
    """Guardian chaos leg (runtime/guardian.py): poison one step's grads
    with the ``nan@step.grads`` fault, let the control loop roll back to
    the health-verified ring checkpoint and skip the window, and report
    ``rollback_recovery_ms`` (detection → training-ready) — the
    self-healing latency the regression sentinel tracks.  Tiny model, CPU
    and TPU alike: the number measures the remediation machinery (restore
    + cursor rewind + pipeline rebuild), not the model."""
    import tempfile

    import numpy as np

    from deepspeed_tpu.models import GPT, GPTConfig
    from deepspeed_tpu.runtime import faults
    out = {} if out is None else out
    tick = emit or (lambda: None)
    vocab, seq = 64, 32
    run_dir = tempfile.mkdtemp(prefix="bench_guardian_")
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        "data_pipeline": {"prefetch_depth": 2},
        "telemetry": {"enabled": False,
                      "health": {"enabled": True,
                                 "dump_path": os.path.join(run_dir, "pm")}},
        "guardian": {"enabled": True, "checkpoint_interval": 2,
                     "ring_keep": 3, "clean_window": 1, "max_rollbacks": 2,
                     "watchdog": {"warmup_deadline_s": 600.0,
                                  "min_deadline_s": 120.0,
                                  "deadline_factor": 100.0}},
    }
    eng, _, _, _ = initialize(
        model=GPT(GPTConfig.tiny(vocab_size=vocab, max_seq_len=seq)),
        config=cfg,
        example_batch={"input_ids": np.zeros((2, seq), np.int32)})
    batch = int(eng.train_batch_size)

    def batch_fn(i):
        rng = np.random.default_rng(7000 + i)
        return {"input_ids": rng.integers(0, vocab,
                                          size=(batch, seq)
                                          ).astype(np.int32)}

    import shutil
    faults.reset()
    try:
        faults.inject("step.grads", "nan", after=5)   # poisons step 6
        guardian = eng.guardian(run_dir, batch_fn=batch_fn)
        report = guardian.run(10)
    finally:
        # a leg abort must not leave the one-shot nan armed process-wide:
        # later measured legs fire the same step.grads site
        faults.reset()
        shutil.rmtree(run_dir, ignore_errors=True)
    out["guardian_status"] = report.status
    out["guardian_rollbacks"] = report.rollbacks
    # numeric healed flag for the regression sentinel: strings are dropped
    # by the flattener and a missing metric is skipped non-strict, so this
    # is the one guaranteed-present number that trips when the
    # self-healing machinery itself breaks
    out["guardian_healed"] = (
        1.0 if report.status == "completed" and report.rollbacks == 1
        else 0.0)
    out["guardian_skipped_sources"] = len(report.skipped_sources)
    if report.rollback_recovery_ms:
        out["rollback_recovery_ms"] = round(
            float(np.mean(report.rollback_recovery_ms)), 2)
    tick()
    return out


def _fail(msg, dev):
    """One JSON line with an ``"error"`` and no value, then a non-zero exit."""
    print(json.dumps({"metric": METRIC, "value": None,
                      "unit": "tokens/s/chip", "error": msg, **dev}),
          flush=True)
    return 1


def run_bench():
    """The whole measurement, in the process that was started."""
    cpu_run = bool(os.environ.get("BENCH_SMOKE")
                   or os.environ.get("BENCH_FORCE_CPU"))
    if cpu_run:
        # plumbing run, CPU-sized: must be decided before jax initializes
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from deepspeed_tpu.runtime.resilience import enable_compilation_cache
    enable_compilation_cache()
    dev = device_info()
    if dev["platform"] != "tpu" and not cpu_run:
        return _fail(f"no TPU: jax reports platform {dev['platform']!r} "
                     f"(BENCH_SMOKE=1 runs the CPU plumbing test)", dev)
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPTChunkedLoss, GPTConfig

    # chunked cross-entropy (ops/cross_entropy.py) keeps the fp32 logits out of
    # HBM, so batch 32 fits; flash attention (ops/flash_attention.py) keeps the
    # [T, T] scores out of HBM
    import jax.numpy as jnp
    smoke = bool(os.environ.get("BENCH_SMOKE"))   # plumbing test (CPU-sized)
    BATCH, SEQ = (2, 64) if smoke else (32, 1024)
    if smoke:
        cfg_model = GPTConfig(num_layers=2, num_heads=4, head_dim=16,
                              hidden_size=64, vocab_size=512, max_seq_len=SEQ,
                              dropout=0.0, loss_chunk=64)
    else:
        # bf16 COMPUTE dtype (not just bf16-cast params): fp32 activations
        # silently demote every matmul off the bf16 MXU path — worth ~12
        # points of MFU on this config.  Norms/softmax/CE/masters stay fp32.
        cfg_model = GPTConfig.gpt2_small(vocab_size=50304, max_seq_len=SEQ,
                                         dropout=0.0, loss_chunk=8192,
                                         dtype=jnp.bfloat16)
    model = GPTChunkedLoss(cfg_model)
    config = {
        "train_micro_batch_size_per_gpu": BATCH,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4,
                                                  "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        # overlap regime on for the sweep: latency-hiding scheduler +
        # async-collective XLA flags (chunking is a stage-3 knob — inert
        # here, live on the zero3 legs below)
        "overlap": {"enabled": True},
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        # telemetry rides the flagship leg: comms-byte + memory columns for
        # the BENCH row.  trace off (its per-step device sync would skew the
        # timing); snapshot_interval 0 (exported explicitly post-measurement)
        "telemetry": {"enabled": True, "trace_enabled": False,
                      "snapshot_interval": 0},
    }
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg_model.vocab_size,
                                       size=(BATCH, SEQ)).astype(np.int32)}
    example = {"input_ids": np.zeros((BATCH, SEQ), np.int32)}

    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config,
                                               example_batch=example)

    dt = _measure(engine, batch, iters=10, prefetch=True)
    m = engine.train_batch(batch)          # final metrics for the report

    # numerics-watch leg: the flagship engine runs health OFF (the health
    # monitor's one per-step scalar fetch would serialize the timed dispatch
    # chain, same reason trace is off) — so drive a short health-ENABLED leg
    # on a small engine afterwards.  Its AnomalyDetector/FlightRecorder
    # counters land in the shared default registry, so the snapshot exported
    # below (and the numerics_anomalies/postmortem_dumps columns) reflect a
    # leg where the tripwire can actually fire.
    try:
        h_cfg = GPTConfig(num_layers=2, num_heads=4, head_dim=16,
                          hidden_size=64, vocab_size=512, max_seq_len=64,
                          dropout=0.0, loss_chunk=64)
        h_config = {
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "mesh": {"dp": -1},
            "steps_per_print": 0,
            "telemetry": {**config["telemetry"],
                          "health": {"enabled": True,
                                     "recorder_steps": 16}},
        }
        h_batch = {"input_ids": rng.integers(
            0, h_cfg.vocab_size, size=(8, 64)).astype(np.int32)}
        h_engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPTChunkedLoss(h_cfg), config=h_config,
            example_batch={"input_ids": np.zeros((8, 64), np.int32)})
        for _ in range(8):
            hm = h_engine.train_batch(h_batch)
        jax.block_until_ready(hm.loss)
        del h_engine
    except Exception as e:  # noqa: BLE001 — the watch leg must not kill bench
        extra_health_err = str(e)[:120]
    else:
        extra_health_err = None

    tokens_per_sec = BATCH * SEQ / dt
    flops = train_flops_per_step(engine.num_parameters, cfg_model.num_layers,
                                 cfg_model.hidden_size, BATCH, SEQ)
    mfu = _mfu(flops, dt)               # None on the CPU plumbing run
    extra = {"step_time_s": round(dt, 4), "mfu": mfu,
             "params_m": round(engine.num_parameters / 1e6, 1),
             "loss": float(m.loss)}
    try:
        # telemetry snapshot next to the timing output: BENCH rows carry
        # comms-byte and peak-memory columns, and the full registry dump
        # lands in a sibling JSON for offline comparison
        snap_path = os.environ.get("BENCH_TELEMETRY_OUT",
                                   "telemetry_snapshot.json")
        snap = engine.telemetry.export(step=engine.global_steps,
                                       write=False)
        engine.telemetry.exporter.write_json(snap_path, snap)
        exe = snap.get("executables", {}).get("train_batch", {})
        extra["comms_bytes_per_step"] = int(
            exe.get("per_execution_collective_bytes", 0))
        peak = max((s["value"] for s in snap.get("gauges", {}).get(
            "device_memory_bytes", {}).get("samples", [])
            if s.get("labels", {}).get("kind") == "peak"), default=0)
        extra["peak_device_memory_bytes"] = int(peak)
        extra["jit_cache_misses"] = int(sum(
            s["value"] for s in snap.get("counters", {}).get(
                "jit_cache_misses_total", {}).get("samples", [])))
        # numerics watch columns, fed by the short health-enabled leg above
        # (shared default registry): anomaly detections and postmortem dumps
        # must be zero on a healthy bench run — a nonzero value here flags a
        # numerics regression even when throughput holds
        if extra_health_err is not None:
            extra["numerics_watch_error"] = extra_health_err
        extra["numerics_anomalies"] = int(sum(
            s["value"] for s in snap.get("counters", {}).get(
                "numerics_anomalies_total", {}).get("samples", [])))
        extra["postmortem_dumps"] = int(sum(
            s["value"] for s in snap.get("counters", {}).get(
                "postmortem_dumps_total", {}).get("samples", [])))
        # async-pipeline columns: the flagship timed loop runs through the
        # background prefetcher, so batches handed out / starvation events
        # say whether the input pipeline kept the device fed (starvation
        # must be 0 after warmup for the h2d bubble to be truly gone)
        extra["prefetch_batches"] = int(sum(
            s["value"] for s in snap.get("counters", {}).get(
                "prefetch_batches_total", {}).get("samples", [])))
        extra["prefetch_starvation"] = int(sum(
            s["value"] for s in snap.get("counters", {}).get(
                "prefetch_starvation_total", {}).get("samples", [])))
        overlap = [s["value"] for s in snap.get("gauges", {}).get(
            "host_step_overlap_ratio", {}).get("samples", [])]
        if overlap:  # only present on a ZeRO-Offload overlap_step leg
            extra["host_step_overlap_ratio"] = round(float(overlap[-1]), 4)
        # exposed-comms columns: the static exposed fraction from the
        # compiled-HLO walk (collective_exposed_ratio gauge), converted to
        # ms with the profiler-measured per-collective latency — the
        # collective time NOT hidden under compute on this leg
        ratio = [s["value"] for s in snap.get("gauges", {}).get(
            "collective_exposed_ratio", {}).get("samples", [])
            if s.get("labels", {}).get("fn") == "train_batch"]
        if ratio:
            extra["collective_exposed_ratio"] = round(float(ratio[-1]), 4)
        extra["telemetry_snapshot"] = snap_path
    except Exception as e:  # noqa: BLE001 — telemetry must not kill the bench
        extra["telemetry_error"] = str(e)[:120]
    try:
        comms = engine.profile_comms(batch, iters=2)
        comm_ms = sum(v["time_s"] for v in comms.values()) * 1000.0
        extra["comm_total_ms"] = round(comm_ms, 3)
        if "collective_exposed_ratio" in extra:
            extra["comm_exposed_ms"] = round(
                comm_ms * extra["collective_exposed_ratio"], 3)
    except Exception as e:  # noqa: BLE001 — profiling must not kill the bench
        extra["comm_exposed_error"] = str(e)[:120]
    try:
        # step-time budget (telemetry/profiler.py): the measured flagship
        # step decomposed into compute / exposed_comm / hbm_bound /
        # host_gap / dispatch_floor, with achieved MFU.
        # scripts/perf_report.py renders the same budget from the snapshot.
        from deepspeed_tpu.telemetry.profiler import step_time_budget
        budget = step_time_budget(
            snap, step_ms=dt * 1e3, fn="train_batch",
            comm_total_ms=extra.get("comm_total_ms"),
            registry=engine.telemetry.registry)
        extra["mfu_budget"] = {
            "compute_ms": round(budget["compute_ms"], 3),
            **{f"{cause}_ms": round(ms, 3)
               for cause, ms in budget["terms_ms"].items()},
            "mfu_achieved": round(budget["mfu_achieved"], 4),
            "mfu_lost": {c: round(v, 4)
                         for c, v in budget["mfu_lost"].items()},
        }
    except Exception as e:  # noqa: BLE001 — attribution must not kill bench
        extra["mfu_budget_error"] = str(e)[:120]
    del engine

    def emit():
        failed = sorted(k for k in extra if k.endswith("_error"))
        line = {
            "metric": METRIC,
            "value": round(tokens_per_sec, 1),
            "unit": "tokens/s/chip",
            "vs_baseline": None if mfu is None else round(mfu / 0.35, 4),
            **dev,
            "extra": extra,
        }
        if failed:
            line["error"] = "legs failed: " + ", ".join(failed)
        print(json.dumps(line), flush=True)
        return failed

    # emit the headline number IMMEDIATELY — a run cut by its time limit
    # during a secondary leg has then still printed it
    emit()
    # guardian chaos leg: CPU-sized on every run (smoke included) — it
    # measures the remediation machinery, not the model
    try:
        _guardian_point(deepspeed_tpu.initialize, out=extra, emit=emit)
    except Exception as e:  # noqa: BLE001 — a broken chaos leg must not
        extra["guardian_leg_error"] = str(e)[:120]   # cost the headline
        extra["guardian_healed"] = 0.0   # the sentinel must see the break
    if not smoke:
        _extra_points(GPTChunkedLoss, GPTConfig, deepspeed_tpu.initialize,
                      out=extra, emit=emit)
        extra["legs_complete"] = True
        # bench regression sentinel (telemetry/regression.py): diff this
        # round's numbers against the committed ledger — NON-fatally here
        # (the driver still gets its metric line); scripts/check_bench.py
        # is the enforcing gate.  The count rides the JSON line so a
        # recorded round carries its own trajectory verdict.
        try:
            from deepspeed_tpu.telemetry import regression as _reg
            ledger_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_BASELINE.json")
            if os.path.exists(ledger_path):
                res = _reg.compare(
                    _reg.flatten_bench_record(
                        {"metric": METRIC,
                         "value": round(tokens_per_sec, 1),
                         "extra": extra}),
                    _reg.load_baseline(ledger_path))
                extra["bench_regressions"] = len(res["regressions"])
                if res["failed"]:
                    print(_reg.render(res, "BENCH_BASELINE.json"),
                          file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            extra["bench_sentinel_error"] = str(e)[:120]
    failed = emit()            # the LAST metric line is the result
    if dev["platform"] == "tpu":
        # the regression sentinel reads these records as chip values
        _append_leg_records(METRIC, round(tokens_per_sec, 1), extra)
    return 1 if failed else 0


def _append_leg_records(metric, value, extra):
    """Append the per-leg JSONL records (the regression sentinel's native
    input) next to the stdout JSON line: one machine-readable record per
    metric with the device, the scheduler-regime echo and a timestamp.
    Chip runs only — the caller skips it off-TPU."""
    from deepspeed_tpu.runtime.overlap import effective_xla_flags
    from deepspeed_tpu.telemetry import regression as _reg
    env = {"bench": os.path.basename(
        os.path.abspath(sys.argv[0] or "bench.py")),
        # the compiler flags this process ran under (the resolved per-leg
        # overlap blocks live in each leg's telemetry snapshot)
        "xla_flags": effective_xla_flags(), **device_info()}
    path = os.environ.get("BENCH_JSONL", "bench_records.jsonl")
    # append_bench_records keeps numeric non-bool entries and skips the
    # rest (strings, nested dicts, flags)
    _reg.append_bench_records(path, {metric: value, **extra}, env=env)


if __name__ == "__main__":
    sys.exit(run_bench())
