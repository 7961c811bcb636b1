"""End-to-end engine tests across ZeRO stages — the analog of the reference's
tests/unit/runtime/zero/test_zero.py matrix (stages × precision × accumulation),
run on the virtual 8-device CPU mesh instead of forked processes."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models import GPT, GPTConfig

VOCAB, SEQ = 64, 16


def _data(n_batches, global_bs, seed=0):
    rng = np.random.default_rng(seed)
    # fixed pool of sequences → memorization task, loss must fall
    pool = rng.integers(0, VOCAB, size=(8, SEQ)).astype(np.int32)
    for _ in range(n_batches):
        idx = rng.integers(0, len(pool), size=(global_bs,))
        yield {"input_ids": pool[idx]}


def _build(zero_stage, precision="bf16", gas=1, mesh_kw=None, seed=0,
           gradient_clipping=0.0, scheduler=None, micro_batch=2):
    cfg = {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": zero_stage},
        "mesh": mesh_kw or {"dp": -1},
        "steps_per_print": 0,
        "seed": seed,
    }
    if gradient_clipping:
        cfg["gradient_clipping"] = gradient_clipping
    if scheduler:
        cfg["scheduler"] = scheduler
    if precision == "bf16":
        cfg["bf16"] = {"enabled": True}
    elif precision == "fp16":
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 8}
    model = GPT(GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ))
    example = {"input_ids": np.zeros((micro_batch, SEQ), np.int32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, example_batch=example)
    return engine


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_train_loss_decreases(stage, devices):
    engine = _build(stage)
    gbs = engine.train_batch_size
    losses = [float(engine.train_batch(b).loss)
              for b in _data(30, gbs)]
    assert losses[-1] < losses[0] * 0.7, f"stage {stage}: {losses[0]}->{losses[-1]}"


def test_zero3_params_sharded(devices):
    engine = _build(3, mesh_kw={"dp": 1, "fsdp": 8})
    specs = jax.tree_util.tree_map(lambda s: s.spec, engine.param_shardings)
    flat = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert any("fsdp" in str(s) for s in flat), "no param sharded over fsdp"
    # state matches placement
    p = jax.tree_util.tree_leaves(engine.state.params)[0]
    assert p.sharding.mesh.shape["fsdp"] == 8


def test_zero1_opt_state_sharded_params_replicated(devices):
    engine = _build(1, mesh_kw={"dp": 1, "fsdp": 8})
    pspecs = [s.spec for s in jax.tree_util.tree_leaves(
        engine.param_shardings, is_leaf=lambda x: hasattr(x, "spec"))]
    assert all(all(e is None for e in s) or len(s) == 0 for s in pspecs)
    ospecs = [str(s.spec) for s in jax.tree_util.tree_leaves(
        engine.opt_shardings, is_leaf=lambda x: hasattr(x, "spec"))]
    assert any("fsdp" in s for s in ospecs), "opt state not sharded at stage 1"


def test_gradient_accumulation_matches_large_batch(devices):
    """gas=2 × micro 2 must be numerically equivalent to gas=1 × micro 4 in fp32
    (same data, same seed): loss is a per-micro mean averaged over gas."""
    e1 = _build(0, precision="fp32", gas=2, seed=7,
                mesh_kw={"dp": 1, "fsdp": 1})
    e2 = _build(0, precision="fp32", gas=1, seed=7,
                mesh_kw={"dp": 1, "fsdp": 1},
                micro_batch=2 * e1.train_micro_batch_size_per_gpu)
    assert e1.train_batch_size == e2.train_batch_size
    batches = list(_data(6, e1.train_batch_size, seed=3))
    l1 = [float(e1.train_batch(b).loss) for b in batches]
    l2 = [float(e2.train_batch(b).loss) for b in batches]
    np.testing.assert_allclose(l1, l2, rtol=2e-4)


def test_fp16_loss_scaling_runs(devices):
    engine = _build(2, precision="fp16")
    for b in _data(5, engine.train_batch_size):
        m = engine.train_batch(b)
    assert float(m.loss_scale) > 0
    assert np.isfinite(float(m.loss))


def test_forward_backward_step_trio(devices):
    engine = _build(1, gas=2)
    micro_global = engine.train_micro_batch_size_per_gpu * engine.dp_world_size
    losses = []
    for b in _data(8, micro_global):
        loss = engine.forward(b)
        engine.backward(loss)
        m = engine.step()
        losses.append(float(loss))
    assert engine.global_steps == 4  # 8 micro / gas 2
    assert losses[-1] < losses[0]


def test_gradient_clipping_and_scheduler(devices):
    engine = _build(2, gradient_clipping=1.0,
                    scheduler={"type": "WarmupLR",
                               "params": {"warmup_max_lr": 1e-2,
                                          "warmup_num_steps": 5}})
    for b in _data(6, engine.train_batch_size):
        m = engine.train_batch(b)
    assert np.isfinite(float(m.loss))
    assert engine.get_lr()[0] > 0


def test_checkpoint_roundtrip(tmp_path, devices):
    engine = _build(2)
    batches = list(_data(6, engine.train_batch_size))
    for b in batches[:3]:
        engine.train_batch(b)
    tag = engine.save_checkpoint(str(tmp_path))
    step_before = int(engine.state.step)
    p_before = np.asarray(
        jax.tree_util.tree_leaves(engine.state.params)[0]).copy()

    # continue training, then restore — params must rewind
    engine.train_batch(batches[3])
    engine.load_checkpoint(str(tmp_path), tag)
    assert int(engine.state.step) == step_before
    p_after = np.asarray(jax.tree_util.tree_leaves(engine.state.params)[0])
    np.testing.assert_array_equal(p_before, p_after)


def test_checkpoint_reshard_on_load(tmp_path, devices):
    """Universal-checkpoint capability (reference checkpoint/ds_to_universal.py):
    save at stage 2 (dp=8), restore into stage 3 (fsdp=8) sharding."""
    e1 = _build(2, seed=11)
    for b in _data(2, e1.train_batch_size, seed=5):
        e1.train_batch(b)
    tag = e1.save_checkpoint(str(tmp_path))
    w1 = np.asarray(jax.tree_util.tree_leaves(e1.state.params)[0])

    e2 = _build(3, mesh_kw={"dp": 1, "fsdp": 8}, seed=12)
    e2.load_checkpoint(str(tmp_path), tag)
    w2 = np.asarray(jax.tree_util.tree_leaves(e2.state.params)[0])
    np.testing.assert_allclose(w1, w2, rtol=1e-6)


def test_checkpoint_reshard_into_pipeline(tmp_path, devices):
    """Resharding restore across PHYSICAL layouts (checkpoint/reshard.py,
    per arXiv:2004.13336 a sharding-spec transform): a stage-2 dp=8
    checkpoint restores into a pipeline-stacked pp=2 engine, and the pipe
    tag restores back into a stage-3 fsdp=8 engine — fp32 masters exact in
    both directions (live bf16 params may sit one ulp off the master)."""
    from deepspeed_tpu.checkpoint.universal import (_flatten_params,
                                                    _master_states)
    from deepspeed_tpu.pipe import PipeGPT

    def masters(engine):
        return _flatten_params(_master_states(
            jax.device_get(engine.state.opt_state))[0]["master"])

    mcfg = GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)
    e1 = _build(2, seed=21)
    for b in _data(2, e1.train_batch_size, seed=5):
        e1.train_batch(b)
    tag = e1.save_checkpoint(str(tmp_path / "flat"))
    m1 = masters(e1)

    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "mesh": {"pp": 2, "dp": 4},
        "steps_per_print": 0,
        "seed": 22,
    }
    e2, _, _, _ = deepspeed_tpu.initialize(
        model=PipeGPT(mcfg, num_stages=2), config=cfg,
        example_batch={"input_ids": np.zeros((2, 2, SEQ), np.int32)})
    loaded, cs = e2.load_checkpoint(str(tmp_path / "flat"), tag)
    assert loaded == tag and e2.global_steps == 2
    assert cs["layout"] == {"kind": "flat"}
    m2 = masters(e2)
    # per-layer logical params land in the [S, L/S, ...] stacked leaves
    sub = "Attention_0.wk"
    stacked = np.asarray(m2[f"params.blocks.{sub}"], np.float32)
    for i in range(mcfg.num_layers):
        np.testing.assert_allclose(
            np.asarray(m1[f"params.backbone.block_{i}.{sub}"], np.float32),
            stacked[divmod(i, stacked.shape[1])], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m1["params.backbone.wte"],
                                          np.float32),
                               np.asarray(m2["params.embed"], np.float32),
                               rtol=1e-6)
    # the UNIVERSAL-fragment path does the same layout conversion: e1's export loads
    # into the pipe engine via load_universal_checkpoint
    udir = str(tmp_path / "u")
    e1.export_universal_checkpoint(udir)
    meta = e2.load_universal_checkpoint(udir)
    assert meta["step"] == 2 and meta["layout"] == {"kind": "flat"}
    np.testing.assert_array_equal(
        np.asarray(m1["params.backbone.wte"], np.float32),
        np.asarray(masters(e2)["params.embed"], np.float32))

    # the restored pipeline engine trains on
    loss = float(e2.train_batch(next(_data(
        1, e2.train_batch_size, seed=6))).loss)
    assert np.isfinite(loss)

    # reverse: the pipe tag restores into a stage-3 fsdp=8 engine
    tag2 = e2.save_checkpoint(str(tmp_path / "pipe"))
    m2 = masters(e2)
    e3 = _build(3, mesh_kw={"dp": 1, "fsdp": 8}, seed=23)
    loaded2, cs2 = e3.load_checkpoint(str(tmp_path / "pipe"), tag2)
    assert loaded2 == tag2 and cs2["layout"]["kind"] == "pipe"
    m3 = masters(e3)
    stacked = np.asarray(m2[f"params.blocks.{sub}"], np.float32)
    for i in range(mcfg.num_layers):
        np.testing.assert_allclose(
            stacked[divmod(i, stacked.shape[1])],
            np.asarray(m3[f"params.backbone.block_{i}.{sub}"], np.float32),
            rtol=1e-6)
    assert np.isfinite(float(e3.train_batch(next(_data(
        1, e3.train_batch_size, seed=7))).loss))


class TestMiCS:
    """MiCS subgroup sharding (reference runtime/zero/mics.py): params shard
    within mics_shard_size groups, replicate across them."""

    def test_mesh_and_shardings(self, devices, rng):
        from deepspeed_tpu.models import GPT, GPTConfig
        cfg = GPTConfig.tiny(vocab_size=128, max_seq_len=32)
        pool = rng.integers(0, 128, size=(8, 32)).astype(np.int32)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT(cfg), config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3, "mics_shard_size": 4},
                "steps_per_print": 0,
            }, example_batch={"input_ids": pool})
        assert engine.mesh.shape["fsdp"] == 4       # shard group
        assert engine.mesh.shape["dp"] == 2         # replica groups
        # params shard over fsdp only (not dp): every fsdp-sharded leaf's
        # spec mentions "fsdp" and never "dp"
        specs = [s.spec for s in
                 jax.tree_util.tree_leaves(engine.param_shardings)]
        assert any("fsdp" in str(s) for s in specs)
        assert not any("'dp'" in str(s) for s in specs)
        m = engine.train_batch({"input_ids": pool})
        assert np.isfinite(float(m.loss))

    def test_requires_stage3(self, rng):
        from deepspeed_tpu.models import GPT, GPTConfig
        cfg = GPTConfig.tiny(vocab_size=64, max_seq_len=16)
        with pytest.raises(ValueError, match="stage 3"):
            deepspeed_tpu.initialize(
                model=GPT(cfg), config={
                    "train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 2, "mics_shard_size": 4},
                }, example_batch={"input_ids": rng.integers(
                    0, 64, size=(8, 16)).astype(np.int32)})


class TestAsyncCheckpoint:
    def test_async_save_then_load(self, devices, rng, tmp_path):
        """async_save returns immediately; wait_pending commits; 'latest'
        only appears once the checkpoint is complete."""
        import deepspeed_tpu.checkpoint as ckpt
        from deepspeed_tpu.models import GPT, GPTConfig
        cfg = GPTConfig.tiny(vocab_size=64, max_seq_len=16)
        pool = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT(cfg), config={
                "train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "mesh": {"dp": 1}, "steps_per_print": 0,
            }, example_batch={"input_ids": pool})
        engine.train_batch({"input_ids": pool})
        tag = engine.save_checkpoint(str(tmp_path), async_save=True)
        # training continues while the write streams
        engine.train_batch({"input_ids": pool})
        ckpt.wait_pending()
        assert ckpt.latest_tag(str(tmp_path)) == tag
        loaded_tag, cs = engine.load_checkpoint(str(tmp_path))
        assert loaded_tag == tag
        assert cs["global_steps"] == 1


class TestHpZ:
    """ZeRO++ hpZ (reference zero_hpz_partition_size): params shard within
    the fsdp subgroup only, optimizer state/grads over the full world."""

    def test_shardings_and_training(self, devices, rng):
        from deepspeed_tpu.models import GPT, GPTConfig
        cfg = GPTConfig.tiny(vocab_size=128, max_seq_len=32)
        pool = rng.integers(0, 128, size=(8, 32)).astype(np.int32)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT(cfg), config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3,
                                      "zero_hpz_partition_size": 4},
                "mesh": {"fsdp": 4, "dp": -1},
                "steps_per_print": 0,
            }, example_batch={"input_ids": pool})
        pspecs = [str(s.spec) for s in
                  jax.tree_util.tree_leaves(engine.param_shardings)]
        ospecs = [str(s.spec) for s in
                  jax.tree_util.tree_leaves(engine.opt_shardings)]
        # params: subgroup (fsdp) only — never dp
        assert any("fsdp" in s for s in pspecs)
        assert not any("'dp'" in s for s in ospecs[0:0] + pspecs)
        # optimizer state: full world — fsdp AND dp together on some leaf
        assert any("fsdp" in s and "'dp'" in s for s in ospecs), ospecs[:5]
        m = engine.train_batch({"input_ids": pool})
        assert np.isfinite(float(m.loss))

    def test_requires_matching_mesh(self, rng):
        from deepspeed_tpu.models import GPT, GPTConfig
        cfg = GPTConfig.tiny(vocab_size=64, max_seq_len=16)
        with pytest.raises(ValueError, match="fsdp mesh"):
            deepspeed_tpu.initialize(
                model=GPT(cfg), config={
                    "train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3,
                                          "zero_hpz_partition_size": 2},
                    "mesh": {"fsdp": 4, "dp": -1},
                }, example_batch={"input_ids": rng.integers(
                    0, 64, (8, 16)).astype(np.int32)})


class TestEvalBatch:
    """engine.eval_batch (reference PipelineEngine.eval_batch
    pipe/engine.py:415 + module.eval() forward semantics)."""

    def test_eval_deterministic_and_stateless(self, devices):
        engine = _build(2)
        batch = next(_data(1, engine.train_batch_size))
        step_before = int(np.asarray(jax.device_get(engine.state.step)))
        a = float(engine.eval_batch(batch))
        b = float(engine.eval_batch(batch))
        assert a == b, "eval must be deterministic"
        assert int(np.asarray(jax.device_get(engine.state.step))) == \
            step_before, "eval must not step the optimizer"
        assert engine.global_steps == 0

    def test_eval_tracks_training(self, devices):
        engine = _build(1)
        batch = next(_data(1, engine.train_batch_size, seed=3))
        before = float(engine.eval_batch(batch))
        for b in _data(20, engine.train_batch_size, seed=3):
            engine.train_batch(b)
        after = float(engine.eval_batch(batch))
        assert after < before * 0.8, (before, after)

    def test_eval_ignores_dropout(self, devices):
        """eval loss == a hand-computed deterministic forward (dropout truly
        off, not merely same-rng-twice)."""
        import dataclasses
        mcfg = dataclasses.replace(
            GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ), dropout=0.3)
        model = GPT(mcfg)
        cfg = {
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 0},
            "mesh": {"dp": 8},                      # fp32: params not cast
            "steps_per_print": 0,
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=cfg,
            example_batch={"input_ids": np.zeros((2, SEQ), np.int32)})
        batch = next(_data(1, engine.train_batch_size))
        got = float(engine.eval_batch(batch))
        want = float(model.apply(
            jax.device_get(engine.state.params), batch, deterministic=True,
            rngs={"dropout": jax.random.PRNGKey(99)}))
        assert got == pytest.approx(want, rel=1e-6)
        # and the stochastic train-mode loss differs (dropout is real)
        noisy = float(model.apply(
            jax.device_get(engine.state.params), batch,
            rngs={"dropout": jax.random.PRNGKey(99)}))
        assert abs(noisy - want) > 1e-6

    def test_eval_batch_pipeline_model(self, devices):
        from deepspeed_tpu.pipe import PipeGPT
        cfg = GPTConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=PipeGPT(cfg, num_stages=2), config={
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "bf16": {"enabled": True},
                "mesh": {"pp": 2, "dp": 4},
                "steps_per_print": 0,
            }, example_batch={"input_ids": np.zeros((2, 2, SEQ), np.int32)})
        loss = float(engine.eval_batch(
            {"input_ids": np.zeros((4, SEQ), np.int32)}))
        assert np.isfinite(loss)
