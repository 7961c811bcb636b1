"""Test harness.

Reference analog: tests/unit/common.py DistributedTest — the reference forks N
torch.multiprocessing workers to simulate a cluster.  On JAX we instead run a
*virtual 8-device CPU mesh* in-process (SPMD is compiled, not process-orchestrated),
set up here before jax import.  Multi-process behavior is covered by the driver's
``dryrun_multichip`` entry point.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# Nearly all of the suite's CPU is XLA:CPU compiling programs of two layers
# and width 32, and no test reads their speed: the backend skips its
# optimisation passes (a third off the compile, a fifth off a file; the HLO
# and the floating-point rules stay as they are).  Children the tests start
# inherit both from the environment, as they do the device count.
for _f in ("--xla_backend_optimization_level=0",
           "--xla_llvm_disable_expensive_passes=true"):
    if _f.split("=")[0] not in _flags:
        _flags += " " + _f
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


# ---- quick tier (VERDICT r2 weak #10): `pytest -m quick` runs the core-
# correctness slice (~9 min of test seconds as PR 47's six-worker run counted
# them: engine 129 s + the three ops files 427 s, config/mesh under a second;
# 1,233 s before that PR) for the fast inner loop; the full suite stays the
# merge gate.
QUICK_MODULES = {
    "test_config.py", "test_mesh_partition.py", "test_engine.py",
    "test_ops.py", "test_ops_paged.py", "test_ops_ragged.py",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: fast core-correctness tier (pytest -m quick)")
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1 (-m 'not slow'); true "
        "multi-host / long-wall-clock legs")


def pytest_collection_modifyitems(config, items):
    for it in items:
        mod = it.nodeid.split("::")[0].rsplit("/", 1)[-1]
        if mod in QUICK_MODULES:
            it.add_marker(pytest.mark.quick)


# ``InferenceEngineV2`` jits its step programs per instance, so two engines of
# one configuration share nothing in jax's own cache, and the tests build
# engines by the hundred.  Handed one ``steps_cache``, engines of one
# configuration compile once; the engine keeps configurations apart by a
# fingerprint of all its programs close over (model, block size, dtype, draft,
# mesh, quantization, adapters).  An engine test takes this helper UNLESS it
# patches what a trace reads (its patched program would stay for the next
# test, or it would take an unpatched one) or counts compiles, traces or
# cache misses (sharing changes exactly that): those build a private
# ``InferenceEngineV2`` and say which of the two in a comment.
_STEPS = {}


def v2_engine(model, config=None, **kw):
    """An ``InferenceEngineV2`` on the module's shared step programs: fresh
    pool, fresh request state, nothing compiled twice."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    return InferenceEngineV2(model, config, steps_cache=_STEPS, **kw)


@pytest.fixture(scope="module", autouse=True)
def _steps_of_one_module():
    """No file leans on a program another left behind (and a worker does not
    hold every module's executables to the end of the run)."""
    yield
    _STEPS.clear()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


# XLA:CPU has two schedulers.  The installed jaxlib's default issues every
# ready collective at once (a ZeRO-3 chunk train's gathers back to back); the
# other places each op where its consumer needs it, which is the order that
# shows what the program's data dependences allow between two chunks.  Tests
# that read compute between collectives compile with these options.
CONSUMER_ORDER = {"xla_cpu_enable_concurrency_optimized_scheduler": False}


def make_lm_batch(rng, batch, seq, vocab):
    """Synthetic memorization task batch."""
    ids = rng.integers(0, vocab, size=(batch, seq), dtype=np.int64).astype(np.int32)
    return {"input_ids": ids}


def lower_serving_steps(cfg, cache_dtype, *, slots, tokens, max_q, table_width,
                        block_size, num_pages, steps, quant=None,
                        sharding=None, mesh=None):
    """The three serving step programs (mixed, single decode, fused burst)
    lowered from shapes alone, as the engine jits them (cache donated,
    greedy in-graph sampling): (params, cache, {name: Lowered}).
    ``sharding`` places every argument, for a device that is described and
    not attached; ``mesh`` (with a ``tp`` axis) shards parameters and pool
    as the engine does over it, and replicates the rest."""
    import functools

    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine import _sample_token
    from deepspeed_tpu.inference.v2 import model as v2model
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.parallel.metadata import unbox
    S, N, MB = slots, tokens, table_width
    i32, b1 = jnp.int32, jnp.bool_

    def sd(shape, dtype, at=None):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=at or sharding)
    boxed = jax.eval_shape(
        lambda k: GPTLogits(cfg).init(k, jnp.zeros((1, 8), i32)),
        jax.random.PRNGKey(0))["params"]
    params = unbox(boxed)
    cache = jax.eval_shape(lambda: v2model.PagedKVCache.create(
        cfg, num_pages, block_size, cache_dtype, quant=quant, slots=slots))
    if mesh is None:
        placed = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype),
                                        (params, cache))
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu.parallel import partition
        from deepspeed_tpu.parallel.metadata import annotate_abstract
        sharding = NamedSharding(mesh, P())
        placed = (
            jax.tree_util.tree_map(
                lambda a, s: sd(a.shape, a.dtype, s), params,
                partition.param_shardings(annotate_abstract(boxed), mesh,
                                          zero_stage=0)),
            jax.tree_util.tree_map(
                lambda a: sd(a.shape, a.dtype, NamedSharding(mesh, P(
                    None, None, "tp", *(None,) * (a.ndim - 3)))), cache))
    slot = {"active": sd((S,), b1), "block_table": sd((S, MB), i32),
            "from_device": sd((S,), b1)}
    programs = {
        "ragged_forward_sampled": (
            dict(max_q_per_seq=max_q),
            {"tokens": sd((N,), i32), "token_slot": sd((N,), i32),
             "token_pos": sd((N,), i32),
             "block_table": sd((S, MB), i32), "kv_len": sd((S,), i32),
             "from_device": sd((N,), b1), "served": sd((S,), b1)}),
        "ragged_decode_sampled": (
            {}, {**slot, "tokens": sd((S,), i32), "token_pos": sd((S,), i32),
                 "served": sd((S,), b1)}),
        "ragged_decode_burst": (
            dict(steps=steps), {**slot, "tokens0": sd((S,), i32),
                                "pos0": sd((S,), i32)}),
    }
    sample = functools.partial(_sample_token, do_sample=False, top_k=0)
    lowered = {}
    for name, (static, batch) in programs.items():
        fn = functools.partial(getattr(v2model, name), cfg=cfg,
                               block_size=block_size, sample_fn=sample,
                               mesh=mesh, **static)
        lowered[name] = jax.jit(fn, donate_argnums=(1,)).lower(
            *placed, batch, sd((S,), i32), sd((2,), jnp.uint32),
            sd((), jnp.float32), sd((), jnp.float32))
    return placed[0], placed[1], lowered
