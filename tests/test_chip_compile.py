"""Compile the main path's kernels for a described TPU v5e — no chip needed.

The TPU compiler is installed beside jax and compiles for a chip that is
described, not attached.  That shows what interpret mode cannot: a slice not
aligned to the tiling, too much VMEM, a kernel that cannot be partitioned.
Nothing runs, so a pass here is NOT a chip run (chip_smoke.py is).  Every case
is ``interpret=False`` at the real widths, about two seconds each; the
persistent compile cache is off around them (such a compile can be written to
it but not read back without a chip).
"""

import dataclasses
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference.v2.model import (kv_block_size_for,
                                              kv_major_layout)
from deepspeed_tpu.models import GPTConfig
from deepspeed_tpu.ops.paged_attention import (_dma_layout_ok,
                                               pallas_paged_attention,
                                               pallas_ragged_prefill,
                                               supported as paged_supported)

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
KERNEL = "tpu_custom_call"
BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32


# ------------------------------------------------------------ overlap flags

def test_overlap_flags_are_accepted_by_the_installed_libtpu():
    """libtpu EXITS the process on an argument it does not know, so the
    check runs in a child: every flag the overlap block can compose, handed
    over the way ``apply_overlap_flags`` hands them, must let a sharded
    program compile for the described chip.  First in the file: libtpu
    admits one process at a time (a lock file), and the ``topo`` fixture
    below loads it into this one."""
    from deepspeed_tpu.config import OverlapConfig
    from deepspeed_tpu.runtime.overlap import LIBTPU_ENV, compose_xla_flags
    flags = compose_xla_flags(OverlapConfig(enabled=True))
    assert len(flags) == 6
    code = (
        "import jax, jax.numpy as jnp\n"
        "from jax.experimental import topologies\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "t = topologies.get_topology_desc(platform='tpu',"
        " topology_name='v5e:2x2')\n"
        "m = Mesh(t.devices, ('x',))\n"
        "a = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16,"
        " sharding=NamedSharding(m, P(None, 'x')))\n"
        "b = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16,"
        " sharding=NamedSharding(m, P('x', None)))\n"
        "jax.jit(lambda a, b: a @ b).lower(a, b).compile()\n"
        "print('COMPILED')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               **{LIBTPU_ENV: " ".join(flags)})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=60)
    refused = "Unknown command line flag" in r.stderr
    if r.returncode and not refused and "get_topology_desc" in r.stderr:
        pytest.skip("the child cannot describe a v5e:2x2 topology here "
                    "(no libtpu, or another process holds its lock file)")
    assert not refused, r.stderr[-400:]
    assert r.returncode == 0 and "COMPILED" in r.stdout, r.stderr[-400:]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / cannot describe
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def chip_text(topo, fn, *specs):
    """Compiled text of ``fn`` for one described chip; ``specs`` are
    ShapeDtypeStructs or pytrees of them."""
    one = SingleDeviceSharding(topo.devices[0])
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), specs)
    return jax.jit(fn).lower(*args).compile().as_text()


# ------------------------------------------------------------------ flash

@pytest.mark.parametrize("b,t", [(32, 1024), (4, 4096)])
def test_flash_fwd_bwd_flagship_shapes(topo, b, t):
    from deepspeed_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(F32).sum()

    shape = sds((b, t, 12, 64), BF16)
    text = chip_text(topo, jax.grad(loss, argnums=(0, 1, 2)),
                     shape, shape, shape)
    assert text.count(KERNEL) >= 2          # fwd, and the one-pass bwd


def test_flash_needs_shard_map_over_a_mesh(topo):
    """A Mosaic kernel cannot be partitioned automatically: under a jit over
    four chips the bare kernel is refused at lowering, and the registry's
    Pallas entry runs it per shard when it is handed the mesh."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.constants import MESH_AXES
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4, 1, 1, 1), MESH_AXES)
    sh = NamedSharding(mesh, P(("dp", "fsdp"), None, None, None))
    x = jax.ShapeDtypeStruct((16, 1024, 12, 64), BF16, sharding=sh)

    def attn(mesh_arg):
        return jax.jit(lambda q, k, v: ops._attention_pallas(
            q, k, v, mesh=mesh_arg, interpret=False)).lower(x, x, x)

    with pytest.raises(NotImplementedError, match="partition"):
        attn(None)
    assert KERNEL in attn(mesh).compile().as_text()


# ------------------------------------------------------------------ paged

def _pool(nkv, hd, bs, kv_major, quant, nb=64):
    dt = I8 if quant else BF16
    page = sds((nb, nkv, hd, bs) if kv_major else (nb, nkv, bs, hd), dt)
    return page, sds((nb, nkv, bs), F32)


def decode_text(topo, nkv, g, hd, bs, kv_major, quant=False, S=8, MB=8,
                window=None, v_dim=None):
    """``v_dim``: latent pages, no value pool (``v`` is None)."""
    page, scale = _pool(nkv, hd, bs, kv_major, quant)
    specs = [sds((S, nkv, g, hd), BF16), page, None if v_dim else page,
             sds((S, MB), I32), sds((S,), I32)]
    if quant:
        specs += [scale, scale]

    def fn(q, k, v, bt, lens, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return pallas_paged_attention(q, k, v, bt, lens, interpret=False,
                                         kv_major=kv_major, window=window,
                                         **kw, **_latent(v_dim))
    return chip_text(topo, fn, *specs)


def _latent(v_dim):
    return {"v_dim": v_dim, "scale": 192 ** -0.5} if v_dim else {}


def prefill_text(topo, nkv, g, hd, bs, kv_major, quant=False, S=8, MB=8,
                 Q=128, window=None, v_dim=None, masked=False):
    """The token-major kernel over a flat batch of ``S * Q`` rows, at most
    ``Q`` a slot.  ``masked``: its masked form (``sel_mask``, the positions
    each row keeps, 32 rows a word)."""
    page, scale = _pool(nkv, hd, bs, kv_major, quant)
    specs = [sds((S * Q, nkv, g, hd), BF16), page, None if v_dim else page,
             sds((S, MB), I32), sds((S,), I32), sds((S,), I32),
             sds((S,), I32), sds((S,), I32),
             sds((S * Q // 32, MB * bs), I32) if masked else None]
    if quant:
        specs += [scale, scale]

    def fn(q, k, v, bt, lens, st, ct, rs, keep, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return pallas_ragged_prefill(q, k, v, bt, lens, st, ct, rs, max_q=Q,
                                        interpret=False, kv_major=kv_major,
                                        window=window, sel_mask=keep, **kw,
                                        **_latent(v_dim))
    return chip_text(topo, fn, *specs)


GPT2S = GPTConfig.gpt2_small()
LLAMA128 = GPTConfig.llama(num_layers=1, hidden=4096, heads=32,
                           num_kv_heads=8)          # hd 128, nkv 8, g 4
# Trinity-Large-Preview's attention (the serving cell's decode geometry):
# nkv 8, g 6, hd 128, pages of 128, a window of 4,096 on its window layers
TRINITY = GPTConfig.llama(num_layers=1, hidden=6144, heads=48,
                          num_kv_heads=8, sliding_window=4096)


# Moonlight-16B-A3B's latent attention, absorbed (the serving cell's
# geometry): 16 query heads in one group over one latent row of 512 + 64
# padded to 640, whose leading 512 columns are the value; pages of 128
MOONLIGHT = GPTConfig(num_layers=2, hidden_size=2048, num_heads=16,
                      head_dim=192, kv_lora_rank=512, qk_rope_head_dim=64,
                      v_head_dim=128, use_rope=True, rope_theta=50000.0,
                      use_rmsnorm=True, norm_eps=1e-5, gated_mlp=True,
                      tie_embeddings=False, mlp_dim_override=11264,
                      vocab_size=163840, max_seq_len=4096, num_experts=64,
                      moe_k=6, moe_dropless=True, moe_router="sigmoid",
                      moe_route_scale=2.446, moe_router_bias=True,
                      moe_shared_dim=2816, moe_expert_dim=1408,
                      moe_dense_layers=1)


def _engine_geometry(cfg, quant, block=64):
    """The page layout and size the v2 engine commits to for ``cfg`` when
    the user asks for ``kv_block_size`` ``block`` (64 is the default), and
    the window its layer 0 attends under."""
    from deepspeed_tpu.inference.v2.model import _attn_geometry
    nkv, hd, _, latent = _attn_geometry(cfg)
    return dict(nkv=nkv, g=cfg.num_heads // nkv,
                hd=hd, kv_major=kv_major_layout(cfg),
                bs=kv_block_size_for(cfg, block, quant=quant), quant=quant,
                window=cfg.window_for_layer(0), **latent)


@pytest.mark.parametrize("kernel", [decode_text, prefill_text],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("cfg,quant,block", [
    (LLAMA128, False, 64), (GPT2S, False, 64), (LLAMA128, True, 64),
    (GPT2S, True, 64), (TRINITY, False, 128), (MOONLIGHT, False, 128)],
    ids=["hd128-bf16", "gpt2s-bf16", "hd128-int8kv", "gpt2s-int8kv",
         "trinity-window", "moonlight-latent"])
def test_paged_kernels_in_engine_geometry(topo, kernel, cfg, quant, block):
    geo = _engine_geometry(cfg, quant, block)
    assert _dma_layout_ok(geo["hd"], geo["bs"], geo["kv_major"], quant)
    if cfg is TRINITY:
        assert (geo["nkv"], geo["g"], geo["hd"], geo["bs"], geo["window"]) \
            == (8, 6, 128, 128, 4096)
    if cfg is MOONLIGHT:
        assert (geo["nkv"], geo["g"], geo["hd"], geo["bs"], geo["v_dim"],
                geo["kv_major"]) == (1, 16, 640, 128, 512, False)
        # 576, the width the mathematics needs, is what the rule refuses
        assert not _dma_layout_ok(cfg.latent_dim, 128, False)
        if kernel is prefill_text:  # the cell's chunk: 32 rows a grid step
            geo.update(Q=1024)
    assert KERNEL in kernel(topo, **geo)


def test_gpt2s_geometry_is_kv_major_128():
    """hd=64 compiles only kv-major at block 128 — what the engine must
    pre-commit to from the default block size."""
    geo = _engine_geometry(GPT2S, quant=False)
    assert (geo["kv_major"], geo["bs"]) == (True, 128)
    assert _engine_geometry(LLAMA128, quant=False)["bs"] == 64


LAYOUTS = [(hd, bs, km, q) for hd in (64, 128) for bs in (64, 128)
           for km in (False, True) for q in (False, True)]


@pytest.mark.parametrize("hd,bs,kv_major,quant", LAYOUTS)
def test_dma_layout_rule_agrees_with_the_compiler(topo, hd, bs, kv_major,
                                                  quant):
    """Every page shape ``_dma_layout_ok``/``supported()`` accepts compiles;
    what it refuses is reported unsupported, so the registry never hands it
    to the kernel."""
    ok = _dma_layout_ok(hd, bs, kv_major, quant)
    page, scale = _pool(8, hd, bs, kv_major, quant)
    kw = dict(k_scale=scale, v_scale=scale) if quant else {}
    assert paged_supported(sds((8, 8, 4, hd), BF16), page, page,
                        sds((8, 8), I32), sds((8,), I32),
                        kv_major=kv_major, **kw) == ok
    if ok:
        assert KERNEL in decode_text(topo, 8, 4, hd, bs, kv_major, quant)


@pytest.mark.parametrize("hd,bs,kv_major,quant", [
    (64, 64, False, False),        # hd=64 in the default page layout
    (128, 64, False, True),        # int8 pages with block 64
], ids=["hd64-standard", "int8-block64"])
def test_refused_layouts_are_really_refused(topo, hd, bs, kv_major, quant):
    """The two shapes first contact found: the rule is not overcautious."""
    assert not _dma_layout_ok(hd, bs, kv_major, quant)
    with pytest.raises(Exception, match="aligned|tiling|[Nn]ot implemented"):
        decode_text(topo, 8, 4, hd, bs, kv_major, quant)


# ------------------------------------------------- the pool stays in place

# geometry -> (model, kv_quant, tp, most scratch of the burst): 2 layers at
# Mistral-7B's widths and GPT-2-small's kv-major geometry, 256 pages of 128
# a chip.
# The burst's scratch does not depend on the pool (measured the same at 256
# and at 512 pages: 52.8 MB bf16, 71.0-71.3 MB int8, 3.5 MB GPT-2-small,
# 5.3 MB a chip at tp=2: layout copies of attention weights and, for int8,
# of the step's codes), so the limit is a number of bytes: one layer's k
# pages more (67 MB bf16, 34 MB int8, 50 MB GPT-2-small) would pass it.
# For bf16 that is under half the pool's 268 MB; half of the int8 pool is
# 69 MB, less than what never was the pool.
MISTRAL_2L = GPTConfig.llama(num_layers=2, hidden=4096, heads=32,
                             num_kv_heads=8, vocab_size=32768,
                             max_seq_len=4096)
POOL_GEOMETRIES = {
    "hd128-bf16": (MISTRAL_2L, None, 1, 64 << 20),
    "hd128-int8kv": (MISTRAL_2L, "int8", 1, 80 << 20),
    "gpt2s-bf16": (dataclasses.replace(GPT2S, num_layers=2), None, 1,
                   16 << 20),
    # a latent pool: one dense and one expert layer at Moonlight's widths;
    # a layer's pages are 42 MB (256 x 128 rows of 640)
    "latent-bf16": (MOONLIGHT, None, 1, 32 << 20),
    "hd128-bf16-tp2": (MISTRAL_2L, None, 2, 16 << 20),
    "gpt2s-bf16-tp2": (dataclasses.replace(GPT2S, num_layers=2), None, 2,
                       16 << 20),
}
# ... and one more for what the mixed step's attention holds, which does not
# depend on the experts or the window: Trinity-Large-Preview's attention
# widths (48 query heads in groups of 6 over 8 kv heads of 128), dense layers
# (its weights' layout copies are larger than a layer's pages here, so the
# pool tests do not take it)
STEP_GEOMETRIES = {
    **POOL_GEOMETRIES,
    "trinity-bf16": (GPTConfig.llama(num_layers=2, hidden=6144, heads=48,
                                     num_kv_heads=8, vocab_size=32768,
                                     max_seq_len=4096), None, 1, None)}
POOL_MOVERS = ("copy", "slice", "dynamic-slice", "copy-start", "slice-start",
               "all-gather", "all-gather-start", "all-to-all")
_HLO_OP = re.compile(
    r"^\s*(?:ROOT\s+)?\S+ = (\(?[a-z0-9]+\[.*?) ([a-z][a-z-]*)\(", re.M)
_HLO_ARRAY = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")
_HLO_DTYPE = {"bfloat16": "bf16", "int8": "s8"}


@pytest.fixture(scope="module")
def step_programs(topo):
    """``get(geometry)`` -> (cfg, parameter shapes, cache shapes, {program:
    compiled}): the serving step programs compiled for the described chip
    (for ``tp`` of its chips, parameters and pool sharded as the engine
    shards them) at 32 slots, 512 tokens a forward, 256 a prompt and 256
    pages of 128 a chip: under ``tp`` a chip holds half the heads of twice
    the pages (a smaller shard the compiler moves into VMEM whole, which a
    real pool never fits)."""
    from conftest import lower_serving_steps
    from deepspeed_tpu.constants import MESH_AXES
    done = {}

    def get(geometry):
        if geometry not in done:
            base, quant, tp, _ = STEP_GEOMETRIES[geometry]
            cfg = dataclasses.replace(base, dtype=BF16, param_dtype=BF16,
                                      attn_impl="pallas")
            where = dict(sharding=SingleDeviceSharding(topo.devices[0]))
            if tp > 1:
                where = dict(mesh=Mesh(np.asarray(topo.devices[:tp]).reshape(
                    (1,) * (len(MESH_AXES) - 1) + (tp,)), MESH_AXES))
            params, cache, lowered = lower_serving_steps(
                cfg, BF16, slots=32, tokens=512, max_q=256,
                table_width=cfg.max_seq_len // 128, block_size=128,
                num_pages=256 * tp, steps=8, quant=quant, **where)
            done[geometry] = cfg, params, cache, {
                name: low.compile() for name, low in lowered.items()}
        return done[geometry]
    return get


@pytest.mark.parametrize("program", ["ragged_forward_sampled",
                                     "ragged_decode_sampled",
                                     "ragged_decode_burst"])
@pytest.mark.parametrize("geometry", sorted(POOL_GEOMETRIES))
def test_step_programs_leave_the_pool_in_place(step_programs, monkeypatch,
                                               geometry, program):
    """No step program copies, slices, re-lays or gathers from other chips a
    layer's pages or more: the KV write keeps the pool row-major, as the
    Pallas kernels demand it, and local to its kv heads, and the kernels
    index the flat pool (model.py ``_kv_write``).  A write that prefers
    another layout brings back a whole-pool ``copy`` on the way in and out
    of every program, a per-layer ``slice`` + ``copy`` for each kernel call,
    and a second pool as the burst's scratch; one that the partitioner
    cannot keep on the head shard, an ``all-gather`` of the pool."""
    # the kernels ask jax.default_backend() whether to interpret; here it
    # says "cpu" and the program under test is the chip's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, cache, compiled = step_programs(geometry)
    text = compiled[program].as_text()
    assert text.count(KERNEL) >= cfg.num_layers
    # the KV append is the kernel wherever the pool is k and v of several
    # heads in bfloat16 (ops/kv_append.py:supported): not an int8 pool, not
    # a latent one
    assert bool(re.search(r"%paged_kv_append\S* = ", text)) == (
        cache.k.dtype == BF16 and cache.v is not None)

    def on_a_chip(a):                   # the program's shapes are a chip's
        return a.sharding.shard_shape(a.shape)
    layer = int(np.prod(on_a_chip(cache.k)[1:]))
    dtype = _HLO_DTYPE[cache.k.dtype.name]
    # an embedding table can be as large as a layer's pages, and is not one
    weights = {",".join(map(str, on_a_chip(w)))
               for w in jax.tree_util.tree_leaves(params)}
    moved = []
    for result, op in _HLO_OP.findall(text):
        if op not in POOL_MOVERS:
            continue
        for dt, dims in _HLO_ARRAY.findall(result):
            n = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            if dt == dtype and n >= layer and dims not in weights:
                moved.append(f"{op} -> {dt}[{dims}]")
    assert not moved, moved
    if program == "ragged_decode_burst":
        temp = compiled[program].memory_analysis().temp_size_in_bytes
        assert temp < POOL_GEOMETRIES[geometry][3], temp


# a scan layer beside an attention layer at granite-4.0-h-micro's widths (64
# heads of 64 over a state of 128: 2 MiB a slot a layer, packed [32, 128,
# 128]); 64 slots as the cell has, so a layer's states are 128 MiB and the
# pool is 128 MiB too (at 16 slots the compiler moves the whole 32 MiB pool
# into VMEM around the mixed step's loop, which a real pool never fits)
GRANITE_2L = GPTConfig(
    vocab_size=4096, num_layers=2, num_heads=32, num_kv_heads=8, head_dim=64,
    hidden_size=2048, mlp_dim_override=8192, max_seq_len=2560, use_rope=True,
    rope_layers="none", use_rmsnorm=True, gated_mlp=True, norm_eps=1e-5,
    layer_types=("mamba", "attention"), ssm_heads=64, ssm_head_dim=64,
    ssm_state=128, attn_scale=1 / 64, residual_scale=0.22,
    logits_divisor=8.0, embed_scale=12.0)


def test_scan_state_pool_stays_in_place(topo, monkeypatch):
    """The step programs of a model with scan layers neither copy nor slice
    a layer's states out of the float32 state pool (4.9 GB at 64 slots of
    the whole model): the decode step and the burst update them in place
    through the ``ssm_state_update`` kernel, whose output is the pool, and
    the mixed step's loop over prompt chunks through the
    ``ssm_pool_chunk_scan`` kernel, whose output is the pool too (Mosaic
    takes it at the published shape: one group, two heads of 64 on the
    lanes, a prompt chunk of 256 rows).  The pool and the conv-tail pool are
    donated and aliased to the outputs."""
    from conftest import lower_serving_steps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(GRANITE_2L, dtype=BF16, param_dtype=BF16,
                              attn_impl="pallas")
    S = 64
    _, cache, lowered = lower_serving_steps(
        cfg, BF16, slots=S, tokens=512, max_q=256, table_width=32,
        block_size=128, num_pages=S * 20, steps=8,
        sharding=SingleDeviceSharding(topo.devices[0]))
    assert cache.ssm.shape == (1, S, 32, 128, 128) and cache.k.shape[0] == 1
    layer = int(np.prod(cache.ssm.shape[1:]))
    pools = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in (cache.ssm, cache.conv))
    for name, low in lowered.items():
        compiled = low.compile()
        text = compiled.as_text()
        assert "ssm_state_update" in text, name     # the kernel, by its name
        assert ("ssm_pool_chunk_scan" in text) == ("forward" in name), name
        moved = []
        for result, op in _HLO_OP.findall(text):
            if op not in POOL_MOVERS:
                continue
            for dt, dims in _HLO_ARRAY.findall(result):
                n = int(np.prod([int(d) for d in dims.split(",") if d]
                                or [1]))
                if dt == "f32" and n >= layer:
                    moved.append(f"{op} -> {dt}[{dims}]")
        assert not moved, (name, moved)
        assert compiled.memory_analysis().alias_size_in_bytes >= pools, name


# one attention layer (it selects by blocks) and one lightning layer of
# MiniCPM-SALA at its published widths
SALA_2L = GPTConfig(
    vocab_size=4096, num_layers=2, num_heads=32, num_kv_heads=2, head_dim=128,
    hidden_size=4096, mlp_dim_override=16384, max_seq_len=66048,
    use_rope=True, rope_layers="state", use_rmsnorm=True, gated_mlp=True,
    norm_eps=1e-6, qk_norm=True, attn_gate=True, tie_embeddings=False,
    layer_types=("attention", "lightning"), ssm_heads=32, ssm_head_dim=128,
    ssm_state=128, ssm_groups=32, ssm_chunk=128, embed_scale=12.0,
    residual_scale=0.2475, logits_divisor=16.0, block_topk=64)


def test_block_selection_and_lightning_state_leave_their_pools_in_place(
        topo, monkeypatch):
    """The step programs of MiniCPM-SALA at published widths (a table of 516
    pages for 66,048 positions, a forward of 1,024 rows, 32 slots) compile
    for the chip, and none of them copies, slices or RE-LAYS a pool: the key
    and value pages (every gather of blocks and of a pooled key's 32 keys
    takes rows of a free two-dimensional view: asked for any other way the
    compiler transposes the pool around the gather; a ``reshape`` that
    splits the head width is a copy under the chip's tiling too), the pooled
    keys, and the float32 lightning state, which the recurrence kernel
    updates in place with a column a head and the mixed step's loop scans in
    place through the ``ssm_pool_chunk_scan`` kernel (a group a head, a head
    the lanes' width, a prompt chunk of 1,024 rows) and keeps row-major
    (``_row_major``).  The one-row slots past ``dense_len`` read
    their kept blocks through the paged decode kernel (a view of the pool
    whose pages are one block of one kv head)."""
    from conftest import lower_serving_steps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(SALA_2L, dtype=BF16, param_dtype=BF16,
                              attn_impl="pallas")
    S, pages = 32, 2304        # (so that no row array is as large as a pool)
    _, cache, lowered = lower_serving_steps(
        cfg, BF16, slots=S, tokens=1024, max_q=1024, table_width=516,
        block_size=128, num_pages=pages, steps=8,
        sharding=SingleDeviceSharding(topo.devices[0]))
    assert cache.ssm.shape == (1, S, 32, 128, 128) and cache.conv is None
    assert cache.k.shape == (1, pages, 2, 128, 128)
    assert cache.ki.shape == (1, pages, 8, 2, 128)
    pools = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in (cache.ssm, cache.k, cache.v, cache.ki))
    sizes = {(dt, int(np.prod(a.shape))) for dt, a in (
        ("f32", cache.ssm), ("bf16", cache.k), ("bf16", cache.ki))}
    for name, low in lowered.items():
        compiled = low.compile()
        text = compiled.as_text()
        assert "ssm_state_update" in text, name     # the kernel, by its name
        assert ("ssm_pool_chunk_scan" in text) == ("forward" in name), name
        assert "paged_decode" in text, name
        assert ("ragged_prefill" in text) == ("forward" in name), name
        moved = []
        for result, op in _HLO_OP.findall(text):
            if op not in POOL_MOVERS + ("transpose", "reshape"):
                continue
            for dt, dims in _HLO_ARRAY.findall(result):
                n = int(np.prod([int(d) for d in dims.split(",") if d]
                                or [1]))
                if (dt, n) in sizes:                    # a whole pool
                    moved.append(f"{op} -> {dt}[{dims}]")
        assert not moved, (name, moved)
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= pools, name
        assert mem.temp_size_in_bytes < 256 << 20, name


# the three kinds of LFM2-24B-A2B's layers at its published widths: a dense
# conv layer, an attention layer and a conv layer with all 64 experts
LFM2_3L = GPTConfig(
    vocab_size=4096, num_layers=3, num_heads=32, num_kv_heads=8, head_dim=64,
    hidden_size=2048, mlp_dim_override=11776, max_seq_len=8448,
    use_rope=True, rope_theta=1e6, use_rmsnorm=True, gated_mlp=True,
    norm_eps=1e-5, qk_norm=True, tie_embeddings=True,
    layer_types=("conv", "attention", "conv"), conv_taps=3, num_experts=64,
    moe_k=4, moe_dropless=True, moe_router="sigmoid", moe_router_bias=True,
    moe_route_eps=1e-6, moe_expert_dim=1536, moe_dense_layers=1)


def test_conv_tail_pool_is_the_whole_state_and_stays_in_place(topo,
                                                              monkeypatch):
    """The step programs of a model with short-conv layers at the
    benchmark cell's sizes (64 slots, a forward of 1,024 rows, 66 pages a
    slot): a conv-tail pool ``[conv layers, slots, 2 x 2,048]`` and no
    ``ssm`` array; the pools donated and aliased to the outputs; the expert
    layers through the grouped GEMM kernel and no ``ragged_dot``; both
    paged kernels on the one attention layer."""
    from conftest import lower_serving_steps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(LFM2_3L, dtype=BF16, param_dtype=BF16,
                              attn_impl="pallas")
    S = 64
    _, cache, lowered = lower_serving_steps(
        cfg, BF16, slots=S, tokens=1024, max_q=1024, table_width=66,
        block_size=128, num_pages=S * 66, steps=8,
        sharding=SingleDeviceSharding(topo.devices[0]))
    assert cache.ssm is None and cache.conv.shape == (2, S, 2 * 2048)
    assert cache.k.shape[0] == 1
    pools = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in (cache.k, cache.v, cache.conv))
    for name, low in lowered.items():
        compiled = low.compile()
        text = compiled.as_text()
        assert "grouped_gemm_gate_up" in text and "ragged-dot" not in text, \
            name
        assert "/short_conv/" in text and "/paged_decode/" in text, name
        assert ("/ragged_prefill/" in text) == (
            name == "ragged_forward_sampled"), name
        assert compiled.memory_analysis().alias_size_in_bytes >= pools, name


# one dense and one expert layer at Xing4.0-29B-A4B's published widths: four
# residual streams of 3,584, 32 latent heads through a query latent of 768
# under YaRN, 64 experts of 1,024 beside a shared one
XING4_2L = GPTConfig(
    num_layers=2, hidden_size=3584, num_heads=32, head_dim=192,
    kv_lora_rank=512, q_lora_rank=768, qk_rope_head_dim=64, v_head_dim=128,
    use_rope=True, rope_theta=10000.0,
    rope_scaling=("yarn", 64.0, 32.0, 1.0, 4096.0, 1.0, 1.0),
    use_rmsnorm=True, norm_eps=1e-6, gated_mlp=True, tie_embeddings=False,
    mlp_dim_override=9216, vocab_size=131072, max_seq_len=4224,
    num_experts=64, moe_k=4, moe_dropless=True, moe_router="sigmoid",
    moe_route_scale=2.0, moe_router_bias=True, moe_shared_dim=1024,
    moe_expert_dim=1024, moe_dense_layers=1, hc_mult=4)


def test_multi_stream_step_programs_compile_at_published_widths(topo,
                                                                monkeypatch):
    """The mixed and decode step programs of a model whose residual is four
    streams (``hc_mult``), at the benchmark cell's slots and pages (64 slots,
    33 pages a slot) and twice its forward (2,048 rows): both latent paged kernels
    engaged, the experts through the grouped GEMM kernel, the three
    hyper-connection scopes in every program inside the accepted scopes,
    the latent pool donated and aliased, and the streams ``[rows, 4,
    3,584]`` never laid out with the 4 on a tile's sublanes (which would
    pad every row's 28 KB to 56 or 112)."""
    from conftest import lower_serving_steps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(XING4_2L, dtype=BF16, param_dtype=BF16,
                              attn_impl="pallas")
    S = 64
    _, cache, lowered = lower_serving_steps(
        cfg, BF16, slots=S, tokens=2048, max_q=2048, table_width=33,
        block_size=128, num_pages=S * 33, steps=8,
        sharding=SingleDeviceSharding(topo.devices[0]))
    assert cache.v is None and cache.k.shape[-1] == 640
    pool = int(np.prod(cache.k.shape)) * cache.k.dtype.itemsize
    for name in ("ragged_forward_sampled", "ragged_decode_sampled"):
        compiled = lowered[name].compile()
        text = compiled.as_text()
        assert "grouped_gemm_gate_up" in text and "ragged-dot" not in text, \
            name
        assert "/paged_decode/" in text, name
        assert ("/ragged_prefill/" in text) == (
            name == "ragged_forward_sampled"), name
        for outer, inner in (("attn_qkv", "hc_coef"), ("attn_qkv", "hc_pre"),
                             ("attn_out", "hc_post"), ("mlp", "hc_coef"),
                             ("mlp", "hc_pre"), ("mlp", "hc_post")):
            assert f"/{outer}/{inner}/" in text, (name, outer, inner)
        rows = 2048 if name == "ragged_forward_sampled" else S
        padded = re.findall(
            rf"bf16\[{rows},4,3584\]\{{2,1,0:T\((?:8|16),128\)", text)
        assert not padded, (name, padded[:3])
        assert compiled.memory_analysis().alias_size_in_bytes >= pool, name


@pytest.mark.parametrize("geometry", sorted(STEP_GEOMETRIES))
def test_mixed_step_has_both_attention_kernels_a_layer(step_programs,
                                                       monkeypatch, geometry):
    """The mixed program attends one-row slots with the paged decode kernel
    and the others with the prefill kernel (model.py ``ragged_forward``):
    both are in the compiled program, once a layer each, by the names the
    benchmark's readers take them by; the decode programs hold the decode
    kernel alone."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, _, _, compiled = step_programs(geometry)

    def kernels(program):
        calls = [ln for ln in compiled[program].as_text().splitlines()
                 if f'custom_call_target="{KERNEL}"' in ln]
        return {name: sum(f"/{name}/pallas_call" in ln for ln in calls)
                for name in ("paged_decode", "ragged_prefill")}, len(calls)
    named, total = kernels("ragged_forward_sampled")
    assert named == {"paged_decode": cfg.num_layers,
                     "ragged_prefill": cfg.num_layers}
    assert total >= 2 * cfg.num_layers
    for program in ("ragged_decode_sampled", "ragged_decode_burst"):
        assert kernels(program)[0] == {"paged_decode": cfg.num_layers,
                                       "ragged_prefill": 0}


@pytest.mark.parametrize("geometry", ["hd128-bf16", "trinity-bf16",
                                      "latent-bf16"])
def test_mixed_step_lays_no_rows_out_dense(step_programs, monkeypatch,
                                           geometry):
    """The mixed step's attention takes the token-major rows as they are
    (model.py ``_mixed_attention``): at the Mistral, Trinity and Moonlight
    geometries the compiled program holds no array of ``slots x chunk x
    heads x head width`` elements or more that is neither the pool nor a
    weight.  The dense ``[slots, chunk]`` query and output layouts were two
    such arrays a layer, with a scatter, two gathers and the compiler's
    copies and broadcasts around them."""
    from deepspeed_tpu.inference.v2.model import _attn_geometry
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, cache, compiled = step_programs(geometry)
    _, hd, vd, _ = _attn_geometry(cfg)
    dense = 32 * 256 * cfg.num_heads * min(hd, vd)      # the fixture's sizes
    known = {",".join(map(str, w.shape))
             for w in jax.tree_util.tree_leaves(params)}
    pool = cache.k.shape
    # the pool, all layers flat, and as the rows the KV write scatters
    known |= {",".join(map(str, pool)),
              ",".join(map(str, (pool[0] * pool[1],) + pool[2:])),
              f"{int(np.prod(pool[:-1]))},{pool[-1]}"}
    large = []
    for result, op in _HLO_OP.findall(
            compiled["ragged_forward_sampled"].as_text()):
        for dt, dims in _HLO_ARRAY.findall(result):
            n = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            if n >= dense and dims not in known:
                large.append(f"{op} -> {dt}[{dims}]")
    assert not large, large


# ------------------------------------- a model that selects its keys

# dots3-note-prev's attention at published widths, one full layer (128 heads
# over a latent row of 512 + 64 -> 640, 64 index heads of 128 choosing 2,048
# keys) and one sliding layer (64 heads over 1,024 + 64 -> 1,152, window
# 513), two of 256 experts held: the serving cell's kernels and its three
# pools (a latent pool a page group, and the index keys)
DOTS3 = GPTConfig(
    num_layers=2, hidden_size=5120, num_heads=128, head_dim=192,
    v_head_dim=128, kv_lora_rank=512, q_lora_rank=1024, qk_rope_head_dim=64,
    rope_theta=8e7, mla_lora_rescale=True, attn_gate_headwise=True,
    index_topk=2048, index_n_heads=64, index_head_dim=128,
    sliding_window=513, local_attn_layers=(1,),
    window_attn=(("num_heads", 64), ("head_dim", 256), ("v_head_dim", 128),
                 ("kv_lora_rank", 1024), ("q_lora_rank", 1024),
                 ("qk_rope_head_dim", 64), ("rope_theta", 50000.0)),
    use_rope=True, use_rmsnorm=True, norm_eps=1e-5, gated_mlp=True,
    tie_embeddings=False, mlp_dim_override=13824, vocab_size=19008,
    max_seq_len=32768, num_experts=256, experts_held=2, expert_offset=32,
    moe_k=8, moe_dropless=True, moe_router="sigmoid", moe_router_bias=True,
    moe_shared_dim=1536, moe_expert_dim=1536, moe_dense_layers=1)


def test_index_score_kernel_and_the_ops_around_it(topo):
    """The index-score kernel at the cell's block (64 heads of 128, 128 rows
    against a 32 k context), the op that walks a mixed step's slots with it,
    the exact selection and the attention over the selected rows, each for
    the described chip at published widths."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops import sparse_index as si
    text = chip_text(topo, lambda q, w, k: si._score_block(
        q, w, k, interpret=False),
        sds((64, si.SCORE_ROWS, 128), BF16), sds((64, si.SCORE_ROWS), F32),
        sds((32768, 128), BF16))
    assert "index_score_kernel" in text and KERNEL in text
    N, S, MB, bs = 1024, 8, 64, 512
    text = chip_text(
        topo, lambda q, w, kp, tb, rs, rp: ops.index_scores(
            q, w, kp, tb, rs, rp, max_rows=N, impl="pallas",
            interpret=False),
        sds((N, 64, 128), BF16), sds((N, 64), F32),
        sds((S * MB, 1, bs, 128), BF16), sds((S, MB), I32), sds((N,), I32),
        sds((N,), I32))
    assert "index_score_kernel" in text
    assert chip_text(topo, lambda s: ops.index_select(s, 2048),
                     sds((N, MB * bs), F32))
    # the same selection as bits with no list: 64 rows of 32 k scores and
    # as much of keys in VMEM a grid step; rows that are no whole block
    text = chip_text(
        topo, lambda s, w: ops.threshold_mask(
            s, 2048, width=w, impl="pallas", interpret=False),
        sds((N + 16, MB * bs), F32), sds((), I32))
    assert "threshold_mask_kernel" in text and KERNEL in text
    assert chip_text(
        topo, lambda q, pg, r, c: ops.selected_attention(
            q, pg, r, c, v_dim=512, scale=192 ** -0.5),
        sds((N, 128, 640), BF16), sds((S * MB, 1, bs, 640), BF16),
        sds((N, 2048), I32), sds((N,), I32))


@pytest.mark.parametrize("kernel", [decode_text, prefill_text],
                         ids=["decode", "prefill"])
def test_window_layers_take_the_paged_kernels_at_their_own_width(topo,
                                                                 kernel):
    from deepspeed_tpu.inference.v2.model import _attn_geometry
    nkv, hd, vd, latent = _attn_geometry(DOTS3.for_layer(1))
    assert (nkv, hd, vd) == (1, 1152, 1024)
    geo = dict(nkv=1, g=64, hd=hd, bs=512, kv_major=False, window=513,
               **latent)
    if kernel is prefill_text:
        geo.update(Q=1024)
    assert KERNEL in kernel(topo, **geo)


# a prompt chunk's rows a slot (``max_q_per_seq``) and the pages of a block
# of the prefill kernel's loop in each serving cell of the benchmark
CELL_PREFILL = {
    "mistral": (lambda: _engine_geometry(LLAMA128, False, 128), 256, 8),
    "trinity": (lambda: _engine_geometry(TRINITY, False, 128), 1024, 8),
    "moonlight": (lambda: _engine_geometry(MOONLIGHT, False, 128), 1024, 8),
    "dots3-window": (lambda: dict(
        nkv=1, g=64, hd=1152, bs=512, kv_major=False, window=513,
        v_dim=1024), 1024, 1),
    # a full layer's prompt chunk up to ``MASKED_REACH``: the masked form
    # over the 64 pages of the cell's one table width (4 rows an item)
    "dots3-full-masked": (lambda: dict(
        nkv=1, g=128, hd=640, bs=512, kv_major=False, v_dim=512, S=1,
        MB=64, masked=True), 1024, 3),
}


@pytest.mark.parametrize("nkv,g,window,sink", [
    (4, 16, None, False),       # MiMo-V2-Flash's full layers
    (8, 8, 128, True)])         # its window layers: ONE page, a sink a head
def test_sink_and_unequal_widths_at_mimo_v2s_geometry(topo, nkv, g, window,
                                                      sink):
    """Both paged kernels and the append kernel compile at keys 192 wide
    beside values 128 wide (kv-major pages ``[nkv, 192, 128]`` beside
    ``[nkv, 128, 128]``), with the sink as the softmax's starting state, at
    the serving cell's 96 slots and 512-row chunk."""
    from deepspeed_tpu.ops import kv_append
    from deepspeed_tpu.ops.paged_attention import ragged_prefill_supported
    S, MB, bs, N, Q = 96, 40, 128, 512, 512
    k, v = sds((1024, nkv, 192, bs), BF16), sds((1024, nkv, 128, bs), BF16)
    bt, ln = sds((S, MB), I32), sds((S,), I32)
    sk = sds((nkv * g,), F32)
    kw = dict(kv_major=True, window=window)

    def decode(q, k, v, bt, ln, sk):
        extra = dict(kw, sink=sk if sink else None)
        assert paged_supported(q, k, v, bt, ln, **extra)
        return pallas_paged_attention(q, k, v, bt, ln, interpret=False,
                                      **extra)

    def prefill(q, k, v, bt, ln, sk):
        extra = dict(kw, sink=sk if sink else None)
        assert ragged_prefill_supported(q, k, v, bt, ln, ln, ln, ln, **extra)
        return pallas_ragged_prefill(q, k, v, bt, ln, ln, ln, ln, max_q=Q,
                                     interpret=False, **extra)

    def append(k, v, kn, vn, bt, slot, pos):
        plan = kv_append.append_plan(bt, slot, pos, bs, Q, True)
        assert kv_append.supported((k, v), (kn, vn), plan, 0, kv_major=True)
        return kv_append.pallas_paged_kv_append(
            (k, v), (kn, vn), plan, jnp.int32(5), kv_major=True,
            interpret=False)
    rows = sds((N,), I32)
    for fn, specs in (
            (decode, (sds((S, nkv, g, 192), BF16), k, v, bt, ln, sk)),
            (prefill, (sds((N, nkv, g, 192), BF16), k, v, bt, ln, sk)),
            (append, (k, v, sds((N, nkv, 192), BF16),
                      sds((N, nkv, 128), BF16), bt, rows, rows))):
        assert chip_text(topo, fn, *specs).count(KERNEL) == 1, fn.__name__


@pytest.mark.parametrize("cell", sorted(CELL_PREFILL))
def test_prefill_block_at_the_cell_geometries(topo, monkeypatch, cell):
    """The prefill kernel's block of pages at each serving cell's geometry:
    P as ``_prefill_block_pages`` takes it from the shapes (a later edit to
    the budget shows here), and the kernel compiled for the described chip
    with the scratch and score tiles it asks for, inside the chip's VMEM."""
    import importlib
    pa = importlib.import_module("deepspeed_tpu.ops.paged_attention")
    geo, Q, pages = CELL_PREFILL[cell]
    geo = {k: v for k, v in geo().items() if k != "quant"}
    seen, asked = [], []
    rule, params = pa._prefill_block_pages, pa.pltpu.CompilerParams
    monkeypatch.setattr(pa, "_prefill_block_pages",
                        lambda *a: seen.append(rule(*a)) or seen[-1])
    monkeypatch.setattr(
        pa.pltpu, "CompilerParams",
        lambda **kw: asked.append(kw.get("vmem_limit_bytes")) or params(**kw))
    assert KERNEL in prefill_text(topo, **geo, Q=Q)
    assert seen == [pages]
    assert asked[-1] and asked[-1] < 100 << 20      # of 128 MiB a v5e core


# the KV append at the cells' geometries, a mixed step's and the one-row
# decode step's: (kv-major, nkv, hd, bs, rows N, rows a slot, slots S)
CELL_APPEND = {
    "mistral-mixed": (False, 8, 128, 128, 512, 256, 32),
    "mistral-decode": (False, 8, 128, 128, 32, 1, 32),
    "trinity-mixed": (False, 8, 128, 128, 1024, 1024, 16),
    "trinity-decode": (False, 8, 128, 128, 16, 1, 16),
    "lfm2-mixed": (True, 8, 64, 128, 2048, 2048, 64),
    "lfm2-decode": (True, 8, 64, 128, 64, 1, 64),
    "granite-mixed": (True, 8, 64, 128, 512, 256, 64),
    "granite-decode": (True, 8, 64, 128, 64, 1, 64),
}


@pytest.mark.parametrize("cell", sorted(CELL_APPEND))
def test_kv_append_at_the_cell_geometries(topo, cell):
    """``paged_kv_append``'s kernel at each cell's page layout and step
    shapes, plan and all, compiled for the described chip: ``supported``
    takes the shape, the kernel is in the program by its name, both pools
    are aliased to the outputs and nothing as large as a layer's pages is
    copied or sliced on the way."""
    from deepspeed_tpu.ops import kv_append as kva
    km, nkv, hd, bs, N, per_slot, S = CELL_APPEND[cell]
    NB, layers, MB = 256, 2, 64
    pool = sds((layers * NB,) + ((nkv, hd, bs) if km else (nkv, bs, hd)),
               BF16)
    rows = sds((N, nkv, hd), BF16)

    def write(k, v, new_k, new_v, table, slot, pos, base):
        plan = kva.append_plan(table, slot, pos, bs, per_slot, km)
        assert kva.supported((k, v), (new_k, new_v), plan, base, kv_major=km)
        return kva.pallas_paged_kv_append((k, v), (new_k, new_v), plan, base,
                                          kv_major=km, interpret=False)
    one = SingleDeviceSharding(topo.devices[0])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        (pool, pool, rows, rows, sds((S, MB), I32), sds((N,), I32),
         sds((N,), I32), sds((), I32)))
    compiled = jax.jit(write, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert KERNEL in text and "paged_kv_append" in text
    pools = 2 * int(np.prod(pool.shape)) * 2
    assert compiled.memory_analysis().alias_size_in_bytes == pools
    layer = int(np.prod(pool.shape[1:])) * NB
    moved = [f"{op} -> {dt}[{dims}]"
             for result, op in _HLO_OP.findall(text) if op in POOL_MOVERS
             for dt, dims in _HLO_ARRAY.findall(result)
             if dt == "bf16" and int(np.prod(
                 [int(d) for d in dims.split(",") if d] or [1])) >= layer]
    assert not moved, moved


@pytest.fixture(scope="module")
def selecting_steps(topo):
    """The three step programs of ``DOTS3`` compiled for the described chip
    at 8 slots, 1,024 tokens a forward and pages of 512 (the cell's sizes),
    the table 64 pages wide: ``get()`` -> (cache shapes, {program:
    compiled}), lowered when first asked for (by then the test has told the
    kernels that the backend is the chip)."""
    done = []

    def get():
        if not done:
            done.append(_selecting_steps(topo))
        return done[0]
    return get


def _selecting_steps(topo):
    import functools

    from deepspeed_tpu.inference.engine import _sample_token
    from deepspeed_tpu.inference.v2 import model as v2model
    from deepspeed_tpu.models.gpt import GPTLogits
    from deepspeed_tpu.parallel.metadata import unbox
    cfg = dataclasses.replace(DOTS3, dtype=BF16, param_dtype=BF16,
                              attn_impl="pallas")
    S, N, MB, bs = 8, 1024, 64, 512
    one = SingleDeviceSharding(topo.devices[0])

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = unbox(jax.eval_shape(
        lambda k: GPTLogits(cfg).init(k, jnp.zeros((1, 8), I32)),
        jax.random.PRNGKey(0))["params"])
    cache = jax.eval_shape(lambda: v2model.PagedKVCache.create_latent_groups(
        cfg, S * MB, S * 5, bs, BF16))
    params, cache = jax.tree_util.tree_map(
        lambda a: sd(a.shape, a.dtype), (params, cache))
    tables = {"block_table": sd((S, MB), I32),
              "block_table_w": sd((S, MB), I32)}
    slot = {"active": sd((S,), jnp.bool_), "from_device": sd((S,), jnp.bool_),
            **tables}
    programs = {
        "ragged_forward_sampled": (
            dict(max_q_per_seq=N),
            {"tokens": sd((N,), I32), "token_slot": sd((N,), I32),
             "token_pos": sd((N,), I32), **tables, "kv_len": sd((S,), I32),
             "from_device": sd((N,), jnp.bool_),
             "served": sd((S,), jnp.bool_)}),
        "ragged_decode_sampled": (
            {}, {**slot, "tokens": sd((S,), I32), "token_pos": sd((S,), I32),
                 "served": sd((S,), jnp.bool_)}),
        "ragged_decode_burst": (
            dict(steps=8), {**slot, "tokens0": sd((S,), I32),
                            "pos0": sd((S,), I32)})}
    layout = v2model.kv_page_layout(cfg, S * MB, S * 5, split=True)
    sample = functools.partial(_sample_token, do_sample=False, top_k=0)
    compiled = {}
    for name, (static, batch) in programs.items():
        fn = functools.partial(getattr(v2model, name), cfg=cfg, block_size=bs,
                               sample_fn=sample, kv_layout=layout,
                               moe_stats=True, **static)
        compiled[name] = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, batch, sd((S,), I32), sd((2,), jnp.uint32),
            sd((), F32), sd((), F32)).compile()
    return cache, compiled


@pytest.mark.parametrize("program", ["ragged_forward_sampled",
                                     "ragged_decode_sampled",
                                     "ragged_decode_burst"])
def test_selecting_step_programs_leave_all_three_pools_in_place(
        selecting_steps, monkeypatch, program):
    """PR 27's rule for each of the three pools of a model that selects its
    keys: no step program copies, slices or re-lays a layer's pages of the
    global group's latent pool, of the window group's, or of the index
    keys (the selected rows and a slot's index keys are GATHERED: a gather
    reads what it needs and is no mover).  And the kernels are there by
    the names the benchmark's readers take them by."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache, compiled = selecting_steps()
    text = compiled[program].as_text()
    calls = [ln for ln in text.splitlines()
             if f'custom_call_target="{KERNEL}"' in ln]

    def named(*what):
        return sum(all(w in ln for w in what) for ln in calls)
    if program == "ragged_forward_sampled":
        assert named("index_score_kernel") == 1          # the full layer
        assert named("/window_latent/", "/ragged_prefill/") == 1
        assert named("/window_latent/", "/paged_decode/") == 1
    else:
        assert named("index_score_kernel") == 0          # one row a slot
        assert named("/window_latent/", "/paged_decode/") == 1
        assert named("ragged_prefill") == 0
    assert named("/paged_decode/") == 1     # the full layer takes no kernel
    assert "selected_attention" in text and "attn_index" in text
    # the one expert layer's products are the grouped GEMM kernel's two
    # forms, under the scope the benchmark's readers take them by
    assert named("/moe_experts/", "/grouped_gemm_gate_up/pallas_call") == 1
    assert named("/moe_experts/", "/grouped_gemm_down/pallas_call") == 1
    assert "ragged-dot" not in text and "ragged_dot" not in text
    layers = {"k": 1, "kw": 1, "ki": 1}                  # layers a pool
    moved = []
    for result, op in _HLO_OP.findall(text):
        if op not in POOL_MOVERS:
            continue
        for dt, dims in _HLO_ARRAY.findall(result):
            shape = [int(d) for d in dims.split(",") if d] or [1]
            n = int(np.prod(shape))
            for name, n_layers in layers.items():
                pool = getattr(cache, name)
                layer = int(np.prod(pool.shape)) // n_layers
                if dt == "bf16" and n >= layer \
                        and shape[-1] == pool.shape[-1]:
                    moved.append(f"{op} -> {dt}[{dims}] ({name})")
    assert not moved, moved


def _computations(text):
    """name -> text of every computation of a compiled program."""
    return {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n.*?^\}", text, re.M | re.S)}


def _with_callees(comps, name):
    """A computation's text and that of everything it calls, however deep
    (a fusion's body, a loop's, a reduction's)."""
    todo, seen = [name], {}
    while todo:
        n = todo.pop()
        if n in seen or n not in comps:
            continue
        seen[n] = comps[n]
        for called in re.findall(
                r"(?:calls|to_apply|body|condition)=%[\w.\-]+"
                r"|branch_computations=\{[^}]*\}", comps[n]):
            todo += re.findall(r"%([\w.\-]+)", called)
    return "\n".join(seen.values())


def test_a_chunk_reads_its_keys_one_way_a_branch(selecting_steps,
                                                 monkeypatch):
    """The mixed step of a model that selects its keys holds ONE branch a
    selecting layer kind under ``attn_kernel`` (``masked_prefill`` of the
    step's reach).  The masked branch is the prefill kernel under scope
    ``selected_attention`` on the bits of kernel ``threshold_mask_kernel``
    under ``attn_index``; it sorts nothing and gathers no row a pair: nothing
    of ``[rows, k, 640]``.  The other is the list's sort, the gather's loop
    and no kernel.  (That neither moves a pool is the test above: it reads
    every computation.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = selecting_steps()[1]["ragged_forward_sampled"].as_text()
    # (the gathering branch holds a branch of its own, the sort's widths:
    # more than two ways)
    two_ways = re.compile(r"branch_computations=\{%([\w.\-]+), "
                          r"%([\w.\-]+)\}")
    branches = [ln for ln in text.splitlines() if " conditional(" in ln
                and "/attn_kernel/cond" in ln and two_ways.search(ln)]
    assert len(branches) == 1, branches
    gathers, masked = (_with_callees(_computations(text), name) for name in
                       two_ways.search(branches[0]).groups())

    def row_gathers(part):
        return [f"{dt}[{dims}]" for result, op in _HLO_OP.findall(part)
                if op == "gather" for dt, dims in _HLO_ARRAY.findall(result)
                if np.prod([int(d) for d in dims.split(",") if d] or [1])
                >= DOTS3.index_topk * 640]
    kernels = [ln for ln in masked.splitlines()
               if f'custom_call_target="{KERNEL}"' in ln]
    bits, prefill = sorted(kernels, key=lambda ln: "/ragged_prefill/" in ln)
    assert len(kernels) == 2 and "/selected_attention/" in prefill \
        and "/ragged_prefill/" in prefill and "window_latent" not in prefill
    assert "/attn_index/" in bits and "threshold_mask_kernel" in bits
    assert " sort(" not in masked and not row_gathers(masked)
    assert row_gathers(gathers) and " while(" in gathers
    assert " sort(" in gathers and "/index_select/" in gathers
    assert KERNEL not in gathers and "threshold_mask" not in gathers


# -------------------------------------------------------- quantized GEMMs

def _store(shape, dim=0):
    from deepspeed_tpu.ops.quantization import quantize_weight
    return jax.eval_shape(
        lambda w: quantize_weight(w, bits=8, group=128, dim=dim),
        sds(shape, BF16))


# ---------------------------------------------------------- grouped GEMM

@pytest.mark.parametrize("rows,groups,hidden,expert_dim", [
    (288, 64, 2048, 1408), (6144, 64, 2048, 1408),      # Moonlight
    (64, 32, 3072, 3072), (4096, 32, 3072, 3072),       # Trinity's share
    (128, 32, 5120, 1536), (8192, 32, 5120, 1536),      # dots3's share
    (256, 64, 2048, 1536), (8192, 64, 2048, 1536),      # LFM2
    (256, 64, 3584, 1024), (4096, 64, 3584, 1024)],     # Xing4
    ids=["moonlight-decode", "moonlight-mixed", "trinity-decode",
         "trinity-mixed", "dots3-decode", "dots3-mixed", "lfm2-decode",
         "lfm2-mixed", "xing4-decode", "xing4-mixed"])
def test_grouped_gemm_at_the_cells_widths(topo, rows, groups, hidden,
                                          expert_dim):
    """An expert layer's two kernel calls (gate-up, then down) at a decode
    and a mixed step's buffer of each MoE cell, tiles by the shape rules:
    the double-buffered weight panels fit the VMEM limit the call asks for,
    and the rules' choice is what the predicate accepts."""
    gg = sys.modules["deepspeed_tpu.ops.grouped_gemm"]

    def ffn(x, wi, wg, wo, sizes):
        h = gg.pallas_grouped_gemm(x, wi, sizes, wg, interpret=False)
        return gg.pallas_grouped_gemm(h, wo, sizes, interpret=False)
    x, wi = sds((rows, hidden), BF16), sds((groups, hidden, expert_dim), BF16)
    wo, sizes = sds((groups, expert_dim, hidden), BF16), sds((groups,), I32)
    assert gg.supported(x, wi, sizes, wi) and gg.supported(x, wi, sizes)
    text = chip_text(topo, ffn, x, wi, wi, wo, sizes)
    assert text.count(KERNEL) >= 2 and "ragged-dot" not in text


@pytest.mark.parametrize("k,n", [(768, 3072), (3072, 768), (768, 50304)])
def test_wq_matmul(topo, k, n):
    from deepspeed_tpu.ops.wq_matmul import wq_matmul
    text = chip_text(topo, lambda x, st: wq_matmul(x, st, interpret=False),
                     sds((256, k), BF16), _store((k, n)))
    assert KERNEL in text


def test_wq_matmul_t_tied_unembed(topo):
    from deepspeed_tpu.ops.wq_matmul import wq_matmul_t
    text = chip_text(topo, lambda x, st: wq_matmul_t(x, st, interpret=False),
                     sds((256, 768), BF16), _store((50304, 768)))
    assert KERNEL in text


def test_lora_bgmv_rank16(topo):
    from deepspeed_tpu.ops.lora_matmul import pallas_lora_matmul
    text = chip_text(
        topo, lambda *a: pallas_lora_matmul(*a, interpret=False),
        sds((256, 768), BF16), sds((8, 768, 16), BF16),
        sds((8, 16, 768), BF16), sds((256,), I32), sds((8,), F32))
    assert KERNEL in text
