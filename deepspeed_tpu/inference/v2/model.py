"""Ragged GPT forward over a paged KV cache — the v2 model implementation.

Analog of the reference's ``DSTransformerBase`` layer-by-layer ragged forward
(inference/v2/model_implementations/inference_transformer_base.py:617) plus the
ragged kernel set (inference/v2/kernels/ragged_ops/): ``linear_blocked_kv_rotary``
(qkv + rotary + paged-KV append) and ``blocked_flash`` (attention over blocked
KV) become scatter-into-pages + the paged attention ops over the token-major
rows (ops/paged_attention.py: Pallas kernels on TPU, a gather and a masked
dense attention in XLA elsewhere); ``logits_gather`` becomes a row gather
before the unembed.

Works directly on the GPT parameter tree (models/gpt.py naming: backbone/
block_i/{Attention_0,MLP_0,Norm_0,Norm_1}, wte/wpe/final_norm) the way the
reference's flat-parameter model implementations bypass the torch module
(flat_model_helpers.py) — a training checkpoint serves without conversion.

Every array shape is static: N token budget, S sequence slots, MB blocks/seq,
Qmax new tokens per sequence per step.  Raggedness is carried by index arrays
(see ragged.py).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import (GPTConfig, hc_maps, hc_read, hc_write,
                                      latent_softmax_scale, mlp_activation,
                                      rope, value_scale)


def named_partial(fn, **static):
    """``functools.partial`` that keeps ``fn``'s name.  jit names a program
    after its function, and a bare partial has none: every step program
    would reach the compiler, the profiler and IR dumps as ``<unknown>``."""
    bound = functools.partial(fn, **static)
    bound.__name__ = fn.__name__
    return bound


def quantize_kv_token(x):
    """Per-token symmetric int8: x [..., hd] → (codes int8 [..., hd],
    scales f32 [...]) with amax-over-head-dim granularity."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
    q = jnp.round(xf / s[..., None]).astype(jnp.int8)
    return q, s


def kv_major_layout(cfg: GPTConfig) -> bool:
    """True ⇒ pages are stored token-on-lanes, [NB, nkv, hd, bs].

    The Pallas DMA slab's lane dim must be 128-aligned (ops/
    paged_attention.py module docstring); head dims that aren't already
    128-multiples get the transposed layout so the TOKEN axis (a
    framework-controlled knob — the engine sizes pages to 128) carries the
    lanes instead.  Pure function of the model config, so every component
    (cache alloc, scatter, kernels, fallbacks) derives the same answer.
    A latent page is row-major: its row is padded to whole lane tiles
    instead (``cfg.latent_page_dim``).  A value head of its own width
    (``cfg.value_dim``) obeys the same rule as the key's."""
    return ((cfg.head_dim % 128 != 0 or cfg.value_dim % 128 != 0)
            and not cfg.mla)


def _page_geometry(cfg: GPTConfig):
    """What shapes an ordinary layer's pages: (kv heads, key width, value
    width) of the layer's view (``GPTConfig.for_layer``)."""
    return cfg.kv_heads, cfg.head_dim, cfg.value_dim


def kv_groups_split(cfg: GPTConfig) -> bool:
    """Whether a model with window AND global layers keeps a pool a page
    group (``PagedKVCache.create_latent_groups`` / ``create_grouped``'s
    ``kw``, ``vw``) instead of one flat array for both: latent pages always;
    ordinary heads where the two kinds of layer differ in kv heads or widths
    (MiMo-V2: 4 against 8 kv heads), or a value is not as wide as its key
    (one flat array would hold K and V pages of one shape)."""
    if cfg.mla:
        return True
    geometry = {cfg.window_for_layer(i) is not None:
                _page_geometry(cfg.for_layer(i))
                for i in cfg.attention_layers}
    g, w = geometry[False], geometry[True]
    return g != w or g[1] != g[2]


def kv_block_size_for(cfg: GPTConfig, requested: int,
                      quant: bool = False) -> int:
    """Effective page size: kv-major pages need block_size % 128 == 0, and
    int8-quantized pages need it in EITHER layout (the per-token scale slab
    [bs] f32 is DMA'd per page and its lane dim must be 128-aligned)."""
    if (kv_major_layout(cfg) or quant) and requested % 128 != 0:
        return -(-requested // 128) * 128
    return requested


class PagedKVCache(NamedTuple):
    """Per-layer paged KV arrays stacked on a leading layer axis (reference:
    KVCacheManager kv_cache.py).

    Layout: [L, num_blocks, nkv, block_size, head_dim], OR the kv-major
    transpose [L, num_blocks, nkv, head_dim, block_size] when
    ``kv_major_layout(cfg)`` — one page × one kv head is then a clean TPU
    tile with a 128-aligned lane dim for EVERY hd % 8 == 0 model, which is
    what the Pallas paged/prefill kernels DMA (ops/paged_attention.py).

    The pool is row-major in memory as created and stays so, in the compute
    dtype (or int8): the kernels are custom calls that take it no other
    way, so every step program opens it (``_KVPool``: free reshapes to [L *
    num_blocks, ...]), writes into that in place (``_layer``, through
    ``_kv_write``) and hands the kernels that same flat pool with the
    layer's first page added to the block table (``_layer_pages``).
    Nothing slices a layer out of it, casts it or prefers another layout
    for it: each of those is a copy of a layer's pages or of the whole pool
    in every step (tests/test_chip_compile.py).

    int8 quantized mode (``kv_quant="int8"``): k/v hold int8 codes and
    ``k_scale``/``v_scale`` hold the per-(page, head, token) fp32 scales,
    [L, num_blocks, nkv, block_size] — amax-over-head-dim granularity, the
    standard KV-quant recipe.  Halves KV HBM (the decode bandwidth bound)
    and doubles cache capacity for ~6% scale overhead.

    Latent pages (``cfg.mla``): ONE pool, ``k`` [L, num_blocks, 1,
    block_size, cfg.latent_page_dim], and ``v`` None.  A token's row is its
    normed latent, then its rotated key part, then zeros up to whole lane
    tiles (512 + 64 -> 640): 1,280 B in bf16 where the mathematics needs
    1,152.  One row kind in one row-major page rather than the latent and a
    kv-major key part in two pools: one copy a page in the kernels, one
    scatter a step, and the page is key and value as it lies; the price is
    the 64 pad columns, a ninth more bytes to hold and to read.  The
    attention ops take it as ``v_pages=None`` with ``v_dim``.

    Latent pages of a model with window AND global layers
    (``create_latent_groups``): a pool PER page group, each with its group's
    own row width: ``k`` the global layers' ``[1, pages, 1, block_size,
    row]`` and ``kw`` the window layers', since the two kinds of layer may
    differ in their latent (``cfg.window_attn``) and the group that grows
    with the context should not be padded to the other's row.  ``ki``: the
    INDEX-KEY pool of the global layers where they select their keys
    (``cfg.index_topk``): ``[1, pages, 1, block_size, index_head_dim]``,
    page for page beside ``k`` and addressed by the global group's own block
    table: a third array, no third allocator group.  In a model whose
    attention layers select by BLOCKS (``cfg.block_topk``, ordinary heads, one
    page group) ``ki`` holds their POOLED keys instead: ``[attention layers,
    pages, block_size / stride, nkv, head_dim]``, pooled key ``j`` of a
    sequence in the page its span begins in (``ops/block_select.py``).

    State layers (``cfg.is_state_layer``: Mamba-2 scan layers, gated short
    convolutions) write no pages: ``k``/``v`` hold the attention layers only
    (``_layer_pages`` says where each begins), and beside them lie the
    residents that do not grow, one fixed-size slot a tracked sequence,
    addressed by the sequence's slot, in the parts the model needs.
    ``conv [state layers, slots, (taps - 1) * channels]``: the
    conv's last rows one after the other, in the compute dtype (ONE row a
    slot: as ``[taps - 1, channels]`` a slot's tile is padded fivefold on
    the chip, and with the slots behind the taps a scatter by slot re-lays
    the whole pool, 8 ms of a mixed step on the v5e; PERF.md section 6, PR
    44); a short-conv layer's whole state (2 rows of the hidden width).
    ``ssm [scan layers, slots, heads / k, state, k * head_dim]`` in float32,
    only where layers scan (a recurrence of thousands of steps rounds at
    every one; ``k`` heads side by side on the lanes, ``ops/ssm_scan.py``
    "the packed state pool": 64 heads of 64 over a state of 128 are ``[32,
    128, 128]``; a lightning layer's 32 heads of 128 over 128 too, and it has
    no ``conv`` part).  A slot is never cleared: a sequence's row at position 0
    starts from zero whatever the slot held (``_scan_plan``), which is also
    how a preempted sequence is recomputed.

    Ordinary heads whose page groups differ (``kv_groups_split``: the window
    layers' kv heads or widths are not the global layers', or a value head
    is not as wide as a key head): a pool a group here too, ``k`` / ``v``
    the global layers' ``[1, pages, nkv, ...]`` and ``kw`` / ``vw`` the
    window layers', each at its own heads and widths (``create_grouped``)."""

    k: jax.Array
    v: Optional[jax.Array]
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    kw: Optional[jax.Array] = None
    ki: Optional[jax.Array] = None
    ssm: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    vw: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @classmethod
    def create(cls, cfg: GPTConfig, num_blocks: int, block_size: int, dtype,
               quant: Optional[str] = None, slots: int = 0):
        """``slots``: the tracked sequences, each of which owns one state
        slot in every state layer (a model without state layers has none)."""
        layers = len(cfg.attention_layers)      # a state layer owns no pages
        scan = {}
        mixer = state_mixer(cfg)
        if mixer is not None:
            if quant is not None or cfg.mla:
                raise NotImplementedError(
                    "scan or conv layers beside kv_quant or latent pages are "
                    "not built")
            n = len(cfg.state_layers)
            if mixer.conv_scope is not None:   # (a lightning layer: none)
                scan = dict(conv=jnp.zeros(
                    (n, slots, (mixer.taps(cfg) - 1) * mixer.channels(cfg)),
                    dtype))
            if cfg.scan_layers:
                from deepspeed_tpu.ops.ssm_scan import packed_state_shape
                scan["ssm"] = jnp.zeros((n, slots) + packed_state_shape(
                    cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32)
        if cfg.mla:
            if quant is not None:
                raise NotImplementedError(
                    "kv_quant over latent pages (kv_lora_rank) is not built")
            return cls(k=jnp.zeros((layers, num_blocks, 1, block_size,
                                    cfg.latent_page_dim), dtype), v=None)
        if kv_major_layout(cfg):
            shape = (layers, num_blocks, cfg.kv_heads, cfg.head_dim,
                     block_size)
        else:
            shape = (layers, num_blocks, cfg.kv_heads, block_size,
                     cfg.head_dim)
        if cfg.block_topk:
            # a selection by blocks: the pooled keys page for page beside
            # the keys (class docstring, ``ki``)
            geo = cfg.block_geometry
            if (quant is not None or kv_major_layout(cfg)
                    or block_size % geo.block or block_size % geo.stride):
                raise NotImplementedError(
                    f"a selection by blocks (block_topk) keeps pooled keys "
                    f"beside row-major unquantised pages that hold whole "
                    f"blocks: not built with kv_quant, heads that are no "
                    f"multiple of 128 wide, or a page of {block_size} "
                    f"positions under blocks of {geo.block}")
            scan["ki"] = jnp.zeros(
                (layers, num_blocks, block_size // geo.stride, cfg.kv_heads,
                 cfg.head_dim), dtype)
        if quant is None:
            vshape = shape
            if cfg.value_dim != cfg.head_dim:      # a value of its own width
                vshape = shape[:2] + _page_shape(cfg, block_size)[1]
            return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(vshape, dtype),
                       **scan)
        if cfg.value_dim != cfg.head_dim:
            raise NotImplementedError(
                "kv_quant beside a value width of its own (v_head_dim) is "
                "not built")
        if quant != "int8":
            raise ValueError(f"unsupported kv_quant {quant!r}; use 'int8'")
        sshape = (layers, num_blocks, cfg.kv_heads, block_size)
        return cls(k=jnp.zeros(shape, jnp.int8),
                   v=jnp.zeros(shape, jnp.int8),
                   k_scale=jnp.zeros(sshape, jnp.float32),
                   v_scale=jnp.zeros(sshape, jnp.float32))

    @classmethod
    def create_latent_groups(cls, cfg: GPTConfig, nb_global: int,
                             nb_window: int, block_size: int, dtype):
        """Latent pools of a model with window and global layers, a pool a
        page group (class docstring): ``nb_global`` pages for each global
        layer in ``k`` (and in ``ki`` where they keep index keys),
        ``nb_window`` for each window layer in ``kw``."""
        kinds = [cfg.window_for_layer(i) is not None
                 for i in range(cfg.num_layers)]
        g, w = (cfg.for_layer(kinds.index(False)),
                cfg.for_layer(kinds.index(True)))

        def pool(layers, pages, width):
            return jnp.zeros((1, layers * pages, 1, block_size, width), dtype)
        return cls(
            k=pool(kinds.count(False), nb_global, g.latent_page_dim), v=None,
            kw=pool(kinds.count(True), nb_window, w.latent_page_dim),
            ki=(pool(kinds.count(False), nb_global, g.index_head_dim)
                if g.index_topk else None))

    @classmethod
    def create_grouped(cls, cfg: GPTConfig, nb_global: int, nb_window: int,
                       block_size: int, dtype):
        """The pool of a model with window AND global layers: one flat
        row-major array ``[1, pages, nkv, block_size, head_dim]`` (kv-major:
        ``[.., head_dim, block_size]``) holding
        ``nb_global`` pages for each global layer and then ``nb_window`` for
        each window layer (``kv_page_layout`` says where each layer's
        begin), so that a window layer does not keep what it will never
        read again (ragged.py, the window group's ring).  Where the groups'
        pages differ in shape (``kv_groups_split``): a pool a group, ``k`` /
        ``v`` of the global layers' pages and ``kw`` / ``vw`` of the window
        layers', each group's K and V at its own heads and widths."""
        n_window = sum(cfg.window_for_layer(i) is not None
                       for i in range(cfg.num_layers))
        if kv_groups_split(cfg):
            kinds = [cfg.window_for_layer(i) is not None
                     for i in range(cfg.num_layers)]

            def pools(window, pages):
                lc = cfg.for_layer(kinds.index(window))
                if kv_major_layout(lc) != kv_major_layout(cfg):
                    raise NotImplementedError(
                        "window and global layers whose head widths fall "
                        "on either side of the 128-lane rule (one kv-major, "
                        "one not) are not built: the step programs write "
                        "both groups in one page layout")
                return [jnp.zeros((1, kinds.count(window) * pages) + shape,
                                  dtype)
                        for shape in _page_shape(lc, block_size)]
            (k, v), (kw, vw) = (pools(False, nb_global),
                                pools(True, nb_window))
            return cls(k=k, v=v, kw=kw, vw=vw)
        pages = ((cfg.num_layers - n_window) * nb_global
                 + n_window * nb_window)
        page = ((cfg.head_dim, block_size) if kv_major_layout(cfg)
                else (block_size, cfg.head_dim))
        shape = (1, pages, cfg.kv_heads) + page
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _page_shape(cfg: GPTConfig, block_size: int):
    """(a K page's shape, a V page's) of an ordinary layer at ``cfg``'s (its
    view's) heads and widths: ``[nkv, block_size, width]``, kv-major
    ``[nkv, width, block_size]``."""
    km = kv_major_layout(cfg)
    return tuple((cfg.kv_heads,) + ((w, block_size) if km
                                    else (block_size, w))
                 for w in (cfg.head_dim, cfg.value_dim))


class _KVPool(PagedKVCache):
    """A ``PagedKVCache`` as a step program holds it, ONE value of the same
    fields: the paged arrays as their flat ``[layers * pages, ...]`` views,
    the state pools as they are.  Opened once from the cache and closed once
    back into it; in between it is what the cores take and return, what the
    bursts carry through ``lax.scan`` (a pytree whose leaves come in the
    fields' order) and what ``_layer`` updates IN PLACE through the donated
    cache buffer.  Never rebuild the whole pool (a jnp.stack of per-layer
    copies costs a full cache rewrite per step).  The reshapes are free, and
    nothing downstream slices, casts or re-lays the pool (``PagedKVCache``),
    so an unquantised pool has to be in the compute dtype already, as the
    engine creates it.  Scope ``kv_pool``, like everything that only moves
    the pool."""
    __slots__ = ()

    @classmethod
    def open(cls, cache: PagedKVCache, cfg: GPTConfig):
        if not cache.quantized and cache.k.dtype != jnp.dtype(
                cfg.dtype or jnp.float32):
            raise ValueError(
                f"KV pool is {cache.k.dtype} but the model computes in "
                f"{cfg.dtype}: create the pool in the compute dtype")
        with jax.named_scope("kv_pool"):
            return cls(**{
                n: a if a is None or n in ("ssm", "conv")
                else a.reshape((-1,) + a.shape[2:])
                for n, a in cache._asdict().items()})

    def close(self, cache: PagedKVCache) -> PagedKVCache:
        """Back into ``cache``, the one it was opened from (the page groups'
        own arrays first, as the programs have closed it since they had
        them)."""
        def shaped(n):
            a = getattr(self, n)
            return a if a is None else a.reshape(getattr(cache, n).shape)
        with jax.named_scope("kv_pool"):
            return cache._replace(ssm=self.ssm, conv=self.conv, **{
                n: shaped(n)
                for n in ("kw", "ki", "k", "v", "k_scale", "v_scale", "vw")})


class _LayerPages(NamedTuple):
    """What one attention layer attends over: the arrays of the pool its
    pages lie in (``_layer_pages``), the index keys beside them for a layer
    that selects, and its slots' block table, to which each kind of step
    adds the layer's first page where in its program that add belongs.  The
    fields' order is the operand order of the attention functions jitted
    over it."""
    k: jax.Array
    v: Optional[jax.Array]
    ki: Optional[jax.Array]
    table: jax.Array
    k_scale: Optional[jax.Array]
    v_scale: Optional[jax.Array]
    sink: Optional[jax.Array] = None     # the layer's sink logits [heads]

    def at(self, base):
        return self._replace(table=self.table + base)

    @property
    def scales(self):
        """Scale kwargs of the attention ops for an int8 pool, and the sink
        of a layer that has one."""
        out = ({} if self.k_scale is None
               else dict(k_scale=self.k_scale, v_scale=self.v_scale))
        return out if self.sink is None else dict(out, sink=self.sink)


def _layer_pages(cfg: GPTConfig, kv_layout, pool: _KVPool, li: int):
    """THE answer to "attention layer ``li``: which arrays of the pool, its
    first page there, which of the step's block tables", as ``(K field, V
    field, first page, page group)``.  One page group (``kv_layout`` None):
    the layer's place among the layers that own pages (a scan or conv layer
    writes none) times the pages each holds, in ``k`` / ``v``, under table
    0.  Two (``kv_page_layout``'s entry): in ``kw`` / ``vw`` for a window
    layer of a pool with arrays a group (latent pages, which have no V;
    ordinary heads whose groups differ, ``kv_groups_split``), else in ``k``
    / ``v``."""
    if kv_layout is None:
        layers = cfg.attention_layers
        return ("k", "v",
                layers.index(li) * (pool.k.shape[0] // len(layers)), 0)
    base, grp = kv_layout[li]
    if grp == 1 and pool.kw is not None:
        return "kw", "vw" if pool.vw is not None else "v", base, grp
    return "k", "v", base, grp


def _norm(p, x, cfg):
    from deepspeed_tpu.ops import layer_norm, rms_norm
    from deepspeed_tpu.ops.norms import LN_EPS, RMS_EPS
    if cfg.use_rmsnorm:
        return rms_norm(x, p["scale"], eps=cfg.norm_eps or RMS_EPS)
    return layer_norm(x, p["scale"], p["bias"], eps=cfg.norm_eps or LN_EPS)


def _mlp(p, x, cfg, mesh=None):
    # TP layout (parallel/partition.py DEFAULT_RULES): wi/wg shard the mlp
    # dim (column-parallel), wo shards the contraction (row-parallel) —
    # wspec keeps the quantized kernel engaged per shard
    h = _wmm(x, p["wi"], x.dtype, mesh=mesh, wspec="col")
    if cfg.mlp_bias:
        h = h + p["bi"].astype(x.dtype)
    if cfg.gated_mlp:
        h = mlp_activation(cfg.gate_act)(_wmm(x, p["wg"], x.dtype,
                                              mesh=mesh, wspec="col")) * h
    else:
        h = mlp_activation(cfg.activation)(h)
    y = _wmm(h, p["wo"], x.dtype, mesh=mesh, wspec="row")
    if cfg.mlp_bias:
        y = y + p["bo"].astype(x.dtype)
    return y


def _block_residual(blk, x, h, attn_delta, cfg, mesh=None, live=None,
                    stats=None, routes=None, experts=None, hc=None):
    """Close out one block given the normed input ``h`` and the attention
    branch output: sequential (x+attn, then MLP on a fresh norm) or falcon/phi
    parallel residual (attn and MLP both read the shared/paired input norms) —
    the single source of truth for BOTH the ragged prefill and paged decode
    loops.  ``live``/``stats``/``routes``/``experts`` go to an MoE layer
    (``_ffn``).  ``hc`` (the step's ``_HyperMix``): ``x [N, n, H]`` is a
    multi-stream residual that already holds the attention branch
    (``_layer`` writes it back under ``attn_out``), and the FFN reads and
    writes it through the layer's second hyper-connection."""
    if hc is not None:
        xin, maps = hc.open(blk["hc_ffn"], x)
        f = _ffn(blk, _norm(blk["Norm_1"], xin, cfg), cfg, mesh=mesh,
                 live=live, stats=stats, routes=routes, experts=experts)
        return hc.close(x, f, maps)
    if cfg.parallel_block:
        h_mlp = _norm(blk["Norm_1"], x, cfg) if cfg.parallel_norms == 2 else h
        return x + attn_delta + _ffn(blk, h_mlp, cfg, mesh=mesh)
    x = x + _scale_branch(attn_delta, cfg)
    f = _ffn(blk, _norm(blk["Norm_1"], x, cfg), cfg, mesh=mesh, live=live,
             stats=stats, routes=routes, experts=experts)
    if cfg.sandwich_norm:
        f = _norm(blk["post_ffn_norm"], f, cfg)
    return x + _scale_branch(f, cfg)


def _w(p, dtype):
    """Weight accessor: dequantize a ``quantize_weight`` (int8) or
    ``quantize_weight4`` (nibble-packed) store leaf at its USE SITE
    (reference quantized_linear.py:205 matmul-time dequant — the
    full-precision tensor exists only transiently inside the layer that
    consumes it), or cast a plain array."""
    from deepspeed_tpu.ops.quantization import (dequantize_weight,
                                                dequantize_weight4,
                                                is_quantized_weight,
                                                is_quantized_weight4)
    if is_quantized_weight(p):
        return dequantize_weight(p, dtype)
    if is_quantized_weight4(p):
        return dequantize_weight4(p, dtype)
    return p.astype(dtype)



def _wmm(x, p, dtype, mesh=None, wspec=None):
    """``x @ W`` routing 2-D quantized stores through the quantized-weight
    Pallas kernels (ops/wq_matmul.py: int8 → half the bf16 weight HBM
    traffic; nibble-packed int4 → a quarter); everything else dequantizes
    at the use site (_w).  Leading dims of x are flattened for the kernel.

    ``wspec`` names the store's tensor-parallel layout ("col" = output dim
    sharded, "row" = contraction dim sharded) so a tp mesh keeps the
    kernel engaged per shard via a manual shard_map (wq_matmul_tp) —
    GSPMD cannot partition the Mosaic custom call itself.  wspec=None
    under a mesh stays on the partitioned dequant-matmul path."""
    from deepspeed_tpu.ops.quantization import quantized_codes
    from deepspeed_tpu.ops import wq_matmul as wqm
    vv = quantized_codes(p) if isinstance(p, dict) else None
    if vv is not None and vv.ndim == 2 and (mesh is None
                                            or wspec is not None):
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1]).astype(dtype)
        if mesh is None:
            y = wqm.wq_any(x2, p)
        else:
            y = wqm.wq_matmul_tp(x2, p, mesh, wspec)
        return y.reshape(lead + (vv.shape[1],))
    return x.astype(dtype) @ _w(p, dtype)


def _logits_out(params, bb, x, cfg, dtype, mesh=None):
    """Final unembed + optional bias — the ONE implementation shared by the
    ragged prefill, paged decode, and speculative verify cores.  Untied
    lm_head rides the W8A16 kernel; tied tables ride its transposed variant
    (same [V, H] dim-0-grouped store the embed gather needs)."""
    from deepspeed_tpu.ops.quantization import is_quantized_weight
    if cfg.tie_embeddings:
        wte = bb["wte"]
        if is_quantized_weight(wte):
            from deepspeed_tpu.ops.wq_matmul import wq_matmul_t, wq_matmul_tp
            lead = x.shape[:-1]
            x2 = x.reshape(-1, x.shape[-1]).astype(dtype)
            y = (wq_matmul_tp(x2, wte, mesh, "tcol") if mesh is not None
                 else wq_matmul_t(x2, wte))
            logits = y.reshape(lead + (y.shape[-1],)).astype(jnp.float32)
        else:
            logits = (x.astype(dtype) @ _w(wte, dtype).T
                      ).astype(jnp.float32)
        if logits.shape[-1] != cfg.vocab_size:
            # vocab-padded store (engine packer pads odd vocabs like GPT-2's
            # 50257 to the quantization group so the table can quantize and
            # the transposed kernel can tile); padded rows are zero weight
            logits = logits[..., :cfg.vocab_size]
    else:
        logits = _wmm(x, params["lm_head"], dtype,
                      mesh=mesh, wspec="col").astype(jnp.float32)
    if cfg.logits_divisor:
        logits = logits / cfg.logits_divisor
    if cfg.unembed_bias:
        logits = logits + params["lm_head_bias"].astype(jnp.float32)
    return logits


def _embed(wte, tokens, dtype):
    """Row-gather from a possibly int8-quantized table: gather codes AND the
    gathered rows' group scales — dequant cost scales with the tokens
    actually read, never the vocab."""
    from deepspeed_tpu.ops.quantization import (_store_dim,
                                                is_quantized_weight,
                                                is_quantized_weight4)
    if is_quantized_weight(wte):
        v, s = wte["v"], wte["s"]
        if _store_dim(wte) != 0:
            raise ValueError(
                "embedding stores must group along dim 0 (vocab) — the "
                f"row gather needs per-row scales; got codes {v.shape} "
                f"vs scales {s.shape}")
        g = v.shape[0] // s.shape[0]
        return (v[tokens].astype(jnp.float32) * s[tokens // g]).astype(dtype)
    if is_quantized_weight4(wte):
        # nibble-packed rows: byte r//2 holds row r in nibble r%2.  tokens
        # may be any rank (the speculative verify core gathers [S, G])
        from deepspeed_tpu.ops.quantization import unpack_nibbles
        p, s = wte["v4"], wte["s"]
        lo, hi = unpack_nibbles(p[tokens // 2])
        q = jnp.where((tokens % 2 == 0)[..., None], lo, hi)
        g = 2 * p.shape[0] // s.shape[0]
        return (q.astype(jnp.float32) * s[tokens // g]).astype(dtype)
    return wte.astype(dtype)[tokens]


def _embed_tokens(bb, tokens, positions, cfg):
    """Token rows (+ learned positions where the model has them): the start
    of every step program, under the ``embed`` scope."""
    dtype = cfg.dtype
    with jax.named_scope("embed"):
        x = _embed(bb["wte"], tokens, dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.embed_scale, dtype)
        if cfg.embed_norm:
            x = _norm(bb["embed_norm"], x, cfg)
        if not cfg.use_rope and not cfg.use_alibi:
            x = x + bb["wpe"].astype(dtype)[positions]
        if cfg.hc:          # every residual stream starts as the token's row
            x = jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (
                cfg.hc_mult, x.shape[-1]))
    return x


def _write_plan(block_table, row_slot, row_pos, block_size: int,
                rows_per_slot: int, km: bool):
    """Where a step's new k/v rows go in a layer's pages: what ``_kv_write``
    needs besides the rows, the same for every layer of a page group and so
    computed once a step (``ops/kv_append.py:append_plan``, which says what
    a plan holds).  Scope ``kv_write``.

    Row ``n`` of the step's [N, nkv, hd] k/v belongs to slot ``row_slot[n]``
    (``S``, out of range, for a pad or an inactive slot: such a row is
    dropped, never clamped to a real slot, whose rows it would overwrite)
    and holds position ``row_pos[n]``.

    The plan is by UNIT for both page layouts: a slot's rows are one run of
    the step's rows, in position order, for contiguous positions (ragged.py
    packs them so; the dense [S, G] verify layout and the one-row decode
    are the same thing), at most ``rows_per_slot`` (static: 1 in the decode
    programs and the burst, the chunk width in ``ragged_forward``, ``G`` in
    the verify layout), so it touches a bounded number of units (a kv-major
    page [nkv, hd, bs]; 16 tokens of a standard page [nkv, bs, hd]), and the
    candidates that hold a row of the step come first.  For standard pages
    it also holds the write by row (page, offset, live), which the XLA form
    scatters."""
    from deepspeed_tpu.ops.kv_append import append_plan
    with jax.named_scope("kv_write"):
        return append_plan(block_table, row_slot, row_pos, block_size,
                           rows_per_slot, km)


def _kv_write(flat_k, flat_v, flat_ks, flat_vs, k, v, plan, base, km,
              mesh=None):
    """Paged KV append (reference linear_blocked_kv_rotary): one layer's new
    ``k``/``v`` rows [N, nkv, hd] go into their pages of the flat
    [L * NB, nkv, ...] pool views (quantised first when the pool is int8),
    ``base = li * NB`` being the layer's first page (an int, or a traced
    scalar: a step program traces and lowers ONE write a page group and
    every layer calls it, as ``attend``) and ``plan`` the step's
    ``_write_plan``.  Scope ``kv_write``.  The write itself is op
    ``paged_kv_append`` (ops/kv_append.py), in two forms.

    Every form is shaped for the pool's layout before its own cost: the
    pool is row-major as created, the Pallas kernels are custom calls that
    demand it so, and a write that prefers another layout makes the compiler
    re-lay the whole pool on the way into and out of every step program,
    keep a second copy through the burst's loop and copy every layer's pages
    for the kernel (a scatter of [nkv, hd] windows at (page, :, offset) did:
    XLA's TPU scatter wants scattered dimensions major and window dimensions
    minor, i.e. the pool token-major).  tests/test_chip_compile.py holds the
    compiled programs to it.

    The kernel (PR 48; a TPU, k and v of several kv heads in bfloat16 or
    float32): the pools are aliased operands of ONE call a layer, the grid
    walks the plan's candidate units, and a unit that holds a row of the
    step comes in, has its tokens ``lo <= t < hi`` replaced under a mask and
    goes back, its bytes moved once; the step's rows are read as they are.
    Both layouts and every ``rows_per_slot`` take it, the one-row decode
    write too (PERF.md section 6, PR 48: 16-token units make that cheaper
    than the row scatter).

    The XLA forms (the numeric reference; a CPU, an int8 pool with its scale
    pools, a one-head latent or index-key pool, a shape ``supported``
    declines), as PR 27 left them.  Standard pages: a scatter of [hd] rows
    at (page, head, offset), i.e. rows of the pool seen as [L * NB * nkv *
    bs, hd], which has no layout but row-major.  ``N * nkv`` updates instead
    of ``N``, each ~65-90 ns on a v5e whatever its size: 0.8 ms of a 20.5 ms
    decode step and 8.6 ms of a 57 ms 512-token mixed step at Mistral-7B's
    widths (PERF.md, PR 27); with one head, a latent pool's, it is one
    update a row, which is why such a pool keeps it.  kv-major pages: a
    token is a lane of [nkv, hd, bs] and a scatter per lane is no row-major
    write, so whole candidate pages are read, merged with the new rows by a
    select, and scattered back by page index: a window that is the page
    leaves the page index as the only scattered dimension.  On standard
    pages that costs a decode step 4 ms more than the row scatter (same PR),
    which is why they did not share it.

    With a ``tp`` axis the pool's kv-head dim is sharded and the write runs
    per shard under shard_map, as the kernels do: a head's rows go to that
    head's pages and nowhere else.  Left to the partitioner, the row view
    folds the sharded head dim into the scattered one, and every chip
    all-gathers the whole pool each step."""
    quant = flat_ks is not None
    write = functools.partial(_kv_write_local, km=km)
    if flat_v is None:                 # latent pages: one pool, one row kind
        with jax.named_scope("kv_write"):
            return write((flat_k,), k, None, plan, base) + (None, None, None)
    pools = (flat_k, flat_v) + ((flat_ks, flat_vs) if quant else ())
    if (mesh is not None and mesh.shape.get("tp", 1) > 1
            and k.shape[1] % mesh.shape["tp"] == 0):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def heads(a):
            return P(None, "tp", *(None,) * (a.ndim - 2))
        pool_specs = tuple(heads(p) for p in pools)
        write = shard_map(
            write, mesh=mesh,
            in_specs=(pool_specs, heads(k), heads(v),
                      jax.tree.map(lambda _: P(), plan), P()),
            out_specs=pool_specs, check_vma=False)
    with jax.named_scope("kv_write"):
        pools = write(pools, k, v, plan, jnp.asarray(base, jnp.int32))
    return pools if quant else pools + (None, None)


def _kv_write_local(pools, k, v, plan, base, *, km):
    """``_kv_write`` on the kv heads at hand: (k, v[, k_scale, v_scale])
    pools in, the same out."""
    from deepspeed_tpu import ops
    new = (k,) if v is None else (k, v)
    if len(pools) == 4:
        k, ks = quantize_kv_token(k)                  # [N,nkv,hd], [N,nkv]
        v, vs = quantize_kv_token(v)
        new = (k, v, ks, vs)
    return ops.paged_kv_append(pools, new, plan, base, kv_major=km)


def _kv_writer(km: bool, mesh=None):
    """``_kv_write`` as ONE jitted function, made once a step program and
    called by every attention layer of it with the layer's first page as an
    operand: the kernel is traced once and lowered once a page group (the
    pools' shapes), not once a layer (what ``attend`` is to the attention
    kernels).  A jit of the trace's own, not of the module: the op chooses
    its implementation while tracing.  The rows cross into it as [N, nkv *
    hd], the projections' own output: handed over as [N, nkv, hd] the
    compiler settles each projection's form before it sees what reads it,
    re-lays the v projection's weights for it and, in the burst, keeps those
    copies across the loop (8 MB a layer at Mistral-7B's widths)."""
    @functools.partial(jax.jit, static_argnames="heads")
    def _kv_write_rows(flat_k, flat_v, flat_ks, flat_vs, k, v, plan, base, *,
                       heads):
        def rows(x):
            return None if x is None else x.reshape(x.shape[0], heads, -1)
        return _kv_write(flat_k, flat_v, flat_ks, flat_vs, rows(k), rows(v),
                         plan, base, km, mesh=mesh)

    def write(flat_k, flat_v, flat_ks, flat_vs, k, v, plan, base):
        return _kv_write_rows(
            flat_k, flat_v, flat_ks, flat_vs, k.reshape(k.shape[0], -1),
            None if v is None else v.reshape(v.shape[0], -1), plan, base,
            heads=k.shape[1])
    return write


def _head(params, bb, x, cfg, mesh=None, rows=None):
    """Final norm + unembed (of ``rows`` of x only, where given: the rows
    that carry a next-token distribution).  Scope ``head``."""
    with jax.named_scope("head"):
        if cfg.hc:          # the streams summed (of the rows asked for)
            if rows is not None:
                x, rows = x[rows], None
            x = x.sum(-2)
        x = _norm(bb["final_norm"], x, cfg)
        if rows is not None:
            x = x[rows]
        return _logits_out(params, bb, x, cfg, cfg.dtype, mesh=mesh)


def _sample_next(sample_fn, logits, rng, temperature, top_p, served,
                 prev_tokens):
    """In-graph sampling + device feedback: slots flagged ``served`` get
    their sampled token written into ``prev_tokens``.  Scope ``sample``.
    Returns (prev_tokens', rng')."""
    with jax.named_scope("sample"):
        rng, sub = jax.random.split(rng)
        nxt = sample_fn(logits, sub, temperature=temperature, top_p=top_p)
        return jnp.where(served, nxt.astype(jnp.int32), prev_tokens), rng


def _moe_route(mp, x, cfg):
    """An expert layer's router: (expert ids ``[N, k]`` over all the
    router's experts, weights ``[N, k]``), softmax (Mixtral) or sigmoid
    (afmoe: the matmul in float32, a selection-only bias, the chosen
    scores renormalised and scaled)."""
    gate = _w(mp["gate"], x.dtype)
    if cfg.moe_router == "sigmoid":
        from deepspeed_tpu.moe.sharded_moe import sigmoid_topk
        logits = jnp.dot(x, gate, preferred_element_type=jnp.float32)
        return sigmoid_topk(logits, cfg.moe_k, mp.get("expert_bias"),
                            cfg.moe_route_norm, cfg.moe_route_scale,
                            cfg.moe_route_eps)
    from deepspeed_tpu.moe.sharded_moe import dropless_topk
    _, idx, w = dropless_topk(x @ gate, cfg.moe_k)
    return idx, w


def _experts_fn(cfg, with_stats: bool):
    """The expert FFN of ``cfg`` (moe/layer.py:_expert_ffn_ragged with the
    grouped GEMM left to the registry) as ONE jitted function, made once a
    step program and called by every expert layer of it: the positions by
    count, the row gather, the two kernel calls and the combine's gathers
    are traced once and lowered once a program, not once a layer (what
    ``attend`` is to the attention kernels).  A jit of the trace's own, not of the module: the
    ops choose their implementation while tracing."""
    from deepspeed_tpu.moe.layer import _expert_ffn_ragged
    return jax.jit(named_partial(
        _expert_ffn_ragged, expert_offset=cfg.expert_offset,
        num_experts=cfg.num_experts, with_stats=with_stats, impl=None))


def _ffn(blk, x, cfg, mesh=None, live=None, stats=None, routes=None,
         experts=None):
    """Dense MLP or MoE block body on FLAT tokens [N, H] — MoE routes through
    the dropless ragged grouped GEMM (moe/layer.py), which fits serving
    exactly: the ragged token set per step IS the ragged expert batch
    (reference inference/v2 MoE gather/scatter + cutlass grouped GEMM,
    model_implementations/mixtral).

    An expert layer is the same function as the flax module's
    (moe/layer.py) on the same parameters, in three scopes inside the
    caller's ``mlp``: ``moe_route`` (``_moe_route``), ``moe_experts`` (each
    assignment's position by count, the rows gathered to their experts,
    grouped GEMMs over the experts held here: the registry's choice, the
    Pallas kernel on a TPU, since nothing here is differentiated; each
    token's k products gathered back and summed in float32) and, where the
    model has a shared expert, ``moe_shared``.
    ``live [N]`` marks the rows that are tokens (not padding, not an idle
    slot): the others' assignments are dropped with those to experts not
    held.  ``stats``, a list, takes the layer's counter vector, and
    ``routes`` the experts it chose ``[N, k]`` (``put(...,
    with_routes=True)``: what a comparison with a reference's routing
    needs).  ``experts``: the step program's ``_experts_fn`` (a caller
    with one expert layer to run may leave it out)."""
    if "moe" not in blk:
        return _mlp(blk["MLP_0"], x, cfg, mesh=mesh)
    mp = blk["moe"]
    with jax.named_scope("moe_route"):
        idx, w = _moe_route(mp, x, cfg)
        if routes is not None:
            routes.append(idx)
    with jax.named_scope("moe_experts"):
        y = (experts or _experts_fn(cfg, stats is not None))(
            x, idx, w, _w(mp["wi"], x.dtype), _w(mp["wo"], x.dtype),
            _w(mp["wge"], x.dtype) if "wge" in mp else None, live=live)
        if stats is not None:
            y, st = y
            stats.append(st)
    if cfg.moe_shared_dim:
        with jax.named_scope("moe_shared"):
            y = y + (jax.nn.silu(x @ _w(mp["shared_wg"], x.dtype))
                     * (x @ _w(mp["shared_wi"], x.dtype))
                     ) @ _w(mp["shared_wo"], x.dtype)
    return y


def _proj3(x, p, dtype, mesh, wspec):
    """``x [..., H] @ W [H, k, d] → [..., k, d]`` keeping a quantized store
    on the kernel path: a dim-0-grouped 3-D store flattens to a free 2-D
    view (wq_matmul.store_as_2d) so QKV projections ride the same
    int8/int4 stream as the MLP (round-4 verdict item 3: a large fraction
    of decode weight traffic was still bf16).  Non-quantized weights take
    the plain einsum."""
    from deepspeed_tpu.ops import wq_matmul as wqm
    from deepspeed_tpu.ops.quantization import quantized_codes
    vv = quantized_codes(p) if isinstance(p, dict) else None
    if vv is not None and vv.ndim == 3:
        v2d = wqm.store_as_2d(p)
        # dim-0 grouping only: codes' trailing dims are the output dims
        if v2d is not None and p["s"].shape[1:] == vv.shape[1:]:
            y = _wmm(x, v2d, dtype, mesh=mesh, wspec=wspec)
            return y.reshape(y.shape[:-1] + vv.shape[1:])
    lead = x.shape[:-1]
    w = _w(p, dtype)
    y = x.astype(dtype).reshape(-1, x.shape[-1]) @ w.reshape(w.shape[0], -1)
    return y.reshape(lead + w.shape[1:])


def _lora_qv(q, v, h, lora, row_ids, li):
    """Per-row LoRA deltas on the q and v projections for layer ``li`` —
    the multi-tenant batched-gather path (ops/lora_matmul.py): every row
    carries its own adapter id and the whole mixed-adapter batch rides ONE
    op call.  ``lora`` holds the pool's packed tables (``a_q``/``b_q``/
    ``a_v``/``b_v`` [slots, L, …] + per-slot ``scale``); slot 0 is the
    base-model identity (zero pages, scale 0), so base rows pay a zero
    delta instead of a branch.  Applied pre-rope (rotation acts on the
    adapted projection), matching delta-on-the-projection LoRA
    semantics."""
    from deepspeed_tpu import ops
    lead = h.shape[:-1]
    h2 = h.reshape(-1, h.shape[-1])
    ids = row_ids.reshape(-1)
    scale = lora["scale"]
    dq = ops.lora_matmul(h2, lora["a_q"][:, li], lora["b_q"][:, li],
                         ids, scale)
    dv = ops.lora_matmul(h2, lora["a_v"][:, li], lora["b_v"][:, li],
                         ids, scale)
    return q + dq.reshape(q.shape), v + dv.reshape(v.shape)


def _qkv(ap, h, cfg, mesh=None):
    """q/k/v projections with optional biases (qwen2/gpt2 checkpoints).
    TP layout: the heads dim shards (column-parallel), so quantized stores
    route via wspec="col"."""
    dtype = h.dtype
    q = _proj3(h, ap["wq"], dtype, mesh, "col")
    k = _proj3(h, ap["wk"], dtype, mesh, "col")
    v = _proj3(h, ap["wv"], dtype, mesh, "col")
    if cfg.qkv_bias:
        q = q + ap["bq"].astype(dtype)
        k = k + ap["bk"].astype(dtype)
        v = v + ap["bv"].astype(dtype)
    return q, k, v


def _qk_norm_gate(ap, h, q, k, cfg, mesh=None):
    """afmoe attention's two extras, both inside ``attn_qkv``: RMSNorm on
    each query and key head (before RoPE), and the output gate's projection
    ``sigmoid(Wg h) [.., heads, d]`` (None where the model has none), which
    ``_attn_out`` multiplies into the attention output."""
    if cfg.qk_norm:
        from deepspeed_tpu.models.gpt import head_norm
        q = head_norm(q, ap["q_norm"], cfg)
        k = head_norm(k, ap["k_norm"], cfg)
    gate = None
    if cfg.attn_gate:
        gate = jax.nn.sigmoid(_proj3(h, ap["wgate"], h.dtype, mesh, "col"))
    return q, k, gate


def _attn_geometry(cfg: GPTConfig):
    """How the attention ops see a layer's heads: (kv heads, query/key
    width, value width, the ops' latent keyword).  Latent attention is
    absorbed into MQA form: every query head in one group over the one
    latent row, which is key (its whole padded width) and value (the
    latent)."""
    if cfg.mla:
        return (1, cfg.latent_page_dim, cfg.kv_lora_rank,
                {"v_dim": cfg.kv_lora_rank})
    return cfg.kv_heads, cfg.head_dim, cfg.value_dim, {}


def _attn_scale(cfg: GPTConfig):
    """The ops' ``scale``: the configured one, None for their default
    ``width ** -0.5``; a latent head's is said out loud
    (``models/gpt.py:latent_softmax_scale``), since the width the ops see is
    the padded page row's and not the head's."""
    if cfg.mla:             # (with YaRN's mscale where the rope has it)
        return latent_softmax_scale(cfg)
    return cfg.attn_scale


def _alibi(cfg: GPTConfig):
    """The attention ops' ``alibi_slopes`` of a layer (None without)."""
    if not cfg.use_alibi:
        return None
    from deepspeed_tpu.models.gpt import alibi_slopes
    return jnp.asarray(alibi_slopes(
        cfg.num_heads, _attn_geometry(cfg)[1], cfg.alibi_prescale))


def _mla_qkv(ap, h, positions, cfg: GPTConfig):
    """Latent attention's side of ``attn_qkv`` on rows ``h [N, H]`` at
    ``positions [N]`` (``cfg``: the layer's view, ``GPTConfig.for_layer``):
    the queries ABSORBED (``q_nope_h Wkvb_h[:, :nope]^T``,
    the latent's width, beside the rotated ``q_pe_h``) and the token's cache
    row ``[c_kv | k_pe]`` (normed latent, rotated shared key part), both
    padded with zeros to the page row's width, ``[N, nh, P]`` and
    ``[N, 1, P]``.  The score ``q_lat . c + q_pe . k_pe`` is then the
    published ``q_nope . k_nope + q_pe . k_pe`` by associativity, and the
    row is key and value at once.  The absorb product has its own scope,
    ``mla_absorb``.  Also the headwise gate ``sigmoid(Wg h) [N, nh, 1]``
    (None without one) and the query latent ``cq [N, q_lora_rank]`` (``h``
    itself without one), which the indexer reads."""
    from deepspeed_tpu.models.gpt import (mla_latent, mla_query,
                                          mla_query_latent, mla_split)
    dtype = h.dtype
    nope = mla_split(cfg)[0]
    cq = h
    if cfg.q_lora_rank:
        cq = mla_query_latent(_w(ap["wq_a"], dtype), ap["q_norm"], h, cfg)
    q_nope, q_pe = mla_query(
        _w(ap["wq_b" if cfg.q_lora_rank else "wq"], dtype), cq, positions,
        cfg)
    c_kv, k_pe = mla_latent(_w(ap["wkv_a"], dtype), ap["kv_norm"], h,
                            positions, cfg)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("tnd,rnd->tnr", q_nope,
                           _w(ap["wkv_b"], dtype)[..., :nope])
    pad = cfg.latent_page_dim - cfg.latent_dim

    def row(*parts):
        z = jnp.zeros(parts[0].shape[:-1] + (pad,), dtype)
        return jnp.concatenate(parts + ((z,) if pad else ()), -1)
    gate = None
    if cfg.attn_gate_headwise:
        gate = jax.nn.sigmoid(h @ _w(ap["wgate"], dtype))[..., None]
    return row(q_lat, q_pe), row(c_kv, k_pe)[:, None, :], gate, cq


def _index_rows(ap, h, cq, positions, cfg: GPTConfig):
    """The indexer's projections of rows ``h [N, H]`` (``cq``: their query
    latent): index queries ``[N, nI, dI]``, their weights ``[N, nI]``
    float32, and the rows' own index keys ``[N, 1, dI]`` as the index-key
    pool stores them.  Inside scope ``attn_index``."""
    from deepspeed_tpu.models.gpt import index_key, index_query
    dtype = h.dtype
    qi, wi = index_query(_w(ap["wq_idx"], dtype), _w(ap["ww_idx"], dtype),
                         cq, h, positions, cfg)
    ki = index_key(_w(ap["wk_idx"], dtype), ap["k_idx_norm_scale"],
                   ap["k_idx_norm_bias"], h, positions, cfg)
    return qi, wi, ki[:, None, :]


def _selected_attention(q, qi, wi, pages: _LayerPages, row_slot, row_pos,
                        cfg: GPTConfig, *, block_size: int,
                        max_rows: int = 1, rows=None):
    """A full layer's attention where the selection binds: index scores of
    the step's rows over their slots' index keys, the exact top
    ``index_topk`` of them (scope ``attn_index``, a list's sort
    ``index_select`` inside it), then attention over the selected rows of
    the latent pool and no others (scope ``selected_attention``); all of it
    inside scope ``attn_kernel``, whose time is a layer's attention whichever
    way it reads its keys.  ``pages``: the latent pool, the index keys and
    the global group's table ``[S, MB]``, the layer's first page added;
    ``row_slot``: ``S`` for a pad row.  -> ``[N, nh, kv_lora_rank]``.

    A mixed step (``rows``: its ``_MixedRows``, at most ``max_rows`` a
    slot) selects for its one-row slots (riding
    decode rows, whose contexts are the longest a step holds) apart from the
    slots that hold a prompt chunk: a chunk's rows are then scored and
    selected over their OWN contexts' width, not over the riders', and what
    a step costs does not follow which sequences happen to ride it.  The
    riders gather their rows, as a decode step's do.  A chunk's rows read
    their keys whichever way is cheaper at the contexts this step holds
    (``ops.sparse_index.masked_prefill``, decided in the program): the
    prefill kernel over the slot's pages with the selection as a mask
    (``threshold_mask``: each row's ``k``-th score by a search, no sort), or
    the same gather over the sorted list (``index_select``), which only
    that branch builds."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops.sparse_index import masked_prefill
    k_pages, ki_pages, table = pages.k, pages.ki, pages.table
    S, MB = table.shape
    k = min(cfg.index_topk, MB * block_size)
    q = q.astype(cfg.dtype)
    attn = dict(v_dim=cfg.kv_lora_rank, scale=_attn_scale(cfg))

    def scores(qi, wi, slots, pos, rows_a_slot):
        return ops.index_scores(qi, wi, ki_pages, table, slots, pos,
                                max_rows=rows_a_slot, impl=cfg.attn_impl)

    def gathered(q, picked, slots, pos):
        """Rows ``q`` of slots ``slots`` at positions ``pos`` over the pool
        rows their picks name."""
        with jax.named_scope("attn_index"), jax.named_scope("index_select"):
            # each picked position's page of its row's table: a compare and
            # a sum over the table's few columns, which fuse; a gather of
            # one int32 a pair is the slow way on this chip
            mine = table[jnp.minimum(slots, S - 1)]                # [n, MB]
            pages = jnp.sum(jnp.where(
                (picked // block_size)[:, :, None]
                == jnp.arange(MB, dtype=jnp.int32), mine[:, None, :], 0),
                axis=-1)
            pool_rows = pages * block_size + picked % block_size
            counts = jnp.where(slots < S, jnp.minimum(pos + 1, k), 0)
        return ops.selected_attention(q, k_pages, pool_rows, counts, **attn)

    with jax.named_scope("attn_kernel"):
        if rows is None:                   # a decode step: a row a slot
            with jax.named_scope("attn_index"):
                picked = ops.index_select(
                    scores(qi, wi, row_slot, row_pos, 1), k)       # [N, k]
            return gathered(q, picked, row_slot, row_pos)
        first = rows.first_row
        one_row = rows.q_counts == 1
        slot = jnp.minimum(row_slot, S - 1)
        alone = one_row[slot] & (row_slot < S)
        riders = jnp.where(one_row, jnp.arange(S), S)
        shared = jnp.where(alone, S, row_slot)
        with jax.named_scope("attn_index"):
            one = ops.index_select(
                scores(qi[first], wi[first], riders, row_pos[first], 1), k)
            reach = jnp.max(jnp.where((row_slot < S) & ~alone, row_pos + 1,
                                      0))
            many_scores = scores(qi, wi, shared, row_pos, max_rows)
        o_one = gathered(q[first], one, riders, row_pos[first])    # [S, ..]

        def masked():
            # the kernel takes bits, and a row's bits need its k-th largest
            # score and no list: no sort on this branch
            with jax.named_scope("attn_index"):
                keep = ops.threshold_mask(many_scores, k, width=reach,
                                          impl=cfg.attn_impl)
            with jax.named_scope("selected_attention"):
                # each slot's rows are one span of positions ending at its
                # kv_len; the riders' slots are told empty
                return ops.ragged_prefill_attention(
                    q[:, None], k_pages, None, table, rows.kv_len,
                    rows.kv_len - rows.q_counts,
                    jnp.where(one_row, 0, rows.q_counts), first,
                    max_q=max_rows, sel_mask=keep, impl=cfg.attn_impl,
                    **attn)[:, 0]

        def listed():
            with jax.named_scope("attn_index"):
                many = ops.index_select(many_scores, k, width=reach)
            return gathered(q, many, shared, row_pos)

        o_many = jax.lax.cond(masked_prefill(reach), masked, listed)
        o = jnp.where(alone[:, None, None], o_one[slot], o_many)
        return jnp.where((row_slot < S)[:, None, None], o, 0)


def _attn_proj(ap, o, gate, cfg, mesh=None):
    """Everything of ``attn_out`` before the sandwich norm: the gate, and
    for latent attention the second absorb product, ``o_lat_h Wkvb_h[:,
    nope:]`` (scope ``mla_absorb``): the attention ops returned ``s_h c``,
    the latent's width a head."""
    if cfg.mla:
        from deepspeed_tpu.models.gpt import mla_split
        with jax.named_scope("mla_absorb"):
            o = jnp.einsum("...nr,rnd->...nd", o, _w(ap["wkv_b"], o.dtype)[
                ..., mla_split(cfg)[0]:])
    return _attn_out(ap, o if gate is None else o * gate, cfg, mesh=mesh)


def _attn_out(ap, o, cfg, mesh=None):
    """Attention output projection ``o [..., k, d] @ wo [k, d, H]``.  The
    heads dim shards under TP (row-parallel: contraction sharded), so a
    dim-1-grouped quantized store flattens to a 2-D kernel view and rides
    wq_matmul_tp(mode="row")."""
    from deepspeed_tpu.ops import wq_matmul as wqm
    from deepspeed_tpu.ops.quantization import quantized_codes
    dtype = o.dtype
    p = ap["wo"]
    lead = o.shape[:-2]
    o2 = o.reshape(lead + (o.shape[-2] * o.shape[-1],))
    vv = quantized_codes(p) if isinstance(p, dict) else None
    if vv is not None:
        v2d = wqm.store_as_2d(p) if vv.ndim == 3 else None
        # only the dim-1-grouped flatten is a valid [k·d, H] contraction
        # view; dim-0-grouped wo stores (small-head models whose hd can't
        # group) dequantize at the use site instead
        if (v2d is not None
                and quantized_codes(v2d).shape[0] == o2.shape[-1]):
            y = _wmm(o2, v2d, dtype, mesh=mesh, wspec="row")
        else:
            y = o2 @ _w(p, dtype).reshape(-1, vv.shape[-1])
    else:
        w = _w(p, dtype)
        y = o2 @ w.reshape(-1, w.shape[-1])
    if cfg.attn_out_bias:
        y = y + ap["bo"].astype(dtype)
    return y


class _MixedRows(NamedTuple):
    """Where a mixed step's token rows sit, the same for every layer."""
    scat_slot: jnp.ndarray   # [N] slot of each token row; S for padding
    first_row: jnp.ndarray   # [S] the slot's first row of the flat batch
    kv_len: jnp.ndarray      # [S] context after the step
    q_counts: jnp.ndarray    # [S] rows the slot holds in this step


def _mixed_attention(q, rows: _MixedRows, pages: _LayerPages, *,
                     cfg: GPTConfig, Q: int, window, mesh):
    """Ragged blocked attention of a mixed step (reference blocked_flash +
    atom_builder): token-major ``q`` [N, nh, hd] over the layer's pages of
    the flat pool -> [N, nh, vd].  Each slot's rows are one contiguous span
    of the flat batch and of positions, and stay where they are: both
    kernels are told where a slot's rows begin.

    A slot's rows pick its kernel.  The prefill kernel walks the live
    (slot, q-chunk) items and DMAs only the pages each can causally see, but
    a chunk is up to 128 rows: a slot with ONE row (a decode row riding the
    step, a prompt's one-token tail) goes to the paged decode kernel, whose
    tile is that row.  Each kernel is told the other's slots are empty
    (length 0, count 0) and skips them outright; a row takes its slot's
    kernel's result."""
    from deepspeed_tpu import ops
    k_pages, v_pages, table = pages.k, pages.v, pages.table
    S = table.shape[0]
    N = q.shape[0]
    nh = cfg.num_heads
    nkv, hd, vd, latent = _attn_geometry(cfg)
    with jax.named_scope("attn_kernel"):
        valid = rows.scat_slot < S
        slot = jnp.where(valid, rows.scat_slot, 0)
        q = q.reshape(N, nkv, nh // nkv, hd).astype(cfg.dtype)
        pool = dict(alibi_slopes=_alibi(cfg), window=window,
                    scale=_attn_scale(cfg),
                    mesh=mesh, kv_major=kv_major_layout(cfg),
                    impl=cfg.attn_impl, **pages.scales, **latent)
        one_row = rows.q_counts == 1
        # (a latent window layer's kernels under a scope of their own, so
        # that a trace tells them from the global layers' in one program)
        with _window_latent_scope(cfg, window):
            o_one = ops.paged_attention(
                q[rows.first_row], k_pages, v_pages, table,
                jnp.where(one_row, rows.kv_len, 0), **pool)
            # each slot's rows are one span of positions ending at its
            # kv_len
            o_many = ops.ragged_prefill_attention(
                q, k_pages, v_pages, table, rows.kv_len,
                rows.kv_len - rows.q_counts,
                jnp.where(one_row, 0, rows.q_counts), rows.first_row,
                max_q=Q, **pool)
        o = jnp.where(one_row[slot, None, None],
                      o_one.reshape(S, nh, vd)[slot],
                      o_many.reshape(N, nh, vd))
        return jnp.where(valid[:, None, None], o, 0)


# ------------------------------------------------------- selection by blocks
# An attention layer that selects its keys by BLOCKS (``cfg.block_topk``,
# ops/block_select.py): plain GQA pages and, beside them, the pooled keys
# (``PagedKVCache.ki``).  Rows whose context is within ``block_dense_len``
# read every key through the two paged kernels, as any layer's do; rows past
# it choose ``block_topk`` blocks a KV head.  Rows under and over ride in one
# step and one program: the choice is by data.  Scopes, all inside
# ``attn_kernel``: ``attn_index`` (the pooled keys' upkeep, the block scores,
# the choice and its bits), ``block_attention`` (attention over the kept
# blocks, whichever way a row reads them).

BLOCK_SCORE_ROWS = 128    # rows of one slot a pass of the block scores: the
#                           [rows, heads, pooled keys] products stay ~70 MB


def _pooled_upkeep(k_pages, ki, table, row_slot, row_pos, geo):
    """The pooled keys the step's rows complete, from keys already in the
    pages (the step's own among them), into ``ki``: ``table [S, MB]`` with
    the layer's first page added, ``row_slot [R]`` (``S``: a pad row)."""
    from deepspeed_tpu.ops import block_select
    S = table.shape[0]
    mine = table[jnp.minimum(row_slot, S - 1)]
    new, j, done = block_select.completed_pooled_keys(k_pages, mine, row_pos,
                                                      geo)
    return block_select.write_pooled_keys(ki, new, j, done & (row_slot < S),
                                          mine)


def _block_rows_one(qg, pages: _LayerPages, pos, sparse, cfg: GPTConfig):
    """One row a slot past ``block_dense_len``: block scores over the slot's
    pooled keys, the kept blocks as a list, attention over those blocks and
    no others (``block_select.kept_block_table``).  ``qg [S, nkv, g, d]`` at
    ``pos [S]``; a slot that is not ``sparse`` reads zeros."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops import block_select
    geo, scale = cfg.block_geometry, _attn_scale(cfg) or cfg.head_dim ** -0.5
    S, nkv = qg.shape[:2]
    with jax.named_scope("attn_index"):
        marked = block_select.mark_blocks(ops.block_scores(
            qg, block_select.slot_pooled_keys(pages.ki, pages.table), pos,
            geo=geo, scale=scale, impl=cfg.attn_impl), pos, geo)
        blocks = ops.index_select(
            marked.reshape(S * nkv, -1), geo.topk,
            impl=cfg.attn_impl).reshape(S, nkv, geo.topk)
    with jax.named_scope("block_attention"):
        # each kv head of a slot as a sequence of its own over pages of one
        # block: the paged decode kernel reads the kept blocks and no others
        kept, lens = block_select.kept_block_table(
            pages.table, blocks, pos, sparse, pages.k.shape[2], geo)
        g, d = qg.shape[2:]
        return ops.paged_attention(
            qg.astype(cfg.dtype).reshape(S * nkv, 1, g, d),
            block_select.block_pages(pages.k, geo),
            block_select.block_pages(pages.v, geo), kept, lens, scale=scale,
            kv_major=False, impl=cfg.attn_impl).reshape(qg.shape)


def _block_one_row(qg, pages: _LayerPages, pos, lens, cfg: GPTConfig, mesh):
    """One row a slot of a layer that selects by blocks, ``lens [S]`` its
    context (0: the slot has no such row): within ``block_dense_len`` the
    paged decode kernel over the whole context, past it ``_block_rows_one``
    (a branch no step takes whose slots are all within it)."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops import block_select
    sparse = (lens > 0) & block_select.selects(pos, cfg.block_geometry)
    o = ops.paged_attention(
        qg, pages.k, pages.v, pages.table, jnp.where(sparse, 0, lens),
        scale=_attn_scale(cfg), mesh=mesh, kv_major=False,
        impl=cfg.attn_impl)
    o_kept = jax.lax.cond(
        jnp.any(sparse),
        lambda: _block_rows_one(qg, pages, pos, sparse, cfg),
        lambda: jnp.zeros_like(o))
    return jnp.where(sparse[:, None, None, None], o_kept, o)


def _block_chunk_bits(qg, rows: "_MixedRows", pages: _LayerPages, row_pos,
                      chunk_row, cfg: GPTConfig):
    """The bits of a mixed step's prompt chunks: for every row of the flat
    batch and each KV head, the key positions of its sequence it keeps beside
    what is causal, ``[nkv, N / 32, MB * bs]`` int32 as the masked prefill
    kernel takes them (a row that does not select, or is no chunk's, keeps
    everything).  The block scores are computed ``BLOCK_SCORE_ROWS`` rows of
    one slot at a time, over the slots with more than one row whose context
    passes ``block_dense_len`` (a loop whose trip count the device reads)."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops import block_select
    from deepspeed_tpu.ops.sparse_index import _pack_rows
    geo, scale = cfg.block_geometry, _attn_scale(cfg) or cfg.head_dim ** -0.5
    table = pages.table
    S, MB = table.shape
    N, nkv = qg.shape[:2]
    bs = pages.k.shape[2]
    NB = MB * bs // geo.block
    TQ = BLOCK_SCORE_ROWS
    with jax.named_scope("attn_index"):
        counts = jnp.where((rows.q_counts > 1)
                           & (rows.kv_len > geo.dense_len), rows.q_counts, 0)
        passes = -(-counts // TQ)
        ends = jnp.cumsum(passes)
        qp = jnp.pad(qg, ((0, TQ), (0, 0), (0, 0), (0, 0)))
        pp = jnp.pad(row_pos, (0, TQ))

        # a slot's pooled keys are scored over the narrowest of a few
        # shares of the table that holds its context (a branch a share,
        # taken at run time: a context of 16 k pays for 16 k, not for 66 k)
        shares = [MB]
        while shares[-1] % 2 == 0 and len(shares) < 3:
            shares.append(shares[-1] // 2)

        def over(mb):
            def scored(qb, pb, mine):
                got = ops.block_scores(
                    qb, block_select.slot_pooled_keys(pages.ki,
                                                      mine[None, :mb]),
                    pb, geo=geo, scale=scale, impl=cfg.attn_impl)
                return jnp.pad(got, ((0, 0), (0, 0),
                                     (0, NB - got.shape[-1])))
            return scored

        def one_pass(i, out):
            s = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                            S - 1).astype(jnp.int32)
            j = i - (ends[s] - passes[s])
            start = rows.first_row[s] + j * TQ
            live = jnp.arange(TQ) < counts[s] - j * TQ
            held = -(-rows.kv_len[s] // bs)              # pages in sight
            got = jax.lax.switch(
                jnp.sum(jnp.asarray(shares, jnp.int32) >= held) - 1,
                [over(mb) for mb in shares],
                jax.lax.dynamic_slice_in_dim(qp, start, TQ),
                jax.lax.dynamic_slice_in_dim(pp, start, TQ), table[s])
            old = jax.lax.dynamic_slice_in_dim(out, start, TQ)
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(live[:, None, None], got, old), start, 0)

        scores = jax.lax.fori_loop(
            0, ends[-1], one_pass,
            jnp.zeros((N + TQ, nkv, NB), jnp.float32))[:N]
        marked = block_select.mark_blocks(scores, row_pos, geo)
        marked = jnp.pad(jnp.moveaxis(marked, 1, 0).reshape(nkv * N, NB),
                         ((0, 0), (0, -NB % 128)),
                         constant_values=-jnp.inf)
        words = ops.threshold_mask(marked, geo.topk, impl=cfg.attn_impl)
        words = words.reshape(nkv, N // 32, -1)[..., :NB]
        # a row that does not select keeps every block
        every = _pack_rows((~(chunk_row & block_select.selects(
            row_pos, geo)))[:, None])                       # [N / 32, 1]
        # (each block's word under all its keys: what the kernel takes)
        return jnp.repeat(words | every[None], geo.block, axis=-1)


def _block_mixed_attention(q, rows: _MixedRows, pages: _LayerPages, row_pos,
                           *, cfg: GPTConfig, Q: int, mesh):
    """``_mixed_attention`` for a layer that selects by blocks: a slot's rows
    pick its kernel as there, and its context picks the dense or the
    selected form: a one-row slot within ``block_dense_len`` the paged decode
    kernel, past it ``_block_rows_one``; a prompt chunk the prefill kernel,
    in its masked form on ``_block_chunk_bits`` where any of the step's
    chunk rows selects."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops import block_select
    k_pages, v_pages, table = pages.k, pages.v, pages.table
    S = table.shape[0]
    N = q.shape[0]
    nh = cfg.num_heads
    nkv, hd, vd, _ = _attn_geometry(cfg)
    geo = cfg.block_geometry
    with jax.named_scope("attn_kernel"):
        valid = rows.scat_slot < S
        slot = jnp.where(valid, rows.scat_slot, 0)
        qg = q.reshape(N, nkv, nh // nkv, hd).astype(cfg.dtype)
        pool = dict(scale=_attn_scale(cfg), mesh=mesh, kv_major=False,
                    impl=cfg.attn_impl)
        one_row = rows.q_counts == 1
        first = rows.first_row
        o_one = _block_one_row(qg[first], pages, row_pos[first],
                               jnp.where(one_row, rows.kv_len, 0), cfg, mesh)
        chunk_row = valid & ~one_row[slot]
        prefill = (qg, k_pages, v_pages, table, rows.kv_len,
                   rows.kv_len - rows.q_counts,
                   jnp.where(one_row, 0, rows.q_counts), first)

        def masked():
            bits = _block_chunk_bits(qg, rows, pages, row_pos, chunk_row,
                                     cfg)
            with jax.named_scope("block_attention"):
                return ops.ragged_prefill_attention(
                    *prefill, max_q=Q, sel_mask=bits, **pool)

        o_many = jax.lax.cond(
            jnp.any(chunk_row & block_select.selects(row_pos, geo)), masked,
            lambda: ops.ragged_prefill_attention(*prefill, max_q=Q, **pool))
        o = jnp.where(one_row[slot, None, None],
                      o_one.reshape(S, nh, vd)[slot],
                      o_many.reshape(N, nh, vd))
        return jnp.where(valid[:, None, None], o, 0)


# --------------------------------------------------------------- state layers
# A layer that keeps a fixed-size state a sequence (GPTConfig.is_state_layer)
# in the step programs: a Mamba-2 scan layer (models/gpt.py Mamba2Mixer) or a
# gated short convolution (ShortConvMixer).  ONE path serves both: the plan of
# which slot takes which route, the fresh / active handling, the one-row and
# the chunked route, the write-back and the loop over layers are below; a
# kind of mixer (``_Mamba2``, ``_ShortConv``) supplies its projections, its
# conv's parameters and what it does between the conv and the output: the
# recurrence over a float32 state, or nothing (a short conv's whole state is
# its conv's tail).  Scopes nest inside the attention scopes, so that a
# reader that knows only those still files every operation.  A scan layer:
# the input projection under attn_qkv/ssm_in_proj, the conv and the scan
# under attn_kernel/ssm_conv and attn_kernel/ssm_scan (both routes update a
# slot's state where it lies in the pool), the write-back of a slot's conv
# tail under kv_write/ssm_scan, the gated norm and the output projection
# under attn_out/ssm_gate_norm.  A short-conv layer:
# attn_qkv/conv_in_proj, attn_kernel/short_conv, kv_write/short_conv,
# attn_out/conv_out_proj.

SCAN_CHUNK = 128    # rows a chunk of the mixed step's scan, at most: a head's
#                     decay matrix [chunk, chunk] float32 is 16 vector
#                     registers in the kernel (ops.ssm_pool_chunk_scan), and
#                     the work within a chunk grows with its square


class _Mamba2:
    """What a Mamba-2 scan layer supplies to the state-layer path."""
    key = "Mamba2Mixer_0"
    in_scope, conv_scope, scope, out_scope = (
        "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm")
    activation = "silu"
    positions = False   # ``project`` takes no positions
    group = 4           # prompt chunks convolved and scanned together in a
    #                     mixed step's pass

    @staticmethod
    def taps(cfg):
        return cfg.ssm_conv

    @staticmethod
    def channels(cfg):
        return cfg.ssm_conv_dim

    @staticmethod
    def y_like(cfg, u):
        """(width, dtype) of a row between the conv and the output."""
        return cfg.ssm_inner, jnp.float32

    @staticmethod
    def project(mp, h, cfg, mesh=None):
        """Rows ``h [R, H]`` -> (z [R, inner] for the output's gate, xBC [R,
        channels] for the conv, dt [R, heads] float32 after its softplus)."""
        from deepspeed_tpu.models.gpt import ssm_split
        zxd = _wmm(h, mp["w_in"], h.dtype, mesh=mesh, wspec="col")
        a, b = ssm_split(cfg)
        dt = jax.nn.softplus(zxd[:, b:].astype(jnp.float32)
                             + mp["dt_bias"].astype(jnp.float32))
        return zxd[:, :a], zxd[:, a:b], dt

    @staticmethod
    def conv_params(mp):
        return mp["conv_w"], mp.get("conv_b")

    @staticmethod
    def _xbc(xbc, cfg):
        """The conv's output rows ``[..., channels]`` as the scan's (x [...,
        h, p], B [..., g, n], C [..., g, n])."""
        inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
        lead = xbc.shape[:-1]
        groups = lead + (cfg.ssm_groups, cfg.ssm_state)
        return (xbc[..., :inner].reshape(
                    lead + (cfg.ssm_heads, cfg.ssm_head_dim)),
                xbc[..., inner:inner + gn].reshape(groups),
                xbc[..., inner + gn:].reshape(groups))

    @staticmethod
    def _scan_params(mp):
        return -jnp.exp(mp["A_log"].astype(jnp.float32)), mp["D"]

    @classmethod
    def step(cls, mp, out, dt, ssm, si, active, fresh, cfg):
        """One conv'd row a slot ``out [S, channels]`` through the
        recurrence, in place in layer ``si`` of the state pool -> (y [S,
        inner] float32, ssm')."""
        from deepspeed_tpu import ops
        A, D = cls._scan_params(mp)
        x, B, C = cls._xbc(out, cfg)
        y, ssm = ops.ssm_state_update(x, dt, A, B, C, D, ssm, si, active,
                                      fresh)
        return y.reshape(y.shape[0], -1), ssm

    @classmethod
    def chunk(cls, mp, out, dt, ssm, si, slots, fresh, count, live, cfg):
        """A pass's conv'd prompt chunks ``out [G, Q, channels]`` through
        the chunked scan from their slots' states, in place in layer ``si``
        of the state pool -> (y [G, Q, inner] float32, ssm')."""
        from deepspeed_tpu import ops
        A, D = cls._scan_params(mp)
        return ops.ssm_pool_chunk_scan(
            out, dt, A, D, ssm, si, slots, count, fresh, live,
            chunk=min(cfg.ssm_chunk, SCAN_CHUNK))

    @staticmethod
    def output(mp, y, z, cfg, mesh=None):
        """The gate, the norm over the inner width and the output projection
        of rows ``y [R, inner]``."""
        from deepspeed_tpu.models.gpt import ssm_gate_norm
        from deepspeed_tpu.ops.norms import RMS_EPS
        g = ssm_gate_norm(y, z, mp["norm"], cfg.norm_eps or RMS_EPS)
        return _wmm(g.astype(z.dtype), mp["w_out"], z.dtype, mesh=mesh,
                    wspec="row")


class _Lightning(_Mamba2):
    """What a lightning-attention layer (models/gpt.py ``LightningMixer``)
    supplies: the recurrence of ``_Mamba2`` at ``dt = 1`` under a fixed decay
    a head with ``x = v``, ``B = k``, ``C = q / sqrt(p)`` and a group a head,
    q/k norms and RoPE inside ``project`` (which therefore takes the rows'
    positions), NO conv (``conv_scope`` None: the path skips it and the pool
    has no ``conv`` part), and a norm a head before a sigmoid gate."""
    key = "LightningMixer_0"
    in_scope, conv_scope, scope, out_scope = (
        "ssm_in_proj", None, "ssm_scan", "ssm_gate_norm")
    activation = None
    positions = True    # ``project`` takes the rows' positions
    group = 1           # a forward holds one prompt chunk of 1,024 rows more
    #                     often than four

    @staticmethod
    def project(mp, h, cfg, mesh=None, pos=None):
        """Rows ``h [R, H]`` at ``pos [R]`` -> (the gate's logits [R, inner],
        ``[v | k | q]`` [R, 3 inner] as the recurrence takes them, dt = 1)."""
        from deepspeed_tpu.models.gpt import lightning_qk, lightning_split
        q, k, v, gate = lightning_split(
            _wmm(h, mp["w_in"], h.dtype, mesh=mesh, wspec="col"), cfg)
        q, k = lightning_qk(
            q[None], k[None], mp["q_norm"], mp["k_norm"], pos[None], cfg,
            rotate=cfg.use_rope and cfg.rope_layers in ("all", "state"))
        R = h.shape[0]
        u = jnp.concatenate([a.reshape(R, -1) for a in (v, k[0], q[0])], -1)
        return gate, u, jnp.ones((R, cfg.ssm_heads), jnp.float32)

    @staticmethod
    def _scan_params(mp):
        from deepspeed_tpu.models.gpt import lightning_decay
        heads = mp["w_out"].shape[0] // mp["norm"].shape[0]
        return (jnp.asarray(lightning_decay(heads)),
                jnp.zeros((heads,), jnp.float32))

    @staticmethod
    def output(mp, y, gate, cfg, mesh=None):
        from deepspeed_tpu.models.gpt import lightning_gate_norm
        from deepspeed_tpu.ops.norms import RMS_EPS
        g = lightning_gate_norm(
            y.reshape(y.shape[0], cfg.ssm_heads, cfg.ssm_head_dim), gate,
            mp["norm"], cfg.norm_eps or RMS_EPS)
        return _wmm(g.astype(gate.dtype), mp["w_out"], gate.dtype, mesh=mesh,
                    wspec="row")


class _ShortConv:
    """What a gated short convolution supplies: ``[B | C | X] = W_in h``,
    the conv over ``B * X`` without bias or activation, ``W_out (C * v)``.
    Its state is the conv's tail and nothing else, so ``step`` and ``chunk``
    pass the conv's rows on and the pool has no ``ssm`` part."""
    key = "ShortConvMixer_0"
    in_scope, conv_scope, scope, out_scope = (
        "conv_in_proj", "short_conv", "short_conv", "conv_out_proj")
    activation = None
    positions = False
    group = 1           # a forward holds one prompt chunk beside its riders
    #                     more often than four: a pass a chunk

    @staticmethod
    def taps(cfg):
        return cfg.conv_taps

    @staticmethod
    def channels(cfg):
        return cfg.hidden_size

    @staticmethod
    def y_like(cfg, u):
        return cfg.hidden_size, u.dtype

    @staticmethod
    def project(mp, h, cfg, mesh=None):
        """Rows ``h [R, H]`` -> (the output's gate C, the conv's input B *
        X, nothing)."""
        from deepspeed_tpu.models.gpt import short_conv_gates
        u, gate = short_conv_gates(
            _wmm(h, mp["w_in"], h.dtype, mesh=mesh, wspec="col"))
        return gate, u, None

    @staticmethod
    def conv_params(mp):
        return mp["conv_w"], None

    @staticmethod
    def step(mp, out, aux, ssm, si, active, fresh, cfg):
        return out, ssm

    @staticmethod
    def chunk(mp, out, aux, ssm, si, slots, fresh, count, live, cfg):
        return out, ssm

    @staticmethod
    def output(mp, v, gate, cfg, mesh=None):
        return _wmm(gate * v, mp["w_out"], gate.dtype, mesh=mesh,
                    wspec="row")


def state_mixer(cfg: GPTConfig):
    """The kind of state layer ``cfg`` has (None: none).  One kind a model:
    the conv-tail pool has one row width."""
    kinds = {cfg.layer_kind(i) for i in cfg.state_layers}
    if len(kinds) > 1:
        raise NotImplementedError(
            "more than one of scan (mamba), lightning and conv layers in "
            "one model: the state pool is built for one kind of state layer")
    return {"mamba": _Mamba2, "lightning": _Lightning,
            "conv": _ShortConv}[kinds.pop()] if kinds else None


class _ScanPlan(NamedTuple):
    """Which slots of a mixed step take which path through a state layer,
    the same for every layer."""
    one: jnp.ndarray      # [S] the slot holds one row: the one-row route
    fresh: jnp.ndarray    # [S] its first row is position 0: from zero
    order: jnp.ndarray    # [S + pad] slots, those with more rows first
    n_many: jnp.ndarray   # [] how many slots hold more than one row
    row_one: jnp.ndarray  # [N] the row's slot holds one row
    row_slot: jnp.ndarray  # [N] the row's slot, 0 for padding


def _scan_plan(rows: "_MixedRows", mixer) -> _ScanPlan:
    S = rows.first_row.shape[0]
    with jax.named_scope("attn_kernel"), jax.named_scope(mixer.scope):
        one = rows.q_counts == 1
        many = rows.q_counts > 1
        fresh = (rows.q_counts > 0) & (rows.kv_len == rows.q_counts)
        order = jnp.argsort(jnp.where(many, 0, 1), stable=True).astype(
            jnp.int32)
        order = jnp.pad(order, (0, -S % mixer.group))
        slot = jnp.where(rows.scat_slot < S, rows.scat_slot, 0)
        return _ScanPlan(one, fresh, order, jnp.sum(many), one[slot]
                         & (rows.scat_slot < S), slot)


def _conv_tail(conv, si, slots, fresh, mixer, cfg: GPTConfig):
    """The conv tails ``[len(slots), taps - 1, channels]`` of ``slots`` in
    state layer ``si`` (all slots: None), zero where ``fresh``."""
    rows = conv[si] if slots is None else conv[si, slots]
    tail = rows.reshape(rows.shape[0], mixer.taps(cfg) - 1,
                        mixer.channels(cfg))
    return jnp.where(fresh[:, None, None], 0, tail)


def _scan_rows_one(mp, u, aux, scan, si, active, fresh, mixer,
                   cfg: GPTConfig):
    """One row a slot through state layer ``si`` of the pool: the conv from
    the slot's tail, the mixer's update from its state (from zero where
    ``fresh``), both written back for the ``active`` slots.  ``u [S,
    channels]`` -> (y [S, width], scan')."""
    from deepspeed_tpu import ops
    ssm, conv = scan
    out = tail = None
    with jax.named_scope("attn_kernel"):
        if mixer.conv_scope is not None:   # (a lightning layer has no conv)
            cw, cb = mixer.conv_params(mp)
            with jax.named_scope(mixer.conv_scope):
                out, tail = ops.causal_conv1d(
                    u[:, None], cw, cb,
                    _conv_tail(conv, si, None, fresh, mixer, cfg),
                    active.astype(jnp.int32), activation=mixer.activation)
        with jax.named_scope(mixer.scope):
            y, ssm = mixer.step(mp, u if out is None else out[:, 0], aux,
                                ssm, si, active, fresh, cfg)
    if tail is not None:
        with jax.named_scope("kv_write"), jax.named_scope(mixer.scope):
            conv = conv.at[si].set(tail.reshape(tail.shape[0], -1))
    return y, (ssm, conv)


def _row_major(a):
    """``a`` constrained to the row-major layout it was created in."""
    from jax.experimental.layout import Layout, with_layout_constraint
    return with_layout_constraint(
        a, Layout(major_to_minor=tuple(range(a.ndim))))


def _scan_rows_many(mp, u, aux, scan, si, plan: _ScanPlan,
                    rows: "_MixedRows", mixer, cfg: GPTConfig, Q: int):
    """The prompt chunks of a mixed step through state layer ``si``:
    ``mixer.group`` slots a pass, as many passes as the step's slots with
    more than one row take (a loop whose trip count the device reads).  A
    pass gathers its slots' rows out of the token-major ``u [N, channels]``
    (and ``aux``) ONCE into ``[group, Q, ...]``, convolves them from the
    slots' tails, takes the mixer's update of the slots' states in place in
    the pool (``ops.ssm_pool_chunk_scan``), scatters ``y`` back and writes
    the tails in place.  -> (y [N, width], zero
    on rows of no prompt chunk, scan')."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.ops.ssm_scan import segment_rows
    convolves = mixer.conv_scope is not None   # (a lightning layer: no conv)
    if convolves:
        cw, cb = mixer.conv_params(mp)
    S = rows.first_row.shape[0]
    N = u.shape[0]
    G = mixer.group
    lanes = jnp.arange(G, dtype=jnp.int32)

    def one_pass(carry):
        i, y, ssm, conv = carry
        if not convolves:
            # (the pool stays row-major through the loop: left free, the
            # compiler carries it in the layout the scan's products like
            # best and copies all of it in and out of the loop,
            # tests/test_chip_compile.py)
            ssm = _row_major(ssm)
        with jax.named_scope("attn_kernel"):
            with jax.named_scope(mixer.conv_scope or mixer.scope):
                slots = jax.lax.dynamic_slice_in_dim(plan.order, i * G, G)
                live = i * G + lanes < plan.n_many
                count = jnp.where(live, rows.q_counts[slots], 0)
                read, write, _ = segment_rows(
                    (rows.first_row[slots], count), Q, N)
                fresh = plan.fresh[slots]
                out, tail = u[read], None
                if convolves:
                    out, tail = ops.causal_conv1d(
                        out, cw, cb,
                        _conv_tail(conv, si, slots, fresh, mixer, cfg),
                        count, activation=mixer.activation)
            with jax.named_scope(mixer.scope):
                y_pass, ssm = mixer.chunk(
                    mp, out, None if aux is None else aux[read], ssm, si,
                    slots, fresh, count, live, cfg)
                y = y.at[write].set(y_pass, mode="drop")
        if tail is not None:
            with jax.named_scope("kv_write"), jax.named_scope(mixer.scope):
                conv = conv.at[si, jnp.where(live, slots, S)].set(
                    tail.reshape(G, -1), mode="drop")
        return i + 1, y, ssm, conv

    with jax.named_scope("attn_kernel"), jax.named_scope(mixer.scope):
        width, dtype = mixer.y_like(cfg, u)
        y0 = jnp.zeros((N, width), dtype)
        _, y, ssm, conv = jax.lax.while_loop(
            lambda c: c[0] * G < plan.n_many, one_pass,
            (jnp.int32(0), y0) + tuple(scan))
    return y, (ssm, conv)


def _project(mixer, mp, h, cfg, mesh, pos):
    """``mixer.project``, with the rows' positions for a mixer that takes
    them (``mixer.positions``: RoPE inside a lightning layer)."""
    return mixer.project(mp, h, cfg, mesh=mesh,
                         **({} if pos is None else {"pos": pos}))


def _scan_mixed(mp, h, scan, si, plan: _ScanPlan, rows: "_MixedRows",
                pos=None, *, mixer, cfg: GPTConfig, Q: int, mesh=None):
    """A state layer's mixer on a mixed step's token-major rows ``h [N,
    H]``: a slot with one row (a decode row riding the step, a prompt's
    one-token tail) takes the one-row route, a slot with more the chunked
    one.  -> (the mixer's output [N, H], scan')."""
    with jax.named_scope("attn_qkv"), jax.named_scope(mixer.in_scope):
        keep, u, aux = _project(mixer, mp, h, cfg, mesh, pos)
    first = rows.first_row
    y_one, scan = _scan_rows_one(
        mp, u[first], None if aux is None else aux[first], scan, si,
        plan.one, plan.fresh, mixer, cfg)
    y_many, scan = _scan_rows_many(mp, u, aux, scan, si, plan, rows, mixer,
                                   cfg, Q)
    with jax.named_scope("attn_kernel"), jax.named_scope(mixer.scope):
        y = jnp.where(plan.row_one[:, None], y_one[plan.row_slot], y_many)
    with jax.named_scope("attn_out"), jax.named_scope(mixer.out_scope):
        return mixer.output(mp, y, keep, cfg, mesh=mesh), scan


def _scan_decode(mp, h, scan, si, active, token_pos, *, mixer,
                 cfg: GPTConfig, mesh=None):
    """A state layer's mixer in a decode step: one row ``h [S, H]`` a
    slot."""
    with jax.named_scope("attn_qkv"), jax.named_scope(mixer.in_scope):
        keep, u, aux = _project(mixer, mp, h, cfg, mesh,
                                token_pos if mixer.positions else None)
    y, scan = _scan_rows_one(mp, u, aux, scan, si, active,
                             active & (token_pos == 0), mixer, cfg)
    with jax.named_scope("attn_out"), jax.named_scope(mixer.out_scope):
        return mixer.output(mp, y, keep, cfg, mesh=mesh), scan


def _scale_branch(delta, cfg: GPTConfig):
    """A branch's output times ``residual_scale`` where the model has one."""
    if cfg.residual_scale is None:
        return delta
    return delta * jnp.asarray(cfg.residual_scale, delta.dtype)


def _window_latent_scope(cfg: GPTConfig, window):
    """Scope ``window_latent`` around a latent WINDOW layer's kernels (inside
    ``attn_kernel``), nothing around any other layer's."""
    return (jax.named_scope("window_latent") if cfg.mla and window
            else contextlib.nullcontext())


def kv_page_layout(cfg: GPTConfig, nb_global: int, nb_window: int,
                   split: bool = False):
    """Where each layer's pages lie in a pool of TWO page groups: per layer
    ``(first page, group)``, group 0 the ``global`` layers (no window: they
    keep every page of a context) and group 1 the ``window`` layers
    (``cfg.window_for_layer``: they keep a ring of pages, ragged.py).  The
    pool stays one flat row-major array: the global layers' ``nb_global``
    pages each come first, then the window layers' ``nb_window`` each.
    Static (a tuple of ints), so it is a step program's keyword; a model
    whose layers are all alike has one group and passes None, which is
    ``(li * NB, 0)``.  ``split``: a pool per group (latent pages,
    ``PagedKVCache.create_latent_groups``), so a window layer's first page
    counts from its own pool's start."""
    kinds = [cfg.window_for_layer(i) is not None
             for i in range(cfg.num_layers)]
    n_global = kinds.count(False)
    out, g, w = [], 0, 0
    for is_window in kinds:
        if is_window:
            out.append(((0 if split else n_global * nb_global)
                        + w * nb_window, 1))
            w += 1
        else:
            out.append((g * nb_global, 0))
            g += 1
    return tuple(out)


def _group_tables(batch):
    """The step's block tables by page group: (global,) or (global,
    window)."""
    bt = (batch["block_table"],)
    if "block_table_w" in batch:
        bt += (batch["block_table_w"],)
    return bt


class _Step(NamedTuple):
    """What a KIND of step (mixed: N ragged rows; decode: one row a slot;
    verify: a dense [S, G]) supplies to ``_layer``: built once a program,
    before the loop over the layers, the same for every layer."""
    pos: jax.Array          # the rows' positions, flat
    tables: tuple           # the block table [S, MB] of each page group
    plans: tuple            # and its ``_write_plan`` for the step's rows
    write: Any              # the program's ``_kv_writer``
    rope: Any               # (lc, q, k) -> (q, k) on its row layout, at the
    #                         layer's own base and rotated width
    attend: Any             # (li, lc, q, pages, base) -> o [.., nh, vd]
    ffn: dict               # live / stats / routes / experts of ``_ffn``
    selected: Any = None    # (lc, q, qi, wi, pages, base) -> o, where the
    #                         selection binds (``_selects``)
    mixer: Any = None       # a state layer's: (blk, h, scan, si) -> (delta,
    #                         scan')
    lora: Any = None        # (the adapter pool's tables, the rows' ids)
    hc: Any = None          # the program's ``_HyperMix`` where the residual
    #                         is several streams (``cfg.hc``)
    pooled: Any = None      # a selection by blocks: (lc, k pages, pooled
    #                         keys, base) -> the pooled keys after the step


class _HyperMix(NamedTuple):
    """A multi-stream residual's three operations (models/gpt.py
    ``hc_maps``, ``hc_read``, ``hc_write``) as jitted functions made ONCE a
    step program and called by both sublayers of every layer: traced once
    and lowered once a program, not once a sublayer (what ``attend`` is to
    the attention kernels).  Scopes ``hc_coef`` (the norm, the projection,
    the three maps with Sinkhorn), ``hc_pre`` (``Hpre X``) and ``hc_post``
    (``Hres X + Hpost^T y``), each inside the accepted scope the caller
    stands in (``attn_qkv``, ``attn_out``, ``mlp``)."""
    maps: Any
    read: Any
    write: Any

    @classmethod
    def of(cls, cfg: GPTConfig):
        if not cfg.hc:
            return None
        return cls(jax.jit(named_partial(hc_maps, cfg=cfg)),
                   jax.jit(hc_read), jax.jit(hc_write))

    def open(self, hp, x):
        """(the sublayer's input ``Hpre X [N, H]``, what ``close`` needs)."""
        with jax.named_scope("hc_coef"):
            pre, post, res = self.maps(hp["phi"], hp["bias"], hp["alpha"], x)
        with jax.named_scope("hc_pre"):
            # (the row is an array of its own: left to the compiler it is
            # recomputed from the four streams inside both of the norm's
            # fusions, under the norm's scope, and ``hc_pre`` reads 0 ms)
            return jax.lax.optimization_barrier(self.read(x, pre)), (post,
                                                                     res)

    def close(self, x, y, maps):
        """The streams after the sublayer whose output is ``y [N, H]``."""
        with jax.named_scope("hc_post"):
            return self.write(x, y, *maps)


def _selects(cfg: GPTConfig, tables, block_size: int) -> bool:
    """The selection binds only where a context can outgrow it: a step
    program whose table is no wider reads every key, through the kernels."""
    return tables[0].shape[1] * block_size > cfg.index_topk > 0


def _rope(cfg: GPTConfig, q, k, pos, seq_lens):
    """``rope()`` as every step calls it: [B, T, n, d] + positions [B, T];
    ``cfg``: the layer's view (its base, ``window_attn``; the leading
    ``rope_pct`` of its head rotates)."""
    return rope(q, k, pos, cfg.head_dim, base=cfg.rope_theta,
                rope_pct=cfg.rope_pct, scaling=cfg.rope_scaling,
                seq_lens=seq_lens)


def _group_scope(kv_layout, grp: int):
    """Scope ``attn_window`` / ``attn_global`` round a layer's attention
    (``attn_kernel`` and what else the step's ``attend`` holds) in a model
    with two page groups, so that a trace tells the window layers' kernels
    from the global layers' in one program; nothing round a model's with
    one.  A scope moves no op."""
    if kv_layout is None:
        return contextlib.nullcontext()
    return jax.named_scope("attn_window" if grp == 1 else "attn_global")


def _layer(bb, li: int, x, pool: _KVPool, step: _Step, cfg: GPTConfig,
           kv_layout=None, mesh=None):
    """Layer ``li`` of a serving step on the step's rows ``x [..., H]``, the
    ONE place the sequence is written: norm -> (state mixer | latent q/kv |
    q, k, v + LoRA + qk-norm/gate) -> RoPE -> the rows into the layer's
    pages -> (index rows + index keys into theirs) -> (selected attention |
    attend) -> output projection -> sandwich norm -> residual + FFN/MoE.
    Where the residual is several streams (``step.hc``), each of the two
    sublayers reads ``Hpre X`` and closes with ``Hres X + Hpost^T y``
    instead of ``x + y``.  -> (x', pool')."""
    blk = bb[f"block_{li}"]
    hc = step.hc            # a multi-stream residual: x is rows [N, n, H]
    dense = x.ndim > 2 and hc is None   # the verify step's [S, G, H]; else
    #                                     rows [N, H]

    def residual(h, delta, x=x):
        # the FFN/MoE body is token-wise and (for MoE) expects FLAT tokens
        # (asked only of a dense layout: a reshape that changes nothing
        # emits nothing, but costs the host's trace 65 us a call)
        with jax.named_scope("mlp"):
            if hc is not None:
                return _block_residual(blk, x, h, delta, cfg, mesh=mesh,
                                       hc=hc, **step.ffn)
            flat = [a.reshape(-1, a.shape[-1]) if dense else a
                    for a in (x, h, delta)]
            out = _block_residual(blk, *flat, cfg, mesh=mesh, **step.ffn)
            return out.reshape(x.shape) if dense else out

    if cfg.is_state_layer(li):
        with jax.named_scope("attn_qkv"):
            h = _norm(blk["Norm_0"], x, cfg)
        delta, (ssm, conv) = step.mixer(
            blk, h, (pool.ssm, pool.conv),
            jnp.int32(cfg.state_layers.index(li)))
        return residual(h, delta), pool._replace(ssm=ssm, conv=conv)
    ap = blk["Attention_0"]
    lc = cfg.for_layer(li)           # this layer's attention geometry
    with jax.named_scope("attn_qkv"):
        xin = x
        if hc is not None:
            xin, maps = hc.open(blk["hc_attn"], x)
        h = _norm(blk["Norm_0"], xin, cfg)
        if cfg.mla:
            q, k, gate, cq = _mla_qkv(ap, h, step.pos, lc)
            v = None
        else:
            q, k, v = _qkv(ap, h, cfg, mesh=mesh)
            if step.lora is not None:
                q, v = _lora_qv(q, v, h, *step.lora, li)
            q, k, gate = _qk_norm_gate(ap, h, q, k, cfg, mesh=mesh)
            v = value_scale(v, lc)
        if cfg.rope_for_layer(li) and not cfg.mla:
            q, k = step.rope(lc, q, k)

    field, vfield, base, grp = _layer_pages(cfg, kv_layout, pool, li)

    def rows(a):                # [rows, heads, d], as the write takes them
        return a.reshape((-1,) + a.shape[-2:]) if dense and a is not None \
            else a
    pk, pv, pks, pvs = step.write(
        getattr(pool, field), getattr(pool, vfield), pool.k_scale,
        pool.v_scale, rows(k), rows(v), step.plans[grp], base)
    pool = pool._replace(**{field: pk, vfield: pv}, k_scale=pks,
                         v_scale=pvs)
    if lc.index_topk:
        with jax.named_scope("attn_kernel"), jax.named_scope("attn_index"):
            qi, wi, ki = _index_rows(ap, h, cq, step.pos, lc)
            pool = pool._replace(ki=_kv_write_local(
                (pool.ki,), ki, None, step.plans[grp], base, km=False)[0])
    if lc.block_topk:
        with jax.named_scope("attn_kernel"), jax.named_scope("attn_index"):
            pool = pool._replace(ki=step.pooled(lc, pk, pool.ki, base))
    pages = _LayerPages(k=pk, v=pv, ki=pool.ki if lc.block_topk else None,
                        table=step.tables[grp], k_scale=pks, v_scale=pvs,
                        sink=ap["sink"] if lc.attn_sink else None)
    with _group_scope(kv_layout, grp):
        if lc.index_topk and step.selected is not None:
            o = step.selected(lc, q, qi, wi, pages._replace(ki=pool.ki),
                              base)
        else:
            o = step.attend(li, lc, q, pages, base)
    with jax.named_scope("attn_out"):
        attn_delta = _attn_proj(ap, o, gate, lc, mesh=mesh)
        if cfg.sandwich_norm:
            attn_delta = _norm(blk["post_attn_norm"], attn_delta, cfg)
        if hc is not None:
            x = hc.close(x, attn_delta, maps)
    return residual(h, attn_delta, x), pool


def ragged_forward(params, cache: PagedKVCache, batch, cfg: GPTConfig, *,
                   block_size: int, max_q_per_seq: int, mesh=None,
                   kv_layout=None, moe_stats: bool = False,
                   moe_routes: bool = False):
    """One ragged step.

    params: unboxed GPT param tree (the "params" subtree).
    batch: dict of device arrays mirroring ragged.RaggedBatch fields.
    Returns (logits [S, vocab] — per-slot last-token logits, updated cache).
    """
    bb = params["backbone"]
    tokens = batch["tokens"]               # [N]
    token_slot = batch["token_slot"]       # [N] (-1 pad)
    token_pos = batch["token_pos"]         # [N]
    tables = _group_tables(batch)          # [S, MB] per page group
    kv_len = batch["kv_len"]               # [S]
    ffn = dict(
        stats=[] if moe_stats else None, routes=[] if moe_routes else None,
        experts=_experts_fn(cfg, moe_stats) if cfg.num_experts else None)

    N = tokens.shape[0]
    S = tables[0].shape[0]
    Q = max_q_per_seq
    km = kv_major_layout(cfg)
    valid = token_slot >= 0                # [N]

    # ---- embed (reference ragged_ops/embed) ----
    x = _embed_tokens(bb, tokens, token_pos, cfg)

    # pad tokens get an out-of-range slot so mode="drop" discards them
    # (never index-clamp pads to slot 0: duplicate scatter indices would
    # corrupt real rows)
    scat_slot = jnp.where(valid, token_slot, S)          # S = out of range
    with jax.named_scope("attn_kernel"):
        # per-slot live q rows (each slot's batch tokens are one CONTIGUOUS
        # span ending at kv_len: SplitFuse chunks)
        q_counts = jnp.zeros((S,), jnp.int32).at[scat_slot].add(
            1, mode="drop")
        first_row = jnp.full((S,), N - 1, jnp.int32).at[scat_slot].min(
            jnp.arange(N, dtype=jnp.int32), mode="drop")
        rows = _MixedRows(scat_slot, first_row, kv_len, q_counts)
    # one traced and lowered attention per KIND of layer (window, global),
    # called by every layer of the kind: the two kernels are lowered once a
    # kind and not once a layer, which is most of what a step program costs
    # the host before jax can look its compile cache up.  (A jit of this
    # trace's own: the ops choose their implementation while tracing.)
    attend = {kind: jax.jit(named_partial(
        _mixed_attention, cfg=kind[0], Q=Q, window=kind[1], mesh=mesh))
        for kind in {(cfg.for_layer(i), cfg.window_for_layer(i))
                     for i in cfg.attention_layers}}
    if cfg.block_topk:      # (a selection by blocks: its own, as above)
        attend = {kind: (lambda q, rows, pages, fn=jax.jit(named_partial(
            _block_mixed_attention, cfg=kind[0], Q=Q, mesh=mesh)):
            fn(q, rows, pages, token_pos)) for kind in attend}
    plans = tuple(_write_plan(t, scat_slot, token_pos, block_size, Q, km)
                  for t in tables)
    pool = _KVPool.open(cache, cfg)
    # (one traced and lowered attention a kind of SELECTING layer too: both
    # ways a chunk's rows can read their keys are in it)
    selected = {lc: jax.jit(named_partial(
        _selected_attention, cfg=lc, block_size=block_size, max_rows=Q))
        for lc in {cfg.for_layer(i) for i in cfg.attention_layers}
        if lc.index_topk and _selects(cfg, tables, block_size)}
    # state layers: which slots take which route through their pools
    mixer = state_mixer(cfg)
    if mixer is not None:
        plan = _scan_plan(rows, mixer)
        # (one traced and lowered mixer for all the state layers too: the
        # layer's place in the pools is an operand)
        scan_mixer = jax.jit(named_partial(_scan_mixed, mixer=mixer, cfg=cfg,
                                           Q=Q, mesh=mesh))

    # multi-tenant LoRA (static trace-time branch — adapter-less engines
    # send no "lora" key and trace the identical program): per-TOKEN
    # adapter slot via each token's sequence slot; pad rows map to the
    # identity slot 0 (zero delta)
    lora = batch.get("lora")
    if lora is not None:
        lora = lora, jnp.where(
            valid, batch["adapter_slot"][jnp.clip(token_slot, 0)], 0)

    def rope_rows(lc, q, k):
        q, k = _rope(lc, q[None], k[None], token_pos[None],
                     kv_len[jnp.clip(token_slot, 0)][None])
        return q[0], k[0]

    step = _Step(
        token_pos, tables, plans, _kv_writer(km, mesh), rope_rows,
        attend=lambda li, lc, q, pages, base: attend[
            lc, cfg.window_for_layer(li)](q, rows, pages.at(base)),
        selected=(lambda lc, q, qi, wi, pages, base: selected[lc](
            q, qi, wi, pages.at(base), scat_slot, token_pos, rows=rows))
        if selected else None,
        mixer=(lambda blk, h, scan, si: scan_mixer(
            blk[mixer.key], h, scan, si, plan, rows,
            *((token_pos,) if mixer.positions else ())))
        if mixer else None,
        lora=lora, ffn=dict(ffn, live=valid), hc=_HyperMix.of(cfg),
        pooled=(lambda lc, pk, ki, base: _pooled_upkeep(
            pk, ki, tables[0] + base, scat_slot, token_pos,
            lc.block_geometry)) if cfg.block_topk else None)
    for li in range(cfg.num_layers):
        x, pool = _layer(bb, li, x, pool, step, cfg, kv_layout, mesh)

    # ---- logits gather (reference ragged_ops/logits_gather): the LAST token
    # of each slot's q rows carries the next-token distribution ----
    with jax.named_scope("head"):
        last_flat = jnp.zeros((S,), jnp.int32).at[scat_slot].max(
            jnp.arange(N, dtype=jnp.int32), mode="drop")
    logits = _head(params, bb, x, cfg, mesh=mesh, rows=last_flat)  # [S, V]
    out = (logits, pool.close(cache)) + (
        (sum(ffn["stats"]),) if moe_stats else ())
    # [expert layers, N, k]: the experts each row's router chose
    return out + ((jnp.stack(ffn["routes"]),) if moe_routes else ())


def _decode_core(params, pool: _KVPool, tokens, active, token_pos, tables,
                 cfg: GPTConfig, block_size: int, mesh=None, lora=None,
                 adapter_slot=None, kv_layout=None, moe_stats: bool = False,
                 routes=None):
    """One decode micro-step: writes each active slot's kv into its page and
    attends over exactly that slot's pages via the paged-attention op
    (ops/paged_attention.py — Pallas kernel on TPU, masked-gather XLA
    fallback).  Shared by the single-step and burst programs.  ``tables``:
    the page groups' block tables (``_group_tables``).  Returns (logits
    [S, V], the updated pool, the step's MoE counters: None unless
    ``moe_stats``)."""
    from deepspeed_tpu import ops
    bb = params["backbone"]
    stats = [] if moe_stats else None
    S = tokens.shape[0]
    km = kv_major_layout(cfg)

    x = _embed_tokens(bb, tokens, token_pos, cfg)              # [S, H]

    row_slot = jnp.where(active, jnp.arange(S), S)
    plans = tuple(_write_plan(t, row_slot, token_pos, block_size, 1, km)
                  for t in tables)
    with jax.named_scope("attn_kernel"):
        kv_len = jnp.where(active, token_pos + 1, 0)                # [S]
    if lora is not None:
        # decode rows ARE slots: mask inactive lanes to the identity slot
        # so a recycled lane's stale selection never computes a delta
        lora = lora, jnp.where(active, adapter_slot, 0)
    mixer = state_mixer(cfg)
    if mixer is not None:      # one traced and lowered mixer for them all
        scan_mixer = jax.jit(named_partial(_scan_decode, mixer=mixer,
                                           cfg=cfg, mesh=mesh))

    def rope_rows(lc, q, k):
        q, k = _rope(lc, q[:, None], k[:, None], token_pos[:, None],
                     kv_len[:, None])
        return q[:, 0], k[:, 0]

    def attend(li, lc, q, pages, base):
        nkv, hd, vd, latent = _attn_geometry(lc)
        with jax.named_scope("attn_kernel"):
            qg = q.reshape(S, nkv, lc.num_heads // nkv, hd)
            slopes, win = _alibi(lc), cfg.window_for_layer(li)
            if lc.block_topk:       # (a selection by blocks: its own)
                return _block_one_row(qg, pages.at(base), token_pos, kv_len,
                                      lc, mesh).reshape(S, lc.num_heads, vd)
            with _window_latent_scope(cfg, win):
                o = ops.paged_attention(
                    qg, pages.k, pages.v, pages.table + base, kv_len,
                    alibi_slopes=slopes, window=win, scale=_attn_scale(lc),
                    mesh=mesh, kv_major=km, impl=cfg.attn_impl,
                    **pages.scales, **latent)
            return o.reshape(S, lc.num_heads, vd)

    step = _Step(
        token_pos, tables, plans, _kv_writer(km, mesh), rope_rows, attend,
        selected=(lambda lc, q, qi, wi, pages, base: _selected_attention(
            q, qi, wi, pages.at(base), row_slot, token_pos, lc,
            block_size=block_size))
        if _selects(cfg, tables, block_size) else None,
        mixer=(lambda blk, h, scan, si: scan_mixer(
            blk[mixer.key], h, scan, si, active, token_pos))
        if mixer else None,
        lora=lora, ffn=dict(
            live=active, stats=stats, routes=routes,
            experts=_experts_fn(cfg, moe_stats) if cfg.num_experts else None),
        hc=_HyperMix.of(cfg),
        pooled=(lambda lc, pk, ki, base: _pooled_upkeep(
            pk, ki, tables[0] + base, row_slot, token_pos,
            lc.block_geometry)) if cfg.block_topk else None)
    for li in range(cfg.num_layers):
        x, pool = _layer(bb, li, x, pool, step, cfg, kv_layout, mesh)

    logits = _head(params, bb, x, cfg, mesh=mesh)                  # [S, V]
    return logits, pool, sum(stats) if moe_stats else None


def ragged_decode_burst(params, cache: PagedKVCache, batch, prev_tokens, rng,
                        temperature, top_p,
                        cfg: GPTConfig, *, block_size: int, steps: int,
                        sample_fn, mesh=None, kv_layout=None,
                        moe_stats: bool = False):
    """T decode steps fused into one device program (``lax``-unrolled scan):
    each step samples on device and feeds the token to the next step, so a
    burst costs ONE dispatch instead of T× (transfer + step + sample + fetch) —
    the decisive win when the host↔device link has per-call latency.

    batch: tokens0 [S] (host first-step tokens), from_device [S] (take the
    first-step token from ``prev_tokens`` instead — the device-resident
    feedback path, so burst follows burst with no host round trip), active [S],
    pos0 [S], block_table [S, MB] — blocks for positions pos0..pos0+T-1 must
    be pre-allocated.
    Returns (tokens [T, S], prev_tokens' [S], rng', cache), and with
    ``moe_stats`` the burst's MoE counters, summed over its steps.
    """
    pool = _KVPool.open(cache, cfg)
    bt = _group_tables(batch)
    active = batch["active"]
    with jax.named_scope("embed"):
        tokens0 = jnp.where(batch["from_device"], prev_tokens,
                            batch["tokens0"])

    def step(carry, _):
        pool, tokens, pos, rng = carry
        logits, pool, stats = _decode_core(
            params, pool, tokens, active, pos, bt, cfg, block_size,
            mesh=mesh, lora=batch.get("lora"),
            adapter_slot=batch.get("adapter_slot"), kv_layout=kv_layout,
            moe_stats=moe_stats)
        with jax.named_scope("sample"):
            rng, sub = jax.random.split(rng)
            nxt = sample_fn(logits, sub, temperature=temperature,
                            top_p=top_p)
            nxt = nxt.astype(jnp.int32)
            pos = pos + 1
        return (pool, nxt, pos, rng), (nxt, stats)

    # the loop itself belongs to the pool: what it does besides its body's
    # (scoped) work is carry the pool's views from step to step
    with jax.named_scope("kv_pool"):
        (pool, last, _, rng), (toks, stats) = jax.lax.scan(
            step, (pool, tokens0, batch["pos0"], rng), None, length=steps)
    with jax.named_scope("sample"):
        prev_out = jnp.where(active, last, prev_tokens)
    out = (toks, prev_out, rng, pool.close(cache))
    return out + (jnp.sum(stats, axis=0),) if moe_stats else out


def ragged_forward_sampled(params, cache: PagedKVCache, batch, prev_tokens,
                           rng, temperature, top_p, cfg: GPTConfig, *,
                           block_size: int, max_q_per_seq: int, sample_fn,
                           mesh=None, kv_layout=None,
                           moe_stats: bool = False):
    """Mixed prefill/decode step with in-graph sampling and device-resident
    token feedback: tokens flagged ``from_device`` are read from
    ``prev_tokens[slot]`` (the previous step's on-device samples) instead of
    the host batch, and slots flagged ``served`` get their freshly sampled
    token written into the returned ``prev_tokens``.  The [S, vocab] logits
    therefore never leave the device — generate() chains these dispatches
    without a single host sync (the FastGen hot loop re-shaped for a
    high-latency host↔device link).
    Returns (prev_tokens' [S], rng', cache), and with ``moe_stats`` the
    step's MoE counters."""
    with jax.named_scope("embed"):
        tokens = jnp.where(batch["from_device"],
                           prev_tokens[jnp.clip(batch["token_slot"], 0)],
                           batch["tokens"])
    logits, cache, *stats = ragged_forward(
        params, cache, {**batch, "tokens": tokens}, cfg,
        block_size=block_size, max_q_per_seq=max_q_per_seq, mesh=mesh,
        kv_layout=kv_layout, moe_stats=moe_stats)
    prev_out, rng = _sample_next(sample_fn, logits, rng, temperature, top_p,
                                 batch["served"], prev_tokens)
    return (prev_out, rng, cache, *stats)


def ragged_forward_sampled_draft(params, draft_params, cache: PagedKVCache,
                                 draft_cache: PagedKVCache, batch,
                                 prev_tokens, rng, temperature, top_p,
                                 cfg: GPTConfig, draft_cfg: GPTConfig, *,
                                 block_size: int, max_q_per_seq: int,
                                 sample_fn, mesh=None):
    """ragged_forward_sampled that ALSO runs the draft model over the same
    ragged batch (its logits discarded) so the draft's paged KV ingests
    every prompt chunk in lockstep with the target — the prerequisite for
    useful speculative acceptance.  Draft staleness never affects
    correctness (greedy verify is exact for any draft), only acceptance.
    Returns (prev', rng', cache', draft_cache')."""
    with jax.named_scope("embed"):
        tokens = jnp.where(batch["from_device"],
                           prev_tokens[jnp.clip(batch["token_slot"], 0)],
                           batch["tokens"])
    batch = {**batch, "tokens": tokens}
    logits, cache = ragged_forward(
        params, cache, batch, cfg,
        block_size=block_size, max_q_per_seq=max_q_per_seq, mesh=mesh)
    with jax.named_scope("draft"):
        _, draft_cache = ragged_forward(
            draft_params, draft_cache, batch, draft_cfg,
            block_size=block_size, max_q_per_seq=max_q_per_seq, mesh=mesh)
    prev_out, rng = _sample_next(sample_fn, logits, rng, temperature, top_p,
                                 batch["served"], prev_tokens)
    return prev_out, rng, cache, draft_cache


def ragged_decode_sampled_draft(params, draft_params, cache: PagedKVCache,
                                draft_cache: PagedKVCache, batch,
                                prev_tokens, rng, temperature, top_p,
                                cfg: GPTConfig, draft_cfg: GPTConfig, *,
                                block_size: int, sample_fn, mesh=None):
    """ragged_decode_sampled with the draft model ingesting the same tokens
    (logits discarded) — keeps the draft KV in lockstep through decode-only
    scheduler rounds so later speculative bursts don't attend draft-cache
    holes.  Returns (prev', rng', cache', draft_cache')."""
    with jax.named_scope("embed"):
        tokens = jnp.where(batch["from_device"], prev_tokens,
                           batch["tokens"])
    batch = {**batch, "tokens": tokens}
    logits, cache = ragged_decode_forward(
        params, cache, batch, cfg, block_size=block_size, mesh=mesh)
    with jax.named_scope("draft"):
        _, draft_cache = ragged_decode_forward(
            draft_params, draft_cache, batch, draft_cfg,
            block_size=block_size, mesh=mesh)
    prev_out, rng = _sample_next(sample_fn, logits, rng, temperature, top_p,
                                 batch["served"], prev_tokens)
    return prev_out, rng, cache, draft_cache


def ragged_decode_sampled(params, cache: PagedKVCache, batch, prev_tokens,
                          rng, temperature, top_p, cfg: GPTConfig, *,
                          block_size: int, sample_fn, mesh=None,
                          kv_layout=None, moe_stats: bool = False):
    """Decode-only step with in-graph sampling + device feedback (see
    ragged_forward_sampled).  batch tokens/active/token_pos/block_table are
    slot-indexed [S]; from_device [S] selects prev_tokens as input; served [S]
    marks the slots whose sample is a real next token (a 1-token mid-prefill
    chunk is active but NOT served — its logits are mid-prompt garbage).
    Returns (prev_tokens' [S], rng', cache), and with ``moe_stats`` the
    step's MoE counters."""
    with jax.named_scope("embed"):
        tokens = jnp.where(batch["from_device"], prev_tokens,
                           batch["tokens"])
    logits, cache, *stats = ragged_decode_forward(
        params, cache, {**batch, "tokens": tokens}, cfg,
        block_size=block_size, mesh=mesh, kv_layout=kv_layout,
        moe_stats=moe_stats)
    prev_out, rng = _sample_next(sample_fn, logits, rng, temperature, top_p,
                                 batch["served"], prev_tokens)
    return (prev_out, rng, cache, *stats)


def _verify_core(params, pool: _KVPool, tokens, active, pos0, block_table,
                 cfg: GPTConfig, block_size: int, mesh=None):
    """Multi-token scoring forward for speculative decoding: every active
    slot ingests G contiguous tokens at positions pos0..pos0+G-1 (KV written
    into its pages) and gets logits for ALL G positions back — one program
    scores a whole draft run.  Dense [S, G] layout (no packing: every slot
    scores the same G), attention through the ragged-prefill op over the
    ``S * G`` rows, slot ``s`` holding the G from row ``s * G``.  Returns
    (logits [S, G, V], the updated pool)."""
    from deepspeed_tpu import ops
    bb = params["backbone"]
    S, G = tokens.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    km = kv_major_layout(cfg)

    positions = pos0[:, None] + jnp.arange(G, dtype=jnp.int32)[None]  # [S,G]
    x = _embed_tokens(bb, tokens, positions, cfg)                     # [S,G,H]

    q_counts = jnp.where(active, G, 0).astype(jnp.int32)
    plan = _write_plan(   # (the flat positions taken where they are made)
        block_table, jnp.repeat(jnp.where(active, jnp.arange(S), S), G),
        (flat_pos := positions.reshape(-1)), block_size, G, km)
    with jax.named_scope("attn_kernel"):
        kv_len = jnp.where(active, pos0 + G, 0)

    def attend(li, lc, q, pages, base):
        with jax.named_scope("attn_kernel"):
            o = ops.ragged_prefill_attention(
                q.reshape(S * G, nkv, nh // nkv, hd).astype(cfg.dtype),
                pages.k, pages.v, pages.table + base, kv_len, pos0, q_counts,
                jnp.arange(S, dtype=jnp.int32) * G, max_q=G,
                scale=cfg.attn_scale, alibi_slopes=_alibi(cfg),
                window=cfg.window_for_layer(li), mesh=mesh, kv_major=km,
                impl=cfg.attn_impl, **pages.scales).reshape(S, G, nh, hd)
            # inactive slots (q_counts=0) hold rows the kernel never writes;
            # zero them like ragged_forward does so no future cross-row op
            # (capacity MoE, aux stats) can see NaNs from dead rows
            return jnp.where(active[:, None, None, None], o, 0)

    step = _Step(
        flat_pos, (block_table,), (plan,), _kv_writer(km, mesh),
        lambda lc, q, k: _rope(lc, q, k, positions, kv_len[:, None]),
        attend, ffn={})
    for li in range(cfg.num_layers):
        x, pool = _layer(bb, li, x, pool, step, cfg, mesh=mesh)
    return _head(params, bb, x, cfg, mesh=mesh), pool           # [S, G, V]


def _speculative_burst_core(params, draft_params, cache: PagedKVCache,
                            draft_cache: PagedKVCache, batch, prev_tokens,
                            rng, xform, cfg: GPTConfig,
                            draft_cfg: GPTConfig, *, block_size: int,
                            gamma: int, steps: int, sampled: bool,
                            mesh=None):
    """Shared draft-and-verify choreography (greedy and rejection-sampling
    differ ONLY in the token choice and the acceptance rule): each outer
    step runs the draft for gamma cheap decodes — plus one extra ingest so
    a fully-accepted round leaves no draft-cache hole at pos+gamma (later
    draft attention would read garbage there forever, silently decaying
    acceptance) — scores the whole run with ONE multi-token target forward
    (_verify_core), accepts a prefix, and emits accepted + 1 correction
    token.  The paged KV design makes rollback free: positions past the
    accepted point are simply overwritten by later writes.

    batch: tokens0/from_device/active/pos0/block_table as in
    ragged_decode_burst; blocks for positions pos0..pos0+steps*(gamma+1)-1
    must be pre-allocated.
    Returns (toks [steps, gamma+1, S], counts [steps, S], prev', rng',
    cache', draft_cache') — the first counts[k, s] of toks[k, :, s] are
    real."""
    pool = _KVPool.open(cache, cfg)
    dpool = _KVPool.open(draft_cache, draft_cfg)
    bt = batch["block_table"]
    active = batch["active"]
    prev0 = jnp.where(batch["from_device"], prev_tokens, batch["tokens0"])
    if rng is None:
        rng = jax.random.PRNGKey(0)         # greedy: threaded but unused

    def outer(carry, _):
        pool, dpool, prev, pos, rng = carry
        d_list, q_list = [], []
        dtok, dpos = prev, pos
        # the two halves of an outer step, named in the device trace: what
        # the draft costs against the verify is read there, inside the one
        # fused program
        with jax.named_scope("draft"):
            for j in range(gamma + 1):
                dlogits, dpool, _ = _decode_core(
                    draft_params, dpool, dtok, active, dpos, (bt,),
                    draft_cfg, block_size, mesh=mesh)
                if j < gamma:
                    with jax.named_scope("sample"):
                        if sampled:
                            ql = xform(dlogits)
                            rng, sub = jax.random.split(rng)
                            dtok = jax.random.categorical(
                                sub, ql, axis=-1).astype(jnp.int32)
                            q_list.append(ql)
                        else:
                            dtok = jnp.argmax(dlogits, axis=-1).astype(
                                jnp.int32)
                    d_list.append(dtok)
                # the j == gamma pass only ingests d_gamma's KV
                dpos = dpos + 1
            d = jnp.stack(d_list, axis=1)                   # [S, gamma]
        with jax.named_scope("verify"):
            ver_in = jnp.concatenate([prev[:, None], d], axis=1)  # [S, g+1]
            vlogits, pool = _verify_core(
                params, pool, ver_in, active, pos, bt, cfg, block_size,
                mesh=mesh)
            with jax.named_scope("sample"):
                if sampled:
                    rng, sub = jax.random.split(rng)
                    emit, counts = spec_accept(
                        sub, jnp.stack(q_list, axis=1), xform(vlogits), d)
                else:
                    emit, counts = _greedy_accept(vlogits, d, gamma)
                counts = jnp.where(active, counts, 0)
                last = jnp.take_along_axis(
                    emit, jnp.maximum(counts - 1, 0)[:, None], axis=1)[:, 0]
                new_prev = jnp.where(active, last, prev)
                new_pos = jnp.where(active, pos + counts, pos)
        return (pool, dpool, new_prev, new_pos, rng), (emit.T, counts)

    with jax.named_scope("kv_pool"):     # the loop carries both pools
        (pool, dpool, prev, _, rng), (toks, counts) = jax.lax.scan(
            outer, (pool, dpool, prev0, batch["pos0"], rng), None,
            length=steps)
    prev_out = jnp.where(active, prev, prev_tokens)
    return (toks, counts, prev_out, rng, pool.close(cache),
            dpool.close(draft_cache))


def speculative_burst(params, draft_params, cache: PagedKVCache,
                      draft_cache: PagedKVCache, batch, prev_tokens,
                      cfg: GPTConfig, draft_cfg: GPTConfig, *,
                      block_size: int, gamma: int, steps: int, mesh=None):
    """GREEDY speculative decoding: acceptance is exact token match, so the
    output is token-identical to target-only greedy decoding for ANY draft
    *up to floating-point argmax ties* — the verify step is a multi-token
    (prefill-shaped) program, numerically different from the Q=1 decode
    baseline, so near-tied logits can argmax differently on low-precision
    hardware.  The tests pin exactness on fp32 configs.  See
    _speculative_burst_core.

    Inactive-lane contract: slots outside ``batch["active"]`` pass their
    ``prev_tokens`` state through untouched (``counts`` 0, KV unwritten) —
    each lane's trajectory depends only on its own slot state, never on
    which OTHER lanes share the dispatch.  The engine's cross-request
    batching (SpeculativeConfig.batch_across_requests) leans on exactly
    this: one all-requests dispatch and a sequence of one-request
    dispatches through this same program are token-identical, which is
    what makes the batched/per-request comparison a fair dispatch-count
    experiment rather than two different decoders.
    Returns (toks, counts, prev', cache', draft_cache')."""
    toks, counts, prev, _, cache, draft_cache = _speculative_burst_core(
        params, draft_params, cache, draft_cache, batch, prev_tokens,
        None, None, cfg, draft_cfg, block_size=block_size, gamma=gamma,
        steps=steps, sampled=False, mesh=mesh)
    return toks, counts, prev, cache, draft_cache


def _greedy_accept(vlogits, d, gamma: int):
    """Greedy speculative acceptance: accept the longest prefix of draft
    tokens matching the target argmax, then emit the target's token at the
    stop position (the correction when rejected, the bonus when all gamma
    accepted).
    Returns (emit [S, gamma+1], counts [S] in 1..gamma+1)."""
    t = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)      # [S, g+1]
    match = (d == t[:, :gamma])
    n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                    axis=1)                                 # 0..gamma
    correction = jnp.take_along_axis(t, n_acc[:, None], axis=1)[:, 0]
    j_idx = jnp.arange(gamma + 1)[None]
    emit = jnp.where(j_idx < n_acc[:, None],
                     jnp.pad(d, ((0, 0), (0, 1))),
                     correction[:, None])                   # [S, g+1]
    return emit, n_acc + 1


def spec_accept(rng, q_logits, p_logits, d):
    """Rejection-sampling acceptance for speculative decoding (Leviathan et
    al. 2023) — PURE math, unit-tested distributionally in isolation.

    q_logits [S, gamma, V]: the draft's POST-transform sampling logits at
    each draft position (d[s, j] was sampled from softmax(q_logits[s, j])).
    p_logits [S, gamma+1, V]: the target's post-transform logits for the
    same positions plus the bonus position.
    d [S, gamma]: the draft tokens.

    Per position: accept d_j w.p. min(1, p(d_j)/q(d_j)); at the first
    rejection emit a token from the residual max(p − q, 0)/Z; if all gamma
    accepted emit a bonus token from the gamma+1-th target distribution.
    Each emitted token is exactly target-distributed for ANY draft.

    Returns (emit [S, gamma+1], counts [S] in 1..gamma+1)."""
    S, gamma = d.shape
    q = jax.nn.softmax(q_logits, axis=-1)            # [S, gamma, V]
    p = jax.nn.softmax(p_logits, axis=-1)            # [S, gamma+1, V]
    pd = jnp.take_along_axis(p[:, :gamma], d[..., None], axis=-1)[..., 0]
    qd = jnp.take_along_axis(q, d[..., None], axis=-1)[..., 0]
    r_acc, r_cor = jax.random.split(rng)
    u = jax.random.uniform(r_acc, (S, gamma))
    accept = u * qd < pd                             # u < min(1, pd/qd)
    n = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)  # [S]
    # correction distribution at the stop position: residual when rejected,
    # the bonus target distribution when everything was accepted
    p_n = jnp.take_along_axis(p, n[:, None, None], axis=1)[:, 0]  # [S, V]
    q_n = jnp.take_along_axis(
        q, jnp.minimum(n, gamma - 1)[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(p_n - q_n, 0.0)
    resid_mass = jnp.sum(resid, axis=-1, keepdims=True)
    # numerically-empty residual (p ≈ q) degrades gracefully to p itself
    resid = jnp.where(resid_mass > 1e-9, resid / jnp.maximum(resid_mass,
                                                             1e-9), p_n)
    dist = jnp.where((n == gamma)[:, None], p_n, resid)           # [S, V]
    correction = jax.random.categorical(
        r_cor, jnp.log(jnp.maximum(dist, 1e-30)), axis=-1).astype(jnp.int32)
    j = jnp.arange(gamma + 1)[None]
    emit = jnp.where(j < n[:, None], jnp.pad(d, ((0, 0), (0, 1))),
                     correction[:, None])            # [S, gamma+1]
    return emit, n + 1


def speculative_burst_sampled(params, draft_params, cache: PagedKVCache,
                              draft_cache: PagedKVCache, batch, prev_tokens,
                              rng, temperature, top_p,
                              cfg: GPTConfig, draft_cfg: GPTConfig, *,
                              block_size: int, gamma: int, steps: int,
                              top_k: int = 0, mesh=None):
    """Sampled speculative decoding: the draft SAMPLES its tokens and the
    verify step runs rejection-sampling acceptance (spec_accept), so every
    emitted token is distributed exactly as target-only sampling under the
    same temperature/top-k/top-p transforms — for any draft.  See
    _speculative_burst_core for the shared choreography.
    Returns (toks, counts, prev', rng', cache', draft_cache')."""
    from deepspeed_tpu.inference.engine import _sampling_logits
    xform = functools.partial(_sampling_logits, temperature=temperature,
                              top_k=top_k, top_p=top_p)
    return _speculative_burst_core(
        params, draft_params, cache, draft_cache, batch, prev_tokens,
        rng, xform, cfg, draft_cfg, block_size=block_size, gamma=gamma,
        steps=steps, sampled=True, mesh=mesh)


def ragged_decode_forward(params, cache: PagedKVCache, batch,
                          cfg: GPTConfig, *, block_size: int, mesh=None,
                          kv_layout=None, moe_stats: bool = False,
                          moe_routes: bool = False):
    """Decode-only step: one token per active slot, attending over exactly that
    slot's pages via the paged-attention op (Pallas kernel on TPU; the gathered
    masked-softmax XLA path is the fallback + ground truth) — the analog of the
    reference's blocked_flash decode kernel (inference/v2/kernels/ragged_ops/
    blocked_flash).

    batch: tokens [S], active [S] bool, token_pos [S] (position being written),
    block_table [S, MB] int32 (each slot's physical pages, in order).
    """
    routes = [] if moe_routes else None
    logits, pool, stats = _decode_core(
        params, _KVPool.open(cache, cfg), batch["tokens"], batch["active"],
        batch["token_pos"], _group_tables(batch), cfg, block_size, mesh=mesh,
        lora=batch.get("lora"), adapter_slot=batch.get("adapter_slot"),
        kv_layout=kv_layout, moe_stats=moe_stats, routes=routes)
    out = (logits, pool.close(cache)) + ((stats,) if moe_stats else ())
    return out + ((jnp.stack(routes),) if moe_routes else ())
