"""MoE layer with expert parallelism.

Reference parity: ``deepspeed/moe/layer.py:17`` (MoE module), ``sharded_moe.py:455``
(MOELayer: einsum dispatch → all-to-all → local experts → all-to-all → combine),
``moe/experts.py`` (Experts container).

TPU-native: expert weights are stacked [E, ...] arrays annotated with the
``expert`` logical axis (sharded over the ``ep`` mesh axis); the token route is
the same GShard einsum algebra — which was *born* on TPU — with the two
all-to-alls expressed in ``shard_map`` over ``ep`` when ep > 1.  EP composes
with dp/fsdp exactly like the reference's expert+data parallel groups
(utils/groups.py:114 _create_expert_and_data_parallel).

call: ``MoE(...)(x, rng)`` → ``(y, aux_loss)`` with x [B, T, H].
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from deepspeed_tpu.moe.comm import qwire_a2a, resolve_a2a_bits
from deepspeed_tpu.moe.sharded_moe import topk_gating


def _part(init, names):
    return nn.with_partitioning(init, names)


def aggregate_moe_stats(collection):
    """Fold the per-layer ``moe_stats`` sows (engine's
    ``mutable=["moe_stats"]`` apply) into ONE small dict: token counts sum
    across MoE layers, aux-loss/gate-entropy average.  {} when the model
    sowed nothing (dense model, or telemetry off)."""
    dicts = jax.tree_util.tree_leaves(
        collection,
        is_leaf=lambda x: isinstance(x, dict) and "expert_tokens" in x)
    dicts = [d for d in dicts if isinstance(d, dict)]
    if not dicts:
        return {}
    n = len(dicts)      # static python int — divides arrays exactly
    return {
        "expert_tokens": sum(d["expert_tokens"] for d in dicts),
        "dropped_tokens": sum(d["dropped_tokens"] for d in dicts),
        "assigned_tokens": sum(d["assigned_tokens"] for d in dicts),
        "aux_loss": sum(d["aux_loss"] for d in dicts) / n,
        "gate_entropy": sum(d["gate_entropy"] for d in dicts) / n,
    }


def _resolve_chunks(n_units: int, num_chunks: int) -> int:
    """Largest divisor of ``n_units`` that is <= ``num_chunks`` — the chunk
    count must tile the expert (or assignment) dim exactly, and asking for
    more chunks than units degrades gracefully to one unit per chunk."""
    nc = max(1, min(num_chunks, n_units))
    while n_units % nc:
        nc -= 1
    return nc


def _expert_ffn(d, wi, wo, wg=None):
    """Grouped expert FFN: one big [E,...] einsum (MXU grouped matmul) instead of
    the reference's per-expert module list (moe/experts.py).  wg (per-expert
    gate, [E, H, M]) switches GELU → SwiGLU (Mixtral experts)."""
    h = jnp.einsum("ech,ehm->ecm", d, wi.astype(d.dtype))
    if wg is not None:
        h = nn.silu(jnp.einsum("ech,ehm->ecm", d, wg.astype(d.dtype))) * h
    else:
        h = nn.gelu(h)
    return jnp.einsum("ecm,emh->ech", h, wo.astype(d.dtype))


_COUNT_BLOCK = 128


def _positions_by_count(ids, num):
    """Where a stable sort by id would put each entry, from counts and not
    from a sort: ``ids [A]`` int32 in ``[0, num)`` -> ``(dest [A], sizes
    [num])`` int32 with ``dest[a] = sum(sizes[:ids[a]]) + the entries before
    a with a's id``, which is ``argsort(argsort(ids))`` for the stable sort,
    and ``sizes`` the count of each id.

    The ids are few (an expert layer's held experts and one sentinel), so
    the entries before ``a`` are a prefix sum down a one-hot ``[A, num]``:
    inside a block of 128 entries a strictly lower-triangular matmul
    (zeros and ones in bfloat16, float32 sums of at most 128 of them: exact,
    and on the MXU), across the blocks a cumulative sum of the blocks'
    totals.  An entry past ``A`` (the last block's padding) matches no id
    and counts for nothing."""
    A = ids.shape[0]
    B = _COUNT_BLOCK
    nb = -(-A // B)
    ids = jnp.pad(ids, (0, nb * B - A), constant_values=num)
    hot = (ids[:, None] == jnp.arange(num, dtype=ids.dtype)).reshape(nb, B, num)
    earlier = jnp.tril(jnp.ones((B, B), jnp.bfloat16), -1)
    within = jnp.einsum("ij,njg->nig", earlier, hot.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    totals = jnp.sum(hot, axis=1, dtype=jnp.int32)              # [nb, num]
    sizes = jnp.sum(totals, axis=0)
    base = (jnp.cumsum(totals, axis=0) - totals                 # blocks before
            + jnp.cumsum(sizes) - sizes)                        # ids before
    dest = jnp.sum(jnp.where(hot, within.astype(jnp.int32) + base[:, None],
                             0), axis=-1)
    return dest.reshape(-1)[:A], sizes


def _expert_ffn_ragged(tokens, expert_idx, weights, wi, wo, wg=None, *,
                       expert_offset: int = 0, num_experts=None, live=None,
                       with_stats: bool = False, impl="xla"):
    """Dropless grouped GEMM (megablox semantics; reference analog:
    inference/v2 MoE gather/scatter + cutlass grouped GEMM, and the
    MegaBlocks paper): assignments group by expert, each expert multiplies
    exactly its rows: no capacity padding, no dropped tokens.

    tokens [S, H]; expert_idx [S, k] over all ``num_experts``; weights
    [S, k] -> [S, H].

    **A share.**  ``wi``/``wo``/``wg`` hold the experts ``[expert_offset,
    expert_offset + E)`` of ``num_experts`` (default: all of them, offset
    0): one chip's part of an expert-parallel layer.  The result is the
    part those experts give; what the absent ones would add is left out,
    and nothing here stands in for them.  An assignment to an expert not
    held (or of a row ``live`` masks out: padding, an idle slot) is dropped
    BEFORE the gather: it takes the sentinel id ``E``, lies behind every
    held expert's rows and belongs to no group.  Shapes stay static (the
    row buffer is ``S * k`` long, the worst case of every assignment being
    local) while the rows are dynamic: the grouped GEMM multiplies the
    first ``sum(group_sizes)`` rows, one run per expert, and what lies
    behind them is neither multiplied nor read back.

    **The permutation, there and back (PR 56).**  The rows go out by one
    gather ``tokens[order // k]``, ``order`` the stable ``argsort`` of the
    ids (0.009 ms a layer of 8,192 assignments inside a step program on a
    v5e; its inverse as an int32 scatter of ``arange`` read 0.038, so the
    sort stayed).  ``dest [S*k]``, the place of assignment ``a = s*k + j``
    in the expert-grouped buffer and ``order``'s inverse, comes from counts
    (``_positions_by_count``, and ``group_sizes`` with it), and the
    products come back by a gather too: ``out[s] = sum_j w[s, j] *
    o[dest[s, j]]``, product and sum in float32, one rounding.  A dropped
    assignment is masked by ``where`` on the gathered row, never by a
    product with 0: its ``dest`` points behind the last group, where the
    buffer holds whatever the backend left (``ragged_dot`` zeros, the kernel
    its last tile's products of the tail and nothing behind that tile, NaN
    included), and a kept one never points there, so no pass over the
    buffer masks the tail and a row that is not live reads exactly 0.

    ``with_stats``: also int32 ``[local assignments, assignments of live
    rows, local experts with at least one row]``, for the serving counters.

    ``impl`` goes to ``ops.grouped_gemm``.  The default is ``lax.ragged_dot``
    by name, which is what everything differentiated needs (the flax module
    below; the two gathers transpose to scatter-adds of cotangents);
    serving's forward-only step passes None and lets the registry take the
    Pallas kernel where the backend and the shape allow, which needs a gate
    (the GELU form keeps ``lax.ragged_dot``).
    """
    from deepspeed_tpu import ops
    S, H = tokens.shape
    k = expert_idx.shape[1]
    E = wi.shape[0]
    flat_e = expert_idx.reshape(-1).astype(jnp.int32)     # [S*k]
    keep = None
    if not (expert_offset == 0 and num_experts in (None, E) and live is None):
        local = flat_e - expert_offset
        keep = (local >= 0) & (local < E)
        if live is not None:
            keep = keep & jnp.repeat(live, k)
        flat_e = jnp.where(keep, local, E)
    dest, sizes = _positions_by_count(flat_e, E + 1)      # group by expert
    group_sizes = sizes[:E]
    order = jnp.argsort(flat_e)                           # dest's inverse
    sorted_tok = tokens[order // k]                       # source token/row
    if wg is not None:
        h = ops.grouped_gemm(sorted_tok, wi.astype(tokens.dtype), group_sizes,
                             wg.astype(tokens.dtype), impl=impl)
    else:
        impl = "xla"
        h = nn.gelu(ops.grouped_gemm(sorted_tok, wi.astype(tokens.dtype),
                                     group_sizes, impl=impl))
    o = ops.grouped_gemm(h, wo.astype(tokens.dtype), group_sizes, impl=impl)
    # a token's j-th product by one gather of [S, H] a choice, summed as
    # they come: ONE gather reshaped to [S, k, H] puts k where a tile wants
    # 8 rows and is laid out again, and as [k, S, H] the compiler converts
    # it to float32 in a pass of its own (both seen in the v5e's HLO)
    dest, w = dest.reshape(S, k), weights.astype(jnp.float32)
    kept = None if keep is None else keep.reshape(S, k)
    out = 0.0
    for j in range(k):
        rows = o[dest[:, j]].astype(jnp.float32) * w[:, j:j + 1]
        if kept is not None:
            rows = jnp.where(kept[:, j:j + 1], rows, 0)
        out = out + rows
    out = out.astype(tokens.dtype)
    if not with_stats:
        return out
    n_live = (S if live is None else jnp.sum(live.astype(jnp.int32))) * k
    stats = jnp.stack([jnp.sum(group_sizes),
                       jnp.asarray(n_live, jnp.int32),
                       jnp.sum((group_sizes > 0).astype(jnp.int32))])
    return out, stats


class MoE(nn.Module):
    """Mixture-of-experts layer (reference deepspeed.moe.layer.MoE).

    Experts are distributed over the ``ep`` mesh axis; each ep rank holds
    num_experts/ep_size experts.  use_residual=True gives Residual MoE
    (reference layer.py:27).
    """

    hidden_size: int
    num_experts: int = 8
    k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    use_residual: bool = False
    mlp_ratio: int = 4
    mlp_dim: Optional[int] = None       # explicit FFN width (Mixtral 14336)
    mesh: Optional[Mesh] = None
    param_dtype: object = jnp.float32
    # dropless routing (ragged grouped GEMM, no capacity/no token drops);
    # ep>1 keeps the capacity path (the A2A needs static per-rank shapes)
    dropless: bool = False
    # SwiGLU experts (per-expert gate matrix — Mixtral style)
    gated: bool = False
    # wire format of the ep dispatch/combine all-to-alls (moe/comm.py):
    # 0 = full width; 8/4 = blockwise int codes + fp32 scales
    wire_bits: int = 0
    wire_block: int = 256
    # hierarchical wire policy: all-ICI ep axes stay full width
    hierarchical: bool = False
    # chunk the dispatch-a2a -> expert FFN -> combine-a2a chain over this
    # many expert sub-groups so GEMMs interleave with in-flight a2a chunks
    num_chunks: int = 1
    # afmoe / Trinity (GPTConfig.moe_*): a sigmoid router with a
    # selection-only bias, a shared expert every token takes, and the share
    # of the experts held here (see _expert_ffn_ragged)
    router: str = "softmax"
    route_norm: bool = True
    route_scale: float = 1.0
    route_eps: float = 1e-20
    router_bias: bool = False
    shared_dim: int = 0
    experts_held: Optional[int] = None
    expert_offset: int = 0

    @nn.compact
    def __call__(self, x, rng: Optional[jax.Array] = None,
                 deterministic: bool = False):
        B, T, H = x.shape
        E = self.num_experts                 # the router's width
        El = self.experts_held or E          # experts whose weights are here
        M = self.mlp_dim or self.hidden_size * self.mlp_ratio
        cf = self.eval_capacity_factor if deterministic else self.capacity_factor
        k_init = nn.initializers.normal(stddev=0.02)

        wg = self.param("gate", _part(k_init, ("embed", None)),
                        (H, E), self.param_dtype)
        wi = self.param("wi", _part(k_init, ("expert", "embed", "mlp")),
                        (El, H, M), self.param_dtype)
        wo = self.param("wo", _part(k_init, ("expert", "mlp", "embed")),
                        (El, M, H), self.param_dtype)
        weg = (self.param("wge", _part(k_init, ("expert", "embed", "mlp")),
                          (El, H, M), self.param_dtype)
               if self.gated else None)    # per-expert SwiGLU gate (Mixtral)

        tokens = x.reshape(B * T, H)
        if self.router == "sigmoid":
            return self._sigmoid_route(x, tokens, wg, wi, wo, weg, k_init)
        if self.router != "softmax":
            raise ValueError(f"unknown MoE router {self.router!r}; "
                             f"expected softmax|sigmoid")
        if El != E or self.shared_dim or self.router_bias:
            raise ValueError(
                "experts_held / shared_dim / router_bias are wired for the "
                "sigmoid router's dropless route only")
        logits = tokens @ wg.astype(x.dtype)
        noise_std = 1.0 / E if (self.noisy_gate_policy and not deterministic
                                and rng is not None) else 0.0

        ep = self.mesh.shape["ep"] if self.mesh is not None else 1
        # per-axis hierarchy policy resolves OUTSIDE the shard_map (static
        # per mesh); ep == 1 has no wire at all
        bits = resolve_a2a_bits(self.wire_bits, hierarchical=self.hierarchical,
                                mesh=self.mesh) if ep > 1 else 0
        if self.dropless:
            from deepspeed_tpu.moe.sharded_moe import dropless_topk
            aux, expert_idx, weights = dropless_topk(logits, self.k, rng,
                                                     noise_std)
            if ep > 1:
                if E % ep:
                    raise ValueError(f"num_experts {E} not divisible by "
                                     f"ep {ep}")
                out = _ep_route_dropless(self.mesh, tokens, expert_idx,
                                         weights, wi, wo, weg,
                                         wire_bits=bits,
                                         wire_block=self.wire_block,
                                         num_chunks=self.num_chunks)
            else:
                out = _expert_ffn_ragged(tokens, expert_idx, weights, wi, wo,
                                         weg)
            exp_tokens = jnp.bincount(expert_idx.reshape(-1), length=E)
            self._sow_stats(logits, aux, exp_tokens, jnp.float32(0.0))
            return self._finish(x, out.reshape(B, T, H), aux, k_init)

        aux, combine, dispatch = topk_gating(
            logits, self.k, cf, self.min_capacity, rng, noise_std)

        if ep > 1:
            out = _ep_route(self.mesh, tokens, combine, dispatch, wi, wo, weg,
                            wire_bits=bits, wire_block=self.wire_block,
                            num_chunks=self.num_chunks)
        else:
            dispatched = jnp.einsum("sec,sh->ech",
                                    dispatch.astype(x.dtype), tokens)
            expert_out = _expert_ffn(dispatched, wi, wo, weg)
            out = jnp.einsum("sec,ech->sh", combine.astype(x.dtype), expert_out)

        kept = dispatch.astype(jnp.float32)
        self._sow_stats(logits, aux, kept.sum(axis=(0, 2)),
                        logits.shape[0] * self.k - kept.sum())
        return self._finish(x, out.reshape(B, T, H), aux, k_init)

    def _sigmoid_route(self, x, tokens, wg, wi, wo, weg, k_init):
        """The afmoe expert layer: ``shared(m) + sum over the chosen k of
        w_e * expert_e(m)``, router in float32, the routed part over the
        experts held here.  No auxiliary loss: the family balances its
        load through the selection bias, outside the graph."""
        from deepspeed_tpu.moe.sharded_moe import sigmoid_topk
        B, T, H = x.shape
        E = self.num_experts
        if self.mesh is not None and self.mesh.shape.get("ep", 1) > 1:
            raise NotImplementedError(
                "sigmoid router over an ep mesh: the exchange is not "
                "built; give each chip its share (experts_held)")
        if not self.dropless:
            raise ValueError("the sigmoid router routes dropless "
                             "(moe_dropless=True): it has no capacity form")
        bias = (self.param("expert_bias", _part(nn.initializers.normal(stddev=0.005),
                                                (None,)),
                           (E,), self.param_dtype)
                if self.router_bias else None)
        logits = jnp.dot(tokens, wg.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        idx, w = sigmoid_topk(logits, self.k, bias, self.route_norm,
                              self.route_scale, self.route_eps)
        out = _expert_ffn_ragged(tokens, idx, w, wi, wo, weg,
                                 expert_offset=self.expert_offset,
                                 num_experts=E)
        if self.shared_dim:
            Ms = self.shared_dim
            si = self.param("shared_wi", _part(k_init, ("embed", "mlp")),
                            (H, Ms), self.param_dtype)
            sg = self.param("shared_wg", _part(k_init, ("embed", "mlp")),
                            (H, Ms), self.param_dtype)
            so = self.param("shared_wo", _part(k_init, ("mlp", "embed")),
                            (Ms, H), self.param_dtype)
            out = out + (nn.silu(tokens @ sg.astype(x.dtype))
                         * (tokens @ si.astype(x.dtype))) @ so.astype(x.dtype)
        return out.reshape(B, T, H), jnp.float32(0.0)

    def _sow_stats(self, logits, aux, expert_tokens, dropped):
        """Expert-load observability: sow per-layer routing stats into the
        ``moe_stats`` collection (lax.stop_gradient — pure telemetry).  A
        no-op unless the caller passes ``mutable=["moe_stats"]`` (the
        engine's stats apply fn); guarded against ``init``, where every
        collection is mutable and the sow would pollute the params tree."""
        if self.is_initializing():
            return
        p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        ent = jnp.mean(-jnp.sum(p * jnp.log(p + 1e-9), axis=-1))
        self.sow("moe_stats", "stats", jax.lax.stop_gradient({
            "expert_tokens": expert_tokens.astype(jnp.float32),
            "dropped_tokens": jnp.asarray(dropped, jnp.float32),
            "assigned_tokens": jnp.float32(logits.shape[0] * self.k),
            "aux_loss": jnp.asarray(aux, jnp.float32),
            "gate_entropy": ent,
        }))

    def _finish(self, x, out, aux, k_init):
        if self.use_residual:
            # Residual MoE (reference layer.py use_residual): dense MLP branch
            # mixed with the MoE branch by a learned per-token coefficient
            H, M = self.hidden_size, self.hidden_size * self.mlp_ratio
            mi = self.param("residual_wi", _part(k_init, ("embed", "mlp")),
                            (H, M), self.param_dtype)
            mo = self.param("residual_wo", _part(k_init, ("mlp", "embed")),
                            (M, H), self.param_dtype)
            mlp_out = nn.gelu(x @ mi.astype(x.dtype)) @ mo.astype(x.dtype)
            coef_w = self.param("coefficient", _part(nn.initializers.zeros,
                                                     ("embed", None)),
                                (H, 2), self.param_dtype)
            coef = jax.nn.softmax(x @ coef_w.astype(x.dtype), axis=-1)
            out = out * coef[..., 0:1] + mlp_out * coef[..., 1:2]
        return out, aux


def _ep_route(mesh: Mesh, tokens, combine, dispatch, wi, wo, weg=None, *,
              wire_bits: int = 0, wire_block: int = 256, num_chunks: int = 1):
    """all-to-all route (reference sharded_moe.py MOELayer.forward): dispatch
    einsum → A2A (tokens meet their expert owners) → local experts → A2A back →
    combine einsum, inside shard_map over the ep axis.

    Token batch is replicated over ep within each dp shard here (ep composes
    with dp/fsdp at the mesh level; each ep rank routes its 1/ep slice of the
    local tokens — reference: EP group is orthogonal to DP group).

    The a2a pair goes through ``moe/comm.qwire_a2a`` — int codes + scales on
    the wire when ``wire_bits`` is 4/8 — and the dispatch-a2a → FFN →
    combine-a2a chain tiles over ``num_chunks`` local-expert sub-groups so
    XLA's latency-hiding scheduler can interleave chunk c's expert GEMM with
    chunk c+1's in-flight a2a (the T3 pattern; PR 4 chunk semantics).
    """

    # tokens/combine/dispatch split over the joint (dp, fsdp, ep) group so dp
    # replicas don't redo each other's expert work (reference: expert+data
    # parallel groups, utils/groups.py:114); expert weights live on ep only.
    tok_spec = P(("dp", "fsdp", "ep"), None)
    sec_spec = P(("dp", "fsdp", "ep"), None, None)
    w_spec = P("ep", None, None)
    gated = weg is not None
    in_specs = (tok_spec, sec_spec, sec_spec, w_spec, w_spec) + \
        ((w_spec,) if gated else ())

    ep = mesh.shape["ep"]
    E_local = wi.shape[0] // ep
    nc = _resolve_chunks(E_local, num_chunks)
    g = E_local // nc                       # local experts per chunk
    ex_d = qwire_a2a("ep", ep, 0, 1, bits=wire_bits, block_size=wire_block)
    ex_c = qwire_a2a("ep", ep, 1, 0, bits=wire_bits, block_size=wire_block)

    @partial(shard_map, mesh=mesh, in_specs=in_specs,
             out_specs=tok_spec, check_vma=False)
    def route(tokens, combine, dispatch, wi, wo, *maybe_weg):
        # local shapes: tokens [S/(dp·fsdp·ep), H]; combine/dispatch [S', E, C];
        # wi [E/ep, H, M]
        weg_l = maybe_weg[0] if maybe_weg else None
        dispatched = jnp.einsum("sec,sh->ech",
                                dispatch.astype(tokens.dtype), tokens)
        E, C, H = dispatched.shape
        # global expert e = p*E_local + l (dest rank p, local expert l):
        # chunk c covers local experts [c*g, (c+1)*g) on EVERY rank
        disp4 = dispatched.reshape(ep, E_local, C, H)
        outs = []
        for c in range(nc):
            lo, hi = c * g, (c + 1) * g
            part = disp4[:, lo:hi].reshape(ep * g, C, H)
            ex = ex_d(part)                 # [g, C*ep, H]: this rank's chunk
            eo = _expert_ffn(ex, wi[lo:hi], wo[lo:hi],
                             weg_l[lo:hi] if weg_l is not None else None)
            back = ex_c(eo)                 # [g*ep, C, H], peer-major
            outs.append(back.reshape(ep, g, C, H))
        # [ep, nc, g, C, H] → [E, C, H]: global id p*E_local + c*g + j
        expert_out = jnp.stack(outs, axis=1).reshape(E, C, H)
        return jnp.einsum("sec,ech->sh", combine.astype(tokens.dtype),
                          expert_out)

    args = (tokens, combine, dispatch, wi, wo) + ((weg,) if gated else ())
    return route(*args)


def _ep_route_dropless(mesh: Mesh, tokens, expert_idx, weights, wi, wo,
                       weg=None, *, wire_bits: int = 0, wire_block: int = 256,
                       num_chunks: int = 1):
    """Capacity-FREE expert-parallel route (round-3 VERDICT item 7 —
    reference analog: inference/v2 cutlass grouped GEMM consumed under EP;
    MegaBlocks): no token is ever dropped.

    Static-shape scheme (XLA needs fixed a2a sizes): each rank sorts its
    A = S_local·k assignments by destination rank, packs them into a
    per-destination bucket PADDED to A rows (worst case: every assignment
    goes to one peer), all-to-alls the [ep, A, H] buffer + a parallel
    local-expert id buffer (sentinel id = dead row), runs ``ragged_dot``
    over its received rows grouped by local expert (sentinel rows hit a
    zero-weight dummy expert), and all-to-alls results back to be combined
    at the source.  Bandwidth is worst-case padded — the price of static
    shapes; the capacity path stays available when a bounded a2a matters
    more than zero drops.

    The three value a2as ride ``moe/comm.qwire_a2a`` (int wire when
    ``wire_bits``); the int32 id buffer always moves FULL width — routing
    indices must survive the wire exactly.  ``num_chunks`` tiles the
    assignment dim so per-chunk expert GEMMs interleave with in-flight a2a
    chunks; the grouping only changes GEMM batching, so outputs agree
    row-wise up to float rounding (a chunk's ``ragged_dot`` sees fewer
    rows, and a backend blocks a matmul's accumulation by its shape)."""
    ep = mesh.shape["ep"]
    E, H, M = wi.shape
    E_local = E // ep
    k = expert_idx.shape[1]
    gated = weg is not None

    tok_spec = P(("dp", "fsdp", "ep"), None)
    idx_spec = P(("dp", "fsdp", "ep"), None)
    w_spec = P("ep", None, None)
    in_specs = (tok_spec, idx_spec, idx_spec, w_spec, w_spec) + \
        ((w_spec,) if gated else ())

    # (0,0) a2a is its own transpose — one exchange serves both directions
    ex_v = qwire_a2a("ep", ep, 0, 0, bits=wire_bits, block_size=wire_block)

    @partial(shard_map, mesh=mesh, in_specs=in_specs,
             out_specs=tok_spec, check_vma=False)
    def route(tokens, expert_idx, weights, wi, wo, *maybe_weg):
        S = tokens.shape[0]                      # local rows
        A = S * k
        nc = _resolve_chunks(A, num_chunks)
        ac = A // nc                             # assignments per chunk
        flat_e = expert_idx.reshape(A)           # global expert ids
        order = jnp.argsort(flat_e)              # by (dest rank, local expert)
        e_sorted = flat_e[order]
        tok_rows = jnp.repeat(jnp.arange(S), k)[order]
        d_sorted = e_sorted // E_local           # nondecreasing dest rank
        cnt = jnp.bincount(d_sorted, length=ep)
        start = jnp.concatenate([jnp.zeros((1,), cnt.dtype),
                                 jnp.cumsum(cnt)])[:-1]
        pos = jnp.arange(A) - start[d_sorted]    # slot within dest bucket

        send = jnp.zeros((ep * A, H), tokens.dtype).at[
            d_sorted * A + pos].set(tokens[tok_rows]).reshape(ep, A, H)
        ids = jnp.full((ep * A,), E_local, jnp.int32).at[
            d_sorted * A + pos].set((e_sorted % E_local).astype(
                jnp.int32)).reshape(ep, A)

        pad_i = jnp.concatenate([wi, jnp.zeros((1, H, M), wi.dtype)])
        pad_o = jnp.concatenate([wo, jnp.zeros((1, M, H), wo.dtype)])
        pad_g = (jnp.concatenate([maybe_weg[0],
                                  jnp.zeros((1, H, M), wo.dtype)])
                 if maybe_weg else None)

        back_chunks = []
        for c in range(nc):
            lo, hi = c * ac, (c + 1) * ac
            recv = ex_v(send[:, lo:hi])          # [ep, ac, H] values
            rids = lax.all_to_all(ids[:, lo:hi], "ep", 0, 0, tiled=True)

            flat = recv.reshape(ep * ac, H)
            fids = rids.reshape(ep * ac)
            ord2 = jnp.argsort(fids)             # group by local expert;
            rows = flat[ord2]                    # sentinel rows sort last
            gs = jnp.bincount(fids, length=E_local + 1).astype(jnp.int32)
            h = jax.lax.ragged_dot(rows, pad_i.astype(rows.dtype), gs)
            if pad_g is not None:
                h = nn.silu(jax.lax.ragged_dot(
                    rows, pad_g.astype(rows.dtype), gs)) * h
            else:
                h = nn.gelu(h)
            o = jax.lax.ragged_dot(h, pad_o.astype(rows.dtype), gs)
            o = o[jnp.argsort(ord2)].reshape(ep, ac, H)
            back_chunks.append(ex_v(o))          # [ep, ac, H] results
        back = jnp.concatenate(back_chunks, axis=1)   # == unchunked [ep, A, H]

        res_sorted = back[d_sorted, pos]         # [A, H] expert outputs
        w_sorted = weights.reshape(A)[order].astype(res_sorted.dtype)
        return jnp.zeros_like(tokens).at[tok_rows].add(
            res_sorted * w_sorted[:, None])

    args = (tokens, expert_idx, weights, wi, wo) + ((weg,) if gated else ())
    return route(*args)
