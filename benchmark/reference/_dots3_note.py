"""Plain dots3-note forward (``model_type: dots3_note``): latent attention
with a query latent on every layer; on the FULL layers a learned selection of
keys (a DeepSeek-V3.2-style "lightning indexer") and on the SLIDING layers a
second latent geometry under a window; a headwise gate; sigmoid-routed
experts beside one shared expert.  Float32 ``jax.numpy``, ``HIGHEST``
precision, no kernels, no cache, no batching, NOT absorbed, the selection a
mask over dense scores; written from the published ``config.json`` of
``dots3-note-prev`` and importing nothing from the program under test.

Per layer, on a sequence ``x [T, H]`` (``n*`` RMSNorm, ``x / sqrt(mean(x^2)
+ eps) * scale``; pre-norm; no embedding scale; head untied), with the
layer's own geometry (full: 128 heads, nope 128, rope 64, value 128, latents
1,024 / 512, base 8e7; sliding: 64 heads, nope 192, rope 64, value 128,
latents 1,024 / 1,024, base 50,000, window 513)::

    a  = n1(x)
    cq = nq(Wqa a) * rq                        the query latent
    [q_nope_h | q_pe_h] = Wqb_h cq             per head
    [c | k_pe] = Wkva a;  c = nkv(c) * rkv     k_pe ONE head, shared
    q_pe, k_pe = rope(., pos)                  the layer's base
    [k_nope_h | v_h] = Wkvb_h c
    full layer:    qI_j = WqI_j cq (j < 64),  kI = LayerNorm(WkI a),
                   rope on the LEADING 64 columns of qI_j and kI,
                   w = (WwI a) * 64^-0.5 * 128^-0.5
                   I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s]),  s <= t
                   S_t = the min(2048, t + 1) keys of largest I[t, .],
                         ties to the lower position
    sliding layer: S_t = {s : 0 <= t - s < 513}
    p_h = softmax over S_t of (q_nope_h . k_nope_h + q_pe_h . k_pe)
          / sqrt(nope + rope);     o_h = sum p_h v_h
    g = sigmoid(Wg a)                          ONE scalar a head
    h = x + Wo [g_h o_h];          m = n2(h)
    layer 0:       f = Wd (silu(Wg m) * (Wu m))
    later layers:  s = sigmoid(float32(Wr m));  S = top_8(s + b)
                   w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
                   f = shared(m) + sum_{e in S, e held} w_e * expert_e(m)
    y = h + f;     logits = Wout nf(y)

The held share is computed as the program computes it: the router is as
wide as published, the weights hold experts ``expert_offset ..`` of them, and
what the absent experts would add is left out here and there alike.

ASSUMED (the configuration file's ``assumed`` block gives each reason):
``apply_mla_qkv_lora_rescale`` read as the LongCat-Flash convention, ``rq =
sqrt(hidden / q_lora_rank)``, ``rkv = sqrt(hidden / kv_lora_rank)`` per kind
of layer; ``attention_gate_type: headwise`` read as a sigmoid scalar a head
from the layer's normed input, applied before ``Wo``; the indexer's shape,
its LayerNorm (eps 1e-6, with a bias), its two scales and its rope-first
column order from the published DeepSeek-V3.2 indexer, its FP8 quantisation
and Hadamard rotation left out (bf16 is the stated precision; the rotation
is orthogonal and leaves ``q . k`` as it is); the window counts the query's
own position; RoPE rotates the two halves of the rope columns where the
published weights pair neighbours (a fixed permutation the loader applies);
no EOS; the vision and audio towers and the MTP module are left out.

Weights come in the shapes and the type the program stores them in and are
raised to float32 where they are used: a layer per jitted call, attention
one head at a time, index scores one index head at a time, an expert layer
one expert at a time, so that 6.4 k positions fit beside a serving engine.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"     # on a TPU a float32 matmul is bf16 passes without it
LN_EPS = 1e-6           # the indexer's LayerNorm


class Geometry(NamedTuple):
    """One kind of layer's attention, static."""
    rope_dim: int
    theta: float
    rq: float
    rkv: float
    window: Optional[int] = None
    topk: int = 0


def layer_kinds(sizes):
    """(kind, is_moe) of each kept layer, by its published index."""
    kept = sizes.get("layers_kept") or range(sizes["num_hidden_layers"])
    return [("sliding" if sizes["layer_types"][j] == "sliding_attention"
             else "full", j >= sizes["first_k_dense_replace"]) for j in kept]


def geometry(sizes, kind):
    pre = "swa_" if kind == "sliding" else ""
    rescale = bool(sizes["apply_mla_qkv_lora_rescale"])
    hidden = sizes["hidden_size"]

    def r(rank):
        return float((hidden / rank) ** 0.5) if rescale else 1.0
    return Geometry(
        rope_dim=int(sizes[pre + "qk_rope_head_dim"]),
        theta=float(sizes[pre + "rope_theta"]),
        rq=r(sizes[pre + "q_lora_rank"]), rkv=r(sizes[pre + "kv_lora_rank"]),
        window=int(sizes["sliding_window_size"]) if kind == "sliding"
        else None,
        topk=0 if kind == "sliding" else int(sizes["index_topk"]))


def tree(params):
    """The program's parameter tree under the reference's names (views)."""
    bb = params["backbone"]
    n = sum(1 for k in bb if k.startswith("block_"))
    layers = []
    for i in range(n):
        blk = bb[f"block_{i}"]
        a = blk["Attention_0"]
        lp = {"n1": blk["Norm_0"]["scale"], "n2": blk["Norm_1"]["scale"],
              "wq_a": a["wq_a"], "n_q": a["q_norm"], "wq_b": a["wq_b"],
              "wkv_a": a["wkv_a"], "n_kv": a["kv_norm"],
              "wkv_b": a["wkv_b"], "wo": a["wo"], "w_gate_attn": a["wgate"]}
        if "wq_idx" in a:
            lp.update(wq_idx=a["wq_idx"], wk_idx=a["wk_idx"],
                      ww_idx=a["ww_idx"], kn_scale=a["k_idx_norm_scale"],
                      kn_bias=a["k_idx_norm_bias"])
        if "moe" in blk:
            m = blk["moe"]
            lp.update(router=m["gate"], bias=m["expert_bias"],
                      e_gate=m["wge"], e_up=m["wi"], e_down=m["wo"],
                      s_gate=m["shared_wg"], s_up=m["shared_wi"],
                      s_down=m["shared_wo"])
        else:
            m = blk["MLP_0"]
            lp.update(w_gate=m["wg"], w_up=m["wi"], w_down=m["wo"])
        layers.append(lp)
    return {"embed": bb["wte"], "layers": layers,
            "final_norm": bb["final_norm"]["scale"],
            "lm_head": params["lm_head"]}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _layer_norm(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale.astype(F32) \
        + bias.astype(F32)


def _rope(x, pos, theta):
    """``x [T, ..., d]`` rotated by halves at positions ``pos [T]``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq            # [T, half]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _index_rope(x, pos, g):
    """The indexer's RoPE: the LEADING ``rope_dim`` columns."""
    return jnp.concatenate([_rope(x[..., :g.rope_dim], pos, g.theta),
                            x[..., g.rope_dim:]], -1)


def _index_key(p, a, pos, g):
    return _index_rope(_layer_norm(a @ p["wk_idx"].astype(F32),
                                   p["kn_scale"], p["kn_bias"]), pos, g)


def _index_weights(p, a):
    n, d = p["wq_idx"].shape[1:]
    return (a @ p["ww_idx"].astype(F32)) * (n ** -0.5 * d ** -0.5)


def index_scores(p, a, cq, pos, g):
    """``I [T, T]`` (every pair; the caller masks), one index head at a
    time."""
    ki = _index_key(p, a, pos, g)                              # [T, dI]
    w = _index_weights(p, a)                                   # [T, nI]

    def head(acc, args):
        wq, wj = args                                          # [R, dI], [T]
        qj = _index_rope((cq @ wq)[:, None, :], pos, g)[:, 0]
        return acc + wj[:, None] * jax.nn.relu(qj @ ki.T), None
    acc, _ = jax.lax.scan(
        head, jnp.zeros((a.shape[0],) * 2, F32),
        (p["wq_idx"].astype(F32).transpose(1, 0, 2), w.T))
    return acc


def select(scores, seen, k):
    """Of each row's ``seen`` keys the ``k`` of largest score, ties to the
    lower position, as a mask: by the k-th value, not by a list."""
    T = scores.shape[-1]
    s = jnp.where(seen, scores, -jnp.inf)
    kk = min(int(k), T)
    kth = jax.lax.top_k(s, kk)[0][:, -1:]
    above = s > kth
    tie = (s == kth) & seen
    room = kk - jnp.sum(above, -1, keepdims=True)
    return (above | (tie & (jnp.cumsum(tie, -1) <= room))) & seen


def _gate(p, a):
    return jax.nn.sigmoid(a @ p["w_gate_attn"].astype(F32))    # [T, nh]


def attention_mask(p, a, cq, pos, g):
    """[T, T]: the keys each row attends over."""
    rel = pos[:, None] - pos[None, :]
    seen = rel >= 0
    if g.window is not None:
        return seen & (rel < g.window)
    if g.topk:
        return select(index_scores(p, a, cq, pos, g), seen, g.topk)
    return seen


def _attention_half(p, x, eps, g):
    """``h = x + Wo [g o]``: a layer up to its feed-forward."""
    T = x.shape[0]
    pos = jnp.arange(T)
    a = _rms(x, p["n1"], eps)
    cq = _rms(a @ p["wq_a"].astype(F32), p["n_q"], eps) * g.rq
    q = jnp.einsum("tr,rnd->tnd", cq, p["wq_b"].astype(F32))
    d = q.shape[-1]
    nope = d - g.rope_dim
    rank = p["n_kv"].shape[0]
    ckv = a @ p["wkv_a"].astype(F32)                          # [T, rank+rope]
    c = _rms(ckv[:, :rank], p["n_kv"], eps) * g.rkv
    k_pe = _rope(ckv[:, rank:], pos, g.theta)                 # [T, rope]
    q_pe = _rope(q[..., nope:], pos, g.theta)                 # [T, n, rope]
    mask = attention_mask(p, a, cq, pos, g)

    def head(args):                          # one head
        qn, qp, wkvb = args                  # [T, nope], [T, rope], [rank, .]
        kv = c @ wkvb                        # [T, nope + v]
        s = (qn @ kv[:, :nope].T + qp @ k_pe.T) * (d ** -0.5)
        s = jnp.where(mask, s, -jnp.inf)
        return jax.nn.softmax(s, -1) @ kv[:, nope:]           # [T, v]

    o = jax.lax.map(head, (q[..., :nope].transpose(1, 0, 2),
                           q_pe.transpose(1, 0, 2),
                           p["wkv_b"].astype(F32).transpose(1, 0, 2)))
    o = o * _gate(p, a).T[:, :, None]
    return x + jnp.einsum("ntd,ndh->th", o, p["wo"].astype(F32))


def _swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate.astype(F32)) * (m @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def route(m, router, bias, k, norm_topk, scale):
    """(chosen [T, k], weights [T, k], margin [T]): the k largest of
    ``s + b`` over all the router's experts, the weights from ``s`` alone,
    and how far the k-th lies above the (k+1)-th."""
    s = jax.nn.sigmoid(m @ router.astype(F32))               # [T, E]
    top, chosen = jax.lax.top_k(s + bias.astype(F32), k + 1)
    margin = top[:, k - 1] - top[:, k]
    chosen = chosen[:, :k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * scale, margin


def routed_part(m, chosen, w, e_gate, e_up, e_down, offset):
    """sum over the chosen experts that are held of ``w_e * expert_e(m)``:
    the held experts are ``offset ..`` of the router's; one at a time."""
    local = chosen - offset                                   # [T, k]

    def one(acc, args):
        e, wg, wu, wd = args
        c = jnp.sum(jnp.where(local == e, w, 0.0), -1)        # [T]
        return acc + c[:, None] * _swiglu(m, wg, wu, wd), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (jnp.arange(e_gate.shape[0]), e_gate, e_up, e_down))
    return acc


@functools.partial(jax.jit, static_argnames=(
    "eps", "g", "k", "norm_topk", "scale", "parts"))
def layer(p, x, *, eps, g, k=0, norm_topk=True, scale=1.0, offset=0,
          parts="all"):
    """One layer.  ``parts``: "all", or "routed" / "shared": that part of an
    expert layer's ``f`` alone; "mask": the attention mask [T, T]."""
    with jax.default_matmul_precision(HIGHEST):
        if parts == "mask":
            a = _rms(x, p["n1"], eps)
            cq = _rms(a @ p["wq_a"].astype(F32), p["n_q"], eps) * g.rq
            return attention_mask(p, a, cq, jnp.arange(x.shape[0]), g)
        h = _attention_half(p, x, eps, g)
        m = _rms(h, p["n2"], eps)
        if "router" not in p:
            f = _swiglu(m, p["w_gate"], p["w_up"], p["w_down"])
        else:
            chosen, w, _ = route(m, p["router"], p["bias"], k, norm_topk,
                                 scale)
            routed = routed_part(m, chosen, w, p["e_gate"], p["e_up"],
                                 p["e_down"], offset)
            if parts == "routed":
                return routed
            shared = _swiglu(m, p["s_gate"], p["s_up"], p["s_down"])
            if parts == "shared":
                return shared
            f = shared + routed
        return h + f


@functools.partial(jax.jit, static_argnames=(
    "eps", "g", "k", "norm_topk", "scale"))
def layer_routing(p, x, *, eps, g, k, norm_topk, scale):
    """(chosen [T, k], margin [T]) of an expert layer at its input ``x``."""
    with jax.default_matmul_precision(HIGHEST):
        m = _rms(_attention_half(p, x, eps, g), p["n2"], eps)
        chosen, _, margin = route(m, p["router"], p["bias"], k, norm_topk,
                                  scale)
        return chosen, margin


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(norm, lm_head, x, *, eps):
    """Logits [T, V] of the rows ``x [T, H]``."""
    with jax.default_matmul_precision(HIGHEST):
        return _rms(x, norm, eps) @ lm_head.astype(F32)


def _layer_args(sizes, kind, is_moe):
    kw = dict(eps=float(sizes["rms_norm_eps"]), g=geometry(sizes, kind))
    if is_moe:
        kw.update(k=int(sizes["num_experts_per_tok"]),
                  norm_topk=bool(sizes["norm_topk_prob"]),
                  scale=float(sizes["routed_scaling_factor"]))
    return kw


def hidden(p, tokens, sizes, routing_out=None, masks_out=None):
    assert sizes["scoring_func"] == "sigmoid"
    x = embed(p["embed"], jnp.asarray(tokens))
    offset = int(sizes.get("expert_offset", 0))
    for lp, (kind, is_moe) in zip(p["layers"], layer_kinds(sizes)):
        assert is_moe == ("router" in lp) and (kind == "full") == (
            "wq_idx" in lp), kind
        kw = _layer_args(sizes, kind, is_moe)
        if is_moe and routing_out is not None:
            routing_out.append(layer_routing(lp, x, **kw))
        if masks_out is not None and kind == "full":
            masks_out.append(layer(lp, x, parts="mask", eps=kw["eps"],
                                   g=kw["g"]))
        x = layer(lp, x, offset=offset, **kw) if is_moe \
            else layer(lp, x, **kw)
    return x


def logits(params, tokens, sizes, rows=None):
    """Float32 logits of one sequence ``tokens [T]`` at ``rows`` (all rows
    by default) from the program's parameter tree."""
    p = tree(params)
    x = hidden(p, tokens, sizes)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(p["final_norm"], p["lm_head"], x,
                eps=float(sizes["rms_norm_eps"]))


def routing(params, ids, sizes):
    """Per expert layer ``(chosen [T, k], margin [T])`` of the float32
    reference (ids over all the router's experts)."""
    out = []
    hidden(tree(params), ids, sizes, routing_out=out)
    return out


def selections(params, ids, sizes):
    """Per full layer the mask ``[T, T]`` of the keys each row attends
    over: the selected SETS."""
    out = []
    hidden(tree(params), ids, sizes, masks_out=out)
    return out


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "dots3_note" and sizes["hidden_act"] == "silu"
    assert sizes["moe_layer_freq"] == 1 and not sizes["attention_bias"]
    assert not sizes.get("rope_scaling") and sizes["n_shared_experts"] == 1
    assert sizes["num_key_value_heads"] == sizes["num_attention_heads"]
    assert sizes["attention_gate_type"] == "headwise" \
        == sizes["swa_attention_gate_type"]
    assert sizes["topk_method"] == "noaux_tc"
    kinds = layer_kinds(sizes)
    assert len(kinds) == sizes["num_hidden_layers"], kinds
    router_width = int(sizes.get("router_width", sizes["n_routed_experts"]))
    return dict(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        hidden_size=sizes["hidden_size"],
        mlp_dim_override=sizes["intermediate_size"],
        use_rope=True, use_rmsnorm=True, norm_eps=sizes["rms_norm_eps"],
        gated_mlp=True, gate_act="silu",
        tie_embeddings=bool(sizes["tie_word_embeddings"]),
        # the full layers' attention ...
        num_heads=sizes["num_attention_heads"],
        head_dim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], kv_lora_rank=sizes["kv_lora_rank"],
        q_lora_rank=sizes["q_lora_rank"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        rope_theta=float(sizes["rope_theta"]),
        mla_lora_rescale=bool(sizes["apply_mla_qkv_lora_rescale"]),
        attn_gate_headwise=True,
        index_topk=sizes["index_topk"], index_n_heads=sizes["index_n_heads"],
        index_head_dim=sizes["index_head_dim"],
        # ... and the sliding layers' own
        # (a cut that keeps no sliding layer has no window: GPTConfig reads
        # an empty ``local_attn_layers`` as "every layer")
        sliding_window=(int(sizes["sliding_window_size"])
                        if any(kind == "sliding" for kind, _ in kinds)
                        else None),
        local_attn_layers=tuple(i for i, (kind, _) in enumerate(kinds)
                                if kind == "sliding"),
        window_attn=(
            ("num_heads", sizes["swa_num_attention_heads"]),
            ("head_dim", sizes["swa_qk_nope_head_dim"]
             + sizes["swa_qk_rope_head_dim"]),
            ("v_head_dim", sizes["swa_v_head_dim"]),
            ("kv_lora_rank", sizes["swa_kv_lora_rank"]),
            ("q_lora_rank", sizes["swa_q_lora_rank"]),
            ("qk_rope_head_dim", sizes["swa_qk_rope_head_dim"]),
            ("rope_theta", float(sizes["swa_rope_theta"]))),
        num_experts=router_width, moe_k=sizes["num_experts_per_tok"],
        moe_dropless=True, moe_router="sigmoid",
        moe_route_norm=bool(sizes["norm_topk_prob"]),
        moe_route_scale=float(sizes["routed_scaling_factor"]),
        moe_router_bias=True,
        moe_shared_dim=sizes["moe_intermediate_size"]
        * sizes["n_shared_experts"],
        moe_expert_dim=sizes["moe_intermediate_size"],
        moe_dense_layers=sizes["first_k_dense_replace"],
        experts_held=(sizes["n_routed_experts"]
                      if sizes["n_routed_experts"] != router_width else None),
        expert_offset=int(sizes.get("expert_offset", 0)))
