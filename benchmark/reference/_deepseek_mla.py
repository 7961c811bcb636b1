"""Plain DeepSeek-V3-style forward (latent attention, sigmoid-routed experts
beside shared ones): float32 ``jax.numpy``, no kernels, no cache, no
batching, NOT absorbed; written from the published ``config.json`` of
Moonlight-16B-A3B (``model_type: deepseek_v3``) and the family's published
modelling code, and importing nothing from the program under test.

Per layer, on a sequence ``x [T, H]`` (``n*`` RMSNorm, ``x / sqrt(mean(x^2) +
eps) * scale``; pre-norm; no embedding scale; head untied):

    a = n1(x)
    q = Wq a                       per head [q_nope (nope) | q_pe (rope)]
    [c | k_pe] = Wkva a            kv_lora_rank + rope;  c = n_kv(c);
                                   k_pe is ONE head, shared by all heads
    q_pe, k_pe = rope(., pos)      theta rope_theta over the rope dims
    [k_nope_h | v_h] = Wkvb_h c    for each head
    s_h = softmax_causal((q_nope_h . k_nope_h + q_pe_h . k_pe)
                         / sqrt(nope + rope));    o_h = s_h v_h
    h = x + Wo [o_1 .. o_n];       m = n2(h)
    dense layer:   f = Wd (silu(Wg m) * (Wu m))
    expert layer:  s = sigmoid(float32(Wr m));  S = top_k(s + b)
                   w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
                   f = shared(m) + sum_{e in S} w_e * expert_e(m)
    y = h + f;     logits = Wout nf(y)

``b`` is the published ``e_score_correction_bias``: it enters the selection
and not the weights.  ``n_group`` and ``topk_group`` are 1: no group limit.
There is no ``rope_scaling``, so no mscale on the softmax scale.

Departures from the description, all of layout and none of arithmetic:
RoPE rotates the two halves of the rope dims (``rotate_half``) where the
published weights pair neighbouring columns: a fixed permutation of the 64
rope columns of ``Wq`` and ``Wkva`` (the program's loader applies it), and
with random weights the same model.  The shared experts are one SwiGLU of
width ``n_shared_experts * moe_intermediate_size``, as the family's code
builds them.  Weights come in the shapes and the type the program stores
them in (bf16 when serving) and are raised to float32 where they are used:
a layer per jitted call, inside an expert layer one expert at a time, and
attention one head at a time.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"     # on a TPU a float32 matmul is bf16 passes without it


def tree(params):
    """The program's parameter tree under the reference's names (views)."""
    bb = params["backbone"]
    n = sum(1 for k in bb if k.startswith("block_"))
    layers = []
    for i in range(n):
        blk = bb[f"block_{i}"]
        a = blk["Attention_0"]
        lp = {"n1": blk["Norm_0"]["scale"], "n2": blk["Norm_1"]["scale"],
              "wq": a["wq"], "wkv_a": a["wkv_a"], "n_kv": a["kv_norm"],
              "wkv_b": a["wkv_b"], "wo": a["wo"]}
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


def _rope(x, pos, theta):
    """``x [T, ..., d]`` rotated by halves at positions ``pos [T]``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq            # [T, half]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate.astype(F32)) * (m @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def route(m, router, bias, k, norm_topk, scale):
    """(chosen [T, k], weights [T, k], margin [T]): the k largest of
    ``s + b``, the weights from ``s`` alone, and how far the k-th lies above
    the (k+1)-th."""
    s = jax.nn.sigmoid(m @ router.astype(F32))               # [T, E]
    top, chosen = jax.lax.top_k(s + bias.astype(F32), k + 1)
    margin = top[:, k - 1] - top[:, k]
    chosen = chosen[:, :k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * scale, margin


def routed_part(m, chosen, w, e_gate, e_up, e_down):
    """sum over the chosen experts of ``w_e * expert_e(m)``, one expert at
    a time."""
    def one(acc, args):
        e, wg, wu, wd = args
        c = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)       # [T]
        return acc + c[:, None] * _swiglu(m, wg, wu, wd), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (jnp.arange(e_gate.shape[0]), e_gate, e_up, e_down))
    return acc


def _attention_half(p, x, eps, theta, rope_dim):
    """``h = x + Wo o``: a layer up to its feed-forward."""
    T = x.shape[0]
    pos = jnp.arange(T)
    a = _rms(x, p["n1"], eps)
    q = jnp.einsum("th,hnd->tnd", a, p["wq"].astype(F32))     # [T, n, 192]
    d = q.shape[-1]
    nope = d - rope_dim
    rank = p["n_kv"].shape[0]
    ckv = a @ p["wkv_a"].astype(F32)                          # [T, rank+rope]
    c = _rms(ckv[:, :rank], p["n_kv"], eps)
    k_pe = _rope(ckv[:, rank:], pos, theta)                   # [T, rope]
    q_pe = _rope(q[..., nope:], pos, theta)                   # [T, n, rope]
    causal = pos[:, None] >= pos[None, :]

    def head(args):                          # one head
        qn, qp, wkvb = args                  # [T, nope], [T, rope], [rank, nope+v]
        kv = c @ wkvb                        # [T, nope + v]
        s = (qn @ kv[:, :nope].T + qp @ k_pe.T) * (d ** -0.5)
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, -1) @ kv[:, nope:]           # [T, v]

    o = jax.lax.map(head, (q[..., :nope].transpose(1, 0, 2),
                           q_pe.transpose(1, 0, 2),
                           p["wkv_b"].astype(F32).transpose(1, 0, 2)))
    att = jnp.einsum("ntd,ndh->th", o, p["wo"].astype(F32))
    return x + att


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "rope_dim", "k", "norm_topk", "scale", "parts"))
def layer(p, x, *, eps, theta, rope_dim, k=0, norm_topk=True, scale=1.0,
          parts="all"):
    """One layer.  ``parts``: "all", or "routed" / "shared": that part of an
    expert layer's ``f`` alone."""
    with jax.default_matmul_precision(HIGHEST):
        h = _attention_half(p, x, eps, theta, rope_dim)
        m = _rms(h, p["n2"], eps)
        if "router" not in p:
            f = _swiglu(m, p["w_gate"], p["w_up"], p["w_down"])
        else:
            chosen, w, _ = route(m, p["router"], p["bias"], k, norm_topk,
                                 scale)
            routed = routed_part(m, chosen, w, p["e_gate"], p["e_up"],
                                 p["e_down"])
            if parts == "routed":
                return routed
            shared = _swiglu(m, p["s_gate"], p["s_up"], p["s_down"])
            if parts == "shared":
                return shared
            f = shared + routed
        return h + f


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "rope_dim", "k", "norm_topk", "scale"))
def layer_routing(p, x, *, eps, theta, rope_dim, k, norm_topk, scale):
    """(chosen [T, k], margin [T]) of an expert layer at its input ``x``."""
    with jax.default_matmul_precision(HIGHEST):
        m = _rms(_attention_half(p, x, eps, theta, rope_dim), p["n2"], eps)
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


def _layer_args(sizes, is_moe):
    kw = dict(eps=float(sizes["rms_norm_eps"]),
              theta=float(sizes["rope_theta"]),
              rope_dim=int(sizes["qk_rope_head_dim"]))
    if is_moe:
        kw.update(k=int(sizes["num_experts_per_tok"]),
                  norm_topk=bool(sizes["norm_topk_prob"]),
                  scale=float(sizes["routed_scaling_factor"]))
    return kw


def hidden(p, tokens, sizes, routing_out=None):
    assert sizes["scoring_func"] == "sigmoid" and sizes["n_group"] == 1
    x = embed(p["embed"], jnp.asarray(tokens))
    for i, lp in enumerate(p["layers"]):
        is_moe = i >= int(sizes["first_k_dense_replace"])
        assert is_moe == ("router" in lp), i
        kw = _layer_args(sizes, is_moe)
        if is_moe and routing_out is not None:
            routing_out.append(layer_routing(lp, x, **kw))
        x = layer(lp, x, **kw)
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
    """Per expert layer ``(chosen [T, k], margin [T])``: the experts the
    float32 reference chooses for each row and the margin between the k-th
    and the (k+1)-th of ``s + b``: a disagreement with the program counts
    only where that margin is within the program's precision."""
    out = []
    hidden(tree(params), ids, sizes, routing_out=out)
    return out


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "deepseek_v3" and sizes["hidden_act"] == "silu"
    assert sizes["q_lora_rank"] is None and sizes["topk_group"] == 1
    assert sizes["moe_layer_freq"] == 1 and not sizes["attention_bias"]
    assert not sizes.get("rope_scaling") and sizes["ep_size"] == 1
    assert sizes["num_key_value_heads"] == sizes["num_attention_heads"]
    return dict(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        head_dim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        hidden_size=sizes["hidden_size"],
        mlp_dim_override=sizes["intermediate_size"],
        use_rope=True, rope_theta=float(sizes["rope_theta"]),
        use_rmsnorm=True, norm_eps=sizes["rms_norm_eps"], gated_mlp=True,
        gate_act="silu", tie_embeddings=bool(sizes["tie_word_embeddings"]),
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        num_experts=sizes["n_routed_experts"],
        moe_k=sizes["num_experts_per_tok"], moe_dropless=True,
        moe_router="sigmoid", moe_route_norm=bool(sizes["norm_topk_prob"]),
        moe_route_scale=float(sizes["routed_scaling_factor"]),
        moe_router_bias=True,
        moe_shared_dim=sizes["moe_intermediate_size"]
        * sizes["n_shared_experts"],
        moe_expert_dim=sizes["moe_intermediate_size"],
        moe_dense_layers=sizes["first_k_dense_replace"])
