"""Plain afmoe (Arcee Trinity) forward: float32 ``jax.numpy``, no kernels, no
cache, no batching; written from the published ``config.json`` of
Trinity-Large-Preview (``model_type: afmoe``) and the family's published
modelling code, and importing nothing from the program under test.

Per layer, on a sequence ``x [T, H]`` (``n*`` RMSNorm, ``x / sqrt(mean(x^2) +
eps) * scale``):

    x0 = E[ids] * sqrt(H)                                          (mup_enabled)
    a  = n1(x);  q = qn(Wq a);  k = kn(Wk a);  v = Wv a     (qn, kn per head)
    window layer:  q, k = rope(q, k, pos);  key j visible to query i iff
                   i - window < j <= i
    global layer:  no rope;  causal over the whole prefix
    o  = softmax(q k^T / sqrt(d)) v;   att = Wo (o * sigmoid(Wg a))
    h  = x + n2(att)
    m  = n3(h)
    dense layer:   f = Wd (silu(Wgt m) * (Wu m))
    expert layer:  s = sigmoid(float32(Wr m));  S = top_k(s + b)
                   w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
                   f = shared(m) + sum_{e in S} w_e * expert_e(m)
    y  = h + n4(f);      logits = Wout nf(y_last)                (head untied)

Attention has ``num_key_value_heads`` key/value heads, each shared by a group
of query heads; RoPE rotates the two halves of each head (``rotate_half``) by
``pos * theta^(-2i/d)``.  ``b`` is the published ``expert_bias``: it enters
the selection and not the weights.

**The share.**  The configuration holds ``num_experts`` of the router's
``router_width`` experts, those from ``expert_offset`` on, and a slice of the
vocabulary.  The router is ``router_width`` wide and chooses among all of
them; the sum runs over the chosen experts that are held, and what the absent
ones would add is left out, here as in the program.  ``layer`` with
every expert held is the uncut layer (the tests add eight shares up to it).

Departures from the description, all of layout and none of arithmetic:
weights come in the shapes and the type the program stores them in (bf16
when serving) and are raised to float32 where they are used: a layer per
jitted call, and inside an expert layer one expert at a time, so that the
largest float32 thing alive is one expert (113 MB at the published widths),
not one layer's experts (3.6 GB).  Attention runs one key/value group at a
time.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"     # on a TPU a float32 matmul is bf16 passes without it


def layer_kinds(sizes):
    """Per kept layer: (window or None, has experts)."""
    kept = sizes.get("layers_kept") or list(range(sizes["num_hidden_layers"]))
    assert len(kept) == sizes["num_hidden_layers"], kept
    types = sizes["layer_types"]
    # the cut keeps the leading dense layers first, then whole periods
    return [(int(sizes["sliding_window"])
             if types[j] == "sliding_attention" else None,
             i >= sizes["num_dense_layers"]) for i, j in enumerate(kept)]


def tree(params):
    """The program's parameter tree under the reference's names (views)."""
    bb = params["backbone"]
    n = sum(1 for k in bb if k.startswith("block_"))
    layers = []
    for i in range(n):
        blk = bb[f"block_{i}"]
        a = blk["Attention_0"]
        lp = {"n1": blk["Norm_0"]["scale"], "n2": blk["post_attn_norm"]["scale"],
              "n3": blk["Norm_1"]["scale"], "n4": blk["post_ffn_norm"]["scale"],
              "wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
              "w_attn_gate": a["wgate"], "qn": a["q_norm"], "kn": a["k_norm"]}
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
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq            # [T, half]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate.astype(F32)) * (m @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def route(m, router, bias, k, route_norm, route_scale):
    """(chosen [T, k], weights [T, k], margin [T]): the k largest of
    ``s + b`` over all the router's experts, the weights from ``s`` alone,
    and how far the k-th lies above the (k+1)-th."""
    s = jax.nn.sigmoid(m @ router.astype(F32))               # [T, E]
    top, chosen = jax.lax.top_k(s + bias.astype(F32), k + 1)
    margin = top[:, k - 1] - top[:, k]
    chosen = chosen[:, :k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * route_scale, margin


def routed_part(m, chosen, w, e_gate, e_up, e_down, offset):
    """sum over the chosen experts that are held of ``w_e * expert_e(m)``:
    the held experts are ``offset ..`` of the router's; one at a time."""
    held = e_gate.shape[0]
    local = chosen - offset                                   # [T, k]

    def one(acc, args):
        e, wg, wu, wd = args
        c = jnp.sum(jnp.where(local == e, w, 0.0), -1)        # [T]
        return acc + c[:, None] * _swiglu(m, wg, wu, wd), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (jnp.arange(held), e_gate, e_up, e_down))
    return acc


def _attention_half(p, x, eps, theta, window):
    """``h = x + n2(Wo (o * gate))``: a layer up to its feed-forward."""
    T = x.shape[0]
    pos = jnp.arange(T)
    a = _rms(x, p["n1"], eps)
    q = jnp.einsum("th,hnd->tnd", a, p["wq"].astype(F32))
    kk = jnp.einsum("th,hnd->tnd", a, p["wk"].astype(F32))
    v = jnp.einsum("th,hnd->tnd", a, p["wv"].astype(F32))
    q, kk = _rms(q, p["qn"], eps), _rms(kk, p["kn"], eps)
    causal = pos[:, None] >= pos[None, :]
    if window is not None:                 # a window layer: RoPE, window
        q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
        causal = causal & (pos[None, :] > pos[:, None] - window)
    nh, nkv, d = q.shape[1], kk.shape[1], q.shape[2]
    qg = q.reshape(T, nkv, nh // nkv, d).transpose(1, 2, 0, 3)

    def group(args):                       # one key/value head
        qh, kh, vh = args                  # [g, T, d], [T, d], [T, d]
        s = jnp.einsum("gtd,sd->gts", qh, kh) * (d ** -0.5)
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->gtd", jax.nn.softmax(s, -1), vh)

    o = jax.lax.map(group, (qg, kk.transpose(1, 0, 2),
                            v.transpose(1, 0, 2)))    # [nkv, g, T, d]
    o = o.transpose(2, 0, 1, 3).reshape(T, nh, d)
    gate = jax.nn.sigmoid(jnp.einsum("th,hnd->tnd", a,
                                     p["w_attn_gate"].astype(F32)))
    att = jnp.einsum("tnd,ndh->th", o * gate, p["wo"].astype(F32))
    return x + _rms(att, p["n2"], eps)


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "window", "k", "route_norm", "route_scale", "offset",
    "parts"))
def layer(p, x, *, eps, theta, window, k=0, route_norm=True, route_scale=1.0,
          offset=0, parts="all"):
    """One layer.  ``parts``: "all", or for the share test "routed" (the
    routed experts' part of ``f`` alone) / "shared" (the shared expert's)."""
    with jax.default_matmul_precision(HIGHEST):
        h = _attention_half(p, x, eps, theta, window)
        m = _rms(h, p["n3"], eps)
        if "router" not in p:
            f = _swiglu(m, p["w_gate"], p["w_up"], p["w_down"])
        else:
            chosen, w, _ = route(m, p["router"], p["bias"], k, route_norm,
                                 route_scale)
            routed = routed_part(m, chosen, w, p["e_gate"], p["e_up"],
                                 p["e_down"], offset)
            if parts == "routed":
                return routed
            shared = _swiglu(m, p["s_gate"], p["s_up"], p["s_down"])
            if parts == "shared":
                return shared
            f = shared + routed
        return h + _rms(f, p["n4"], eps)


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "window", "k", "route_norm", "route_scale"))
def layer_routing(p, x, *, eps, theta, window, k, route_norm, route_scale):
    """(chosen [T, k], margin [T]) of an expert layer at its input ``x``."""
    with jax.default_matmul_precision(HIGHEST):
        m = _rms(_attention_half(p, x, eps, theta, window), p["n3"], eps)
        chosen, _, margin = route(m, p["router"], p["bias"], k, route_norm,
                                  route_scale)
        return chosen, margin


@functools.partial(jax.jit, static_argnames=("scale",))
def embed(table, tokens, *, scale):
    return table[tokens].astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("eps",))
def head(norm, lm_head, x, *, eps):
    """Logits [T, V] of the rows ``x [T, H]``."""
    with jax.default_matmul_precision(HIGHEST):
        return _rms(x, norm, eps) @ lm_head.astype(F32)


def _layer_args(sizes, window, is_moe):
    kw = dict(eps=float(sizes["rms_norm_eps"]),
              theta=float(sizes["rope_theta"]), window=window)
    if is_moe:
        kw.update(k=int(sizes["num_experts_per_tok"]),
                  route_norm=bool(sizes["route_norm"]),
                  route_scale=float(sizes["route_scale"]))
    return kw


def hidden(p, tokens, sizes, routing_out=None):
    assert sizes["mup_enabled"] and sizes["score_func"] == "sigmoid"
    x = embed(p["embed"], jnp.asarray(tokens),
              scale=float(sizes["hidden_size"]) ** 0.5)
    offset = int(sizes.get("expert_offset", 0))
    for lp, (window, is_moe) in zip(p["layers"], layer_kinds(sizes)):
        kw = _layer_args(sizes, window, is_moe)
        if is_moe and routing_out is not None:
            routing_out.append(layer_routing(lp, x, **kw))
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
    """Per expert layer ``(chosen [T, k], margin [T])``: the experts the
    float32 reference chooses for each row (ids over all the router's
    experts) and the margin between the k-th and the (k+1)-th of ``s + b``:
    a disagreement with the program counts only where that margin is within
    the program's precision."""
    out = []
    hidden(tree(params), ids, sizes, routing_out=out)
    return out


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "afmoe" and sizes["hidden_act"] == "silu"
    assert sizes["num_shared_experts"] == 1 and sizes["n_group"] == 1
    kinds = layer_kinds(sizes)
    dense = [i for i, (_, moe) in enumerate(kinds) if not moe]
    assert dense == list(range(sizes["num_dense_layers"])), kinds
    router_width = int(sizes.get("router_width", sizes["num_experts"]))
    return dict(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], hidden_size=sizes["hidden_size"],
        mlp_dim_override=sizes["intermediate_size"],
        use_rope=True, rope_theta=float(sizes["rope_theta"]),
        rope_layers="window", use_rmsnorm=True,
        norm_eps=sizes["rms_norm_eps"], gated_mlp=True, gate_act="silu",
        tie_embeddings=bool(sizes["tie_word_embeddings"]),
        embed_scale=float(sizes["hidden_size"]) ** 0.5,
        sliding_window=int(sizes["sliding_window"]),
        local_attn_layers=tuple(i for i, (w, _) in enumerate(kinds)
                                if w is not None),
        attn_gate=True, qk_norm=True, sandwich_norm=True,
        num_experts=router_width, moe_k=sizes["num_experts_per_tok"],
        moe_dropless=True, moe_router="sigmoid",
        moe_route_norm=bool(sizes["route_norm"]),
        moe_route_scale=float(sizes["route_scale"]), moe_router_bias=True,
        moe_shared_dim=sizes["moe_intermediate_size"]
        * sizes["num_shared_experts"],
        moe_expert_dim=sizes["moe_intermediate_size"],
        moe_dense_layers=sizes["num_dense_layers"],
        experts_held=(sizes["num_experts"]
                      if sizes["num_experts"] != router_width else None),
        expert_offset=int(sizes.get("expert_offset", 0)))
