"""Plain MiMo-V2-Flash (``model_type: mimo_v2_flash``) forward: float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no kernels,
no cache, no batching; written from the published ``config.json`` (the
catalog's row) and the equations of ISSUE 53, and importing nothing from the
program under test.

Per layer, on a sequence ``x [T, H]`` (``n*`` RMSNorm with a gain, ``x /
sqrt(mean(x^2) + eps) * scale``; kind = full | window by
``hybrid_layer_pattern``, 0 = full):

    a = x + Attn_kind(n1(x));   y = a + F(n2(a));   logits = W_head nf(x_L)

    Attn_kind(h):  Q query heads, nkv = num_key_value_heads (full) |
                   swa_num_key_value_heads (window) key/value heads
      q = Wq h [Q x d];  k = Wk h [nkv x d];  v = value_scale * (Wv h) [nkv x dv]
      the LEADING rot = int(d * partial_rotary_factor) columns of q and k
      rotate by RoPE (rotate-half over those columns), base rope_theta (full)
      | swa_rope_theta (window); the other columns carry no position
      s[t, j] = d^-0.5 q_t . k_j   over j <= t (full) | t - W < j <= t (window)
      full:    p = softmax_j(s)            (no sink: add_full_attention_sink_bias false)
      window:  m = max(b_h, max_j s);  p[t, j] = exp(s - m) / (exp(b_h - m) + sum_j exp(s - m))
               (b_h: one float32 logit a query head; it has no value)
      o_t = sum_j p[t, j] v_j [Q x dv];   Attn = Wo o

    F: SwiGLU of intermediate_size where moe_layer_freq is 0, else
    MoE(h):  s = sigmoid(Wr h) in float32 [router_width]
             E = top-k of (s + bias);  w_e = s_e / (sum_{e in E} s_e + 1e-20)
             (norm_topk_prob; times routed_scaling_factor, null = 1)
             y = sum_{e in E, e held} w_e * Wd_e (silu(Wg_e h) * Wu_e h)
             (no shared expert)

**The share.**  The configuration holds ``n_routed_experts`` of the router's
``router_width`` experts, those from ``expert_offset`` on, and a slice of the
vocabulary.  The router is ``router_width`` wide and chooses among all of
them; the sum runs over the chosen experts that are held, and what the absent
ones would add is left out, here as in the program.  ``layer`` with every
expert held is the uncut layer (the tests add sixteen shares up to it).

**Assumed** (the configuration's ``assumed`` gives the reason for each): the
rotated columns are the leading ones and rotate by halves; the value scale
multiplies ``v``; the sink joins the denominator and carries no value; the
window counts the query's own position; the selection bias enters the choice
only; no q/k norm; ``attention_chunk_size`` is no second mask; the three
multi-token-prediction layers are left out.

Departures from the description, all of layout and none of arithmetic:
weights come in the shapes and the type the program stores them in (bf16 when
serving) and are raised to float32 where they are used: a layer per jitted
call, inside an expert layer one expert at a time (100 MB in float32 at the
published widths, not one layer's held experts, 1.6 GB), attention one
key/value head at a time (its ``[g, T, T]`` scores are 290 MB at 16 query
heads and 2,140 positions) and the head in blocks of rows.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"     # on a TPU a float32 matmul is bf16 passes without it


def layer_kinds(sizes):
    """Per kept layer: (has a window, has experts), ``hybrid_layer_pattern``
    and ``moe_layer_freq`` read at the published indices ``layers_kept``."""
    kept = sizes.get("layers_kept") or list(range(sizes["num_hidden_layers"]))
    assert len(kept) == sizes["num_hidden_layers"], kept
    return [(bool(sizes["hybrid_layer_pattern"][j]),
             bool(sizes["moe_layer_freq"][j])) for j in kept]


def tree(params):
    """The program's parameter tree under the reference's names (views)."""
    bb = params["backbone"]
    layers = []
    for i in range(sum(1 for k in bb if k.startswith("block_"))):
        blk = bb[f"block_{i}"]
        a = blk["Attention_0"]
        lp = {"n1": blk["Norm_0"]["scale"], "n2": blk["Norm_1"]["scale"],
              "wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"]}
        if "sink" in a:
            lp["sink"] = a["sink"]
        if "moe" in blk:
            m = blk["moe"]
            lp.update(router=m["gate"], bias=m["expert_bias"],
                      e_gate=m["wge"], e_up=m["wi"], e_down=m["wo"])
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


def rope_leading(x, pos, theta, rot):
    """The leading ``rot`` columns of every head of ``x [T, n, d]`` rotated
    by halves by ``pos * theta^(-2i/rot)``; the rest as they are."""
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq            # [T, half]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def sink_softmax(s, sink):
    """Over the last axis of ``s [g, T, S]`` (masked scores -inf), a sink
    logit ``sink [g]`` a head in the denominator (None: the plain
    softmax)."""
    if sink is None:
        return jax.nn.softmax(s, -1)
    b = sink.astype(F32)[:, None, None]
    m = jnp.maximum(jnp.max(s, -1, keepdims=True), b)
    e = jnp.exp(s - m)
    return e / (jnp.exp(b - m) + jnp.sum(e, -1, keepdims=True))


def _swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate.astype(F32)) * (m @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def route(m, router, bias, k, norm_topk, scale):
    """(chosen [T, k], weights [T, k], margin [T]): the k largest of ``s +
    bias`` over all the router's experts (``noaux_tc``: the bias enters the
    choice only), the weights from ``s`` alone, and how far the k-th lies
    above the (k+1)-th."""
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


def _attention_half(p, x, eps, theta, window, rot, value_scale, sink):
    """``a = x + Attn(n1(x))``: a layer up to its feed-forward."""
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, p["n1"], eps)
    q = jnp.einsum("th,hnd->tnd", h, p["wq"].astype(F32))
    kk = jnp.einsum("th,hnd->tnd", h, p["wk"].astype(F32))
    v = value_scale * jnp.einsum("th,hnd->tnd", h, p["wv"].astype(F32))
    q, kk = rope_leading(q, pos, theta, rot), rope_leading(kk, pos, theta,
                                                           rot)
    seen = pos[:, None] >= pos[None, :]
    if window is not None:       # the query's own position and window - 1
        seen = seen & (pos[None, :] > pos[:, None] - window)      # before
    nh, nkv, d = q.shape[1], kk.shape[1], q.shape[2]
    g = nh // nkv
    qg = q.reshape(T, nkv, g, d).transpose(1, 2, 0, 3)
    sinks = (p["sink"].astype(F32).reshape(nkv, g) if sink
             else jnp.zeros((nkv, g), F32))

    def group(args):                       # one key/value head
        qh, kh, vh, b = args               # [g, T, d], [T, d], [T, dv], [g]
        s = jnp.einsum("gtd,sd->gts", qh, kh) * (d ** -0.5)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->gtd",
                          sink_softmax(s, b if sink else None), vh)

    o = jax.lax.map(group, (qg, kk.transpose(1, 0, 2),
                            v.transpose(1, 0, 2), sinks))  # [nkv, g, T, dv]
    o = o.transpose(2, 0, 1, 3).reshape(T, nh, v.shape[-1])
    return x + jnp.einsum("tnd,ndh->th", o, p["wo"].astype(F32))


_LAYER_STATICS = ("eps", "theta", "window", "rot", "value_scale", "sink",
                  "k", "norm_topk", "scale", "offset", "parts")


@functools.partial(jax.jit, static_argnames=_LAYER_STATICS)
def layer(p, x, *, eps, theta, window, rot, value_scale, sink, k=0,
          norm_topk=True, scale=1.0, offset=0, parts="all"):
    """One layer.  ``parts="routed"``: the routed experts' part of ``F``
    alone (the share test)."""
    with jax.default_matmul_precision(HIGHEST):
        a = _attention_half(p, x, eps, theta, window, rot, value_scale, sink)
        m = _rms(a, p["n2"], eps)
        if "router" not in p:
            return a + _swiglu(m, p["w_gate"], p["w_up"], p["w_down"])
        chosen, w, _ = route(m, p["router"], p["bias"], k, norm_topk, scale)
        routed = routed_part(m, chosen, w, p["e_gate"], p["e_up"],
                             p["e_down"], offset)
        return routed if parts == "routed" else a + routed


@functools.partial(jax.jit, static_argnames=_LAYER_STATICS[:9])
def layer_routing(p, x, *, eps, theta, window, rot, value_scale, sink, k,
                  norm_topk, scale):
    """(chosen [T, k], margin [T]) of an expert layer at its input ``x``."""
    with jax.default_matmul_precision(HIGHEST):
        m = _rms(_attention_half(p, x, eps, theta, window, rot, value_scale,
                                 sink), p["n2"], eps)
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


def rotated_columns(sizes):
    """``int(head_dim * partial_rotary_factor)``, rounded down to even."""
    rot = int(sizes["head_dim"] * sizes["partial_rotary_factor"])
    return rot - rot % 2


def _layer_args(sizes, is_window, is_moe):
    kw = dict(
        eps=float(sizes["layernorm_epsilon"]),
        theta=float(sizes["swa_rope_theta" if is_window else "rope_theta"]),
        window=int(sizes["sliding_window"]) if is_window else None,
        rot=rotated_columns(sizes),
        value_scale=float(sizes["attention_value_scale"]),
        sink=bool(sizes["add_swa_attention_sink_bias" if is_window
                        else "add_full_attention_sink_bias"]))
    if is_moe:
        kw.update(k=int(sizes["num_experts_per_tok"]),
                  norm_topk=bool(sizes["norm_topk_prob"]),
                  scale=float(sizes["routed_scaling_factor"] or 1.0))
    return kw


def hidden(p, tokens, sizes, routing_out=None):
    assert sizes["scoring_func"] == "sigmoid" and sizes["n_group"] == 1
    assert sizes["topk_method"] == "noaux_tc" and not sizes["n_shared_experts"]
    x = embed(p["embed"], jnp.asarray(tokens))
    offset = int(sizes.get("expert_offset", 0))
    for lp, (is_window, is_moe) in zip(p["layers"], layer_kinds(sizes)):
        kw = _layer_args(sizes, is_window, is_moe)
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
    eps = float(sizes["layernorm_epsilon"])
    return jnp.concatenate([
        head(p["final_norm"], p["lm_head"], x[i:i + 512], eps=eps)
        for i in range(0, x.shape[0], 512)])


def routing(params, ids, sizes):
    """Per expert layer ``(chosen [T, k], margin [T])``: the experts the
    float32 reference chooses for each row (ids over all the router's
    experts) and the margin between the k-th and the (k+1)-th of ``s +
    bias``."""
    out = []
    hidden(tree(params), ids, sizes, routing_out=out)
    return out


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "mimo_v2_flash"
    assert sizes["hidden_act"] == "silu" and not sizes["attention_bias"]
    kinds = layer_kinds(sizes)
    dense = [i for i, (_, moe) in enumerate(kinds) if not moe]
    assert dense == list(range(len(dense))), kinds    # leading dense layers
    router_width = int(sizes.get("router_width", sizes["n_routed_experts"]))
    sinks = (bool(sizes["add_swa_attention_sink_bias"]),
             bool(sizes["add_full_attention_sink_bias"]))
    assert sinks != (False, True), "a sink on the full layers alone"
    # what a window layer's attention has of its own
    window_attn = tuple(
        (field, sizes[swa]) for field, swa, full in (
            ("num_heads", "swa_num_attention_heads", "num_attention_heads"),
            ("num_kv_heads", "swa_num_key_value_heads",
             "num_key_value_heads"),
            ("head_dim", "swa_head_dim", "head_dim"),
            ("v_head_dim", "swa_v_head_dim", "v_head_dim"))
        if sizes[swa] != sizes[full])
    if sizes["swa_rope_theta"] != sizes["rope_theta"]:
        window_attn += (("rope_theta", float(sizes["swa_rope_theta"])),)
    return dict(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], v_head_dim=sizes["v_head_dim"],
        hidden_size=sizes["hidden_size"],
        mlp_dim_override=sizes["intermediate_size"],
        use_rope=True, rope_theta=float(sizes["rope_theta"]),
        rope_pct=float(sizes["partial_rotary_factor"]),
        use_rmsnorm=True, norm_eps=sizes["layernorm_epsilon"],
        gated_mlp=True, gate_act="silu",
        tie_embeddings=bool(sizes["tie_word_embeddings"]),
        sliding_window=int(sizes["sliding_window"]),
        local_attn_layers=tuple(i for i, (w, _) in enumerate(kinds) if w),
        window_attn=window_attn,
        attn_sink={(True, True): "all", (True, False): "window",
                   (False, False): None}[sinks],
        attn_value_scale=float(sizes["attention_value_scale"]),
        num_experts=router_width, moe_k=sizes["num_experts_per_tok"],
        moe_dropless=True, moe_router="sigmoid",
        moe_route_norm=bool(sizes["norm_topk_prob"]),
        moe_route_scale=float(sizes["routed_scaling_factor"] or 1.0),
        moe_router_bias=True, moe_expert_dim=sizes["moe_intermediate_size"],
        moe_dense_layers=len(dense),
        experts_held=(sizes["n_routed_experts"]
                      if sizes["n_routed_experts"] != router_width else None),
        expert_offset=int(sizes.get("expert_offset", 0)))
