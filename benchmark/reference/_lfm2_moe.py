"""Plain LFM2-MoE forward (``model_type: lfm2_moe``): float32 ``jax.numpy``,
no kernels, no cache, no batching; written from the published ``config.json``
of ``LiquidAI/LFM2-24B-A2B`` and the family's published modelling code as
remembered (``Lfm2Moe*``: what the config does not state outright is listed
under the configuration's ``assumed``), and importing nothing from the
program under test.

    embedding:  x = E[ids]                                    (no scale)
    layer i:    h = x + mixer_i(n1(x));   y = h + ffn_i(n2(h))
                n = RMSNorm (x / sqrt(mean(x^2) + eps) * w), eps norm_eps
    conv:       [B | C | X] = W_in u            (H -> 3 H, split in this order)
                g_t = B_t * X_t
                v_t = sum_{j<K} w[:, j] * g_{t-K+1+j}    (K = conv_L_cache 3 taps,
                      depthwise over the H channels, zeros before position 0,
                      no bias, NO activation)
                out = W_out (C * v)
    attention:  q = Wq u as heads of d, k, v as key/value heads of d;
                q = qn(q), k = kn(k): RMSNorm over the d of each head, BEFORE
                the rotation;  q, k = rope(q, k, pos) over the whole head,
                rotating halves, base rope_theta;  causal softmax(q.k / sqrt(d));
                each key/value head shared by a group of query heads;  Wo
    dense ffn:  (layers < num_dense_layers)  W2 (silu(W1 m) * (W3 m))
    expert ffn: s = sigmoid(float32(Wg m));  S = top_k(s + expert_bias)
                w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-6)
                f = sum_{e in S} w_e * SwiGLU_e(m)        (no shared expert)
    head:       logits = E^T nf(y)                        (tied)

The conv is the left-to-right sum over explicitly shifted rows: the program's
carried tail (a prompt's chunks, a decode step's one row, a reused slot) is
what this is there to check.  ``expert_bias`` enters the selection and not the
weights.

Departures from the description, all of layout and none of arithmetic:
weights come in the shapes the program stores them in (``wq [H, heads, d]``,
``wo [heads, d, H]``, ``w_in [H, 3 H]``, ``conv_w [H, K]``: no reshape, so no
second copy on the device), in whatever type they are held in (bf16 when
serving) and are raised to float32 where they are used: a layer per jitted
call, and inside an expert layer one expert at a time, so that the largest
float32 thing alive beside an engine is the embedding (537 MB) and, in a
layer, one expert (38 MB), not one layer's experts (2.4 GB).  Attention runs
one key/value group at a time.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"     # on a TPU a float32 matmul is bf16 passes without it
ROUTE_EPS = 1e-6        # the family's norm_topk_prob denominator


def tree(params):
    """The program's parameter tree under the reference's names (views)."""
    bb = params["backbone"]
    n = sum(1 for k in bb if k.startswith("block_"))
    layers = []
    for i in range(n):
        blk = bb[f"block_{i}"]
        p = {"n1": blk["Norm_0"]["scale"], "n2": blk["Norm_1"]["scale"]}
        if "ShortConvMixer_0" in blk:
            c = blk["ShortConvMixer_0"]
            p.update(w_in=c["w_in"], conv_w=c["conv_w"], w_out=c["w_out"])
        else:
            a = blk["Attention_0"]
            p.update(wq=a["wq"], wk=a["wk"], wv=a["wv"], wo=a["wo"],
                     qn=a["q_norm"], kn=a["k_norm"])
        if "moe" in blk:
            m = blk["moe"]
            p.update(router=m["gate"], bias=m["expert_bias"],
                     e_gate=m["wge"], e_up=m["wi"], e_down=m["wo"])
        else:
            m = blk["MLP_0"]
            p.update(w_gate=m["wg"], w_up=m["wi"], w_down=m["wo"])
        layers.append(p)
    return {"embed": bb["wte"], "layers": layers,
            "final_norm": bb["final_norm"]["scale"]}


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


def _conv(g, w):
    """``v_t = sum_j w[:, j] * g_{t-K+1+j}``, zeros before position 0: ``g
    [T, H]``, ``w [H, K]``; no bias, no activation."""
    T, K = g.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, g.shape[1]), F32), g])
    return sum(padded[j:j + T] * w[:, j] for j in range(K))


def _gates(bcx):
    """``[B | C | X]`` -> (B, C, X)."""
    return jnp.split(bcx, 3, axis=-1)


def _short_conv(p, u):
    b, c, x = _gates(u @ p["w_in"].astype(F32))
    return (c * _conv(b * x, p["conv_w"].astype(F32))) @ p["w_out"].astype(F32)


def _qk(p, q, k, pos, eps, theta):
    """The norms over each head, then the rotation."""
    q, k = _rms(q, p["qn"], eps), _rms(k, p["kn"], eps)
    return _rope(q, pos, theta), _rope(k, pos, theta)


def _attention(p, h, eps, theta):
    """Causal attention of rows ``h [T, H]`` at positions 0 .. T - 1."""
    T = h.shape[0]
    pos = jnp.arange(T)
    q = jnp.einsum("th,hnd->tnd", h, p["wq"].astype(F32))
    k = jnp.einsum("th,hnd->tnd", h, p["wk"].astype(F32))
    v = jnp.einsum("th,hnd->tnd", h, p["wv"].astype(F32))
    q, k = _qk(p, q, k, pos, eps, theta)
    nh, nkv, d = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(T, nkv, nh // nkv, d).transpose(1, 2, 0, 3)
    causal = pos[:, None] >= pos[None, :]

    def group(args):                      # one key/value head
        qh, kh, vh = args                 # [g, T, d], [T, d], [T, d]
        s = jnp.einsum("gtd,sd->gts", qh, kh) * d ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->gtd", jax.nn.softmax(s, -1), vh)

    o = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(T, nh, d)
    return jnp.einsum("tnd,ndh->th", o, p["wo"].astype(F32))


def route(m, router, bias, k, route_norm, route_scale):
    """(chosen [T, k], weights [T, k], margin [T]): the k largest of ``s +
    b``, the weights from ``s`` alone, and how far the k-th lies above the
    (k+1)-th."""
    s = jax.nn.sigmoid(m @ router.astype(F32))               # [T, E]
    top, chosen = jax.lax.top_k(s + bias.astype(F32), k + 1)
    margin = top[:, k - 1] - top[:, k]
    chosen = chosen[:, :k]
    return chosen, _weights(s, chosen, route_norm, route_scale), margin


def _weights(s, chosen, route_norm, route_scale):
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTE_EPS)
    return w * route_scale


def _experts(p, m, k, route_norm, route_scale):
    """``sum over the chosen k of w_e * expert_e(m)``, one expert at a
    time over all the rows (a dense mask: a row an expert was not chosen
    for weighs 0)."""
    chosen, w, _ = route(m, p["router"], p["bias"], k, route_norm,
                         route_scale)

    def one(acc, args):
        e, wg, wu, wd = args
        c = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)        # [T]
        return acc + c[:, None] * _swiglu(m, wg, wu, wd), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(p["e_gate"].shape[0]), p["e_gate"], p["e_up"],
         p["e_down"]))
    return acc


def _mixed(p, x, eps, theta):
    """``x`` after the layer's mixer: a conv layer where ``p`` holds a
    conv's weights, an attention layer otherwise."""
    h = _rms(x, p["n1"], eps)
    return x + (_short_conv(p, h) if "conv_w" in p
                else _attention(p, h, eps, theta))


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "k", "route_norm", "route_scale"))
def layer(p, x, *, eps, theta, k, route_norm, route_scale):
    """One layer on a sequence ``x [T, H]``; an expert layer where ``p``
    holds a router."""
    with jax.default_matmul_precision(HIGHEST):
        x = _mixed(p, x, eps, theta)
        m = _rms(x, p["n2"], eps)
        if "router" in p:
            return x + _experts(p, m, k, route_norm, route_scale)
        return x + _swiglu(m, p["w_gate"], p["w_up"], p["w_down"])


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(norm, table, x, *, eps):
    """Logits [T, V] of the rows ``x [T, H]``; the head is the embedding."""
    with jax.default_matmul_precision(HIGHEST):
        return _rms(x, norm, eps) @ table.astype(F32).T


def _layer_args(sizes):
    return dict(eps=float(sizes["norm_eps"]),
                theta=float(sizes["rope_parameters"]["rope_theta"]),
                k=int(sizes["num_experts_per_tok"]),
                route_norm=bool(sizes["norm_topk_prob"]),
                route_scale=float(sizes["routed_scaling_factor"]))


def hidden(p, tokens, sizes):
    x = embed(p["embed"], jnp.asarray(tokens))
    for lp in p["layers"]:
        x = layer(lp, x, **_layer_args(sizes))
    return x


def logits(params, tokens, sizes, rows=None):
    """Float32 logits of one sequence ``tokens [T]`` at ``rows`` (all rows
    by default) from the program's parameter tree."""
    p = tree(params)
    x = hidden(p, tokens, sizes)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(p["final_norm"], p["embed"], x, eps=float(sizes["norm_eps"]))


def routing(params, tokens, sizes):
    """Per expert layer: (chosen [T, k], margin [T]) of the reference's own
    forward, for a comparison split by routing."""
    p = tree(params)
    x = embed(p["embed"], jnp.asarray(tokens))
    args = _layer_args(sizes)
    out = []
    for lp in p["layers"]:
        if "router" in lp:
            out.append(_route_of_layer(lp, x, **args))
        x = layer(lp, x, **args)
    return out


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "k", "route_norm", "route_scale"))
def _route_of_layer(p, x, *, eps, theta, k, route_norm, route_scale):
    with jax.default_matmul_precision(HIGHEST):
        chosen, _, margin = route(
            _rms(_mixed(p, x, eps, theta), p["n2"], eps), p["router"],
            p["bias"], k, route_norm, route_scale)
        return chosen, margin


def layer_kinds(sizes):
    """The kept layers' kinds: ``layer_types`` is the published list, whole,
    and ``layers_kept`` (where the depth is cut) the published indices of
    the ``num_hidden_layers`` layers held here."""
    kept = sizes.get("layers_kept") or list(range(sizes["num_hidden_layers"]))
    assert len(kept) == sizes["num_hidden_layers"], kept
    return [sizes["layer_types"][j] for j in kept]


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "lfm2_moe" and not sizes["conv_bias"]
    assert sizes["rope_parameters"]["rope_type"] == "default"
    assert sizes["use_expert_bias"]
    kinds = layer_kinds(sizes)
    assert set(kinds) <= {"conv", "full_attention"}
    assert sizes["hidden_size"] % sizes["num_attention_heads"] == 0
    return dict(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["hidden_size"] // sizes["num_attention_heads"],
        hidden_size=sizes["hidden_size"],
        mlp_dim_override=sizes["intermediate_size"],
        use_rope=True, rope_layers="all",
        rope_theta=float(sizes["rope_parameters"]["rope_theta"]),
        use_rmsnorm=True, norm_eps=sizes["norm_eps"], gated_mlp=True,
        gate_act="silu", tie_embeddings=True, qk_norm=True,
        layer_types=tuple("attention" if t == "full_attention" else t
                          for t in kinds),
        conv_taps=sizes["conv_L_cache"],
        num_experts=sizes["num_experts"],
        moe_k=sizes["num_experts_per_tok"], moe_dropless=True,
        moe_router="sigmoid", moe_router_bias=True,
        moe_route_norm=bool(sizes["norm_topk_prob"]),
        moe_route_scale=float(sizes["routed_scaling_factor"]),
        moe_route_eps=ROUTE_EPS,
        moe_expert_dim=sizes["moe_intermediate_size"],
        moe_dense_layers=sizes["num_dense_layers"])
