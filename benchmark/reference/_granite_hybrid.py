"""Plain Granite-4.0-H forward (``model_type: granitemoehybrid`` with no
experts): float32 ``jax.numpy``, no kernels, no cache, no batching, no
chunking; written from the published ``config.json`` of
``ibm-granite/granite-4.0-h-micro`` and the family's published modelling
code (``GraniteMoeHybrid*`` and its ``Mamba2`` mixer), and importing nothing
from the program under test.

    embedding:  x = E[ids] * embedding_multiplier
    layer i:    h = x + r * mixer_i(n1(x));   y = h + r * mlp(n2(h))
                r = residual_multiplier, n = RMSNorm (x / sqrt(mean(x^2) + eps) * w)
    mlp:        mlp(m) = W_down (silu(W_gate m) * (W_up m))      (the "shared" MLP:
                num_local_experts is 0, so it is the whole feed-forward)
    attention:  num_key_value_heads key/value heads, each shared by a group of
                query heads; NO rotation and no positions at all
                (position_embedding_type "nope"); causal;
                softmax(q.k * attention_multiplier)
    mamba:      [z | xBC | dt] = W_in u
                xBC_t = silu(b_c + sum_{j<K} w_c[:, j] * xBC_{t-K+1+j})   (zeros before 0)
                [x | B | C] = xBC_t
                dt_t = softplus(dt_t + dt_bias);   A = -exp(A_log)
                S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (x) B_t        (S_{-1} = 0)
                y_t = S_t C_t + D * x_t
                g = y * silu(z);   out = W_out (g / sqrt(mean(g^2) + eps) * w_n)
    head:       logits = E^T nf(y) / logits_scaling                   (tied)

The scan is the RECURRENCE itself, one position after another
(``lax.scan``): the program's chunked form, its carried state and its conv
tail are what this is there to check.

Departures from the description, all of layout and none of arithmetic:
weights come in the shapes the program stores them in (``wq [H, heads, d]``,
``wo [heads, d, H]``, ``w_in [H, z + xBC + dt]``, ``conv_w [channels, K]``:
no reshape, so no second copy on the device), in whatever type they are held
in (bf16 when serving) and are raised to float32 one layer at a time; the
published ``input_linear`` of the MLP is one matrix whose halves are the
program's ``wg`` and ``wi``; a sequence goes through one layer per jitted
call, so that the largest thing alive beside an engine is one layer's
float32 weights; attention runs one key/value group at a time.  The gated
norm normalises over the whole inner width (``mamba_n_groups`` 1: one
group).  ``time_step_limit`` is (0, inf), the published default: ``dt`` is
not clamped.
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
        m = blk["MLP_0"]
        p = {"n1": blk["Norm_0"]["scale"], "n2": blk["Norm_1"]["scale"],
             "w_gate": m["wg"], "w_up": m["wi"], "w_down": m["wo"]}
        if "Mamba2Mixer_0" in blk:
            s = blk["Mamba2Mixer_0"]
            p.update({k: s[k] for k in ("w_in", "conv_w", "dt_bias", "A_log",
                                        "D", "norm", "w_out")})
            p["conv_b"] = s.get("conv_b")
        else:
            a = blk["Attention_0"]
            p.update(wq=a["wq"], wk=a["wk"], wv=a["wv"], wo=a["wo"])
        layers.append(p)
    return {"embed": bb["wte"], "layers": layers,
            "final_norm": bb["final_norm"]["scale"]}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _mlp(p, h):
    gate = jax.nn.silu(h @ p["w_gate"].astype(F32))
    return (gate * (h @ p["w_up"].astype(F32))) @ p["w_down"].astype(F32)


def _attention(p, h, scale):
    """Causal attention of rows ``h [T, H]``, no positions."""
    T = h.shape[0]
    pos = jnp.arange(T)
    q = jnp.einsum("th,hnd->tnd", h, p["wq"].astype(F32))
    k = jnp.einsum("th,hnd->tnd", h, p["wk"].astype(F32))
    v = jnp.einsum("th,hnd->tnd", h, p["wv"].astype(F32))
    nh, nkv, d = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(T, nkv, nh // nkv, d).transpose(1, 2, 0, 3)
    causal = pos[:, None] >= pos[None, :]

    def group(args):                      # one key/value head
        qh, kh, vh = args                 # [g, T, d], [T, d], [T, d]
        s = jnp.einsum("gtd,sd->gts", qh, kh) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->gtd", jax.nn.softmax(s, -1), vh)

    o = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(T, nh, d)
    return jnp.einsum("tnd,ndh->th", o, p["wo"].astype(F32))


def _conv(xbc, w, b):
    """``out_t = silu(b + sum_j w[:, j] * xbc_{t-K+1+j})``, zeros before
    position 0: ``xbc [T, C]``, ``w [C, K]``."""
    T, K = xbc.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    out = sum(padded[j:j + T] * w[:, j] for j in range(K))
    return jax.nn.silu(out if b is None else out + b)


def _recurrence(x, dt, A, B, C, D):
    """The state-space recurrence, position by position: ``x [T, h, p]``,
    ``dt [T, h]``, ``A [h]``, ``B``/``C [T, g, n]``, ``D [h]`` -> y [T, h,
    p].  A head of group ``k`` reads ``B[:, k]`` and ``C[:, k]``."""
    h, g = x.shape[1], B.shape[1]
    Bh = jnp.repeat(B, h // g, axis=1)
    Ch = jnp.repeat(C, h // g, axis=1)

    def step(S, row):
        x_t, dt_t, B_t, C_t = row
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + D[:, None] * x_t
    S0 = jnp.zeros((h, x.shape[2], B.shape[2]), F32)
    return jax.lax.scan(step, S0, (x, dt, Bh, Ch))[1]


def _gated_norm(y, z, w, eps):
    """The gate FIRST, then RMSNorm over the whole inner width."""
    return _rms(y * jax.nn.silu(z), w, eps)


def _mamba(p, u, *, heads, head_dim, groups, state, eps):
    T = u.shape[0]
    inner, gn = heads * head_dim, groups * state
    zxd = u @ p["w_in"].astype(F32)
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * gn],
                  zxd[:, 2 * inner + 2 * gn:])
    xbc = _conv(xbc, p["conv_w"].astype(F32),
                None if p["conv_b"] is None else p["conv_b"].astype(F32))
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    y = _recurrence(xbc[:, :inner].reshape(T, heads, head_dim), dt,
                    -jnp.exp(p["A_log"].astype(F32)),
                    xbc[:, inner:inner + gn].reshape(T, groups, state),
                    xbc[:, inner + gn:].reshape(T, groups, state),
                    p["D"].astype(F32))
    return _gated_norm(y.reshape(T, inner), z, p["norm"], eps) \
        @ p["w_out"].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "eps", "residual", "attn_scale", "heads", "head_dim", "groups", "state"))
def layer(p, x, *, eps, residual, attn_scale, heads, head_dim, groups,
          state):
    """One layer on a sequence ``x [T, H]``: a scan layer where ``p`` holds
    a mixer's weights, an attention layer otherwise."""
    with jax.default_matmul_precision(HIGHEST):
        h = _rms(x, p["n1"], eps)
        if "w_in" in p:
            mixed = _mamba(p, h, heads=heads, head_dim=head_dim,
                           groups=groups, state=state, eps=eps)
        else:
            mixed = _attention(p, h, attn_scale)
        x = x + residual * mixed
        return x + residual * _mlp(p, _rms(x, p["n2"], eps))


@functools.partial(jax.jit, static_argnames=("multiplier",))
def embed(table, tokens, *, multiplier):
    return table[tokens].astype(F32) * multiplier


@functools.partial(jax.jit, static_argnames=("eps", "divisor"))
def head(norm, table, x, *, eps, divisor):
    """Logits [T, V] of the rows ``x [T, H]``; the head is the embedding."""
    with jax.default_matmul_precision(HIGHEST):
        return _rms(x, norm, eps) @ table.astype(F32).T / divisor


def hidden(p, tokens, sizes):
    x = embed(p["embed"], jnp.asarray(tokens),
              multiplier=float(sizes["embedding_multiplier"]))
    for lp in p["layers"]:
        x = layer(lp, x, eps=float(sizes["rms_norm_eps"]),
                  residual=float(sizes["residual_multiplier"]),
                  attn_scale=float(sizes["attention_multiplier"]),
                  heads=int(sizes["mamba_n_heads"]),
                  head_dim=int(sizes["mamba_d_head"]),
                  groups=int(sizes["mamba_n_groups"]),
                  state=int(sizes["mamba_d_state"]))
    return x


def logits(params, tokens, sizes, rows=None):
    """Float32 logits of one sequence ``tokens [T]`` at ``rows`` (all rows
    by default) from the program's parameter tree."""
    p = tree(params)
    x = hidden(p, tokens, sizes)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(p["final_norm"], p["embed"], x,
                eps=float(sizes["rms_norm_eps"]),
                divisor=float(sizes["logits_scaling"]))


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "granitemoehybrid"
    assert sizes["hidden_act"] == "silu"
    assert sizes["normalization_function"] == "rmsnorm"
    assert sizes["position_embedding_type"] == "nope"
    assert sizes["num_local_experts"] == 0 and not sizes["attention_bias"]
    assert not sizes["mamba_proj_bias"] and sizes["tie_word_embeddings"]
    assert (sizes["mamba_n_heads"] * sizes["mamba_d_head"]
            == sizes["mamba_expand"] * sizes["hidden_size"])
    assert len(sizes["layer_types"]) == sizes["num_hidden_layers"]
    assert sizes["hidden_size"] % sizes["num_attention_heads"] == 0
    return dict(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["hidden_size"] // sizes["num_attention_heads"],
        hidden_size=sizes["hidden_size"],
        mlp_dim_override=sizes["shared_intermediate_size"],
        # "nope": the rope switch on (no learned positions) and no layer
        # rotating
        use_rope=True, rope_layers="none", use_rmsnorm=True,
        norm_eps=sizes["rms_norm_eps"], gated_mlp=True, gate_act="silu",
        tie_embeddings=True,
        layer_types=tuple(sizes["layer_types"]),
        ssm_heads=sizes["mamba_n_heads"], ssm_head_dim=sizes["mamba_d_head"],
        ssm_state=sizes["mamba_d_state"], ssm_groups=sizes["mamba_n_groups"],
        ssm_conv=sizes["mamba_d_conv"], ssm_chunk=sizes["mamba_chunk_size"],
        ssm_conv_bias=bool(sizes["mamba_conv_bias"]),
        embed_scale=float(sizes["embedding_multiplier"]),
        attn_scale=float(sizes["attention_multiplier"]),
        residual_scale=float(sizes["residual_multiplier"]),
        logits_divisor=float(sizes["logits_scaling"]))
