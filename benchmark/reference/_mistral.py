"""Plain Mistral-7B forward and loss: float32 ``jax.numpy``, no kernels, no
cache, no batching; written from the published description (Jiang et al.,
arXiv:2310.06825, and the ``config.json`` of Mistral-7B-v0.3) and importing
nothing from the program under test.

Per layer, on a sequence ``x [T, H]``:

    h = x + Wo . attention(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
    y = h + Wdown . (silu(Wgate . n2(h)) * (Wup . n2(h)))

``n`` is RMSNorm (``x / sqrt(mean(x^2) + eps) * scale``); attention is causal
softmax attention with ``num_key_value_heads`` key/value heads, each shared by
``num_attention_heads / num_key_value_heads`` query heads; RoPE rotates the
two halves of each head (the ``rotate_half`` convention of the published
code) by ``pos * theta^(-2i/d)``.  The published model's window is null in
v0.3, so attention sees the whole prefix.

Departures from the description, all of layout and none of arithmetic:
weights come in the shapes the program stores them in (``wq [H, heads, d]``,
``wo [heads, d, H]``, no reshape, so no second copy on the device), in
whatever type they are held in (bf16 when serving, fp32 masters when
training) and are raised to float32 one layer at a time; a sequence goes
through one layer per jitted call, so that the largest thing alive is one
layer's float32 weights; attention runs one key/value group at a time.
"""

import functools

import jax
import jax.numpy as jnp

from _loss import mean_next_token_loss

F32 = jnp.float32
HIGHEST = "highest"     # on a TPU a float32 matmul is bf16 passes without it


def tree(params):
    """The program's parameter tree under the reference's names (views)."""
    bb = params["backbone"]
    n = sum(1 for k in bb if k.startswith("block_"))
    layers = []
    for i in range(n):
        blk = bb[f"block_{i}"]
        a, m = blk["Attention_0"], blk["MLP_0"]
        layers.append({"n1": blk["Norm_0"]["scale"], "wq": a["wq"],
                       "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
                       "n2": blk["Norm_1"]["scale"], "w_gate": m["wg"],
                       "w_up": m["wi"], "w_down": m["wo"]})
    return {"embed": bb["wte"], "layers": layers,
            "final_norm": bb["final_norm"]["scale"],
            "lm_head": params["lm_head"]}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq            # [T, half]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def layer(p, x, *, eps, theta):
    with jax.default_matmul_precision(HIGHEST):
        T = x.shape[0]
        pos = jnp.arange(T)
        h = _rms(x, p["n1"], eps)
        q = jnp.einsum("th,hnd->tnd", h, p["wq"].astype(F32))
        k = jnp.einsum("th,hnd->tnd", h, p["wk"].astype(F32))
        v = jnp.einsum("th,hnd->tnd", h, p["wv"].astype(F32))
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        nh, nkv, d = q.shape[1], k.shape[1], q.shape[2]
        qg = q.reshape(T, nkv, nh // nkv, d).transpose(1, 2, 0, 3)
        causal = pos[:, None] >= pos[None, :]

        def group(args):                      # one key/value head
            qh, kh, vh = args                 # [g, T, d], [T, d], [T, d]
            s = jnp.einsum("gtd,sd->gts", qh, kh) * (d ** -0.5)
            s = jnp.where(causal[None], s, -jnp.inf)
            return jnp.einsum("gts,sd->gtd", jax.nn.softmax(s, -1), vh)

        o = jax.lax.map(group, (qg, k.transpose(1, 0, 2),
                                v.transpose(1, 0, 2)))   # [nkv, g, T, d]
        o = o.transpose(2, 0, 1, 3).reshape(T, nh, d)
        x = x + jnp.einsum("tnd,ndh->th", o, p["wo"].astype(F32))
        h = _rms(x, p["n2"], eps)
        gate = jax.nn.silu(h @ p["w_gate"].astype(F32))
        return x + (gate * (h @ p["w_up"].astype(F32))) \
            @ p["w_down"].astype(F32)


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(norm, lm_head, x, *, eps):
    """Logits [T, V] of the rows ``x [T, H]``."""
    with jax.default_matmul_precision(HIGHEST):
        return _rms(x, norm, eps) @ lm_head.astype(F32)


def hidden(p, tokens, sizes):
    x = embed(p["embed"], jnp.asarray(tokens))
    for lp in p["layers"]:
        x = layer(lp, x, eps=float(sizes["rms_norm_eps"]),
                  theta=float(sizes["rope_theta"]))
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


def loss(params, batch, sizes, block=1024):
    """Mean next-token cross-entropy over ``batch [B, T]``, sequence by
    sequence and ``block`` rows of logits at a time."""
    p = tree(params)
    return mean_next_token_loss(
        lambda seq: hidden(p, seq, sizes),
        lambda x: head(p["final_norm"], p["lm_head"], x, eps=float(sizes["rms_norm_eps"])),
        batch, block)
