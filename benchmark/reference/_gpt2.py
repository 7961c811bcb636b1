"""Plain GPT-2 forward and loss: float32 ``jax.numpy``, no kernels, no
cache, no batching; written from the published description (Radford et al.
2019, "Language Models are Unsupervised Multitask Learners", and the
``config.json`` of gpt2-medium) and importing nothing from the program under
test.

Per layer, on a sequence ``x [T, H]``, with LayerNorm ``n`` (eps 1e-5,
scale and bias) and biases on every projection:

    h = x + Wo . attention(Wq . n1(x), Wk . n1(x), Wv . n1(x)) + bo
    y = h + Wproj . gelu_new(Wfc . n2(h) + bfc) + bproj

``gelu_new`` is the tanh approximation ``0.5 x (1 + tanh(sqrt(2/pi) (x +
0.044715 x^3)))``; positions are a learned table added to the token
embedding; the output head is the token embedding, transposed.

Departures, of layout only: weights come in the shapes and types the program
holds them in (``wq [H, heads, d]``, ``wo [heads, d, H]``; the published
checkpoint fuses q, k and v into one ``c_attn``), and are raised to float32
one layer at a time; the vocabulary is held as 50,304 rows, of which random
tokens may use all (the 47 extra rows are ordinary rows here).
"""

import functools
import math

import jax
import jax.numpy as jnp

from _loss import mean_next_token_loss

F32 = jnp.float32
HIGHEST = "highest"


def tree(params):
    bb = params["backbone"]
    n = sum(1 for k in bb if k.startswith("block_"))
    layers = []
    for i in range(n):
        blk = bb[f"block_{i}"]
        a, m = blk["Attention_0"], blk["MLP_0"]
        layers.append({"n1": blk["Norm_0"], "n2": blk["Norm_1"],
                       "wq": a["wq"], "wk": a["wk"], "wv": a["wv"],
                       "bq": a["bq"], "bk": a["bk"], "bv": a["bv"],
                       "wo": a["wo"], "bo": a["bo"],
                       "w_fc": m["wi"], "b_fc": m["bi"],
                       "w_proj": m["wo"], "b_proj": m["bo"]})
    return {"wte": bb["wte"], "wpe": bb["wpe"], "layers": layers,
            "final_norm": bb["final_norm"]}


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("eps",))
def layer(p, x, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        T = x.shape[0]
        h = _ln(x, p["n1"], eps)
        q = jnp.einsum("th,hnd->tnd", h, p["wq"].astype(F32)) \
            + p["bq"].astype(F32)
        k = jnp.einsum("th,hnd->tnd", h, p["wk"].astype(F32)) \
            + p["bk"].astype(F32)
        v = jnp.einsum("th,hnd->tnd", h, p["wv"].astype(F32)) \
            + p["bv"].astype(F32)
        d = q.shape[-1]
        s = jnp.einsum("tnd,snd->nts", q, k) * (d ** -0.5)
        pos = jnp.arange(T)
        s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
        o = jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("tnd,ndh->th", o, p["wo"].astype(F32)) \
            + p["bo"].astype(F32)
        h = _ln(x, p["n2"], eps)
        h = _gelu_new(h @ p["w_fc"].astype(F32) + p["b_fc"].astype(F32))
        return x + h @ p["w_proj"].astype(F32) + p["b_proj"].astype(F32)


@jax.jit
def embed(wte, wpe, tokens):
    return wte[tokens].astype(F32) + wpe[:tokens.shape[0]].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(norm, wte, x, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return _ln(x, norm, eps) @ wte.astype(F32).T


def hidden(p, tokens, sizes):
    x = embed(p["wte"], p["wpe"], jnp.asarray(tokens))
    for lp in p["layers"]:
        x = layer(lp, x, eps=float(sizes["layer_norm_epsilon"]))
    return x


def logits(params, tokens, sizes, rows=None):
    p = tree(params)
    x = hidden(p, tokens, sizes)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(p["final_norm"], p["wte"], x,
                eps=float(sizes["layer_norm_epsilon"]))


def loss(params, batch, sizes, block=1024):
    """Mean next-token cross-entropy over ``batch [B, T]``, sequence by
    sequence and ``block`` rows of logits at a time."""
    p = tree(params)
    return mean_next_token_loss(
        lambda seq: hidden(p, seq, sizes),
        lambda x: head(p["final_norm"], p["wte"], x, eps=float(sizes["layer_norm_epsilon"])),
        batch, block)
