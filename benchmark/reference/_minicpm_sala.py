"""Plain MiniCPM-SALA forward (``model_type: minicpm_sala``): float32
``jax.numpy`` at the highest matmul precision, no kernels, no cache, no
chunks; written from the published ``config.json`` of ``openbmb/MiniCPM-SALA``
and the two papers its layers come from (Lightning Attention-2,
arXiv:2401.04658; InfLLM-V2 in MiniCPM4's report, arXiv:2506.07900), and
importing nothing from the program under test.

    embedding:  x = E[ids] * scale_emb
    layer i:    h = x + r * mixer_i(n1(x));   y = h + r * mlp(n2(h))
                r = scale_depth / sqrt(PUBLISHED num_hidden_layers),
                n = RMSNorm (x / sqrt(mean(x^2) + eps) * w)
    mlp:        mlp(m) = W_down (silu(W_gate m) * (W_up m))
    lightning:  [q | k | v | g] = W_in u, 32 heads of 128 each
                q, k = rope(n_q(q), n_k(k))     RMSNorm a head, RoPE by halves
                                                over the whole head, base 1e4
                S_t = lambda_h S_{t-1} + k_t^T v_t        (S_{-1} = 0, float32)
                o_t = q_t S_t / sqrt(128)
                lambda_h = exp(-2^(-8 (h + 1) / 32))
                out = W_out (n_o(o) * sigmoid(g))          n_o a head, one gain
    minicpm4:   32 query heads, 2 key/value heads of 128; n_q, n_k a head; NO
                rotation; scale 1 / sqrt(128); row t (context t + 1):
                  context <= dense_len: every key s <= t
                  else, a key/value head g (16 query heads, ONE choice):
                    kbar_j = mean(k[16 j : 16 j + 32])   (complete spans; seen
                                                          when 16 j + 31 <= t)
                    p_h[t, j] = softmax_j(q_h[t] . kbar_j / sqrt(128))
                    P[t, j] = sum of p_h over the 16 heads
                    score[t, b] = max P[t, j], j = 4 b - 1 .. 4 b + 3
                    kept: block 0, the 32 blocks ending at t's own, the best
                    others up to 64 in all (ties to the lower block): by a
                    FULL SORT of the row's block scores
                    softmax over the keys s <= t of the kept blocks
                out = W_o (o * sigmoid(W_g u))
    head:       logits = W_head nf(y) / (hidden_size / dim_model_base) (untied)

The recurrence is the RECURRENCE itself, one position after another
(``lax.scan``): the program's chunked form and its carried state are what this
is there to check.  The softmax over pooled keys takes the EXACT normaliser
(the family's kernels approximate it through a second, coarser pooling: the
noted departure, in the configuration's ``assumed`` too).

Departures from the description, all of layout and none of arithmetic:
weights come in the shapes the program stores them in (``wq [H, heads, d]``,
``wo [heads, d, H]``, a lightning layer's ``w_in [H, q + k + v + g]``), in
whatever type they are held in (bf16 when serving) and are raised to float32
one layer at a time; a sequence goes through half a layer per jitted call,
through attention and the MLP a block of rows at a time (``ROWS``) and
through the recurrence a few heads at a time (``HEADS``), so that a 21 k-row
prompt fits beside an engine: the blocks are of ROWS and of HEADS, every row
still sees all of its keys at once and every head all of its positions.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"     # on a TPU a float32 matmul is bf16 passes without it
ROWS = 128              # rows a block of attention and of the MLP


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
        if "LightningMixer_0" in blk:
            s = blk["LightningMixer_0"]
            p.update({k: s[k] for k in ("w_in", "q_norm", "k_norm", "norm",
                                        "w_out")})
        else:
            a = blk["Attention_0"]
            p.update({k: a[k] for k in ("wq", "wk", "wv", "wo", "wgate",
                                        "q_norm", "k_norm")})
        layers.append(p)
    return {"embed": bb["wte"], "layers": layers,
            "final_norm": bb["final_norm"]["scale"],
            "head": params["lm_head"]}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _by_rows(fn, *rows):
    """``fn`` over blocks of ``ROWS`` rows of the arrays ``rows`` (padded;
    the pad rows' results are dropped)."""
    T = rows[0].shape[0]
    n = -(-T // ROWS)
    pad = n * ROWS - T
    blocks = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (n, ROWS) + a.shape[1:]) for a in rows]
    out = jax.lax.map(lambda b: fn(*b), tuple(blocks))
    return out.reshape((n * ROWS,) + out.shape[2:])[:T]


def _mlp(p, h):
    wg, wu, wd = (p[k].astype(F32) for k in ("w_gate", "w_up", "w_down"))
    return _by_rows(lambda m: (jax.nn.silu(m @ wg) * (m @ wu)) @ wd, h)


def _rope(x, pos, base):
    """Rotation by halves over the whole head: ``x [T, heads, d]``."""
    half = x.shape[-1] // 2
    freq = base ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _decay(heads):
    """``lambda_h = exp(-2^(-8 (h + 1) / heads))``."""
    return jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=F32)
                             / heads))


def _recurrence(q, k, v, lam):
    """``S_t = lambda S_{t-1} + k_t^T v_t;  o_t = q_t S_t``, position by
    position: ``q``/``k``/``v [T, h, d]``, ``lam [h]`` -> ``[T, h, d]``."""
    def step(S, row):
        q_t, k_t, v_t = row
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)
    S0 = jnp.zeros((q.shape[1], k.shape[2], v.shape[2]), F32)
    return jax.lax.scan(step, S0, (q, k, v))[1]


HEADS = 8               # lightning heads through the recurrence at a time


def _lightning(p, u, *, heads, head_dim, eps, base, rotate):
    """A lightning layer's mixer on rows ``u [T, H]``, ``HEADS`` heads at a
    time (the output projection is a sum over heads, so the groups' parts
    add up): nothing ``[T, 4 x inner]`` wide is ever alive."""
    T = u.shape[0]
    inner = heads * head_dim
    w, w_out = p["w_in"].astype(F32), p["w_out"].astype(F32)
    lam, pos = _decay(heads), jnp.arange(T)
    out = jnp.zeros((T, w_out.shape[1]), F32)
    for h0 in range(0, heads, HEADS):
        n = min(HEADS, heads - h0)
        cols = slice(h0 * head_dim, (h0 + n) * head_dim)
        q, k, v, g = (u @ w[:, i * inner:(i + 1) * inner][:, cols]
                      for i in range(4))
        q, k, v = (a.reshape(T, n, head_dim) for a in (q, k, v))
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
        if rotate:
            q, k = _rope(q, pos, base), _rope(k, pos, base)
        o = _recurrence(q / math.sqrt(head_dim), k, v, lam[h0:h0 + n])
        o = _rms(o, p["norm"], eps).reshape(T, -1) * jax.nn.sigmoid(g)
        out = out + o @ w_out[cols]
    return out


def _pooled(k, kernel, stride):
    """``kbar_j = mean(k[stride j : stride j + kernel])`` over the COMPLETE
    spans of ``k [T, d]`` -> ``[J, d]`` (at least one row, of zeros, where
    no span is complete: no row sees it)."""
    T = k.shape[0]
    J = max((T - kernel) // stride + 1, 0)
    if J == 0:
        return jnp.zeros((1,) + k.shape[1:], F32)
    idx = stride * jnp.arange(J)[:, None] + jnp.arange(kernel)[None, :]
    return jnp.mean(k[idx], axis=1)


def _block_reduce(P, first, last):
    """A block's score from the pooled keys ``first .. last`` (clipped to
    those that exist) that overlap it: the MAX of ``P [R, J]``."""
    J = P.shape[1]
    j = jnp.arange(J)
    over = (j[None, :] >= first[:, None]) & (j[None, :] <= last[:, None])
    return jnp.max(jnp.where(over[None], P[:, None, :], 0.0), axis=-1)


def _select_blocks(qh, kbar, pos, T, sp, scale):
    """The blocks one key/value head's rows keep: ``qh [g, R, d]`` at
    ``pos [R]`` over pooled keys ``kbar [J, d]`` -> bool ``[R, NB]``."""
    kernel, stride, block = sp["kernel_size"], sp["kernel_stride"], \
        sp["block_size"]
    NB = -(-T // block)
    J = kbar.shape[0]
    seen = stride * jnp.arange(J)[None, :] + kernel - 1 <= pos[:, None]
    s = jnp.einsum("grd,jd->grj", qh, kbar) * scale
    s = jnp.where(seen[None], s, -jnp.inf)
    p = jnp.where(seen[None], jax.nn.softmax(s, axis=-1), 0.0)
    P = jnp.where(seen.any(-1, keepdims=True), jnp.sum(p, axis=0), 0.0)
    b = jnp.arange(NB)
    # pooled key j spans keys stride j .. stride j + kernel - 1
    first = -(-(block * b - kernel + 1) // stride)
    last = (block * b + block - 1) // stride
    score = _block_reduce(P, first, last)                    # [R, NB]
    own = pos // block
    forced = (b[None, :] < sp["init_blocks"]) | (
        b[None, :] > own[:, None] - sp["window_size"] // block)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(b[None, :] > own[:, None], -jnp.inf, score)
    order = jnp.argsort(-score, axis=-1, stable=True)        # the full sort
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < sp["topk"]) & (score > -jnp.inf)


def _heads_choice(keeps):
    """The choices of the key/value heads, one a head (a fault's hook)."""
    return keeps


def _attention(p, u, *, eps, sp, base, rotate):
    """A ``minicpm4`` layer's attention on rows ``u [T, H]``: keys, values
    and pooled keys of the whole sequence, then the queries, the gate, the
    choice and the attention a block of rows at a time."""
    T = u.shape[0]
    k = jnp.einsum("th,hnd->tnd", u, p["wk"].astype(F32))
    v = jnp.einsum("th,hnd->tnd", u, p["wv"].astype(F32))
    k = _rms(k, p["k_norm"], eps)
    key_pos = jnp.arange(T)
    if rotate:
        k = _rope(k, key_pos, base)
    wq, wgate, wo = (p[n].astype(F32) for n in ("wq", "wgate", "wo"))
    nh, nkv, d = wq.shape[1], k.shape[1], wq.shape[2]
    g = nh // nkv
    scale = 1.0 / math.sqrt(d)
    block = sp["block_size"]
    kbars = [_pooled(k[:, n], sp["kernel_size"], sp["kernel_stride"])
             for n in range(nkv)]

    def rows(ub, pos):                    # [R, H], [R] -> [R, H]
        q = _rms(jnp.einsum("th,hnd->tnd", ub, wq), p["q_norm"], eps)
        if rotate:
            q = _rope(q, pos, base)
        gate = jax.nn.sigmoid(jnp.einsum("th,hnd->tnd", ub, wgate))
        qg = q.reshape(-1, nkv, g, d)
        keeps = _heads_choice([
            _select_blocks(qg[:, n].transpose(1, 0, 2), kbars[n], pos, T, sp,
                           scale) for n in range(nkv)])
        out = []
        for n in range(nkv):
            keep = jnp.repeat(keeps[n], block, axis=1)[:, :T]
            keep = keep | (pos + 1 <= sp["dense_len"])[:, None]
            keep = keep & (key_pos[None, :] <= pos[:, None])
            s = jnp.einsum("rgd,sd->grs", qg[:, n], k[:, n]) * scale
            s = jnp.where(keep[None], s, -jnp.inf)
            out.append(jnp.einsum("grs,sd->rgd", jax.nn.softmax(s, -1),
                                  v[:, n]))
        o = jnp.stack(out, axis=1).reshape(-1, nh, d) * gate
        return jnp.einsum("tnd,ndh->th", o, wo)

    return _by_rows(rows, u, key_pos)


@functools.partial(jax.jit, donate_argnums=(1,), static_argnames=(
    "eps", "residual", "heads", "head_dim", "sp", "base", "rotate"))
def mixer(p, x, *, eps, residual, heads, head_dim, sp, base, rotate):
    """The first half of a layer on a sequence ``x [T, H]``: a lightning
    layer where ``p`` holds ``w_in``, a ``minicpm4`` layer otherwise.
    ``rotate``: (the lightning layers rotate, the attention layers do)."""
    with jax.default_matmul_precision(HIGHEST):
        h = _rms(x, p["n1"], eps)
        if "w_in" in p:
            mixed = _lightning(p, h, heads=heads, head_dim=head_dim, eps=eps,
                               base=base, rotate=rotate[0])
        else:
            mixed = _attention(p, h, eps=eps, sp=dict(sp), base=base,
                               rotate=rotate[1])
        return x + residual * mixed


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("eps", "residual"))
def feed_forward(p, x, *, eps, residual):
    with jax.default_matmul_precision(HIGHEST):
        return x + residual * _mlp(p, _rms(x, p["n2"], eps))


@functools.partial(jax.jit, static_argnames=("multiplier",))
def embed(table, tokens, *, multiplier):
    return table[tokens].astype(F32) * multiplier


@functools.partial(jax.jit, static_argnames=("eps", "divisor"))
def head(norm, w, x, *, eps, divisor):
    """Logits [T, V] of the rows ``x [T, H]`` through the untied head ``w
    [H, V]``."""
    with jax.default_matmul_precision(HIGHEST):
        return _rms(x, norm, eps) @ w.astype(F32) / divisor


def residual_scale(sizes):
    return float(sizes["scale_depth"]) / math.sqrt(
        float(sizes["published"]["num_hidden_layers"]))


def sparse_sizes(sizes):
    """The sparse layers' sizes as a hashable tuple of pairs."""
    return tuple(sorted((k, int(v))
                        for k, v in sizes["sparse_config"].items()))


def hidden(p, tokens, sizes):
    x = embed(p["embed"], jnp.asarray(tokens),
              multiplier=float(sizes["scale_emb"]))
    eps, r = float(sizes["rms_norm_eps"]), residual_scale(sizes)
    for lp in p["layers"]:
        lp = dict(lp)
        ff = {k: lp.pop(k) for k in ("n2", "w_gate", "w_up", "w_down")}
        x = mixer(lp, x, eps=eps, residual=r,
                  heads=int(sizes["lightning_nh"]),
                  head_dim=int(sizes["lightning_head_dim"]),
                  sp=sparse_sizes(sizes), base=float(sizes["rope_theta"]),
                  rotate=(bool(sizes["lightning_use_rope"]),
                          bool(sizes["attn_use_rope"])))
        x = feed_forward(ff, x, eps=eps, residual=r)
    return x


def logits(params, tokens, sizes, rows=None):
    """Float32 logits of one sequence ``tokens [T]`` at ``rows`` (all rows
    by default) from the program's parameter tree."""
    p = tree(params)
    x = hidden(p, tokens, sizes)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(p["final_norm"], p["head"], x,
                eps=float(sizes["rms_norm_eps"]),
                divisor=float(sizes["hidden_size"])
                / float(sizes["dim_model_base"]))


def layer_kinds(sizes):
    """The program's ``layer_types`` of the layers this file keeps: the
    published ``mixer_types`` read at ``layers_kept``."""
    kinds = {"minicpm4": "attention", "lightning-attn": "lightning"}
    return tuple(kinds[sizes["mixer_types"][i]] for i in sizes["layers_kept"])


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "minicpm_sala"
    assert sizes["hidden_act"] == "silu" and not sizes["attention_bias"]
    assert sizes["qk_norm"] and not sizes["tie_word_embeddings"]
    assert sizes["use_output_gate"] and sizes["use_output_norm"]
    assert sizes["attn_use_output_gate"]
    assert sizes["lightning_use_rope"] and not sizes["attn_use_rope"]
    assert sizes["lightning_scale"] == "1/sqrt(d)"
    assert sizes["lightning_nkv"] == sizes["lightning_nh"]
    assert len(sizes["layers_kept"]) == sizes["num_hidden_layers"]
    assert len(sizes["mixer_types"]) == sizes["published"][
        "num_hidden_layers"] or sizes.get("rehearsal_of")
    sp = sizes["sparse_config"]
    return dict(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], hidden_size=sizes["hidden_size"],
        mlp_dim_override=sizes["intermediate_size"],
        # RoPE inside the lightning layers, none on the attention layers
        use_rope=True, rope_layers="state",
        rope_theta=float(sizes["rope_theta"]), use_rmsnorm=True,
        norm_eps=sizes["rms_norm_eps"], gated_mlp=True, gate_act="silu",
        tie_embeddings=False, qk_norm=True, attn_gate=True,
        layer_types=layer_kinds(sizes),
        ssm_heads=sizes["lightning_nh"],
        ssm_head_dim=sizes["lightning_head_dim"],
        ssm_state=sizes["lightning_head_dim"],
        ssm_groups=sizes["lightning_nkv"], ssm_chunk=128,
        embed_scale=float(sizes["scale_emb"]),
        residual_scale=residual_scale(sizes),
        logits_divisor=float(sizes["hidden_size"])
        / float(sizes["dim_model_base"]),
        block_topk=sp["topk"], block_size=sp["block_size"],
        block_kernel=sp["kernel_size"], block_stride=sp["kernel_stride"],
        block_window=sp["window_size"], block_init=sp["init_blocks"],
        block_dense_len=sp["dense_len"])
