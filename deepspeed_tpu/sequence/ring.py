"""Ring attention — sequence parallelism by rotating KV blocks over the ring.

Reference scope: DeepSpeed's long-context story is Ulysses (sequence/layer.py,
all-to-all head swap).  Ring attention (Liu et al., "Ring Attention with
Blockwise Transformers", PAPERS.md) is the complementary mechanism this
framework ships as a first-class alternative: sequence stays sharded the
WHOLE time — no all-to-all, no head-count divisibility constraint — while K/V
blocks rotate neighbor-to-neighbor over the ``sp`` axis.

TPU-native shape: one ``shard_map`` over ``sp``; inside, a differentiable
``lax.scan`` of sp steps, each step
  - attends the local Q block against the currently-held K/V block with a
    GLOBAL-position causal mask (so ordering is exact regardless of which
    block is visiting),
  - folds the partial result into online-softmax stats (m, l, acc) — the
    flash-attention recurrence across blocks,
  - ``ppermute``s the K/V block to the next neighbor (ICI ring — the same
    link pattern the hardware torus provides natively).

Causality note (contiguous schedule): blocks strictly "ahead" of the local Q
block contribute nothing but are still rotated through (the ring must
complete); their scores are fully masked — ~half the FLOPs are dead on
causal attention.

``schedule="zigzag"`` (round-3 verdict item 8) removes that waste: each
device owns chunks (d, 2·sp−1−d) of the sequence (the zig-zag placement from
zigzag ring attention / llama-3 context parallelism).  At every ring step
exactly TWO of the four (q-chunk × kv-chunk) sub-blocks are causally live,
and — because liveness depends only on (my, src), not on token positions —
they are FULLY live: steps 1..sp−1 run two mask-free half-size attends
(balanced across devices), and only step 0 pays within-chunk diagonal masks.
FLOPs drop to ~(2·sp+1)/(4·sp) ≈ 55% of the contiguous schedule; the ring's
own wire cost is unchanged (each device still sends its KV bytes sp−1 times,
neighbor-only), but the convenience permutation in/out of zig-zag layout —
applied inside the call so the public contract (contiguous [B, T, H, D],
token-exact vs dense) is identical — adds ~4 tensor-sized cross-device
reshuffles per call (q/k/v in, o out; again in backward), booked to the
comms logger.  A training stack that keeps activations in zig-zag layout
end-to-end (permute tokens + positions once at the embedding) amortizes
that to zero; this entry point trades that for drop-in exactness.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.comm.comm import comms_logger

# numpy, NOT jnp: a module-level jnp scalar is a committed device array that
# every trace captures as a jaxpr const — under the engine's donated jit it
# becomes a lifted executable parameter and the second call fails with a
# supplied-vs-expected buffer mismatch (round 5, with the iota-perm note on
# ``_zigzag_perm``)
_NEG = np.float32(-1e30)


def _gqa_scores(qf, kc, scale):
    """q [B, Tq, H, D] × k [B, Tk, Hkv, D] → logits [B, H, Tq, Tk].

    Hkv < H (GQA): the group expansion happens INSIDE the einsum (q reshaped
    to [.., Hkv, g, D] against un-expanded KV), so the ring rotates Hkv-sized
    blocks — wire bytes drop by g = H/Hkv vs pre-expanding KV."""
    B, Tq, H, D = qf.shape
    hkv = kc.shape[2]
    if hkv == H:
        return jnp.einsum("bqhd,bkhd->bhqk", qf,
                          kc.astype(jnp.float32)) * scale
    s = jnp.einsum("bqngd,bknd->bngqk",
                   qf.reshape(B, Tq, hkv, H // hkv, D),
                   kc.astype(jnp.float32)) * scale
    return s.reshape(B, H, Tq, kc.shape[1])


def _gqa_pv(p, vc):
    """probs [B, H, Tq, Tk] × v [B, Tk, Hkv, D] → [B, H, Tq, D] (grouped)."""
    B, H, Tq, Tk = p.shape
    hkv = vc.shape[2]
    if hkv == H:
        return jnp.einsum("bhqk,bkhd->bhqd", p, vc.astype(jnp.float32))
    o = jnp.einsum("bngqk,bknd->bngqd", p.reshape(B, hkv, H // hkv, Tq, Tk),
                   vc.astype(jnp.float32))
    return o.reshape(B, H, Tq, vc.shape[3])


def _ring_body(q, k0, v0, my, sp_size, axis, causal, scale):
    """Local blockwise-softmax accumulation over sp ring steps.

    q [B, Tl, H, D]; k0/v0 the locally-held KV block (possibly fewer, GQA,
    heads).  Returns [B, Tl, H, D].
    """
    B, Tl, H, D = q.shape
    qpos = my * Tl + jnp.arange(Tl)                     # global positions
    perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]

    qf = q.astype(jnp.float32)

    def accumulate(m, l, acc, kcur, vcur, s):
        src = (my - s) % sp_size                        # owner of kcur
        kpos = src * Tl + jnp.arange(Tl)
        s_log = _gqa_scores(qf, kcur, scale)
        if causal:
            mask = kpos[None, :] <= qpos[:, None]       # [Tq, Tk] global
            s_log = jnp.where(mask[None, None], s_log, _NEG)
        m_new = jnp.maximum(m, jnp.max(s_log, axis=-1))
        p = jnp.exp(s_log - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = _gqa_pv(p, vcur)
        return m_new, l_new, acc * alpha[..., None] + pv

    def step(carry, s):
        m, l, acc, kcur, vcur = carry
        m, l, acc = accumulate(m, l, acc, kcur, vcur, s)
        # rotate KV to the next neighbor; the last visiting block is computed
        # OUTSIDE the scan so no dead final rotation is issued (sp-1 hops
        # total — matches the bytes the comms logger books)
        knext = lax.ppermute(kcur, axis, perm)
        vnext = lax.ppermute(vcur, axis, perm)
        return (m, l, acc, knext, vnext), None

    m0 = jnp.full((B, H, Tl), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    acc0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    (m, l, acc, klast, vlast), _ = lax.scan(
        jax.checkpoint(step), (m0, l0, acc0, k0, v0),
        jnp.arange(sp_size - 1))
    m, l, acc = accumulate(m, l, acc, klast, vlast, sp_size - 1)
    l = jnp.where(l == 0.0, 1.0, l)                     # fully-masked rows
    out = acc / l[..., None]                            # [B, H, Tl, D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _zigzag_body(q, k0, v0, my, sp_size, axis, scale):
    """Causal ring over the zig-zag placement: the local block holds chunks
    (a=my, b=2·sp−1−my) as rows [:c] / [c:].  Block-level liveness depends
    only on (my, src), so steps 1..sp−1 run exactly two MASK-FREE half-size
    attends; only step 0 (own chunks) pays diagonal masks.  ~½ the FLOPs of
    the contiguous schedule at identical wire cost (module docstring)."""
    B, T2, H, D = q.shape
    c = T2 // 2
    qf = q.astype(jnp.float32)
    qa, qb = qf[:, :c], qf[:, c:]
    perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]

    def scores(qh, kc):                                   # [B, H, c, c]
        return _gqa_scores(qh, kc, scale)

    def fold(stats, h_idx, s_log, vc):
        """Online-softmax fold of one sub-block into half ``h_idx``'s stats
        (h_idx may be traced — stats are stacked [2, ...])."""
        m, l, acc = stats
        mh = lax.dynamic_index_in_dim(m, h_idx, 0, keepdims=False)
        lh = lax.dynamic_index_in_dim(l, h_idx, 0, keepdims=False)
        ah = lax.dynamic_index_in_dim(acc, h_idx, 0, keepdims=False)
        m_new = jnp.maximum(mh, jnp.max(s_log, axis=-1))
        p = jnp.exp(s_log - m_new[..., None])
        alpha = jnp.exp(mh - m_new)
        l_new = lh * alpha + jnp.sum(p, axis=-1)
        pv = _gqa_pv(p, vc)
        a_new = ah * alpha[..., None] + pv
        return (lax.dynamic_update_index_in_dim(m, m_new, h_idx, 0),
                lax.dynamic_update_index_in_dim(l, l_new, h_idx, 0),
                lax.dynamic_update_index_in_dim(acc, a_new, h_idx, 0))

    # step 0 — own chunks: qa×ka (diag), qb×ka (full: a < sp ≤ b), qb×kb (diag)
    ka, kb = k0[:, :c], k0[:, c:]
    va, vb = v0[:, :c], v0[:, c:]
    tri = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])[None, None]
    m0 = jnp.full((2, B, H, c), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((2, B, H, c), jnp.float32)
    acc0 = jnp.zeros((2, B, H, c, D), jnp.float32)
    stats = (m0, l0, acc0)
    stats = fold(stats, 0, jnp.where(tri, scores(qa, ka), _NEG), va)
    stats = fold(stats, 1, scores(qb, ka), va)
    stats = fold(stats, 1, jnp.where(tri, scores(qb, kb), _NEG), vb)

    def step(carry, s):
        stats, kprev, vprev = carry
        # rotate FIRST: at step s the resident block must come from
        # src = (my − s) mod sp (step 0 consumed the un-rotated own block)
        kcur = lax.ppermute(kprev, axis, perm)
        vcur = lax.ppermute(vprev, axis, perm)
        src = (my - s) % sp_size
        ka_, kb_ = kcur[:, :c], kcur[:, c:]
        va_, vb_ = vcur[:, :c], vcur[:, c:]
        # visiting early chunk a' = src: live for qb always; for qa iff
        # src < my.  visiting late chunk b' = 2sp−1−src: live iff src > my
        # (then b' < b), and only for qb.  Exactly two fully-live sub-blocks.
        stats = fold(stats, 1, scores(qb, ka_), va_)
        early = src < my
        h2 = jnp.where(early, 0, 1).astype(jnp.int32)
        q2 = jnp.where(early, qa, qb)
        k2 = jnp.where(early, ka_, kb_)
        v2 = jnp.where(early, va_, vb_)
        stats = fold(stats, h2, scores(q2, k2), v2)
        return (stats, kcur, vcur), None

    (stats, _, _), _ = lax.scan(jax.checkpoint(step), (stats, k0, v0),
                                jnp.arange(1, sp_size))
    m, l, acc = stats
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]                        # [2, B, H, c, D]
    out = jnp.concatenate([out[0], out[1]], axis=2)  # [B, H, 2c, D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _lse_merge(o1, l1, o2, l2):
    """Exact combine of two softmax-attention partials over disjoint key
    sets: o_i normalized outputs [B, H, Tq, D] (f32), l_i logsumexp rows
    [B, H, Tq].  The flash-decoding / ring-flash merge identity."""
    l = jnp.logaddexp(l1, l2)
    return (o1 * jnp.exp(l1 - l)[..., None]
            + o2 * jnp.exp(l2 - l)[..., None]), l


def _zigzag_body_flash(q, k0, v0, my, sp_size, axis, scale, interpret):
    """``_zigzag_body`` with the Pallas flash kernel as the inner attend —
    the [c, c] logit matrices never materialize (VMEM [bq, bk] tiles
    only), so per-device attention memory is O(inputs + outputs): the
    einsum body's peak 3×[B, H, c, c] score buffers are the last
    long-context memory wall this removes.

    Every zig-zag sub-attend is block-level causal=True (own diagonal) or
    causal=False (fully live) — liveness depends only on (my, src), never
    on token positions — so the stock flash kernels apply unmodified.
    Forward merges per-block (o, lse) with the exact logsumexp combine;
    backward is a ring-level custom_vjp in the ring-flash-attention
    style: replay the KV rotation and run the flash backward kernels per
    live sub-block with the GLOBAL lse (p = exp(s − lse_global) is then
    the true global softmax prob, so per-block dq/dk/dv sum exactly),
    accumulating dk/dv on a buffer that rotates WITH k/v and goes home in
    one reverse hop.  ``my`` enters only through a float liveness mask so
    the custom_vjp's inputs are all float (clean zero cotangents).
    Layouts inside are kernel-major [B, H, T, D].
    """
    # importlib: the ops package re-exports a flash_attention FUNCTION that
    # shadows the submodule on attribute access
    import importlib
    FA = importlib.import_module("deepspeed_tpu.ops.flash_attention")

    B, T2, H, D = q.shape
    c = T2 // 2
    perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]
    homeperm = [(i, (i - (sp_size - 1)) % sp_size) for i in range(sp_size)]
    # early[s−1] == 1.0 ⟺ ring step s's visiting block comes from an
    # EARLIER device (the where-routed sub-attend targets the qa half)
    steps = jnp.arange(1, sp_size)
    early_f = (((my - steps) % sp_size) < my).astype(jnp.float32)

    def kl(x):                         # [B, T, H, D] → kernel-major
        return jnp.transpose(x, (0, 2, 1, 3))

    def sub_fwd(qh, kc, vc, causal):
        o, lse = FA._fwd(qh, kc, vc, None, causal, scale, None, False,
                         interpret)
        # lse rides the kernels' [B, H, 1, T] stat layout — flatten for
        # the merges, re-expand in sub_bwd
        return o.astype(jnp.float32), lse[:, :, 0]  # [B,H,c,D], [B,H,c]

    def sub_bwd(qh, kc, vc, og, lg, do, causal):
        dq, dk, dv = FA._bwd_impl(qh, kc, vc, og.astype(qh.dtype),
                                  lg[:, :, None, :], do.astype(qh.dtype),
                                  None, causal, scale, None, False,
                                  interpret)
        return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                dv.astype(jnp.float32))

    def fwd_scan(qx, kx, vx, ef):
        qa, qb = qx[:, :, :c], qx[:, :, c:]
        ka, kb = kx[:, :, :c], kx[:, :, c:]
        va, vb = vx[:, :, :c], vx[:, :, c:]
        # step 0 — own chunks: qa×ka diag, qb×ka full, qb×kb diag
        oa, la = sub_fwd(qa, ka, va, True)
        ob1, lb1 = sub_fwd(qb, ka, va, False)
        ob2, lb2 = sub_fwd(qb, kb, vb, True)
        ob, lb = _lse_merge(ob1, lb1, ob2, lb2)

        def step(carry, e):
            oa, la, ob, lb, kc, vc = carry
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            ka_, kb_ = kc[:, :, :c], kc[:, :, c:]
            va_, vb_ = vc[:, :, :c], vc[:, :, c:]
            o1, l1 = sub_fwd(qb, ka_, va_, False)  # qb × early chunk: live
            ob, lb = _lse_merge(ob, lb, o1, l1)
            early = e > 0.5
            q2 = jnp.where(early, qa, qb)
            k2 = jnp.where(early, ka_, kb_)
            v2 = jnp.where(early, va_, vb_)
            o2, l2 = sub_fwd(q2, k2, v2, False)
            oa_m, la_m = _lse_merge(oa, la, o2, l2)
            ob_m, lb_m = _lse_merge(ob, lb, o2, l2)
            oa = jnp.where(early, oa_m, oa)
            la = jnp.where(early, la_m, la)
            ob = jnp.where(early, ob, ob_m)
            lb = jnp.where(early, lb, lb_m)
            return (oa, la, ob, lb, kc, vc), None

        (oa, la, ob, lb, _, _), _ = lax.scan(
            step, (oa, la, ob, lb, kx, vx), ef)
        return oa, la, ob, lb

    def bwd_scan(qx, kx, vx, ef, oa, la, ob, lb, doa, dob):
        qa, qb = qx[:, :, :c], qx[:, :, c:]

        def live_sub1(kc, vc, dkc, dvc, dqb):
            """qb × visiting early chunk — live at EVERY ring step."""
            dq1, dk1, dv1 = sub_bwd(qb, kc[:, :, :c], vc[:, :, :c],
                                    ob, lb, dob, False)
            return (dkc.at[:, :, :c].add(dk1), dvc.at[:, :, :c].add(dv1),
                    dqb + dq1)

        # step 0 (resident block, run OUTSIDE the scan — its diagonal
        # sub-attends are the only causal ones, kept trace-static)
        zkv = jnp.zeros(kx.shape, jnp.float32)
        dqa = jnp.zeros((B, H, c, D), jnp.float32)
        dqb = jnp.zeros((B, H, c, D), jnp.float32)
        dkc, dvc, dqb = live_sub1(kx, vx, zkv, jnp.zeros_like(zkv), dqb)
        dq2, dk2, dv2 = sub_bwd(qa, kx[:, :, :c], vx[:, :, :c],
                                oa, la, doa, True)
        dqa = dqa + dq2
        dkc = dkc.at[:, :, :c].add(dk2)
        dvc = dvc.at[:, :, :c].add(dv2)
        dq3, dk3, dv3 = sub_bwd(qb, kx[:, :, c:], vx[:, :, c:],
                                ob, lb, dob, True)
        dqb = dqb + dq3
        dkc = dkc.at[:, :, c:].add(dk3)
        dvc = dvc.at[:, :, c:].add(dv3)

        def step(carry, e):
            kc, vc, dkc, dvc, dqa, dqb = carry
            rot = lambda x: lax.ppermute(x, axis, perm)  # noqa: E731
            kc, vc, dkc, dvc = rot(kc), rot(vc), rot(dkc), rot(dvc)
            dkc, dvc, dqb = live_sub1(kc, vc, dkc, dvc, dqb)
            early = e > 0.5
            ka_, kb_ = kc[:, :, :c], kc[:, :, c:]
            va_, vb_ = vc[:, :, :c], vc[:, :, c:]
            q2 = jnp.where(early, qa, qb)
            k2 = jnp.where(early, ka_, kb_)
            v2 = jnp.where(early, va_, vb_)
            og2 = jnp.where(early, oa, ob)
            lg2 = jnp.where(early, la, lb)
            do2 = jnp.where(early, doa, dob)
            dq2, dk2, dv2 = sub_bwd(q2, k2, v2, og2, lg2, do2, False)
            dqa = dqa + jnp.where(early, dq2, 0.0)
            dqb = dqb + jnp.where(early, 0.0, dq2)
            dkc = dkc.at[:, :, :c].add(jnp.where(early, dk2, 0.0))
            dkc = dkc.at[:, :, c:].add(jnp.where(early, 0.0, dk2))
            dvc = dvc.at[:, :, :c].add(jnp.where(early, dv2, 0.0))
            dvc = dvc.at[:, :, c:].add(jnp.where(early, 0.0, dv2))
            return (kc, vc, dkc, dvc, dqa, dqb), None

        (_, _, dkc, dvc, dqa, dqb), _ = lax.scan(
            step, (kx, vx, dkc, dvc, dqa, dqb), ef)
        # grads rotated sp−1 hops with their blocks; one permute goes home
        dkc = lax.ppermute(dkc, axis, homeperm)
        dvc = lax.ppermute(dvc, axis, homeperm)
        return jnp.concatenate([dqa, dqb], axis=2), dkc, dvc

    @jax.custom_vjp
    def zz(qx, kx, vx, ef):
        oa, _, ob, _ = fwd_scan(qx, kx, vx, ef)
        return jnp.concatenate([oa, ob], axis=2)

    def zz_fwd(qx, kx, vx, ef):
        oa, la, ob, lb = fwd_scan(qx, kx, vx, ef)
        return (jnp.concatenate([oa, ob], axis=2),
                (qx, kx, vx, ef, oa, la, ob, lb))

    def zz_bwd(res, dout):
        qx, kx, vx, ef, oa, la, ob, lb = res
        doa = dout[:, :, :c].astype(jnp.float32)
        dob = dout[:, :, c:].astype(jnp.float32)
        dq, dk, dv = bwd_scan(qx, kx, vx, ef, oa, la, ob, lb, doa, dob)
        return (dq.astype(qx.dtype), dk.astype(kx.dtype),
                dv.astype(vx.dtype), jnp.zeros_like(ef))

    zz.defvjp(zz_fwd, zz_bwd)
    out = zz(kl(q), kl(k0), kl(v0), early_f)      # [B, H, 2c, D] f32
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _zigzag_perm(t: int, sp: int):
    """Global index permutation placing chunks (d, 2sp−1−d) on device d.

    Computed in closed form from ``iota`` arithmetic rather than as a
    materialized index table: a constant array here becomes an XLA
    executable parameter under the engine's donated jit (constant hoisting),
    and the fast-path second call then fails with a supplied-vs-expected
    buffer-count mismatch — found driving the engine 30 steps, round 5.
    Iota-derived indices leave nothing to hoist (and nothing to ship from
    the host)."""
    c = t // (2 * sp)
    r = jnp.arange(t)
    # forward: row r lives on device d = r // (2c); within-device half
    # h selects chunk d (h=0) or chunk 2sp−1−d (h=1)
    d = r // (2 * c)
    w = r % (2 * c)
    chunk = jnp.where(w < c, d, 2 * sp - 1 - d)
    idx = chunk * c + w % c
    # inverse: original position i sits in chunk i//c; early chunks map to
    # (device=chunk, half 0), late ones to (device=2sp−1−chunk, half 1)
    ch_i = r // c
    early = ch_i < sp
    dev = jnp.where(early, ch_i, 2 * sp - 1 - ch_i)
    inv = dev * 2 * c + jnp.where(early, 0, c) + r % c
    return idx, inv


def zigzag_order(t: int, sp: int):
    """(idx, inv) for the zig-zag placement: ``x[:, idx]`` lays a contiguous
    sequence out so shard d of the sp axis holds chunks (d, 2·sp−1−d);
    ``z[:, inv]`` undoes it.  Row r of the zig-zag array holds the token
    whose global position is ``idx[r]`` — so ``positions = idx`` is the
    position vector of the permuted sequence (what RoPE / learned position
    embeddings must see)."""
    if t % (2 * sp):
        raise ValueError(f"seq len {t} not divisible by 2*sp={2 * sp}")
    return _zigzag_perm(t, sp)


def ring_attention(mesh: Mesh, q, k, v, *, causal: bool = True,
                   axis: str = "sp", batch_axes=("dp", "fsdp"),
                   scale=None, schedule: str = "zigzag",
                   layout: str = "contiguous", inner: str = "einsum"):
    """Global-view entry: q/k/v [B, T, H, D] with T sharded over ``axis``.

    Equivalent math to full softmax attention (tested token-exact vs the
    dense path); peak per-device score memory is [B, H, T/sp, T/sp]
    (contiguous) or 3×[B, H, T/2sp, T/2sp] (zigzag).

    ``schedule``: "zigzag" (default — causal FLOPs ≈ halved, module
    docstring) or "contiguous".  Zig-zag needs T % (2·sp) == 0 and causal;
    other cases fall back to the contiguous schedule.

    ``layout``: "contiguous" (default — rows are tokens in order; the
    zig-zag schedule permutes in/out internally, ~4 tensor volumes of wire
    per call) or "zigzag" (rows are ALREADY in zig-zag placement — row r
    holds token ``idx[r]`` of ``zigzag_order(T, sp)`` — so the schedule runs
    with ZERO permute traffic and the output stays in zig-zag layout).  The
    layout-native path is how a training stack amortizes the permutes to
    one token-id shuffle per step: permute ids + positions + labels once at
    the batch (models/gpt.py ``sp_ring_layout='native'``), keep activations
    zig-zag end-to-end — every non-attention op is position-wise and the LM
    loss is permutation-invariant.  Requires causal and T % (2·sp) == 0
    (raises otherwise: the caller re-laid the data out, silence would
    compute garbage).

    ``inner``: "einsum" (default — per-step sub-attends materialize
    [c, c] logits, c = T/(2·sp)) or "flash" (sub-attends run the Pallas
    flash kernel with logsumexp merging and a ring-level custom_vjp —
    per-device attention memory drops to O(inputs + outputs), removing the
    last long-context memory wall; see ``_zigzag_body_flash``).  "flash"
    requires the zig-zag schedule (causal, T % (2·sp) == 0), head_dim % 8
    == 0, and a per-device half-chunk divisible by a flash block (c ≥ 8);
    raises otherwise — an opt-in flag must not silently degrade.
    """
    sp = mesh.shape[axis]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"layout must be contiguous|zigzag, got {layout!r}")
    if inner not in ("einsum", "flash"):
        # validated BEFORE the sp==1 early return (round-5 advisor finding:
        # a bad inner string was silently accepted on single-shard meshes)
        raise ValueError(f"inner must be einsum|flash, got {inner!r}")
    if sp == 1:
        from deepspeed_tpu import ops
        if layout == "zigzag":
            raise ValueError("layout='zigzag' is meaningless at sp=1 — the "
                             "caller permuted for a ring that doesn't exist")
        if inner == "flash":
            # the flag asked for O(inputs) attention memory; honoring that at
            # sp=1 means the registry flash kernel (impl=None lets the op
            # registry pick Pallas where supported), NOT a silent degrade to
            # dense XLA attention with its [B, H, T, T] logits
            return ops.causal_attention(q, k, v, causal=causal, scale=scale,
                                        impl=None)
        return ops.causal_attention(q, k, v, causal=causal, scale=scale,
                                    impl="xla")
    if q.shape[1] % sp:
        raise ValueError(f"seq len {q.shape[1]} not divisible by "
                         f"{axis}={sp}")
    if schedule not in ("zigzag", "contiguous"):
        raise ValueError(f"schedule must be zigzag|contiguous, "
                         f"got {schedule!r}")
    if layout == "zigzag" and (not causal or q.shape[1] % (2 * sp)):
        raise ValueError("layout='zigzag' requires causal attention and "
                         f"seq len divisible by 2*{axis}={2 * sp} "
                         f"(got causal={causal}, T={q.shape[1]})")
    if layout == "zigzag" and schedule == "contiguous":
        raise ValueError("layout='zigzag' forces the zigzag schedule; "
                         "schedule='contiguous' would be silently ignored")
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"query heads {q.shape[2]} not divisible by kv "
                         f"heads {k.shape[2]}")
    # GQA: KV stays at nkv heads through the ring — the group expansion
    # happens inside the per-step einsum (_gqa_scores/_gqa_pv), so each hop
    # moves nkv/nh of the bytes a pre-expanded ring would
    comms_logger.record("ring_attention_ppermute",
                        (k.size + v.size) * k.dtype.itemsize // sp * (sp - 1),
                        axis)
    spec = P(batch_axes, axis, None, None)
    zig = (layout == "zigzag"
           or (schedule == "zigzag" and causal and q.shape[1] % (2 * sp) == 0))

    if inner == "flash":
        c = q.shape[1] // (2 * sp)
        # importlib, NOT `from deepspeed_tpu.ops import flash_attention`:
        # the package re-exports a FUNCTION of that name which shadows the
        # submodule on attribute access
        import importlib
        _fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
        # zig already encodes causal ∧ T % (2·sp) == 0 for this layout;
        # _block_sizes(c) is None for any c < 8.  Backward-pass hop bytes
        # (KV replay + dk/dv homing) are NOT booked, matching the einsum
        # inner whose autodiff backward ppermutes are likewise unbooked —
        # the logger records the forward ring only, for either inner.
        if not (zig and q.shape[3] % 8 == 0
                and _fa._block_sizes(c) is not None):
            raise ValueError(
                "inner='flash' needs the causal zig-zag schedule with "
                f"T % (2*sp) == 0, head_dim % 8 == 0, and half-chunk "
                f"c = T/(2*sp) >= 8 divisible by a flash block (got "
                f"T={q.shape[1]}, sp={sp}, d={q.shape[3]}, c={c})")

    if zig:
        @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                 out_specs=spec, check_vma=False)
        def inner_z(q_, k_, v_):
            my = lax.axis_index(axis)
            if inner == "flash":
                interp = jax.default_backend() != "tpu"
                return _zigzag_body_flash(q_, k_, v_, my, sp, axis, scale,
                                          interp)
            return _zigzag_body(q_, k_, v_, my, sp, axis, scale)

        if layout == "zigzag":
            # data already zig-zag placed: the ring hops are the ONLY wire
            return inner_z(q, k, v)

        idx, inv = _zigzag_perm(q.shape[1], sp)
        # the in/out zig-zag permutes reshard across sp — real wire traffic
        # (≈4 tensor volumes per call), booked separately from the ring hops
        comms_logger.record(
            "ring_attention_zigzag_permute",
            (q.size + k.size + v.size + q.size) * q.dtype.itemsize, axis)
        qz, kz, vz = (jnp.take(x, idx, axis=1) for x in (q, k, v))
        return jnp.take(inner_z(qz, kz, vz), inv, axis=1)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def inner(q_, k_, v_):
        my = lax.axis_index(axis)
        return _ring_body(q_, k_, v_, my, sp, axis, causal, scale)

    return inner(q, k, v)
