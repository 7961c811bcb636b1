#!/usr/bin/env python3
"""Step 0 of the expert weight stream (PR 45, PR 51), on the chip: one
expert layer's three products at the ten shapes the five MoE cells run (a
decode and a mixed step each), as ``lax.ragged_dot``, as megablox's ``gmm``
from the installed jax at a few tilings, and as this repo's kernel
(``ops/grouped_gemm.py``) at every row tile its shape rule could choose,
each as milliseconds, as GB/s of the weights of the experts that have rows
and, for the kernels, as rows multiplied a live row; beside them the other
ops of the ``moe_experts`` scope, so that PERF.md can say what share of
``*_moe_experts_ms`` the GEMMs are: the three the layer ran until PR 56 (the
sort, the ``tokens[tok_rows]`` gather, the weighted scatter-add behind its
mask pass) and the forms PR 56 chose among (an assignment's position from a
blockwise count, from ``cumsum``, from ``argsort`` and its inverse; ``order``
as an int32 scatter; the combine as a gather and a float32 sum over ``k``),
each alone and as the whole dispatch-to-combine chain without the GEMMs.
Those rows carry two times: ``ms``, one dispatch a call as the products are
timed (every call carries ~0.2 ms of launch), and ``ms_in_program``, eight
layers' worth on eight different routings unrolled in one program, an
eighth of it (nearer what a layer of a step program pays; its own floor is
~0.09 ms, and a traced step's op list, ``scripts/step0_moe_scope_ops.py``,
is the reading without one).  The routing is seeded and SKEWED (an expert's
popularity is log-normal): uniform group sizes straddle fewer row tiles than
a model's.  ``--parent <checkout>`` times that checkout's
``ops/grouped_gemm.py`` beside this one at the rule's row tile, in the same
call.

    git archive HEAD | tar -x -C _parent        # _parent/ is git-ignored
    chiprun --timeout 1800 -- python3 scripts/step0_grouped_gemm.py --parent _parent
    chiprun -- python3 scripts/step0_grouped_gemm.py --skip-ffn   # the permutation alone, ~3 min
    JAX_PLATFORMS=cpu python3 scripts/step0_grouped_gemm.py --tiny   # here

Writes ``chiprun_out/pr56/step0.jsonl`` (one line a timing) and
``step0.md`` (the table ``ops/grouped_gemm.py`` quotes; PR 51's is
``chiprun_out/pr51/step0.md``).
"""

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu.ops  # noqa: F401  (the package's function shadows the module's name)
from deepspeed_tpu.moe import layer

gg = sys.modules["deepspeed_tpu.ops.grouped_gemm"]

# cell, step, slots or tokens S, k, experts routed over, held, H, M
SHAPES = [
    ("moonlight", "decode", 48, 6, 64, 64, 2048, 1408),
    ("moonlight", "mixed", 1024, 6, 64, 64, 2048, 1408),
    ("trinity", "decode", 16, 4, 256, 32, 3072, 3072),
    ("trinity", "mixed", 1024, 4, 256, 32, 3072, 3072),
    ("dots3", "decode", 16, 8, 256, 32, 5120, 1536),
    ("dots3", "mixed", 1024, 8, 256, 32, 5120, 1536),
    ("lfm2", "decode", 64, 4, 64, 64, 2048, 1536),
    ("lfm2", "mixed", 2048, 4, 64, 64, 2048, 1536),
    ("xing4", "decode", 64, 4, 64, 64, 3584, 1024),
    ("xing4", "mixed", 1024, 4, 64, 64, 3584, 1024),
]
TINY = [("tiny", "decode", 8, 2, 8, 4, 128, 256),
        ("tiny", "mixed", 64, 2, 8, 4, 128, 256)]
LAYERS = 8      # routings unrolled in one program for ``ms_in_program``


def timed(fn, *args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def in_program(fn, *stacked, reps):
    """Milliseconds a layer of ``fn`` where ``LAYERS`` of them, each on its
    own slice of the stacked arguments, are one program."""
    return timed(jax.jit(lambda *st: [fn(*(a[i] for a in st))
                                      for i in range(LAYERS)]),
                 *stacked, reps=reps) / LAYERS


def route(rng, S, k, routed, held):
    """``k`` distinct experts of ``routed`` a token, an expert's popularity
    log-normal (sigma 0.5: the most popular of 64 draws ~7 times the rows
    of the least; the Gumbel top-k draws without replacement); the held
    share is a random ``held`` of them, numbered first.  -> expert ids
    [S, k] with the sentinel ``held`` where the expert is not here."""
    logits = 0.5 * rng.standard_normal(routed) + rng.gumbel(size=(S, routed))
    ids = rng.permutation(routed)[np.argsort(-logits, axis=1)[:, :k]]
    return np.where(ids < held, ids, held).astype(np.int32)


def rows_multiplied(sizes, tm, sub, by_expert):
    """Rows of ``tm``-row tiles a kernel multiplies for these group sizes:
    ``by_expert`` tiles from each group's own first row (rounded down to the
    sublane packing), else the fixed tiles of the buffer a group overlaps."""
    ends = np.cumsum(sizes)
    starts = ends - sizes
    has = sizes > 0
    if by_expert:
        tiles = -(-(ends - starts // sub * sub) // tm)
    else:
        tiles = (ends - 1) // tm - starts // tm + 1
    return int(tiles[has].sum()) * tm


def corners(out):
    """The kernel COMPILED (on the chip: tests/test_grouped_gemm.py holds
    the same corners interpreted) where a grid could go wrong: no row at
    all, one group holding every row, a NaN tail behind the last group,
    groups that cross row tiles; against ``lax.ragged_dot`` on the live
    rows.  One line a case to ``corners.jsonl``; any miss raises."""
    rng = np.random.default_rng(0)
    K, N, dtype = 256, 11 * 128, jnp.bfloat16
    with open(os.path.join(out, "corners.jsonl"), "w") as log:
        for A, G, kind in [(64, 32, "empty"), (64, 32, "sparse"),
                           (288, 64, "one_group"), (288, 64, "tail"),
                           (4096, 32, "tail"), (4096, 64, "crossing")]:
            sizes = np.zeros(G, int)
            if kind == "sparse":
                sizes[rng.permutation(G)[:5]] = [1, 3, 17, 2, 9]
            elif kind == "one_group":
                sizes[G // 2] = A
            elif kind == "tail":
                sizes = rng.multinomial(A // 3, np.ones(G) / G)
            elif kind == "crossing":
                sizes = rng.multinomial(A - G, np.ones(G) / G) + 1
            live = int(sizes.sum())
            ks = jax.random.split(jax.random.PRNGKey(A + G), 4)
            x = jax.random.normal(ks[0], (A, K), dtype).at[live:].set(jnp.nan)
            wi, wg = ((jax.random.normal(k, (G, K, N)) * K ** -0.5).astype(
                dtype) for k in ks[1:3])
            wo = (jax.random.normal(ks[3], (G, N, K)) * N ** -0.5).astype(dtype)
            gs = jnp.asarray(sizes, jnp.int32)
            got = jax.jit(lambda x, s: gg.pallas_grouped_gemm(
                gg.pallas_grouped_gemm(x, wi, s, wg), wo, s))(x, gs)
            want = jax.jit(lambda x, s: gg.xla_grouped_gemm(
                gg.xla_grouped_gemm(x, wi, s, wg), wo, s))(
                    x.at[live:].set(0), gs)
            a = np.asarray(got[:live], np.float32)
            b = np.asarray(want[:live], np.float32)
            err = float(np.sqrt(((a - b) ** 2).mean() / (b ** 2).mean())
                        ) if live else 0.0
            line = dict(A=A, groups=G, kind=kind, live=live,
                        tm=gg._row_tile(A, G, 2), rel_rms=round(err, 5),
                        finite=bool(np.isfinite(a).all()))
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")
            assert line["finite"] and err < 2e-2, line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--corners", action="store_true",
                    help="only the corner cases, compiled, against ragged_dot")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--skip-ffn", action="store_true",
                    help="only the permutation's ops, not the products")
    ap.add_argument("--out", default="chiprun_out/pr56")
    ap.add_argument("--parent", help="a checkout whose ops/grouped_gemm.py "
                    "is timed beside this one's")
    args = ap.parse_args()
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_grouped_gemm", os.path.join(
                args.parent, "deepspeed_tpu", "ops", "grouped_gemm.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    os.makedirs(args.out, exist_ok=True)
    if args.corners:
        return corners(args.out)
    lines = []
    peak = 819.0
    dtype = jnp.bfloat16
    shapes = TINY if args.tiny else SHAPES
    reps = 2 if args.tiny else args.reps
    interpret = jax.default_backend() != "tpu"

    log = open(os.path.join(args.out, "step0.jsonl"), "w")

    def emit(**kw):
        kw = {k: (round(v, 4) if isinstance(v, float) else v)
              for k, v in kw.items()}
        lines.append(kw)
        print(json.dumps(kw), flush=True)
        log.write(json.dumps(kw) + "\n")
        log.flush()

    weights = {}
    for cell, step, S, k, routed, held, H, M in shapes:
        key = (held, H, M)
        if key not in weights:
            weights.clear()
            ks = jax.random.split(jax.random.PRNGKey(len(key)), 3)
            mk = jax.jit(lambda kk, shape, s: (
                jax.random.normal(kk, shape, jnp.float32) * s).astype(dtype),
                static_argnums=(1, 2))
            weights[key] = (mk(ks[0], (held, H, M), H ** -0.5),
                            mk(ks[1], (held, H, M), H ** -0.5),
                            mk(ks[2], (held, M, H), M ** -0.5))
        wi, wg, wo = weights[key]
        rng = np.random.default_rng(S * k)
        ids = route(rng, S, k, routed, held)
        A = S * k
        flat = jnp.asarray(ids.reshape(-1))
        sizes = jnp.zeros((held,), jnp.int32).at[flat].add(1, mode="drop")
        live = int(sizes.sum())
        touched = int((sizes > 0).sum())
        gb = touched * 3 * H * M * 2 / 1e9
        base = dict(cell=cell, step=step, A=A, live=live, groups=held,
                    touched=touched, K=H, N=M, weights_gb=round(gb, 4),
                    floor_ms=round(gb / peak * 1e3, 4))

        # the scope's other ops: ``LAYERS`` routings of the shape, a form
        # alone on the first and all of them unrolled in one program
        flats = jnp.stack([flat] + [jnp.asarray(route(
            rng, S, k, routed, held).reshape(-1)) for _ in range(LAYERS - 1)])
        toks = jax.random.normal(jax.random.PRNGKey(1), (LAYERS, S, H), dtype)
        o_rows = jax.random.normal(jax.random.PRNGKey(2), (LAYERS, A, H), dtype)
        tws = jnp.asarray(rng.random((LAYERS, S, k)), jnp.float32)
        tokens = toks[0]
        iota = jnp.arange(A, dtype=jnp.int32)
        G = held + 1                            # the sentinel's column too
        whole = routed == held                  # no assignment is dropped

        def both(what, fn, *stacked):
            emit(**base, what=what,
                 ms=timed(jax.jit(fn), *(a[0] for a in stacked), reps=reps),
                 ms_in_program=in_program(fn, *stacked, reps=reps))

        def invert(perm):
            return jnp.zeros((A,), jnp.int32).at[perm].set(
                iota, unique_indices=True)

        def pos_count(f):
            return layer._positions_by_count(f, G)

        def pos_cumsum(f):
            hot = f[:, None] == jnp.arange(G, dtype=f.dtype)
            upto = jnp.cumsum(hot.astype(jnp.int32), axis=0)
            sizes = upto[-1]
            starts = jnp.cumsum(sizes) - sizes
            return jnp.sum(jnp.where(hot, upto - 1 + starts, 0), -1), sizes

        def pos_argsort(f):
            order = jnp.argsort(f)
            sizes = jnp.zeros((G,), jnp.int32).at[f].add(1, mode="drop")
            return invert(order), sizes, order

        def gather_rows(t, o):
            return t[jnp.repeat(jnp.arange(S), k)[o]]

        def scatter_add(o, w, order, tok_rows, n):
            done = iota < n
            o = jnp.where(done[:, None], o, 0)
            tr = jnp.where(done, tok_rows, S)
            ww = w.reshape(-1)[order].astype(o.dtype)
            return jnp.zeros((S, H), o.dtype).at[tr].add(o * ww[:, None],
                                                         mode="drop")

        def combine(o, w, dest, f):
            # as moe/layer.py: a gather of [S, H] a choice, summed in float32
            dest, keep = dest.reshape(S, k), (f < held).reshape(S, k)
            out = 0.0
            for j in range(k):
                got = o[dest[:, j]].astype(jnp.float32) * w[:, j:j + 1]
                out = out + (got if whole else
                             jnp.where(keep[:, j:j + 1], got, 0))
            return out.astype(o.dtype)

        def combine_one_gather(o, w, dest, f):
            # ONE gather of all S*k rows, reshaped [S, k, H] and summed
            got = (o[dest].reshape(S, k, H).astype(jnp.float32)
                   * w[..., None])
            if not whole:
                got = jnp.where((f < held).reshape(S, k, 1), got, 0)
            return jnp.sum(got, axis=1).astype(o.dtype)

        def chain_parent(t, f, w):
            order = jnp.argsort(f)
            tok_rows = jnp.repeat(jnp.arange(S), k)[order]
            sizes = jnp.zeros((held,), jnp.int32).at[f].add(1, mode="drop")
            return scatter_add(t[tok_rows], w, order, tok_rows,
                               jnp.sum(sizes)), sizes

        def chain(positions):
            def run(t, f, w):
                dest, sizes, *order = positions(f)
                order = order[0] if order else invert(dest)
                return combine(t[order // k], w, dest, f), sizes[:held]
            return run

        def pos_count_argsort(f):
            return (*pos_count(f), jnp.argsort(f))

        orders = jax.jit(jax.vmap(jnp.argsort))(flats)
        dests = jax.jit(jax.vmap(invert))(orders)
        tok_rows = jnp.repeat(jnp.arange(S), k)[orders]
        n_local = jnp.sum(flats < held, axis=1)
        both("argsort", jnp.argsort, flats)
        both("gather", gather_rows, toks, orders)
        both("scatter_add", scatter_add, o_rows, tws, orders, tok_rows,
             n_local)
        both("positions_count", pos_count, flats)
        both("positions_cumsum", pos_cumsum, flats)
        both("positions_argsort_inverse", pos_argsort, flats)
        both("order_scatter", invert, dests)
        both("combine_gather", combine, o_rows, tws, dests, flats)
        both("combine_one_gather", combine_one_gather, o_rows, tws, dests,
             flats)
        both("chain_parent", chain_parent, toks, flats, tws)
        both("chain_count_scatter", chain(pos_count), toks, flats, tws)
        both("chain_cumsum_scatter", chain(pos_cumsum), toks, flats, tws)
        both("chain_count_argsort", chain(pos_count_argsort), toks, flats, tws)
        both("chain_argsort_inverse", chain(pos_argsort), toks, flats, tws)
        want = np.asarray(jax.jit(chain_parent)(tokens, flat, tws[0])[0],
                          np.float32)
        for name, positions in (("count", pos_count), ("cumsum", pos_cumsum),
                                ("argsort", pos_argsort)):
            dest, sz, *_ = jax.jit(positions)(flat)
            got = np.asarray(jax.jit(chain(positions))(
                tokens, flat, tws[0])[0], np.float32)
            emit(**base, what="check_chain", positions=name,
                 dest_is_the_stable_sorts=bool(
                     (np.asarray(dest) == np.asarray(dests[0])).all()),
                 sizes_equal=bool((np.asarray(sz[:held])
                                   == np.asarray(sizes)).all()),
                 max_abs_from_parent=float(np.abs(got - want).max()))
        if args.skip_ffn:
            continue
        rows = tokens[tok_rows[0]]

        # the three products
        # the weights are ARGUMENTS: closed over, each compile would copy
        # a gigabyte of constants to the host
        def ffn_xla(x, s, wi, wg, wo):
            h = gg.xla_grouped_gemm(x, wi, s, wg)
            return gg.xla_grouped_gemm(h, wo, s)
        ws = (wi, wg, wo)
        ms_x = timed(jax.jit(ffn_xla), rows, sizes, *ws, reps=reps)
        emit(**base, what="ffn", impl="ragged_dot", ms=ms_x,
             gbps=gb / ms_x * 1e3)
        ms1 = timed(jax.jit(lambda x, s, w: jax.lax.ragged_dot(x, w, s)),
                    rows, sizes, wi, reps=reps)
        emit(**base, what="one_product", impl="ragged_dot", ms=ms1,
             gbps=gb / 3 / ms1 * 1e3)

        from jax.experimental.pallas.ops.tpu.megablox import gmm as mbx_gmm
        for tiling in ([(128, 128, 128), (128, 512, 512), (128, 1024, 512),
                        (128, H, 128)] if not args.tiny else [(8, 128, 128)]):
            if A % tiling[0]:
                continue

            def ffn_mbx(x, s, wi, wg, wo, tiling=tiling):
                kw = dict(preferred_element_type=dtype, tiling=tiling,
                          interpret=interpret)
                h = jax.nn.silu(mbx_gmm(x, wg, s, **kw)) * mbx_gmm(
                    x, wi, s, **kw)
                return mbx_gmm(h, wo, s, **kw)
            try:
                ms = timed(jax.jit(ffn_mbx), rows, sizes, *ws, reps=reps)
                emit(**base, what="ffn", impl="megablox", tiling=tiling,
                     ms=ms, gbps=gb / ms * 1e3)
            except Exception as exc:  # a tiling the compiler refuses
                emit(**base, what="ffn", impl="megablox", tiling=tiling,
                     error=repr(exc)[:300])

        it = jnp.dtype(dtype).itemsize
        rule = gg._row_tile(A, held, it)
        np_sizes = np.asarray(sizes)
        for impl, mod in (("parent", parent), ("pallas", gg)):
            for tm in (16, 32, 64, 128, 256):
                if mod is None or A % tm or (mod is parent and tm != rule):
                    continue

                def ffn_pl(x, s, wi, wg, wo, tm=tm, mod=mod):
                    h = mod.pallas_grouped_gemm(x, wi, s, wg, tm=tm)
                    return mod.pallas_grouped_gemm(h, wo, s, tm=tm)
                note = dict(impl=impl, tm=tm, chosen=tm == rule, tn=[
                    mod._col_tile(H, M, it, 2), mod._col_tile(M, H, it, 1)])
                try:
                    ms = timed(jax.jit(ffn_pl), rows, sizes, *ws, reps=reps)
                    emit(**base, what="ffn", **note, ms=ms,
                         gbps=gb / ms * 1e3, us_per_expert=ms * 1e3 / touched,
                         rows_per_live_row=rows_multiplied(
                             np_sizes, tm, 32 // it, mod is gg) / max(live, 1))
                except Exception as exc:
                    emit(**base, what="ffn", **note, error=repr(exc)[:300])
        got = jax.jit(lambda x, s, wi, wg, wo: gg.pallas_grouped_gemm(
            gg.pallas_grouped_gemm(x, wi, s, wg), wo, s))(rows, sizes, *ws)
        want = jax.jit(ffn_xla)(rows, sizes, *ws)
        a = np.asarray(got[:live], np.float32)
        b = np.asarray(want[:live], np.float32)
        emit(**base, what="check", rel_rms=float(
            np.sqrt(((a - b) ** 2).mean() / max((b ** 2).mean(), 1e-30))),
            finite=bool(np.isfinite(a).all()))

    log.close()
    with open(os.path.join(args.out, "step0.md"), "w") as f:
        f.write("| cell | step | A | live | touched | floor ms | what | "
                "impl | tiling | ms | ms in program | GB/s | us an expert | "
                "rows multiplied a live row |\n|" + " --- |" * 14 + "\n")
        for ln in lines:
            if "ms" not in ln:
                continue
            tiling = ln.get("tiling") or (
                f"tm {ln['tm']}{' (rule)' if ln.get('chosen') else ''}"
                if "tm" in ln else "")
            f.write(f"| {ln['cell']} | {ln['step']} | {ln['A']} | "
                    f"{ln['live']} | {ln['touched']} | {ln['floor_ms']} | "
                    f"{ln['what']} | {ln.get('impl', '')} | {tiling} | "
                    f"{ln['ms']} | {ln.get('ms_in_program', '')} | "
                    f"{ln.get('gbps', '')} | "
                    f"{ln.get('us_per_expert', '')} | "
                    f"{ln.get('rows_per_live_row', '')} |\n")
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "lines": len(lines)}))


if __name__ == "__main__":
    main()
