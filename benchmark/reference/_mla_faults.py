"""Planted faults for the comparison with the plain reference
(``_deepseek_mla.py``): the reference with ONE thing wrong, so that a reading
of the program against it shows whether the comparison would catch the
program making that mistake; and the CONTROL, the reference on the same
weights rounded to fp8 e4m3, the nearest precision below the bf16 the
configuration states, which has to read as not correct.  Used by
``tests/test_moonlight.py`` (tiny sizes, 2e-5) and, through the harness's
own comparison, by ``benchmark/tools/mla_compare.py --plant`` (published
widths on the chip, the cell's tolerances; ``planted_reference``).  The
reference's own file stays plain: a fault swaps one of its functions, or a
weight, for the time of a ``with``.
"""

import contextlib
import types

import jax
import jax.numpy as jnp

import _deepseek_mla as ref

FAULTS = ("no_kv_a_layernorm", "scale_from_nope_width",
          "key_part_rotated_per_head", "no_routed_scaling_factor",
          "shared_experts_left_out", "bias_added_to_the_weights")
CONTROL = "weights_rounded_to_fp8"


@jax.jit
def _fp8(a):
    """``a`` rounded to the nearest float8 e4m3fn value (3 mantissa bits,
    normals from 2**-6, subnormal step 2**-9, largest 448), in ``a``'s own
    type, by arithmetic: a convert to float8 and back is a round trip the
    TPU compiler keeps in bf16 (the v5e has no fp8 type), and the first
    reading taken that way on the chip came out equal to the healthy one to
    the last digit."""
    x = a.astype(ref.F32)
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 2.0 ** -6)))
    step = jnp.exp2(e - 3.0)
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0).astype(
        a.dtype)


def _rounded(weights):
    return jax.tree_util.tree_map(_fp8, weights)


def _zero_shared(params):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if "shared_wo" in jax.tree_util.keystr(path) else a, params)


@contextlib.contextmanager
def planted(fault, params, sizes):
    """``with planted(name, params, sizes) as (params, sizes):`` the
    arguments to hand ``ref.logits`` while the fault is in."""
    saved = {n: getattr(ref, n) for n in ("_rms", "_rope", "route",
                                          "_attention_half", "embed",
                                          "layer", "head")}
    rank, heads = sizes["kv_lora_rank"], sizes["num_attention_heads"]
    if fault == "no_kv_a_layernorm":
        def rms(x, scale, eps):            # the latent goes on un-normalised
            if x.shape[-1] == rank != sizes["hidden_size"]:
                return x * scale.astype(ref.F32)
            return saved["_rms"](x, scale, eps)
        ref._rms = rms
    elif fault == "scale_from_nope_width":
        def attention(p, x, eps, theta, rope_dim):   # 128 ** -0.5, not 192
            d = p["wq"].shape[-1]
            up = (d / (d - rope_dim)) ** 0.5
            return saved["_attention_half"](
                {**p, "wq": p["wq"].astype(ref.F32) * up}, x, eps, theta,
                rope_dim)
        ref._attention_half = attention
    elif fault == "key_part_rotated_per_head":
        def rope(x, pos, theta):           # the one shared key part taken
            if x.ndim == 2:                # for a rope part a head
                t, d = x.shape
                return saved["_rope"](x.reshape(t, heads, d // heads), pos,
                                      theta).reshape(t, d)
            return saved["_rope"](x, pos, theta)
        ref._rope = rope
    elif fault == "no_routed_scaling_factor":
        sizes = {**sizes, "routed_scaling_factor": 1.0}
    elif fault == "shared_experts_left_out":
        params = _zero_shared(params)
    elif fault == "bias_added_to_the_weights":
        def route(m, router, bias, k, norm_topk, scale):
            s = jax.nn.sigmoid(m @ router.astype(ref.F32)) \
                + bias.astype(ref.F32)
            top, chosen = jax.lax.top_k(s, k + 1)
            w = top[:, :k]
            if norm_topk:
                w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
            return chosen[:, :k], w * scale, top[:, k - 1] - top[:, k]
        ref.route = route
    elif fault == CONTROL:
        # every weight, rounded where the reference takes it up: a layer's
        # at a time, so no second copy of the tree lies beside an engine
        ref.embed = lambda table, tokens: saved["embed"](_rounded(table),
                                                         tokens)
        ref.layer = lambda p, x, **kw: saved["layer"](_rounded(p), x, **kw)
        ref.head = lambda norm, w, x, **kw: saved["head"](
            _rounded(norm), _rounded(w), x, **kw)
    else:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS} and "
                         f"{CONTROL!r}")
    saved["layer"].clear_cache()
    ref.layer_routing.clear_cache()
    try:
        yield params, sizes
    finally:
        for n, fn in saved.items():
            setattr(ref, n, fn)
        ref.layer.clear_cache()
        ref.layer_routing.clear_cache()


def planted_reference(fault):
    """A reference module for the harness (``run.py``'s ``ctx["reference"]``)
    whose ``logits`` carry ``fault``: the runner's own comparison then says
    whether the cell's limits catch it."""
    def logits(params, tokens, sizes, rows=None):
        with planted(fault, params, sizes) as (bad_params, bad_sizes):
            return ref.logits(bad_params, tokens, bad_sizes, rows=rows)
    return types.SimpleNamespace(
        logits=logits, program_config=ref.program_config,
        routing=ref.routing, tree=ref.tree)
