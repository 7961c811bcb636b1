"""Planted faults for the comparison with the plain LFM2-MoE reference
(``_lfm2_moe.py``): the reference with ONE thing wrong, each a mistake a
serving engine with a carried conv tail, or a reading of the family's
equations, can make, so that a reading of the program against it shows
whether the comparison would catch the program making that mistake; and the
CONTROL, the reference on the same weights rounded to fp8 e4m3, the nearest
precision below the bf16 the configuration states, which has to read as not
correct.  Used by ``tests/test_lfm2_moe.py`` (tiny sizes) and, through the
harness's own comparison, by ``benchmark/tools/conv_compare.py --plant``
(published widths on the chip, the cell's tolerances;
``planted_reference``).  The reference's own file stays plain: a fault swaps
one of its functions for the time of a ``with``.

The fault of a boundary needs to know where the program's boundaries fall: a
forward takes at most ``run.state_manager.max_q_per_seq`` rows of a
sequence.  The fault of a reused slot reads, before position 0, what a slot
would still hold of an owner like this sequence: the sequence's own last
rows.
"""

import contextlib
import types

import jax
import jax.numpy as jnp

import _lfm2_moe as ref
from _mla_faults import _fp8

FAULTS = ("conv_tail_dropped_at_a_forward_boundary",
          "tail_not_zeroed_on_a_reused_slot", "b_gate_left_out",
          "c_gate_left_out", "qk_norms_left_out", "rope_left_out",
          "bias_added_to_the_weights", "silu_after_the_conv")
CONTROL = "weights_rounded_to_fp8"
_SWAPPED = ("_conv", "_gates", "_qk", "route", "embed", "layer", "head")


def _rounded(weights):
    return jax.tree_util.tree_map(_fp8, weights)


@contextlib.contextmanager
def planted(fault, params, sizes):
    """``with planted(name, params, sizes) as (params, sizes):`` the
    arguments to hand ``ref.logits`` while the fault is in."""
    saved = {n: getattr(ref, n) for n in _SWAPPED}
    if fault == "conv_tail_dropped_at_a_forward_boundary":
        every = int(sizes["run"]["state_manager"]["max_q_per_seq"])

        def conv(g, w):               # a tap from before the row's forward
            T, K = g.shape[0], w.shape[1]           # began reads zero
            padded = jnp.concatenate(
                [jnp.zeros((K - 1, g.shape[1]), ref.F32), g])
            t = jnp.arange(T)
            return sum(jnp.where(
                ((t - (K - 1 - j)) >= t // every * every)[:, None],
                padded[j:j + T], 0.0) * w[:, j] for j in range(K))
        ref._conv = conv
    elif fault == "tail_not_zeroed_on_a_reused_slot":
        def conv(g, w):               # before position 0: a last owner's rows
            T, K = g.shape[0], w.shape[1]
            padded = jnp.concatenate([g[T - (K - 1):], g])
            return sum(padded[j:j + T] * w[:, j] for j in range(K))
        ref._conv = conv
    elif fault == "b_gate_left_out":
        def gates(bcx):
            b, c, x = saved["_gates"](bcx)
            return jnp.ones_like(b), c, x
        ref._gates = gates
    elif fault == "c_gate_left_out":
        def gates(bcx):
            b, c, x = saved["_gates"](bcx)
            return b, jnp.ones_like(c), x
        ref._gates = gates
    elif fault == "qk_norms_left_out":
        ref._qk = lambda p, q, k, pos, eps, theta: (
            ref._rope(q, pos, theta), ref._rope(k, pos, theta))
    elif fault == "rope_left_out":
        ref._qk = lambda p, q, k, pos, eps, theta: (
            ref._rms(q, p["qn"], eps), ref._rms(k, p["kn"], eps))
    elif fault == "bias_added_to_the_weights":
        def route(m, router, bias, k, route_norm, route_scale):
            s = jax.nn.sigmoid(m @ router.astype(ref.F32)) \
                + bias.astype(ref.F32)               # ... and stays in
            top, chosen = jax.lax.top_k(s, k + 1)
            chosen = chosen[:, :k]
            return (chosen, ref._weights(s, chosen, route_norm, route_scale),
                    top[:, k - 1] - top[:, k])
        ref.route = route
    elif fault == "silu_after_the_conv":
        ref._conv = lambda g, w: jax.nn.silu(saved["_conv"](g, w))
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
    try:
        yield params, sizes
    finally:
        for n, fn in saved.items():
            setattr(ref, n, fn)
        ref.layer.clear_cache()


def planted_reference(fault):
    """A reference module for the harness (``run.py``'s ``ctx["reference"]``)
    whose ``logits`` carry ``fault``: the runner's own comparison then says
    whether the cell's limits catch it."""
    def logits(params, tokens, sizes, rows=None):
        with planted(fault, params, sizes) as (bad_params, bad_sizes):
            return ref.logits(bad_params, tokens, bad_sizes, rows=rows)
    return types.SimpleNamespace(
        logits=logits, program_config=ref.program_config, tree=ref.tree,
        routing=ref.routing)
