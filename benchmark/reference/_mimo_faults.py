"""Planted faults for the comparison with the plain reference
(``_mimo_v2.py``): the reference with ONE thing wrong, so that a reading of
the program against it shows whether the comparison would catch the program
making that mistake; and the CONTROL, the reference on the same weights
rounded to fp8 e4m3, the nearest precision below the bf16 the configuration
states, which has to read as not correct.  Used by ``tests/test_mimo_v2.py``
(tiny sizes, float32) and, through the harness's own comparison, by
``benchmark/tools/swa_compare.py --plant`` (published widths on the chip,
the cell's tolerances; ``planted_reference``).  The reference's own file
stays plain: a fault swaps one of its functions, a size or a weight for the
time of a ``with``.
"""

import contextlib
import types

import jax
import jax.numpy as jnp

import _mimo_v2 as ref
from _mla_faults import _fp8

FAULTS = ("sink_left_out", "sink_on_the_full_layers_too",
          "value_scale_left_out", "window_127", "window_129",
          "window_layers_at_the_full_base", "rotated_columns_96",
          "bias_added_to_the_weights")
CONTROL = "weights_rounded_to_fp8"
_SWAPPED = ("route", "embed", "layer", "head")


def _rounded(weights):
    return jax.tree_util.tree_map(_fp8, weights)


def _sinks_everywhere(params):
    """The tree with a sink on the layers that have none: the nearest
    sink-bearing layer's logits (a full layer has no such parameter)."""
    bb = dict(params["backbone"])
    blocks = sorted((k for k in bb if k.startswith("block_")),
                    key=lambda k: int(k[6:]))
    have = [k for k in blocks if "sink" in bb[k]["Attention_0"]]
    for k in blocks:
        if k not in have:
            near = min(have, key=lambda h: abs(int(h[6:]) - int(k[6:])))
            bb[k] = {**bb[k], "Attention_0": {
                **bb[k]["Attention_0"],
                "sink": bb[near]["Attention_0"]["sink"]}}
    return {**params, "backbone": bb}


@contextlib.contextmanager
def planted(fault, params, sizes):
    """``with planted(name, params, sizes) as (params, sizes):`` the
    arguments to hand ``ref.logits`` while the fault is in."""
    saved = {n: getattr(ref, n) for n in _SWAPPED}
    if fault == "sink_left_out":
        sizes = {**sizes, "add_swa_attention_sink_bias": False}
    elif fault == "sink_on_the_full_layers_too":
        sizes = {**sizes, "add_full_attention_sink_bias": True}
        params = _sinks_everywhere(params)
    elif fault == "value_scale_left_out":
        sizes = {**sizes, "attention_value_scale": 1.0}
    elif fault in ("window_127", "window_129"):
        sizes = {**sizes, "sliding_window": sizes["sliding_window"]
                 + (1 if fault == "window_129" else -1)}
    elif fault == "window_layers_at_the_full_base":
        sizes = {**sizes, "swa_rope_theta": sizes["rope_theta"]}
    elif fault == "rotated_columns_96":       # half the head, not a third
        sizes = {**sizes, "partial_rotary_factor": 0.5}
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
