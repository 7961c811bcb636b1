"""Planted faults for the comparison with the plain dots3-note reference
(``_dots3_note.py``): the reference with ONE thing wrong, so that a reading
of the program against it shows whether the comparison would catch the
program making that mistake; and the CONTROL, the reference on the same
weights rounded to fp8 e4m3, the nearest precision below the bf16 the
configuration states, which has to read as not correct.  Used by
``tests/test_dots3_note.py`` (tiny sizes, 1e-4) and, through the harness's
own comparison, by ``benchmark/tools/dsa_compare.py --plant`` (published
widths on the chip, the cell's tolerances; ``planted_reference``).  The
reference's own file stays plain: a fault swaps one of its functions, or a
size, for the time of a ``with``.
"""

import contextlib
import types

import jax
import jax.numpy as jnp

import _dots3_note as ref
from _mla_faults import _fp8

FAULTS = ("selection_ignored", "index_weights_dropped",
          "index_key_not_normed", "index_rope_on_trailing_columns",
          "sliding_layer_at_the_full_layers_rope_base",
          "lora_rescale_dropped", "gate_dropped", "window_512")
CONTROL = "weights_rounded_to_fp8"
_SWAPPED = ("select", "_index_weights", "_layer_norm", "_index_rope",
            "geometry", "_gate", "embed", "layer", "head")


def _rounded(weights):
    return jax.tree_util.tree_map(_fp8, weights)


@contextlib.contextmanager
def planted(fault, params, sizes):
    """``with planted(name, params, sizes) as (params, sizes):`` the
    arguments to hand ``ref.logits`` while the fault is in."""
    saved = {n: getattr(ref, n) for n in _SWAPPED}
    if fault == "selection_ignored":       # every causal key attended over
        ref.select = lambda scores, seen, k: seen
    elif fault == "index_weights_dropped":
        def weights(p, a):
            n, d = p["wq_idx"].shape[1:]
            return jnp.full((a.shape[0], n), n ** -0.5 * d ** -0.5, ref.F32)
        ref._index_weights = weights
    elif fault == "index_key_not_normed":
        ref._layer_norm = lambda x, scale, bias: x
    elif fault == "index_rope_on_trailing_columns":
        def rope_last(x, pos, g):
            cut = x.shape[-1] - g.rope_dim
            return jnp.concatenate(
                [x[..., :cut], ref._rope(x[..., cut:], pos, g.theta)], -1)
        ref._index_rope = rope_last
    elif fault == "sliding_layer_at_the_full_layers_rope_base":
        def geometry(sz, kind):
            g = saved["geometry"](sz, kind)
            return g._replace(theta=float(sz["rope_theta"])) \
                if kind == "sliding" else g
        ref.geometry = geometry
    elif fault == "lora_rescale_dropped":
        sizes = {**sizes, "apply_mla_qkv_lora_rescale": False}
    elif fault == "gate_dropped":
        ref._gate = lambda p, a: jnp.ones(
            (a.shape[0], p["w_gate_attn"].shape[1]), ref.F32)
    elif fault == "window_512":
        sizes = {**sizes,
                 "sliding_window_size": sizes["sliding_window_size"] - 1}
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
        routing=ref.routing, selections=ref.selections, tree=ref.tree)
