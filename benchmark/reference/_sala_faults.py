"""Planted faults for the comparison with the plain MiniCPM-SALA reference
(``_minicpm_sala.py``): the reference with ONE thing wrong, each a mistake a
serving engine with a block selection over pooled keys and a carried matrix
state can make, so that a reading of the program against it shows whether the
comparison would catch the program making that mistake; and the CONTROL, the
reference on the same weights rounded to fp8 e4m3, the nearest precision
below the bf16 the configuration states, which has to read as not correct
(and a second control of the one thing the configuration holds in float32,
``state_rounded_to_bf16``).  Used by ``tests/test_minicpm_sala.py`` (tiny
sizes) and, through the harness's own comparison, by
``benchmark/tools/sala_compare.py --plant`` (published widths on the chip, the
cell's tolerances; ``planted_reference``).  The reference's own file stays
plain: a fault swaps one of its functions, or a size, for the time of a
``with``.
"""

import contextlib
import types

import jax
import jax.numpy as jnp

import _minicpm_sala as ref
from _mla_faults import _fp8

FAULTS = ("63_blocks_kept", "local_run_one_block_short",
          "initial_block_dropped", "sum_over_a_blocks_pooled_keys",
          "one_kv_heads_selection_for_both", "dense_path_past_dense_len",
          "rope_left_off_the_lightning_layers",
          "rope_on_the_attention_layers", "decay_a_head_off",
          "state_rounded_to_bf16")
CONTROL = "weights_rounded_to_fp8"
_SWAPPED = ("_block_reduce", "_heads_choice", "_decay", "_recurrence",
            "embed", "mixer", "feed_forward", "head")


def _rounded(weights):
    return jax.tree_util.tree_map(_fp8, weights)


def _sparse(sizes, **over):
    return {**sizes, "sparse_config": {**sizes["sparse_config"], **over}}


@contextlib.contextmanager
def planted(fault, params, sizes):
    """``with planted(name, params, sizes) as (params, sizes):`` the
    arguments to hand ``ref.logits`` while the fault is in."""
    saved = {n: getattr(ref, n) for n in _SWAPPED}
    sp = sizes["sparse_config"]
    if fault == "63_blocks_kept":
        sizes = _sparse(sizes, topk=sp["topk"] - 1)
    elif fault == "local_run_one_block_short":
        sizes = _sparse(sizes, window_size=sp["window_size"]
                        - sp["block_size"])
    elif fault == "initial_block_dropped":
        sizes = _sparse(sizes, init_blocks=0)
    elif fault == "sum_over_a_blocks_pooled_keys":
        def block_sum(P, first, last):
            j = jnp.arange(P.shape[1])
            over = ((j[None, :] >= first[:, None])
                    & (j[None, :] <= last[:, None]))
            return jnp.sum(jnp.where(over[None], P[:, None, :], 0.0), -1)
        ref._block_reduce = block_sum
    elif fault == "one_kv_heads_selection_for_both":
        ref._heads_choice = lambda keeps: [keeps[0]] * len(keeps)
    elif fault == "dense_path_past_dense_len":
        sizes = _sparse(sizes, dense_len=1 << 30)
    elif fault == "rope_left_off_the_lightning_layers":
        sizes = {**sizes, "lightning_use_rope": False}
    elif fault == "rope_on_the_attention_layers":
        sizes = {**sizes, "attn_use_rope": True}
    elif fault == "decay_a_head_off":      # head h under head h + 1's decay
        ref._decay = lambda heads: jnp.roll(saved["_decay"](heads), -1)
    elif fault == "state_rounded_to_bf16":
        # the second control: the state held in the precision below the
        # float32 the configuration states, rounded after every position
        # (by reduce_precision: the chip's compiler elides a convert to
        # bfloat16 and back)
        def recurrence(q, k, v, lam):
            def step(S, row):
                q_t, k_t, v_t = row
                S = (lam[:, None, None] * S
                     + k_t[:, :, None] * v_t[:, None, :])
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
                return S, jnp.einsum("hk,hkv->hv", q_t, S)
            S0 = jnp.zeros((q.shape[1], k.shape[2], v.shape[2]), ref.F32)
            return jax.lax.scan(step, S0, (q, k, v))[1]
        ref._recurrence = recurrence
    elif fault == CONTROL:
        # every weight, rounded where the reference takes it up: half a
        # layer's at a time, so no second copy of the tree lies beside an
        # engine
        ref.embed = lambda table, tokens, **kw: saved["embed"](
            _rounded(table), tokens, **kw)
        ref.mixer = lambda p, x, **kw: saved["mixer"](_rounded(p), x, **kw)
        ref.feed_forward = lambda p, x, **kw: saved["feed_forward"](
            _rounded(p), x, **kw)
        ref.head = lambda norm, w, x, **kw: saved["head"](
            _rounded(norm), _rounded(w), x, **kw)
    else:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS} and "
                         f"{CONTROL!r}")
    saved["mixer"].clear_cache()
    try:
        yield params, sizes
    finally:
        for n, fn in saved.items():
            setattr(ref, n, fn)
        ref.mixer.clear_cache()


def planted_reference(fault):
    """A reference module for the harness (``run.py``'s ``ctx["reference"]``)
    whose ``logits`` carry ``fault``: the runner's own comparison then says
    whether the cell's limits catch it."""
    def logits(params, tokens, sizes, rows=None):
        with planted(fault, params, sizes) as (bad_params, bad_sizes):
            return ref.logits(bad_params, tokens, bad_sizes, rows=rows)
    return types.SimpleNamespace(
        logits=logits, program_config=ref.program_config, tree=ref.tree)
