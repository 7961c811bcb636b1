"""Planted faults for the comparison with the plain Granite-4.0-H reference
(``_granite_hybrid.py``): the reference with ONE thing wrong, each a mistake
a serving engine with a carried scan state can make, so that a reading of
the program against it shows whether the comparison would catch the program
making that mistake; and the CONTROL, the reference on the same weights
rounded to fp8 e4m3, the nearest precision below the bf16 the configuration
states, which has to read as not correct (and a second control of the one
thing the configuration holds in float32, ``state_rounded_to_bf16``).  Used by
``tests/test_granite_hybrid.py`` (tiny sizes) and, through the harness's own
comparison, by ``benchmark/tools/ssm_compare.py --plant`` (published widths
on the chip, the cell's tolerances; ``planted_reference``).  The reference's
own file stays plain: a fault swaps one of its functions, or a size, for the
time of a ``with``.

The two faults of a boundary need to know where the program's boundaries
fall: a forward takes at most ``run.state_manager.max_q_per_seq`` rows of a
sequence, and the chunked scan works ``mamba_chunk_size`` rows at a time.
"""

import contextlib
import types

import jax
import jax.numpy as jnp

import _granite_hybrid as ref
from _mla_faults import _fp8

FAULTS = ("conv_tail_dropped_at_a_forward_boundary",
          "state_reset_at_a_chunk_boundary", "norm_before_the_gate",
          "d_skip_left_out", "dt_bias_left_out", "softmax_scale_one_eighth",
          "residual_multiplier_one", "state_rounded_to_bf16")
CONTROL = "weights_rounded_to_fp8"
_SWAPPED = ("_conv", "_recurrence", "_gated_norm", "embed", "layer", "head")


def _rounded(weights):
    return jax.tree_util.tree_map(_fp8, weights)


def _without(params, name):
    """``params`` with every scan layer's ``name`` zeroed."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if f"'{name}'" in jax.tree_util.keystr(path) else a, params)


@contextlib.contextmanager
def planted(fault, params, sizes):
    """``with planted(name, params, sizes) as (params, sizes):`` the
    arguments to hand ``ref.logits`` while the fault is in."""
    saved = {n: getattr(ref, n) for n in _SWAPPED}
    if fault == "conv_tail_dropped_at_a_forward_boundary":
        every = int(sizes["run"]["state_manager"]["max_q_per_seq"])

        def conv(xbc, w, b):          # a tap from before the row's forward
            T, K = xbc.shape[0], w.shape[1]         # began reads zero
            padded = jnp.concatenate(
                [jnp.zeros((K - 1, xbc.shape[1]), ref.F32), xbc])
            t = jnp.arange(T)
            out = sum(jnp.where(
                ((t - (K - 1 - j)) >= t // every * every)[:, None],
                padded[j:j + T], 0.0) * w[:, j] for j in range(K))
            return jax.nn.silu(out if b is None else out + b)
        ref._conv = conv
    elif fault == "state_reset_at_a_chunk_boundary":
        every = int(sizes["mamba_chunk_size"])

        def recurrence(x, dt, A, B, C, D):
            h, g = x.shape[1], B.shape[1]
            Bh = jnp.repeat(B, h // g, axis=1)
            Ch = jnp.repeat(C, h // g, axis=1)

            def step(S, row):
                t, x_t, dt_t, B_t, C_t = row
                S = jnp.where(t % every == 0, 0.0, S)
                S = (jnp.exp(dt_t * A)[:, None, None] * S
                     + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
                return S, (jnp.einsum("hpn,hn->hp", S, C_t)
                           + D[:, None] * x_t)
            S0 = jnp.zeros((h, x.shape[2], B.shape[2]), ref.F32)
            return jax.lax.scan(step, S0, (jnp.arange(x.shape[0]), x, dt,
                                           Bh, Ch))[1]
        ref._recurrence = recurrence
    elif fault == "state_rounded_to_bf16":
        # the second control: the recurrent state held in the precision
        # below the float32 the configuration states (assumed.
        # ssm_state_dtype), rounded after every position
        def recurrence(x, dt, A, B, C, D):
            h, g = x.shape[1], B.shape[1]
            Bh = jnp.repeat(B, h // g, axis=1)
            Ch = jnp.repeat(C, h // g, axis=1)

            def step(S, row):
                x_t, dt_t, B_t, C_t = row
                S = (jnp.exp(dt_t * A)[:, None, None] * S
                     + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
                # (by reduce_precision: the chip's compiler elides a
                # convert to bfloat16 and back, and the first reading taken
                # that way equalled the healthy one to the last digit)
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
                return S, (jnp.einsum("hpn,hn->hp", S, C_t)
                           + D[:, None] * x_t)
            S0 = jnp.zeros((h, x.shape[2], B.shape[2]), ref.F32)
            return jax.lax.scan(step, S0, (x, dt, Bh, Ch))[1]
        ref._recurrence = recurrence
    elif fault == "norm_before_the_gate":
        ref._gated_norm = lambda y, z, w, eps: (ref._rms(y, w, eps)
                                                * jax.nn.silu(z))
    elif fault == "d_skip_left_out":
        params = _without(params, "D")
    elif fault == "dt_bias_left_out":
        params = _without(params, "dt_bias")
    elif fault == "softmax_scale_one_eighth":    # 1/sqrt(64), not 1/64
        sizes = {**sizes, "attention_multiplier": 0.125}
    elif fault == "residual_multiplier_one":
        sizes = {**sizes, "residual_multiplier": 1.0}
    elif fault == CONTROL:
        # every weight, rounded where the reference takes it up: a layer's
        # at a time, so no second copy of the tree lies beside an engine
        ref.embed = lambda table, tokens, **kw: saved["embed"](
            _rounded(table), tokens, **kw)
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
        logits=logits, program_config=ref.program_config, tree=ref.tree)
