"""What a model with gated short-convolution layers (``GPTConfig.layer_types``
with ``conv``: LFM2's hybrid of short convs and a few RoPE GQA layers, with
routed experts after the leading dense layers) needs, from shapes:
operations and bytes of the short conv itself for its roofline
(``short_conv_roofline``), the bytes of its state and pages and, through
``layer_costs/conv.py``, the conv's part of a whole serving window's need
(``serve_step_mfu``).  The yardstick's arithmetic lives here so that no
later PR can move it.

The need is the ALGORITHM's, whatever implements it.  A row of a short-conv
layer needs ``taps`` multiply-adds a channel for the conv and one multiply
each for the two gates; it reads ``B * X`` and writes ``v`` (hidden wide
each); a slot reads its tail (``taps - 1`` rows) and leaves the next one.
The gather of a prompt chunk's rows, the plan and the scatter are no need.
"""

import costs_serve


def layers(cfg):
    """(conv layers, attention layers)."""
    conv = sum(cfg.layer_kind(i) == "conv" for i in range(cfg.num_layers))
    return conv, cfg.num_layers - conv


def conv_flops(cfg, rows):
    """Operations of ``rows`` rows through ONE short-conv layer outside its
    projections: ``taps`` multiply-adds and the two gates a channel."""
    return (2.0 * cfg.conv_taps + 2.0) * cfg.hidden_size * rows


def tail_bytes(cfg, bytes_per_el=2):
    """Bytes of one sequence's conv tail in ONE conv layer."""
    return float((cfg.conv_taps - 1) * cfg.hidden_size * bytes_per_el)


def state_bytes_per_slot(cfg, bytes_per_el=2):
    """Bytes of one sequence's state over all conv layers: the tails."""
    return layers(cfg)[0] * tail_bytes(cfg, bytes_per_el)


def kv_bytes_per_token(cfg, bytes_per_el=2):
    """Bytes of pages a token costs: keys and values in the attention
    layers alone."""
    return float(layers(cfg)[1] * 2 * cfg.kv_heads * cfg.head_dim
                 * bytes_per_el)


def short_conv_cost(cfg, rows, slots, bytes_per_el=2):
    """(flops, bytes) the conv of ONE layer needs for ``rows`` rows of
    ``slots`` sequences: a row's ``B * X`` in and ``v`` out; each
    sequence's tail once in and once out."""
    return (2.0 * cfg.conv_taps * cfg.hidden_size * rows,
            2.0 * cfg.hidden_size * bytes_per_el * rows
            + 2.0 * tail_bytes(cfg, bytes_per_el) * slots)


def decode_stream_bytes(cfg, touched_per_layer=None, bytes_per_el=2):
    """{"experts", "other"}: weight bytes one decode step streams: the
    touched experts' three matrices in every expert layer (all of them by
    default), and every other matmul weight once (the head among them)."""
    moe = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
    touched = cfg.local_experts if touched_per_layer is None \
        else touched_per_layer
    other = (sum(costs_serve.row_weights(cfg).values())
             + cfg.hidden_size * cfg.vocab_size)
    return {"experts": float(moe * touched * 3 * cfg.hidden_size
                             * cfg.expert_dim * bytes_per_el),
            "other": float(other * bytes_per_el)}
