"""What a model with gated short-convolution layers (``GPTConfig.layer_types``
with ``conv``: LFM2's hybrid of short convs and a few RoPE GQA layers, with
routed experts after the leading dense layers) needs, from shapes:
operations of a whole serving window for a share of the chip's peak
(``serve_step_mfu.conv``), operations and bytes of the short conv itself for
its roofline (``short_conv_roofline``), and of the two paged attention
kernels on the ATTENTION layers alone (``paged_decode_roofline.conv``,
``ragged_prefill_roofline.conv``), beside ``costs.py``, ``costs_moe.py`` and
``costs_serve.py`` (which stay as they are: a pair's cost, an assignment's
and the pairs of the dispatch spans are theirs, imported, not copied).
``costs_serve`` asks every layer for an attention geometry and
``costs_ssm`` reckons Mamba-2 mixers; this file asks ``cfg.layer_kind``.
The yardstick's arithmetic lives here so that no later PR can move it.

The need is the ALGORITHM's, whatever implements it.  A row of a short-conv
layer needs ``taps`` multiply-adds a channel for the conv and one multiply
each for the two gates; it reads ``B * X`` and writes ``v`` (hidden wide
each); a slot reads its tail (``taps - 1`` rows) and leaves the next one.
The gather of a prompt chunk's rows, the plan and the scatter are no need.
"""

import costs
import costs_moe


def layers(cfg):
    """(conv layers, attention layers)."""
    conv = sum(cfg.layer_kind(i) == "conv" for i in range(cfg.num_layers))
    return conv, cfg.num_layers - conv


def row_weights(cfg):
    """{"conv_proj", "attention", "mlp", "router"}: matmul weight elements a
    row passes over all layers outside the routed experts: a conv layer's in
    projection (hidden x 3 hidden) and out projection (hidden x hidden), an
    attention layer's four, a dense layer's SwiGLU, an expert layer's router
    at its whole width."""
    conv, attn = layers(cfg)
    H = cfg.hidden_size
    moe = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
    return {
        "conv_proj": conv * 4 * H * H,
        "attention": attn * (2 * H * cfg.num_heads * cfg.head_dim
                             + 2 * H * cfg.kv_heads * cfg.head_dim),
        "mlp": (cfg.num_layers - moe) * (3 if cfg.gated_mlp else 2)
        * H * cfg.mlp_dim,
        "router": moe * H * cfg.num_experts}


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
    other = sum(row_weights(cfg).values()) + cfg.hidden_size * cfg.vocab_size
    return {"experts": float(moe * touched * 3 * cfg.hidden_size
                             * cfg.expert_dim * bytes_per_el),
            "other": float(other * bytes_per_el)}


def paged_decode_cost(cfg, context_tokens, slots):
    """(flops, bytes) of one decode step's paged attention over the
    ATTENTION layers: ``costs.paged_decode_cost`` a layer."""
    f, b = costs.paged_decode_cost(context_tokens, cfg.num_heads,
                                   cfg.kv_heads, cfg.head_dim, slots)
    n = layers(cfg)[1]
    return n * f, n * b


def ragged_prefill_cost(cfg, pairs, keys, rows):
    """(flops, bytes) of one mixed step's ragged prefill attention over the
    ATTENTION layers (``costs_moe.ragged_prefill_window_cost`` with no
    window layer)."""
    return costs_moe.ragged_prefill_window_cost(
        pairs, 0.0, keys, 0.0, rows, layers(cfg)[1], 0, cfg.num_heads,
        cfg.kv_heads, cfg.head_dim)


def window_need(cfg, counts):
    """{"flops", "terms", "left_out"} of one serving window.  ``counts``:
    ``rows`` (scheduled rows, prefill + decode), ``sampled`` (tokens
    produced), ``moe_local`` (assignments on held experts, or None),
    ``pairs_global`` (causal pairs on ONE attention layer, summed over the
    window's dispatches: ``costs_serve.pairs_of_dispatches``; None where the
    span buffer no longer held the whole window)."""
    conv, attn = layers(cfg)
    rows = float(counts["rows"])
    terms = {f"weights_{k}": 2.0 * n * rows
             for k, n in row_weights(cfg).items() if n}
    terms["conv"] = conv * conv_flops(cfg, rows)
    terms["weights_head"] = (2.0 * cfg.hidden_size * cfg.vocab_size
                             * float(counts.get("sampled") or 0))
    left_out = []
    if any(cfg.is_moe_layer(i) for i in range(cfg.num_layers)):
        if counts.get("moe_local") is None:
            left_out.append("routed experts (no assignment counter)")
        else:
            terms["weights_experts"] = costs_moe.expert_gemm_cost(
                float(counts["moe_local"]), 0, cfg.hidden_size,
                cfg.expert_dim)[0]
    if counts.get("pairs_global") is None:
        left_out.append("attention (the span buffer lost part of the window)")
    else:
        terms["attention"] = attn * costs.paged_decode_cost(
            float(counts["pairs_global"]), cfg.num_heads, cfg.kv_heads,
            cfg.head_dim, 0)[0]
    return {"flops": sum(terms.values()), "terms": terms,
            "left_out": left_out}
