"""What a model with scan layers (``GPTConfig.layer_types``: Mamba-2 mixers
beside a few attention layers) needs, from shapes: operations of a whole
serving window for a share of the chip's peak (``serve_step_mfu.scan``), and
operations and bytes of the scan itself for its rooflines
(``ssm_decode_roofline``, ``ssm_prefill_roofline``), beside ``costs.py`` and
``costs_serve.py`` (which stay as they are: a pair's cost and the pairs of
the dispatch spans are theirs, imported, not copied).  ``costs_serve``
reckons attention on every layer; this file asks ``cfg.is_scan_layer``.  The
yardstick's arithmetic lives here so that no later PR can move it.

The need is the ALGORITHM's, whatever implements it.  A row of a scan layer
needs, a head of width ``p`` over a state ``[p, n]``: the state decayed and
the outer product added (2 FLOP an element of the state), and the state read
out against ``C`` (2 more): ``4 x inner x state`` FLOP a row a layer.  The
chunked (SSD) form's extra products (``C B^T``, the decay matrix, the
chunk's own state) are no need, nor is the conv (4 taps a channel), the
softplus, the gate or the norm: a share can read low and never impossible.
"""

import costs


def layers(cfg):
    """(scan layers, attention layers)."""
    scan = sum(cfg.is_scan_layer(i) for i in range(cfg.num_layers))
    return scan, cfg.num_layers - scan


def row_weights(cfg):
    """{"scan_proj", "attention", "mlp"}: matmul weight elements a row
    passes over all layers: a scan layer's in projection (hidden x (z + xBC
    + dt)) and out projection, an attention layer's four, the MLP in every
    layer."""
    scan, attn = layers(cfg)
    H = cfg.hidden_size
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {
        "scan_proj": scan * (H * (inner + conv + cfg.ssm_heads) + inner * H),
        "attention": attn * (2 * H * cfg.num_heads * cfg.head_dim
                             + 2 * H * cfg.kv_heads * cfg.head_dim),
        "mlp": cfg.num_layers * (3 if cfg.gated_mlp else 2) * H * cfg.mlp_dim}


def recurrence_flops(cfg, rows):
    """Operations of the recurrence for ``rows`` rows through ONE scan
    layer."""
    return 4.0 * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * rows


def state_bytes(cfg):
    """Bytes of one sequence's recurrent state in ONE scan layer, float32."""
    return 4.0 * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state


def scan_cost(cfg, rows, slots, bytes_per_el=2):
    """(flops, bytes) the scan of ONE layer needs for ``rows`` rows of
    ``slots`` sequences: the recurrence; each sequence's state once in and
    once out; ``x``, ``z`` and ``y`` (inner wide), ``B`` and ``C`` (groups x
    state) a row in the activations' type and ``dt`` (a head) in float32."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    row = ((3 * inner + 2 * cfg.ssm_groups * cfg.ssm_state) * bytes_per_el
           + 4 * cfg.ssm_heads)
    return (recurrence_flops(cfg, rows),
            2.0 * state_bytes(cfg) * slots + float(row) * rows)


def window_need(cfg, counts):
    """{"flops", "terms", "left_out"} of one serving window.  ``counts``:
    ``rows`` (scheduled rows, prefill + decode), ``sampled`` (tokens
    produced), ``pairs_global`` (causal pairs on ONE attention layer, summed
    over the window's dispatches: ``costs_serve.pairs_of_dispatches``; None
    where the span buffer no longer held the whole window)."""
    scan, attn = layers(cfg)
    rows = float(counts["rows"])
    terms = {f"weights_{k}": 2.0 * n * rows
             for k, n in row_weights(cfg).items() if n}
    terms["recurrence"] = scan * recurrence_flops(cfg, rows)
    terms["weights_head"] = (2.0 * cfg.hidden_size * cfg.vocab_size
                             * float(counts.get("sampled") or 0))
    left_out = []
    if counts.get("pairs_global") is None:
        left_out.append("attention (the span buffer lost part of the window)")
    else:
        terms["attention"] = attn * costs.paged_decode_cost(
            float(counts["pairs_global"]), cfg.num_heads, cfg.kv_heads,
            cfg.head_dim, 0)[0]
    return {"flops": sum(terms.values()), "terms": terms,
            "left_out": left_out}
