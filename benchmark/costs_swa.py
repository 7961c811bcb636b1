"""What a model of window AND full layers of two key/value geometries needs
(MiMo-V2-Flash: the full layers' 4 kv heads beside the window layers' 8, keys
192 wide and values 128), from shapes and counts: operations and bytes, beside
``costs.py``, ``costs_moe.py`` and ``costs_serve.py`` (which stay as they are:
they count one kv-head number for every layer and a value as wide as its key).
Here every layer is counted at ITS heads and widths
(``GPTConfig.for_layer``), a score product over the key width and a value
product over the value width, and a window layer at the keys the window
leaves (``min(context, window)``, as the engine's dispatch spans count them).
Nothing here counts what an implementation re-reads or pads: the kernels copy
whole pages of 128 tokens, so a roofline share says what the page rounding
costs, and cannot pass 100% for it.
"""

import costs_moe


def _value_dim(v):
    return getattr(v, "v_head_dim", None) or v.head_dim


def layer_views(cfg):
    """[(the layer's attention view, whether it has a window)] a layer."""
    return [(cfg.for_layer(i), cfg.window_for_layer(i) is not None)
            for i in range(cfg.num_layers)]


def attention_weights(v, hidden):
    """Matmul weight elements a row passes in ONE layer's attention at the
    view ``v``: ``wq`` (hidden x heads x key width), ``wk`` (hidden x kv
    heads x key width), ``wv`` (hidden x kv heads x value width), ``wo``
    (heads x value width x hidden)."""
    return hidden * (v.num_heads * (v.head_dim + _value_dim(v))
                     + v.kv_heads * (v.head_dim + _value_dim(v)))


def row_weights(cfg):
    """{"attention", "mlp", "router"}: matmul weight elements a row passes
    over all layers outside the routed experts (no shared expert here)."""
    out = {"attention": 0, "mlp": 0, "router": 0}
    for i, (v, _) in enumerate(layer_views(cfg)):
        out["attention"] += attention_weights(v, cfg.hidden_size)
        if cfg.is_moe_layer(i):
            out["router"] += cfg.hidden_size * cfg.num_experts
        else:
            out["mlp"] += ((3 if cfg.gated_mlp else 2) * cfg.hidden_size
                           * cfg.mlp_dim)
    return out


def pair_flops(v):
    """Operations of one query-key pair over all of a layer's heads: a
    score product over the key width and a value product over the value
    width, 2 FLOP a multiply-add (the sink is no pair)."""
    return 2.0 * v.num_heads * (v.head_dim + _value_dim(v))


def token_bytes(v, bytes_per_el=2):
    """Bytes of one cached token in ONE layer: its key and its value in
    every kv head of the layer's own geometry."""
    return v.kv_heads * (v.head_dim + _value_dim(v)) * bytes_per_el


def kv_bytes_per_token(cfg, bytes_per_el=2):
    """(global, window): what the pool stores for a cached token over the
    layers of each page group."""
    out = [0, 0]
    for v, is_window in layer_views(cfg):
        out[is_window] += token_bytes(v, bytes_per_el)
    return tuple(out)


def attention_cost(cfg, pairs_global, pairs_window, keys_global,
                   keys_window, rows, bytes_per_el=2):
    """(flops, bytes) of one step's paged attention over ALL layers, each
    kind at its own geometry.  ``pairs_*``: the query-key pairs the mask
    leaves on one layer of the kind; ``keys_*``: the cached tokens a layer
    of the kind has to read, key and value once each; ``rows``: the step's
    query rows, q in (heads x key width) and o out (heads x value width)
    once a layer.  A decode step's pairs ARE its keys (one row a slot)."""
    flops = byts = 0.0
    for v, is_window in layer_views(cfg):
        pairs, keys = ((pairs_window, keys_window) if is_window
                       else (pairs_global, keys_global))
        flops += pair_flops(v) * pairs
        byts += (token_bytes(v, bytes_per_el) * keys
                 + rows * v.num_heads * (v.head_dim + _value_dim(v))
                 * bytes_per_el)
    return flops, byts


def window_need(cfg, counts):
    """{"flops", "terms", "left_out"} of one serving window, as
    ``costs_serve.window_need`` reckons it, with the attention terms at each
    layer's own heads and widths.  ``counts``: ``rows``, ``sampled``,
    ``moe_local``, ``pairs_global`` / ``pairs_window`` (the runner's)."""
    rows = float(counts["rows"])
    terms = {f"weights_{k}": 2.0 * n * rows
             for k, n in row_weights(cfg).items() if n}
    terms["weights_head"] = (2.0 * cfg.hidden_size * cfg.vocab_size
                             * float(counts.get("sampled") or 0))
    left_out = []
    if counts.get("moe_local") is None:
        left_out.append("routed experts (no assignment counter)")
    else:
        terms["weights_experts"] = costs_moe.expert_gemm_cost(
            float(counts["moe_local"]), 0, cfg.hidden_size,
            cfg.expert_dim)[0]
    if counts.get("pairs_global") is None:
        left_out.append("attention (the span buffer lost part of the window)")
    else:
        terms["attention"] = attention_cost(
            cfg, float(counts["pairs_global"]),
            float(counts.get("pairs_window") or 0.0), 0, 0, 0)[0]
    return {"flops": sum(terms.values()), "terms": terms,
            "left_out": left_out}
