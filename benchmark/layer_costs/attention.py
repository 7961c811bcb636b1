"""An attention layer of ordinary heads, at ITS heads and widths
(``cfg.for_layer(i)``: a window layer's geometry may differ, a value may be
narrower than its key): ``wq`` (hidden x heads x key width), ``wk`` (hidden
x kv heads x key width), ``wv`` (hidden x kv heads x value width), ``wo``
(heads x value width x hidden) and the output gate; a query-key pair is a
score product over the key width and a value product over the value width
a head (``costs.paged_decode_cost``'s 2 x 2 x head_dim where they are
equal; a sink is no pair).  A window layer scores at most its window a
row."""

LOST = "attention (the span buffer lost part of the window)"


def value_dim(v):
    return getattr(v, "v_head_dim", None) or v.head_dim


def gate_of(cfg):
    return ("headwise" if cfg.attn_gate_headwise
            else "elementwise" if cfg.attn_gate else None)


def gate_weights(gate, hidden, heads, width):
    """``"elementwise"`` (hidden x heads x value width) or ``"headwise"``
    (hidden x heads)."""
    return {"elementwise": hidden * heads * width,
            "headwise": hidden * heads, None: 0}[gate]


def weights(v, hidden, gate=None):
    """Matmul weight elements a row passes in ONE layer's attention at the
    view ``v``."""
    return (hidden * (v.num_heads + v.kv_heads) * (v.head_dim + value_dim(v))
            + gate_weights(gate, hidden, v.num_heads, value_dim(v)))


def pair_flops(v):
    """Operations of one query-key pair over all of a layer's heads."""
    return 2.0 * v.num_heads * (v.head_dim + value_dim(v))


def token_bytes(v, bytes_per_el=2):
    """Bytes of one cached token in ONE layer: its key and its value in
    every kv head of the layer's own geometry."""
    return v.kv_heads * (v.head_dim + value_dim(v)) * bytes_per_el


def row_weights(cfg, i):
    return {"attention": weights(cfg.for_layer(i), cfg.hidden_size,
                                 gate_of(cfg))}


def pairs_of(cfg, i, counts):
    """The window's pairs on this layer: ``pairs_window`` on a window
    layer, ``pairs_global`` on one that reads every key."""
    if cfg.window_for_layer(i) is None:
        return float(counts["pairs_global"])
    return float(counts.get("pairs_window") or 0.0)


def window_terms(cfg, i, counts, alike):
    if counts.get("pairs_global") is None:
        return {}, [LOST]
    return {"attention": pair_flops(cfg.for_layer(i))
            * pairs_of(cfg, i, counts)}, []


def paged_attention_cost(cfg, i, pairs, keys, rows, bytes_per_el=2):
    """(flops, bytes) of one step's paged attention in this layer:
    ``pairs`` query-key pairs the mask leaves, ``keys`` cached tokens to
    read, key and value once each, ``rows`` query rows, q in (heads x key
    width) and o out (heads x value width).  A decode step's pairs ARE its
    keys (one row a slot).  The kernels copy whole pages, which is no
    need: the share says what the rounding costs."""
    v = cfg.for_layer(i)
    return (pair_flops(v) * pairs,
            token_bytes(v, bytes_per_el) * keys
            + rows * v.num_heads * (v.head_dim + value_dim(v))
            * bytes_per_el)
