"""What the pool stores for a cached token in a model of window AND full
layers of two key/value geometries (MiMo-V2-Flash: the full layers' 4 kv heads
beside the window layers' 8, keys 192 wide and values 128), from shapes:
every layer at ITS heads and widths (``GPTConfig.for_layer``).  What such a
layer's attention needs, in a window and in a step of the paged kernels, is
``layer_costs/attention.py``'s.
"""

from layer_costs import attention


def layer_views(cfg):
    """[(the layer's attention view, whether it has a window)] a layer."""
    return [(cfg.for_layer(i), cfg.window_for_layer(i) is not None)
            for i in range(cfg.num_layers)]


def kv_bytes_per_token(cfg, bytes_per_el=2):
    """(global, window): what the pool stores for a cached token over the
    layers of each page group."""
    out = [0, 0]
    for v, is_window in layer_views(cfg):
        out[is_window] += attention.token_bytes(v, bytes_per_el)
    return tuple(out)
