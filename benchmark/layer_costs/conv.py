"""A gated short-convolution layer: the in projection (hidden x 3 hidden)
and the out projection (hidden x hidden); ``taps`` multiply-adds and the two
gates a channel a row (``costs_conv.conv_flops``).  The gather of a prompt
chunk's rows, the plan and the scatter are no need."""

import costs_conv


def row_weights(cfg, i):
    return {"conv_proj": 4 * cfg.hidden_size * cfg.hidden_size}


def window_terms(cfg, i, counts, alike):
    return {"conv": costs_conv.conv_flops(cfg, float(counts["rows"]))}, []
