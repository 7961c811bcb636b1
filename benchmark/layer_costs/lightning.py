"""A lightning-attention layer (a matrix state a head under a fixed decay):
its q, k, v, gate and output projections; the same recurrence a row as a
Mamba-2 layer's (``costs_ssm.recurrence_flops``)."""

from . import mamba


def row_weights(cfg, i):
    return {"lightning_proj": 5 * cfg.hidden_size
            * cfg.ssm_heads * cfg.ssm_head_dim}


window_terms = mamba.window_terms
