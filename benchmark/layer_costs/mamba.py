"""A Mamba-2 scan layer: the in projection (hidden x (z + xBC + dt)) and
the out projection; the recurrence a row (``costs_ssm.recurrence_flops``:
the state decayed, the outer product added and the state read out, ``4 x
inner x state``).  The chunked form's extra products, the conv (4 taps a
channel), the softplus, the gate and the norm are no need."""

import costs_ssm


def row_weights(cfg, i):
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {"scan_proj": cfg.hidden_size * (inner + conv + cfg.ssm_heads)
            + inner * cfg.hidden_size}


def window_terms(cfg, i, counts, alike):
    return {"recurrence": costs_ssm.recurrence_flops(
        cfg, float(counts["rows"]))}, []
