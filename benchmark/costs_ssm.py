"""What a model with scan layers (``GPTConfig.layer_types``: Mamba-2 mixers
beside a few attention layers) needs, from shapes: operations and bytes of
the scan itself for its rooflines (``ssm_decode_roofline``,
``ssm_prefill_roofline``) and, through ``layer_costs/mamba.py`` and
``lightning.py``, the recurrence's part of a whole serving window's need
(``serve_step_mfu``).  The yardstick's arithmetic lives here so that no
later PR can move it.

The need is the ALGORITHM's, whatever implements it.  A row of a scan layer
needs, a head of width ``p`` over a state ``[p, n]``: the state decayed and
the outer product added (2 FLOP an element of the state), and the state read
out against ``C`` (2 more): ``4 x inner x state`` FLOP a row a layer.  The
chunked (SSD) form's extra products (``C B^T``, the decay matrix, the
chunk's own state) are no need, nor is the conv (4 taps a channel), the
softplus, the gate or the norm: a share can read low and never impossible.
"""


def layers(cfg):
    """(scan layers, attention layers)."""
    scan = sum(cfg.is_scan_layer(i) for i in range(cfg.num_layers))
    return scan, cfg.num_layers - scan


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
