"""A dense feed-forward: two matrices, three where it is gated."""


def row_weights(cfg, i):
    return {"mlp": (3 if cfg.gated_mlp else 2) * cfg.hidden_size
            * cfg.mlp_dim}


def window_terms(cfg, i, counts, alike):
    return {}, []
