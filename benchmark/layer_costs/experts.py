"""Routed experts: a row passes the shared expert (three matrices) and the
router at its whole width; the routed ones cost their three products for
each assignment that landed on an expert held here
(``moe_local_assignments_total`` over the expert layers,
``costs_moe.expert_gemm_cost``)."""

import costs_moe


def row_weights(cfg, i):
    return {"shared": 3 * cfg.hidden_size * cfg.moe_shared_dim,
            "router": cfg.hidden_size * cfg.num_experts}


def window_terms(cfg, i, counts, alike):
    if counts.get("moe_local") is None:
        return {}, ["routed experts (no assignment counter)"]
    return {"weights_experts": costs_moe.expert_gemm_cost(
        float(counts["moe_local"]) / alike, 0, cfg.hidden_size,
        cfg.expert_dim)[0]}, []
