"""A latent attention layer that reads its indexer's best keys
(``index_topk``, on the layers without a window): the latent layer's
weights and the indexer's (``index_n_heads`` queries of ``index_head_dim``
from the query latent, one key and ``index_n_heads`` weights from the
hidden state); the pairs attention KEPT and the pairs the indexer scored,
the program's counters over the selecting layers (``costs_dsa``).  A masked
and a gathered implementation of a selection need the same pairs; the
causal pairs of such a layer are no need."""

import costs_dsa

from . import attention, latent


def row_weights(cfg, i):
    v = cfg.for_layer(i)
    hidden = cfg.hidden_size
    return {"attention": latent.weights(v, hidden, attention.gate_of(cfg))
            + (v.q_lora_rank or hidden) * cfg.index_n_heads
            * cfg.index_head_dim
            + hidden * cfg.index_head_dim + hidden * cfg.index_n_heads}


def window_terms(cfg, i, counts, alike):
    if counts.get("pairs_global") is None:
        return {}, [attention.LOST]
    if counts.get("index_pairs") is None \
            or counts.get("selected_pairs") is None:
        return {}, ["selecting layers (no pair counters)"]
    v = cfg.for_layer(i)
    return {"attention": costs_dsa.selected_attention_cost(
                float(counts["selected_pairs"]) / alike, 0, 1, v.num_heads,
                v.latent_dim, v.kv_lora_rank)[0],
            "index": costs_dsa.index_score_cost(
                float(counts["index_pairs"]) / alike, 0, 1,
                cfg.index_n_heads, cfg.index_head_dim)[0]}, []
