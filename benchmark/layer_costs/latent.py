"""An attention layer over a compressed key/value (``kv_lora_rank``): the
queries (straight, or through a query latent ``q_lora_rank``), ``wkv_a``
(hidden x (rank + rope)), ``wkv_b`` once a row (rank x heads x (nope +
value): absorbed, the key half meets the row's queries and the value half
its outputs), ``wo`` (heads x value x hidden) and the output gate; a pair
costs absorbed ``2 x (latent + rank)`` a head (``costs_mla``)."""

import costs_mla

from . import attention


def weights(v, hidden, gate=None):
    """Matmul weight elements a row passes in ONE layer's latent attention
    at the view ``v``."""
    nope = v.head_dim - v.qk_rope_head_dim
    value = v.v_head_dim or nope
    n = (hidden * v.q_lora_rank + v.q_lora_rank * v.num_heads * v.head_dim
         if v.q_lora_rank else hidden * v.num_heads * v.head_dim)
    n += hidden * (v.kv_lora_rank + v.qk_rope_head_dim)
    n += v.kv_lora_rank * v.num_heads * (nope + value)
    n += v.num_heads * value * hidden
    return n + attention.gate_weights(gate, hidden, v.num_heads,
                                      v.v_head_dim or v.head_dim)


def row_weights(cfg, i):
    return {"attention": weights(cfg.for_layer(i), cfg.hidden_size,
                                 attention.gate_of(cfg))}


def window_terms(cfg, i, counts, alike):
    if counts.get("pairs_global") is None:
        return {}, [attention.LOST]
    v = cfg.for_layer(i)
    return {"attention": costs_mla.latent_prefill_cost(
        attention.pairs_of(cfg, i, counts), 0, 0, 1, v.num_heads,
        v.latent_dim, v.kv_lora_rank)[0]}, []
