"""An attention layer that keeps ``block_topk`` blocks of keys a KV head
from pooled scores (MiniCPM-SALA's InfLLM-V2 layers): q, the gate and o
(heads x head_dim wide) and k, v (kv heads); the pairs attention KEPT
(every causal pair of a row within ``block_dense_len``, the kept blocks'
keys past it) and the (row, pooled key) pairs scored, the program's
counters over the selecting layers (``costs_sala``)."""

import costs_sala

from . import attention

NO_NEED = ("the choice of blocks (a threshold search or a sort), the "
           "softmax over pooled keys, the pooled keys' upkeep, norms, "
           "RoPE and the gates' sigmoids")


row_weights = attention.row_weights


def window_terms(cfg, i, counts, alike):
    terms, left_out = {}, [NO_NEED]
    if counts.get("selected_pairs") is None:
        left_out.append("attention (no kept-pairs counter)")
    else:
        terms["attention_kept"] = costs_sala.kept_attention_cost(
            float(counts["selected_pairs"]) / alike, 0, 1, cfg.num_heads,
            cfg.kv_heads, cfg.head_dim)[0]
    if counts.get("index_pairs") is None:
        left_out.append("block scores (no pooled-pairs counter)")
    else:
        terms["block_scores"] = costs_sala.block_score_cost(
            float(counts["index_pairs"]) / alike, 0, 1, cfg.num_heads,
            cfg.kv_heads, cfg.head_dim)[0]
    return terms, left_out
