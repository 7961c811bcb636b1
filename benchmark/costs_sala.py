"""What MiniCPM-SALA needs (``GPTConfig.layer_types`` with "lightning" layers:
a matrix state a head under a fixed decay; attention layers that select
``block_topk`` blocks of keys a KV head from pooled keys past
``block_dense_len``), from shapes and counts: operations of a whole serving
window for a share of the chip's peak (``serve_step_mfu.sala``), and
operations and bytes of the three new pieces for their rooflines
(``block_sparse_prefill_roofline``, ``block_sparse_decode_roofline``,
``block_select_roofline``), beside ``costs.py``, ``costs_serve.py`` and
``costs_ssm.py`` (which stay as they are: a decode pair's cost, the share of
a peak and the recurrence's cost are theirs, imported, not copied).  The
yardstick's arithmetic lives here so that no later PR can move it.

Each need is the ALGORITHM's, a lower bound on any implementation of the same
mathematics, so that no share computed from it can pass 100%:

- attention over kept blocks: a kept (row, key) pair needs a query head ``4
  x head_dim`` operations (the score and the value product); the bytes are the
  least anything moves: each distinct key and value the step's rows keep,
  once a step.  A decode step: every slot's kept keys (its rows share
  nothing).  A prompt chunk: its rows may share a key, so at most the slot's
  context: ``min(kept pairs, context)``.  Keys an implementation reads and
  masks, the gather's own traffic and the mask's bits are no need.
- the block scores: a (row, pooled key) pair needs a query head ``2 x
  head_dim`` operations; a slot's visible pooled keys are read once a step.
  The softmax over pooled keys, the max over a block's pooled keys, the
  choice itself (a threshold search or a sort) and the pooled keys' upkeep
  are no need.
"""

import costs
import costs_serve
import costs_ssm


def layers(cfg):
    """(lightning layers, attention layers)."""
    return costs_ssm.layers(cfg)


def row_weights(cfg):
    """{"lightning_proj", "attention", "mlp"}: matmul weight elements a row
    passes over all layers: a lightning layer's q, k, v, gate and output
    projections, an attention layer's q, gate and o (heads x head_dim wide)
    and k, v (kv heads), the MLP in every layer."""
    light, attn = layers(cfg)
    H = cfg.hidden_size
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    wide = cfg.num_heads * cfg.head_dim
    return {
        "lightning_proj": light * 5 * H * inner,
        "attention": attn * (H * wide * (3 if cfg.attn_gate else 2)
                             + 2 * H * cfg.kv_heads * cfg.head_dim),
        "mlp": cfg.num_layers * (3 if cfg.gated_mlp else 2) * H * cfg.mlp_dim}


def kept_attention_cost(kept_pairs, keys, layers, heads, kv_heads, head_dim,
                        bytes_per_el=2):
    """(flops, bytes) of attention over kept blocks of one step over
    ``layers`` selecting layers: ``kept_pairs`` (row, key) pairs a layer (a
    pair is all the row's query heads'), ``keys`` distinct cached positions
    a layer has to read, key and value of every KV head."""
    return (costs.paged_decode_cost(kept_pairs, heads, kv_heads, head_dim,
                                    0)[0] * layers,
            2.0 * kv_heads * head_dim * bytes_per_el * keys * layers)


def block_score_cost(pooled_pairs, pooled_keys, layers, heads, kv_heads,
                     head_dim, bytes_per_el=2):
    """(flops, bytes) of the block scores of one step over ``layers``
    selecting layers: ``pooled_pairs`` (row, pooled key) pairs a layer,
    ``pooled_keys`` pooled positions a layer has to read, every KV head's."""
    return (2.0 * heads * head_dim * pooled_pairs * layers,
            float(kv_heads * head_dim * bytes_per_el * pooled_keys * layers))


def window_need(cfg, counts):
    """{"flops", "terms", "left_out"} of one serving window.  ``counts``:
    ``rows``, ``sampled``, ``selected_pairs`` (the pairs attention KEPT,
    summed over the selecting layers: every causal pair of a row within
    ``block_dense_len``, the kept blocks' keys past it) and ``index_pairs``
    (the (row, pooled key) pairs scored, summed over them), both the
    program's counters over the window, None where it has none."""
    light, _ = layers(cfg)
    rows = float(counts["rows"])
    terms = {f"weights_{k}": 2.0 * n * rows
             for k, n in row_weights(cfg).items() if n}
    terms["recurrence"] = light * costs_ssm.recurrence_flops(cfg, rows)
    terms["weights_head"] = (2.0 * cfg.hidden_size * cfg.vocab_size
                             * float(counts.get("sampled") or 0))
    left_out = ["the choice of blocks (a threshold search or a sort), the "
                "softmax over pooled keys, the pooled keys' upkeep, norms, "
                "RoPE and the gates' sigmoids"]
    if counts.get("selected_pairs") is None:
        left_out.append("attention (no kept-pairs counter)")
    else:
        terms["attention_kept"] = kept_attention_cost(
            float(counts["selected_pairs"]), 0, 1, cfg.num_heads,
            cfg.kv_heads, cfg.head_dim)[0]
    if counts.get("index_pairs") is None:
        left_out.append("block scores (no pooled-pairs counter)")
    else:
        terms["block_scores"] = block_score_cost(
            float(counts["index_pairs"]), 0, 1, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim)[0]
    return {"flops": sum(terms.values()), "terms": terms,
            "left_out": left_out}


share_of_peak = costs_serve.share_of_peak
