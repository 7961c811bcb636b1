"""What MiniCPM-SALA needs (``GPTConfig.layer_types`` with "lightning" layers:
a matrix state a head under a fixed decay; attention layers that select
``block_topk`` blocks of keys a KV head from pooled keys past
``block_dense_len``), from shapes and counts: operations and bytes of
the three new pieces for their rooflines (``block_sparse_prefill_roofline``,
``block_sparse_decode_roofline``, ``block_select_roofline``) and, through
``layer_costs/block_selecting.py``, their operations in a whole serving
window's need (``serve_step_mfu``), beside ``costs.py`` and ``costs_ssm.py``
(a decode pair's cost and the count of the lightning layers are theirs).
The yardstick's arithmetic lives here so that no later PR can move it.

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
import costs_ssm


def layers(cfg):
    """(lightning layers, attention layers)."""
    return costs_ssm.layers(cfg)


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
