"""What a model that SELECTS its keys needs, from shapes and counts:
operations and bytes of the index scores, of attention over the selected
latent rows, and of latent attention under a window, beside ``costs.py``,
``costs_mla.py`` and ``costs_moe.py`` (which stay as they are).

Each need is a lower bound on ANY implementation of the same mathematics,
so that no share of a roofline computed from it can pass 100%:

- index scores: a scored (query row, key) pair needs ``2 * heads * dim``
  operations (64 index heads of 128: the relu and the weighted sum over
  heads are not counted), and a key that some row of a step scores has to be
  read once that step: ``dim * 2`` bytes (256 B).  Masked pairs an
  implementation computes and throws away are no need.
- attention over selected rows (absorbed MQA form): a kept pair needs a head
  ``2 * (latent_dim + value_dim)`` operations (576 + 512); a decode row reads
  each of its kept rows, ``latent_dim * 2`` bytes (1,152 B) each; the rows
  of a prefill chunk may share a key, so a chunk reads a context key at most
  once: the bytes are those of ``min(keys in the contexts, kept pairs)``.
- latent attention under a window: a pair inside the window needs a head
  ``2 * (latent_dim + value_dim)`` operations (1,088 + 1,024) and a key
  inside some row's window is read once a step: ``latent_dim * 2`` bytes
  (2,176 B).

Pad columns of a page row, the gather's own traffic, the sort of the scores:
all of that reads as lost share, not as need.
"""


def index_score_cost(pairs, keys, layers, heads, dim, bytes_per_el=2):
    """(flops, bytes) of the index scores of one step over ``layers``
    selecting layers: ``pairs`` scored pairs a layer, ``keys`` index keys a
    layer has to read."""
    return (2.0 * heads * dim * pairs * layers,
            float(dim * keys * layers * bytes_per_el))


def selected_attention_cost(kept_pairs, keys, layers, heads, latent_dim,
                            value_dim, bytes_per_el=2):
    """(flops, bytes) of attention over the selected rows of one step:
    ``kept_pairs`` pairs a layer, ``keys`` latent rows a layer has to read
    (a decode step: the kept pairs themselves; a prefill chunk: at most the
    contexts' keys)."""
    return (2.0 * (latent_dim + value_dim) * heads * kept_pairs * layers,
            float(latent_dim * keys * layers * bytes_per_el))


def window_latent_cost(pairs, keys, layers, heads, latent_dim, value_dim,
                       bytes_per_el=2):
    """(flops, bytes) of latent attention under a window of one step over
    ``layers`` window layers: ``pairs`` pairs inside the window a layer,
    ``keys`` cached rows a layer has to read."""
    return (2.0 * (latent_dim + value_dim) * heads * pairs * layers,
            float(latent_dim * keys * layers * bytes_per_el))
