"""What ONE layer of a served model needs, a kind of layer a file.

``kinds(cfg, i)`` asks the configuration, once, what mixes layer ``i``'s
sequence and what feeds it forward; ``find(kind)`` is the file of that name
beside this one (``attention.py``, ``latent.py``, ``selecting.py``,
``block_selecting.py``, ``mamba.py``, ``lightning.py``, ``conv.py``;
``mlp.py``, ``experts.py``).  ``costs_serve.window_need`` sums a serving
window's need over the layers through them (``serve_step_mfu``), and
``readers/attn_rooflines.py`` one step's paged attention over the layers
whose file has a ``paged_attention_cost``.  So a configuration with a new
kind of layer brings ITS file and joins those entries by a line of
``BENCHMARK.json``; it brings no copy of a reader.  The yardstick's
arithmetic lives here so that no later PR can move it.

A kind's file defines, for layer ``i`` of the model configuration ``cfg``
(``GPTConfig`` as ``reference.program_config`` fills it):

- ``row_weights(cfg, i)``: {term: matmul weight elements a row passes in
  this part of the layer}; the sum reports each as ``weights_<term>``, 2
  FLOP an element a scheduled row.
- ``window_terms(cfg, i, counts, alike)``: ({term: operations of the whole
  window beyond the row weights}, [what a missing count left out, a
  sentence each]).  ``counts`` are ``costs_serve.window_need``'s;
  ``alike`` is how many of the model's layers are of this kind: a counter
  the program sums over them is this layer's by that share.
- and, where the layer's attention runs through the two paged kernels
  (``paged_decode``, ``ragged_prefill``), ``paged_attention_cost(cfg, i,
  pairs, keys, rows)``: (flops, bytes) of one step in this layer.

The need is the ALGORITHM's, whatever implements it: a share computed from
it can read low and never over 100%.
"""

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))
_found = {}


def kinds(cfg, i):
    """(mixer, feed-forward) of layer ``i``, each the name of a file here.
    A layer that is no attention is what ``cfg.layer_kind`` calls it
    (``mamba``, ``lightning``, ``conv``, whatever a later configuration
    brings); an attention layer is ``block_selecting`` (blocks of keys from
    pooled scores), ``selecting`` (an indexer's best keys, on the layers
    without a window), ``latent`` (a compressed key/value) or
    ``attention``."""
    mixer = cfg.layer_kind(i)
    if mixer == "attention":
        if cfg.block_topk:
            mixer = "block_selecting"
        elif cfg.index_topk and cfg.window_for_layer(i) is None:
            mixer = "selecting"
        elif cfg.for_layer(i).kv_lora_rank:
            mixer = "latent"
    return mixer, "experts" if cfg.is_moe_layer(i) else "mlp"


def find(kind):
    """The kind's file as a module, None where there is none."""
    if kind not in _found:
        there = os.path.exists(os.path.join(HERE, f"{kind}.py"))
        _found[kind] = (importlib.import_module(f"{__name__}.{kind}")
                        if there else None)
    return _found[kind]
