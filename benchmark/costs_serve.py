"""What a whole serving window needs, from the configuration's shapes and
the program's counts over the WHOLE window: operations, for a share of the
chip's peak (``serve_step_mfu``).  ``window_need`` sums it over the layers,
each by ITS kind and widths: a kind of layer is a file under
``layer_costs/`` (whose ``__init__`` says what one must define), so a
configuration with a new kind brings that file and no copy of this one.
The yardstick's arithmetic lives here and there so that no later PR can
move it.

The terms, each 2 FLOP a multiply-add:

- ``weights_*``: the matmul weights a row passes on this chip outside the
  routed experts (each kind's ``row_weights``: the attention projections of
  its kind of layer, a latent's absorbed expansion once a row, the query
  latent, the gates and the indexer's projections among them; a scan, a
  lightning or a conv layer's projections; a dense MLP; the shared expert;
  the router at its whole width) times the rows the engine scheduled
  (``serving_tokens_total``, prefill and decode), plus the three products of
  an expert for each assignment that landed on an expert held here
  (``moe_local_assignments_total``), plus the head over the vocabulary rows
  held for each token the window produced.
- ``attention``, ``index``, ``attention_kept``, ``block_scores``: the pairs
  the ALGORITHM has to score, whatever implements it: causal pairs on a
  layer that reads every key, at most the window on a window layer, and on
  a selecting layer the pairs kept plus the pairs the indexer (or the
  pooled keys) scores; a masked and a gathered implementation of a
  selection need the same pairs.
- ``recurrence``, ``conv``: a state layer's work a row beside its
  projections (``costs_ssm``, ``costs_conv``).
- nothing else: embedding lookups, norms, rotations, softmax, activations,
  sampling, the sort of the index scores, recomputation, padding (a bucket's
  dead rows, a page's pad columns) and re-reads are no need.  Rows a fused
  burst schedules past a request's budget ARE in ``serving_tokens_total``
  (0.2% of dots3's rows); the head is counted for produced tokens only.

A term whose count the program did not give over the whole window is left
out and named (``left_out``): the need is then a lower bound and the share
can only read low, never impossible.
"""

import collections

import layer_costs


class NoCostFile(LookupError):
    """A kind of layer with no file under ``layer_costs/``."""


def pairs_of_dispatches(events):
    """(pairs_global, pairs_window) on one layer of each kind, summed over
    the ``*_dispatch`` events of the program's own span buffer (their
    arguments, ``engine_v2.py:_ctx_note``): a mixed step gives its
    ``qk_pairs`` and ``qk_pairs_window``; a decode step or a fused burst of
    ``steps`` rows a slot reads ``steps x ctx_tokens`` keys and its own
    growing rows (``seqs x steps x (steps + 1) / 2``), on a window layer
    ``steps x ctx_tokens_window`` (the growth inside a burst and the row's
    own key left out: a little less need, never more).  ``pairs_window`` is
    None where no event carries a window argument."""
    pg = pw = 0.0
    windowed = False
    for ev in events:
        a = ev["args"]
        if "qk_pairs" in a:
            pg += float(a["qk_pairs"])
            if "qk_pairs_window" in a:
                pw += float(a["qk_pairs_window"])
                windowed = True
        elif "ctx_tokens" in a:
            k = float(a.get("steps", 1))
            pg += (k * float(a["ctx_tokens"])
                   + float(a.get("seqs", 0)) * k * (k + 1) / 2)
            if "ctx_tokens_window" in a:
                pw += k * float(a["ctx_tokens_window"])
                windowed = True
    return pg, (pw if windowed else None)


def _parts(cfg):
    """[(layer, the file of its mixer's or its feed-forward's kind, how
    many of the model's layers are of that kind)], mixer first."""
    kinds = [layer_costs.kinds(cfg, i) for i in range(cfg.num_layers)]
    alike = collections.Counter(k for pair in kinds for k in pair)
    missing = sorted(k for k in alike if layer_costs.find(k) is None)
    if missing:
        raise NoCostFile(", ".join(missing))
    return [(i, layer_costs.find(k), alike[k])
            for i, pair in enumerate(kinds) for k in pair]


def row_weights(cfg):
    """{term: matmul weight elements a row passes over all layers outside
    the routed experts}, from the model configuration (``GPTConfig`` as
    ``reference.program_config`` fills it)."""
    out = {}
    for i, cost, _ in _parts(cfg):
        for k, n in cost.row_weights(cfg, i).items():
            out[k] = out.get(k, 0) + n
    return out


def window_need(cfg, counts):
    """{"flops", "terms", "left_out"} of one serving window: over the
    layers, what each one's mixer and feed-forward need by their kind
    (``layer_costs.kinds``), plus the head.  ``counts``: ``rows`` (scheduled
    rows, prefill + decode), ``sampled`` (tokens produced), ``moe_local``
    (assignments on held experts, or None), ``pairs_global`` /
    ``pairs_window`` (``pairs_of_dispatches``; None where the span buffer no
    longer held the whole window), ``index_pairs`` / ``selected_pairs``
    (counters, or None).  A kind without a file raises ``NoCostFile``: a
    need that left a layer out in silence would read as a slow chip."""
    rows = float(counts["rows"])
    terms = {f"weights_{k}": 2.0 * n * rows
             for k, n in row_weights(cfg).items() if n}
    terms["weights_head"] = (2.0 * cfg.hidden_size * cfg.vocab_size
                             * float(counts.get("sampled") or 0))
    left_out = []
    for i, cost, alike in _parts(cfg):
        got, out = cost.window_terms(cfg, i, counts, alike)
        for k, flops in got.items():
            if flops:
                terms[k] = terms.get(k, 0.0) + flops
        left_out += [s for s in out if s not in left_out]
    return {"flops": sum(terms.values()), "terms": terms,
            "left_out": left_out}


def share_of_peak(flops, seconds, peaks):
    """Needed operations over the chip's bf16 peak over ``seconds``, in %."""
    return 100.0 * flops / (seconds * peaks["bf16_flops_per_s"])
