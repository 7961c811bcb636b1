"""What a whole serving window needs, from the configuration's shapes and
the program's counts over the WHOLE window: operations, for a share of the
chip's peak (``serve_step_mfu``), beside ``costs.py``, ``costs_moe.py``,
``costs_mla.py`` and ``costs_dsa.py`` (which stay as they are: the figures
a pair and an assignment cost are theirs, imported, not copied).  The
yardstick's arithmetic lives here so that no later PR can move it.

Three terms, each 2 FLOP a multiply-add:

- ``weights``: the matmul weights a row passes on this chip outside the
  routed experts (``row_weights``: the attention projections of its kind of
  layer, a latent's absorbed expansion once a row, the query latent, the
  gates and the indexer's projections among them; a dense MLP; the shared
  expert; the router at its whole width) times the rows the engine
  scheduled (``serving_tokens_total``, prefill and decode), plus the three
  products of an expert for each assignment that landed on an expert held
  here (``moe_local_assignments_total``), plus the head over the vocabulary
  rows held for each token the window produced.
- ``attention``: the pairs the ALGORITHM has to score, whatever implements
  it: causal pairs on a layer that reads every key, at most the window on a
  window layer, and on a selecting layer the pairs kept plus the pairs the
  indexer scores; a masked and a gathered implementation of a selection
  need the same pairs.
- nothing else: embedding lookups, norms, rotations, softmax, activations,
  sampling, the sort of the index scores, recomputation, padding (a bucket's
  dead rows, a page's pad columns) and re-reads are no need.  Rows a fused
  burst schedules past a request's budget ARE in ``serving_tokens_total``
  (0.2% of dots3's rows); the head is counted for produced tokens only.

A term whose count the program did not give over the whole window is left
out and named (``left_out``): the need is then a lower bound and the share
can only read low, never impossible.
"""

import costs
import costs_dsa
import costs_mla
import costs_moe


def attention_weights(hidden, heads, kv_heads, head_dim, *, v_head_dim=None,
                      kv_lora_rank=0, qk_rope_head_dim=0, q_lora_rank=0,
                      gate=None, index_heads=0, index_dim=0):
    """Matmul weight elements a row passes in ONE layer's attention.

    Ordinary heads: ``wq`` and ``wo`` (hidden x heads x head_dim each),
    ``wk`` and ``wv`` (hidden x kv_heads x head_dim each).  Latent attention
    (``kv_lora_rank``): the queries (straight, or through a query latent
    ``q_lora_rank``), ``wkv_a`` (hidden x (rank + rope)), ``wkv_b`` once a
    row (rank x heads x (nope + value): absorbed, the key half meets the
    row's queries and the value half its outputs), ``wo`` (heads x value x
    hidden).  ``gate``: ``"elementwise"`` (hidden x heads x head_dim) or
    ``"headwise"`` (hidden x heads).  The indexer: ``index_heads`` queries
    of ``index_dim`` from the query latent, one key and ``index_heads``
    weights from the hidden state."""
    if kv_lora_rank:
        nope = head_dim - qk_rope_head_dim
        value = v_head_dim or nope
        n = (hidden * q_lora_rank + q_lora_rank * heads * head_dim
             if q_lora_rank else hidden * heads * head_dim)
        n += hidden * (kv_lora_rank + qk_rope_head_dim)
        n += kv_lora_rank * heads * (nope + value)
        n += heads * value * hidden
    else:
        n = 2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim
    if gate == "elementwise":
        n += hidden * heads * (v_head_dim or head_dim)
    elif gate == "headwise":
        n += hidden * heads
    if index_heads:
        n += ((q_lora_rank or hidden) * index_heads * index_dim
              + hidden * index_dim + hidden * index_heads)
    return n


def _selects(cfg, i):
    return bool(cfg.index_topk) and cfg.window_for_layer(i) is None


def row_weights(cfg):
    """{"attention", "mlp", "shared", "router"}: matmul weight elements a
    row passes over all layers outside the routed experts, from the model
    configuration (``GPTConfig`` as ``reference.program_config`` fills it)."""
    out = {"attention": 0, "mlp": 0, "shared": 0, "router": 0}
    hidden = cfg.hidden_size
    gate = ("headwise" if cfg.attn_gate_headwise
            else "elementwise" if cfg.attn_gate else None)
    for i in range(cfg.num_layers):
        v = cfg.for_layer(i)               # this kind of layer's geometry
        selects = _selects(cfg, i)
        out["attention"] += attention_weights(
            hidden, v.num_heads, v.kv_heads, v.head_dim,
            v_head_dim=v.v_head_dim, kv_lora_rank=v.kv_lora_rank,
            qk_rope_head_dim=v.qk_rope_head_dim, q_lora_rank=v.q_lora_rank,
            gate=gate, index_heads=cfg.index_n_heads if selects else 0,
            index_dim=cfg.index_head_dim if selects else 0)
        if cfg.is_moe_layer(i):
            out["router"] += hidden * cfg.num_experts
            out["shared"] += 3 * hidden * cfg.moe_shared_dim
        else:
            out["mlp"] += (3 if cfg.gated_mlp else 2) * hidden * cfg.mlp_dim
    return out


def attention_flops(cfg, pairs_global, pairs_window, index_pairs=None,
                    selected_pairs=None):
    """({"attention", "index"}, left_out): operations of the pairs the
    window's dispatches had to score.  ``pairs_global``: causal pairs on ONE
    layer that reads every key, ``pairs_window``: pairs on ONE window layer
    (at most the window a row), both summed over the window's dispatches;
    ``index_pairs``, ``selected_pairs``: the program's counters of the
    pairs its indexer scored and its attention kept, already summed over
    the selecting layers.  A pair costs what the other cost files say: two
    products of ``2 * head_dim`` a head (``costs.paged_decode_cost``), or
    absorbed ``2 * (latent + value)`` a head (``costs_mla``).  A selecting
    layer whose counters are missing is left out, and named."""
    flops = {"attention": 0.0, "index": 0.0}
    selecting = [i for i in range(cfg.num_layers) if _selects(cfg, i)]
    for i in set(range(cfg.num_layers)) - set(selecting):
        v = cfg.for_layer(i)
        pairs = (pairs_global if cfg.window_for_layer(i) is None
                 else pairs_window)
        if v.kv_lora_rank:
            flops["attention"] += costs_mla.latent_prefill_cost(
                pairs, 0, 0, 1, v.num_heads, v.latent_dim, v.kv_lora_rank)[0]
        else:
            flops["attention"] += costs.paged_decode_cost(
                pairs, v.num_heads, v.kv_heads, v.head_dim, 0)[0]
    if not selecting:
        return flops, []
    if index_pairs is None or selected_pairs is None:
        return flops, ["selecting layers (no pair counters)"]
    v = cfg.for_layer(selecting[0])
    flops["attention"] += costs_dsa.selected_attention_cost(
        selected_pairs, 0, 1, v.num_heads, v.latent_dim, v.kv_lora_rank)[0]
    flops["index"] += costs_dsa.index_score_cost(
        index_pairs, 0, 1, cfg.index_n_heads, cfg.index_head_dim)[0]
    return flops, []


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


def window_need(cfg, counts):
    """{"flops", "terms", "left_out"} of one serving window.  ``counts``:
    ``rows`` (scheduled rows, prefill + decode), ``sampled`` (tokens
    produced), ``moe_local`` (assignments on held experts, or None),
    ``pairs_global`` / ``pairs_window`` (``pairs_of_dispatches``; None
    where the span buffer no longer held the whole window),
    ``index_pairs`` / ``selected_pairs`` (counters, or None)."""
    w = row_weights(cfg)
    rows = float(counts["rows"])
    terms = {f"weights_{k}": 2.0 * n * rows for k, n in w.items() if n}
    terms["weights_head"] = (2.0 * cfg.hidden_size * cfg.vocab_size
                             * float(counts.get("sampled") or 0))
    left_out = []
    if any(cfg.is_moe_layer(i) for i in range(cfg.num_layers)):
        if counts.get("moe_local") is None:
            left_out.append("routed experts (no assignment counter)")
        else:
            terms["weights_experts"] = costs_moe.expert_gemm_cost(
                float(counts["moe_local"]), 0, cfg.hidden_size,
                cfg.expert_dim)[0]
    if counts.get("pairs_global") is None:
        left_out.append("attention (the span buffer lost part of the window)")
    else:
        att, missing = attention_flops(
            cfg, counts["pairs_global"], counts.get("pairs_window") or 0.0,
            counts.get("index_pairs"), counts.get("selected_pairs"))
        terms.update({k: v for k, v in att.items() if v})
        left_out += missing
    return {"flops": sum(terms.values()), "terms": terms,
            "left_out": left_out}


def share_of_peak(flops, seconds, peaks):
    """Needed operations over the chip's bf16 peak over ``seconds``, in %."""
    return 100.0 * flops / (seconds * peaks["bf16_flops_per_s"])
