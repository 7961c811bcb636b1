"""The one traffic generator: a mix is a data file, this reads it.

A mix file (``benchmark/traffic/<mix>.json``) has a ``kind``:

- ``train_sequences``: fixed-length sequences for a trainer.  Keys:
  ``seq_len``, ``micro_batch_per_chip``, ``token_skew`` (tokens are
  ``floor(V * u**skew)`` for uniform ``u``: a skewed unigram law, so that a
  model that learns has a loss to lower).
- ``requests``: prompts and answer budgets for a server.  Keys:
  ``prompt_tokens`` and ``output_tokens`` (each a length law, below),
  ``arrivals`` (below), ``order_block``, ``stream_sync``, ``warm_share``,
  ``drain_deadline_s``.

A length law is ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
``{"dist": "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``.  The
``n`` lengths of a run are the ``n`` evenly spaced quantiles of the law, so
every seed offers the same multiset of lengths; the seed only orders and
pairs them.  The order is stratified: every run of ``order_block`` consecutive
requests holds one length from each of ``order_block`` equal slices of the
sorted lengths, so any stretch of the run sees the whole law (a plain shuffle
lets one seed front-load its long prompts, and then the seed changes the work
a fixed window sees).

``arrivals`` is ``{"process": "open_loop", "rate_per_s", "per_slice"}``: ``n =
round(rate * seconds)`` requests; the window is cut into equal slices that
each get ``per_slice`` arrivals at uniform instants (``per_slice >= n`` is a
Poisson process given its count; smaller values keep its local clumping and
take out the slow drift of the rate between seeds).  Or ``{"process":
"all_at_zero", "requests_per_window_s"}``: a closed list, ``n =
round(requests_per_window_s * seconds)``, all due at 0.
"""

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def quantile_lengths(law, n):
    """The n evenly spaced quantiles of a length law, ascending ints."""
    q = (np.arange(n) + 0.5) / n
    dist = law["dist"]
    if dist == "fixed":
        vals = np.full(n, float(law["value"]))
    elif dist == "uniform":
        vals = law["min"] + q * (law["max"] - law["min"])
    elif dist == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(p)) for p in q])
        vals = law["median"] * np.exp(law["sigma"] * z)
    else:
        raise ValueError(f"unknown length law {dist!r}")
    lo = law.get("min", law.get("value"))
    hi = law.get("max", law.get("value"))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def stratified_order(n, block, rng):
    """A permutation of range(n) (ranks of the sorted lengths) in which
    every run of ``block`` consecutive entries draws one rank from each of
    ``block`` equal slices of the ranks."""
    block = max(1, min(int(block), n))
    edges = np.linspace(0, n, block + 1).astype(int)
    strata = [rng.permutation(np.arange(edges[s], edges[s + 1]))
              for s in range(block)]
    out = []
    for j in range(max(len(s) for s in strata)):
        group = np.asarray([s[j] for s in strata if j < len(s)])
        out.extend(rng.permutation(group).tolist())
    return np.asarray(out, np.int64)


def arrival_times(arr, n, seconds, rng):
    if arr["process"] == "all_at_zero":
        return np.zeros(n)
    if arr["process"] != "open_loop":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    per = max(1, int(arr.get("per_slice", n)))
    slices = max(1, math.ceil(n / per))
    edges = np.linspace(0.0, float(seconds), slices + 1)
    counts = np.full(slices, n // slices)
    counts[rng.permutation(slices)[:n - counts.sum()]] += 1
    times = np.concatenate([rng.uniform(edges[i], edges[i + 1], size=c)
                            for i, c in enumerate(counts)])
    return np.sort(times)


def request_count(mix, seconds):
    arr = mix["arrivals"]
    rate = (arr["rate_per_s"] if arr["process"] == "open_loop"
            else arr["requests_per_window_s"])
    return max(1, int(round(rate * seconds)))


def make_requests(mix, seed, seconds, vocab_size):
    """{"prompts": [int32 arrays], "max_new": [ints], "due_s": [floats]},
    in the order they are due."""
    rng = np.random.default_rng(int(seed))
    n = request_count(mix, seconds)
    block = mix.get("order_block", 16)
    p_len = quantile_lengths(mix["prompt_tokens"], n)[
        stratified_order(n, block, rng)]
    o_len = quantile_lengths(mix["output_tokens"], n)[
        stratified_order(n, block, rng)]
    due = arrival_times(mix["arrivals"], n, seconds, rng)
    prompts = [rng.integers(0, vocab_size, size=int(k)).astype(np.int32)
               for k in p_len]
    return {"prompts": prompts, "max_new": [int(k) for k in o_len],
            "due_s": [float(t) for t in due]}


def train_batches(mix, seed, vocab_size, rows):
    """Endless host iterator of {"input_ids": [rows, seq_len] int32}."""
    rng = np.random.default_rng(int(seed))
    skew = float(mix.get("token_skew", 1.0))
    T = int(mix["seq_len"])
    while True:
        u = rng.random((rows, T))
        yield {"input_ids": np.minimum(
            (vocab_size * u ** skew).astype(np.int32), vocab_size - 1)}
