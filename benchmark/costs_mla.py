"""What latent attention (MLA) needs when it is read ABSORBED from a latent
page pool, from shapes and counts: operations and bytes, beside ``costs.py``
and ``costs_moe.py`` (which stay as they are).  The program takes the
absorbed path in decode and in prefill (PERF.md section 6, PR 33), so that
is the need reckoned here; ``expanded_pair_flops`` is what the other path
would need a pair, for the comparison.

A cached token is ONE row a layer, ``latent_dim`` values (the normed latent
and the rotated key part: 512 + 64), key and value at once: a slot that
reads it needs its ``latent_dim * 2`` bytes once a layer a step, whatever
the page stores around them (pad columns read as lost share, not as need).
A (query row, key) pair needs a head ``2 * latent_dim`` operations for the
score and ``2 * value_dim`` for the value (``value_dim`` the latent's 512).
"""


def absorbed_pair_flops(latent_dim, value_dim):
    return 2.0 * (latent_dim + value_dim)


def expanded_pair_flops(qk_head_dim, v_head_dim):
    """Keys and values expanded a head (192 and 128): fewer operations a
    pair, and ``Wkvb`` applied to every token of the context read."""
    return 2.0 * (qk_head_dim + v_head_dim)


def latent_decode_cost(context_tokens, slots, layers, heads, latent_dim,
                       value_dim, bytes_per_el=2):
    """(flops, bytes) of one decode step's latent attention over all
    layers: every cached row of every live sequence once a layer
    (``context_tokens`` summed over them), each against all ``heads`` query
    rows of its slot; q in (``latent_dim`` a head) and o out (``value_dim``)
    once a slot a layer."""
    flops = absorbed_pair_flops(latent_dim, value_dim) * heads \
        * context_tokens * layers
    byts = (latent_dim * context_tokens
            + slots * heads * (latent_dim + value_dim)) * layers \
        * bytes_per_el
    return flops, byts


def latent_prefill_cost(pairs, keys, rows, layers, heads, latent_dim,
                        value_dim, bytes_per_el=2):
    """(flops, bytes) of one mixed step's latent prefill attention over all
    layers: ``pairs`` query-key pairs a causal mask leaves on one layer,
    each for every head; ``keys`` cached rows a layer has to read, once;
    q in and o out for each of the step's ``rows``."""
    flops = absorbed_pair_flops(latent_dim, value_dim) * heads * pairs \
        * layers
    byts = (latent_dim * keys
            + rows * heads * (latent_dim + value_dim)) * layers \
        * bytes_per_el
    return flops, byts
