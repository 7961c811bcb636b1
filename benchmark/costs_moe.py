"""What the afmoe (Trinity) kernels need, from shapes and counts: operations
and bytes, beside ``costs.py`` (which stays as it is).  Nothing here counts
what an implementation re-reads or pads: a roofline share is needed work
over peak over measured time.
"""


def expert_gemm_cost(local_assignments, touched_experts, hidden, expert_dim,
                     bytes_per_el=2):
    """(flops, bytes) of the grouped expert GEMMs of SwiGLU experts, summed
    over whatever steps and layers the two counts cover:
    ``local_assignments`` rows went to experts held here (each through the
    gate, up and down matrices: 3 products of 2 * hidden * expert_dim) and
    ``touched_experts`` (expert, layer, step) triples had at least one row,
    each of whose three matrices is read once; rows go in and out of each
    product (hidden in and expert_dim out twice, expert_dim in and hidden
    out once).  An expert no row chose needs nothing."""
    flops = 2.0 * 3.0 * hidden * expert_dim * local_assignments
    byts = (3.0 * hidden * expert_dim * touched_experts
            + 3.0 * (hidden + expert_dim) * local_assignments) * bytes_per_el
    return flops, byts


def paged_decode_window_cost(ctx_global, ctx_window, global_layers,
                             window_layers, heads, kv_heads, head_dim, slots,
                             bytes_per_el=2):
    """(flops, bytes) of one decode step's paged attention over all layers
    of a model with window and global layers: a global layer reads every
    cached key and value (``ctx_global`` tokens summed over the live
    sequences), a window layer the last ``window`` of each
    (``ctx_window`` = sum of min(context, window)); q and o once per slot
    and layer; 2 matmuls of 2 * head_dim per head per key read."""
    keys = ctx_global * global_layers + ctx_window * window_layers
    flops = 2.0 * 2.0 * heads * head_dim * keys
    byts = (2.0 * kv_heads * head_dim * keys
            + 2.0 * slots * heads * head_dim
            * (global_layers + window_layers)) * bytes_per_el
    return flops, byts


def ragged_prefill_window_cost(pairs_global, pairs_window, keys_global,
                               keys_window, rows, global_layers,
                               window_layers, heads, kv_heads, head_dim,
                               bytes_per_el=2):
    """(flops, bytes) of one mixed step's ragged prefill attention over all
    layers: ``pairs_*`` are the query-key pairs a causal (and windowed)
    mask leaves to score on one layer of the kind, 2 matmuls of 2 *
    head_dim per head per pair; ``keys_*`` the cached tokens a layer of the
    kind has to read, keys and values once each; q in and o out for each of
    the step's ``rows``."""
    pairs = pairs_global * global_layers + pairs_window * window_layers
    keys = keys_global * global_layers + keys_window * window_layers
    flops = 2.0 * 2.0 * heads * head_dim * pairs
    byts = (2.0 * kv_heads * head_dim * keys
            + 2.0 * rows * heads * head_dim
            * (global_layers + window_layers)) * bytes_per_el
    return flops, byts
