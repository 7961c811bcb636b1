"""What the algorithms need, from shapes: operations and bytes.  The
yardstick's arithmetic lives here so that no later PR can move it.  Nothing
here counts recomputation (remat, a backward kernel's second look at the
scores): a roofline share is needed work over peak over measured time.
"""


def train_flops_per_token(n_params, layers, hidden, seq_len):
    """Forward and backward of a dense decoder, per trained token:
    6 per parameter (2 forward, 4 backward) plus attention's two matmuls
    over the sequence, 12 * L * H * T (PaLM, appendix B; causal masking not
    discounted, as the convention has it)."""
    return 6.0 * n_params + 12.0 * layers * hidden * seq_len


def flash_attention_train_cost(batch, heads, kv_heads, seq_len, head_dim,
                               bytes_per_el=2):
    """(flops, bytes) one causal flash-attention layer needs for forward
    and backward on ``batch`` sequences: 2 matmuls forward and 5 backward
    (FlashAttention, sec. 3.1: dS needs S again, which the algorithm
    recomputes; that sixth and seventh matmul is the kernel's choice, not
    the algorithm's need), each 2*T*T*d per head, halved by the causal mask.
    Bytes: q, k, v, o once forward; q, k, v, o, dO in and dq, dk, dv out
    backward."""
    per_matmul = 2.0 * seq_len * seq_len * head_dim / 2.0
    flops = batch * heads * 7.0 * per_matmul
    q_like = batch * heads * seq_len * head_dim * bytes_per_el
    kv_like = batch * kv_heads * seq_len * head_dim * bytes_per_el
    fwd = 2 * q_like + 2 * kv_like
    bwd = 3 * q_like + 2 * kv_like + q_like + 2 * kv_like
    return flops, float(fwd + bwd)


def paged_decode_cost(context_tokens, heads, kv_heads, head_dim, slots,
                      bytes_per_el=2):
    """(flops, bytes) one paged decode-attention layer needs for one new
    token in each live sequence: every cached key and value is read once
    (``context_tokens`` summed over the live sequences), q and o once per
    slot; 2 matmuls of 2*d per head per cached token."""
    flops = 2.0 * 2.0 * heads * head_dim * context_tokens
    byts = (2.0 * kv_heads * head_dim * context_tokens
            + 2.0 * slots * heads * head_dim) * bytes_per_el
    return flops, byts


def roofline_share(flops, byts, seconds, peaks):
    """(share in %, "compute" or "memory"): the least time the chip could
    take over the time it took."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
