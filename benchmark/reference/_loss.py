"""Mean next-token cross-entropy, shared by the plain references: sequence by
sequence, ``block`` rows of float32 logits at a time."""

import jax
import jax.numpy as jnp


def mean_next_token_loss(hidden_of, logits_of, batch, block=1024):
    """``hidden_of(seq) -> x [T, H]``; ``logits_of(x_rows) -> [rows, V]``."""
    total, count = 0.0, 0
    for seq in batch:
        x = hidden_of(seq)
        T = len(seq)
        for a in range(0, T - 1, block):
            b = min(a + block, T - 1)
            lp = jax.nn.log_softmax(logits_of(x[a:b]), -1)
            tgt = jnp.asarray(seq[a + 1:b + 1])
            total += float(-jnp.take_along_axis(lp, tgt[:, None], 1).sum())
            count += b - a
    return total / count
