"""End-to-end model FLOP/s utilisation of a training cell: the operations
forward and backward need per token (``costs.train_flops_per_token``, no
recomputation counted) times the tokens per second per chip that the host
clock read over the untraced part of this run's window, over the chip's bf16
peak from ``peaks.json``.  Not a roofline share of anything."""


def read(ctx, spec):
    if not ctx.get("peaks") or "flops_per_token" not in ctx:
        return None
    return (100.0 * ctx["flops_per_token"] * ctx["rate_untraced"]
            / ctx["peaks"]["bf16_flops_per_s"])
