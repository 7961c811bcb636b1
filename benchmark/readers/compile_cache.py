"""Programs jax asked its compile cache for: misses over the whole run
(each one compiled), and requests of either kind inside the window (a shape
the warm-up did not visit)."""


def read(ctx, spec):
    c = ctx["compiles"]
    return {"misses": c.misses, "in_window": c.in_window}[spec["what"]]
