"""95th percentile of every gap between consecutive output tokens that ends
in the window, ms (nearest rank)."""


def read(ctx):
    from perfbench.stat import pct
    return pct(ctx.window.itl, 95) * 1e3 if ctx.window.itl else None
