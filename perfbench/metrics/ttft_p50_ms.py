"""Median time to first token over every request due in the window, ms
(timed from the moment the request was due)."""


def read(ctx):
    from perfbench.stat import pct
    return pct(ctx.window.ttft, 50) * 1e3 if ctx.window.ttft else None
