"""Device time of the prefill programs over the prompt tokens they computed
in the traced window, ms per 1000 tokens (mean over the cell's chips)."""
from perfbench import xtrace


def read(ctx):
    tokens = ctx.counters["prefill_tokens"]
    if not tokens:
        return None
    secs, runs = xtrace.program_seconds(ctx.trace, "prefill", ctx.lo, ctx.hi)
    if not runs:
        raise RuntimeError("the trace holds no run of the prefill program, "
                           "but the engine computed prompt tokens")
    return secs / tokens * 1e6
