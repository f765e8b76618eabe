"""Device time of the decode program over its runs in the traced window, ms
(mean over the cell's chips)."""
from perfbench import xtrace


def read(ctx):
    secs, runs = xtrace.program_seconds(ctx.trace, "decode", ctx.lo, ctx.hi)
    if not runs:
        if ctx.counters["decode_steps"]:
            raise RuntimeError("the trace holds no run of the decode program")
        return None
    return secs / runs * 1e3
