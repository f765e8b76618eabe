"""1 - (union of device operation intervals / traced window), %, mean over
the cell's chips."""
from perfbench import xtrace


def read(ctx):
    idle = [1.0 - xtrace.length(xtrace.busy(d, ctx.lo, ctx.hi))
            / (ctx.hi - ctx.lo) for d in ctx.trace.devices]
    return 100.0 * sum(idle) / len(idle)
