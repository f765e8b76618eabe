"""The Galaxy executor's host time per decode call in the traced window,
ms: the mean length of the ``galaxy.decode`` spans that start in it (pytree
flattening of the weights and the pool, and the dispatch of the jitted
step).  Read from the program's own spans (``perfbench/progspans.py``); a
program without them gives nothing."""
from perfbench import progspans, xtrace


def read(ctx):
    ps = progspans.of_run(ctx)
    if not ps.spans:
        return None
    spans = ps.named("galaxy.decode", ctx.lo, ctx.hi)
    _, runs = xtrace.program_seconds(ctx.trace, "decode", ctx.lo, ctx.hi)
    if len(spans) != runs:
        raise RuntimeError(f"{len(spans)} galaxy.decode spans on the host, "
                           f"{runs} decode runs in the trace")
    if not spans:
        return None
    return sum(s.dur for s in spans) / len(spans) * 1e3
