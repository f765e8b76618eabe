"""The routed experts' least time over their device time in the decode
runs of the traced window, %.  A step's least time is the larger of its
operations over the chips' bf16 peak (6 · d · moe width per token-expert
pair computed on this chip) and its bytes over their HBM bandwidth (the
weights of each held expert that got a token that step, read once); both
from the family's record of the step's expert ids
(``galaxy_mla_moe.decode_window``).  Device time: ops under
``moe.experts``.  Nothing for a family without that record."""
from perfbench import opscopes


def read(ctx):
    f = ctx.family
    window = getattr(f, "decode_window", None)
    steps = window(ctx) if window else None
    if not steps:
        return None
    secs, runs = opscopes.scoped_seconds(ctx, "moe.experts", "decode")
    if secs is None or not runs:
        return None
    n, p = ctx.chips, ctx.peaks
    least = [max(f.expert_flops(ctx.conf, here) / (n * p["bf16_flops"]),
                 f.expert_bytes(ctx.conf, active) / (n * p["hbm_bytes_per_s"]))
             for here, active, _ in steps]
    # the window's edges may cut one span from its run: a mean step
    return 100.0 * sum(least) / len(least) * runs / secs
