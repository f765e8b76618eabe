"""Model operations of every prompt and output token processed in the traced
window over (window x chips x bf16 peak), %.  The bf16 peak is the
denominator whatever the configuration's dtype."""


def read(ctx):
    if not ctx.calls:
        return None
    flops = sum(c["flops"] for c in ctx.calls)
    return 100.0 * flops / ((ctx.hi - ctx.lo) * ctx.chips
                            * ctx.peaks["bf16_flops"])
