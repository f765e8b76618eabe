"""The latent attention core's least time over its device time in the
decode runs of the traced window, %.  A step's least time is the larger of
its operations over the chips' bf16 peak (absorbed scores over the latent
and RoPE lanes and the weighted latents, every head and layer, per live
position) and its bytes over their HBM bandwidth (each live position's
latent and RoPE key, 576 lanes of 4 B a layer, read once); from the
family's record of the step's block tables and positions
(``galaxy_mla_moe.decode_window``).  Device time: ops under
``mla.attention``.  Nothing for a family without that record."""
from perfbench import opscopes


def read(ctx):
    f = ctx.family
    window = getattr(f, "decode_window", None)
    steps = window(ctx) if window else None
    if not steps:
        return None
    secs, runs = opscopes.scoped_seconds(ctx, "mla.attention", "decode")
    if secs is None or not runs:
        return None
    n, p = ctx.chips, ctx.peaks
    least = [max(f.mla_core_flops(ctx.conf, live) / (n * p["bf16_flops"]),
                 f.mla_core_bytes(ctx.conf, live) / (n * p["hbm_bytes_per_s"]))
             for _, _, live in steps]
    return 100.0 * sum(least) / len(least) * runs / secs
