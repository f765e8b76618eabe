"""Device time of the routed experts (ops under the ``moe.experts`` scope)
per run of the decode program in the traced window, ms (mean over the
cell's chips); an error where the decode runs hold no op of the scope."""
from perfbench import opscopes


def read(ctx):
    secs, runs = opscopes.scoped_seconds(ctx, "moe.experts", "decode")
    if secs is None or not runs:
        return None
    return secs / runs * 1e3
