"""Least time of the decode calls in the traced window over their device
time, %.  A call's least time is the larger of its operations over the
chips' bf16 peak and its bytes over their HBM bandwidth; bytes are the
weights read once, the K/V of the live positions and the row written
(``perfbench/run.py:Counts``)."""
from perfbench import xtrace


def read(ctx):
    calls = [c for c in ctx.calls if c["kind"] == "decode"]
    secs, runs = xtrace.program_seconds(ctx.trace, "decode", ctx.lo, ctx.hi)
    if not calls:
        return None
    if runs != len(calls):
        raise RuntimeError(f"{len(calls)} decode calls on the host, {runs} "
                           "decode runs in the trace")
    n, p = ctx.chips, ctx.peaks
    least = sum(max(c["flops"] / (n * p["bf16_flops"]),
                    c["bytes"] / (n * p["hbm_bytes_per_s"])) for c in calls)
    return 100.0 * least / secs
