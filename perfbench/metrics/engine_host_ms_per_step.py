"""Host time of the engine per decode step in the traced window, ms: the
mean, over the ``engine.step`` spans that start in the window and hold an
``engine.decode.dispatch``, of the step's length less its ``engine.sync``
children (the host waiting for the device's tokens).  Read from the
program's own spans (``perfbench/progspans.py``); a program without them
gives nothing.  Also prints the first device's idle time per decode step,
split by the innermost program span, as the profile's clocks give it and
with the device shifted back by its lead, and the longest idle gaps."""
from perfbench import progspans, xtrace


def _split(split, runs):
    return ", ".join(f"{k} {v / runs * 1e3:.4f}" for k, v in
                     sorted(split.items(), key=lambda kv: -kv[1]))


def read(ctx):
    ps = progspans.of_run(ctx)
    if not ps.spans:
        return None
    _, runs = xtrace.program_seconds(ctx.trace, "decode", ctx.lo, ctx.hi)
    dev, lo, hi = ctx.trace.devices[0], ctx.lo, ctx.hi
    lead = progspans.device_lead(dev, ps.spans, lo, hi)
    shift = max(lead or 0.0, 0.0)
    print(f"progspans: device idle per decode step by innermost span (ms, "
          f"{runs} decode runs), as the profile's clocks give it: "
          f"{_split(progspans.idle_by_span(dev, ps.spans, lo, hi), max(runs, 1))}",
          flush=True)
    print(f"progspans: the device's clock leads the host's by "
          f"{lead * 1e3 if lead is not None else float('nan'):.4f} ms; with "
          f"the device shifted back by {shift * 1e3:.4f} ms: " + _split(
              progspans.idle_by_span(dev, ps.spans, lo, hi, shift),
              max(runs, 1)), flush=True)
    print("progspans: longest idle gaps, shifted (s into the window, ms, "
          "span): " + ", ".join(
              f"({t:.4f}, {d * 1e3:.4f}, {n})" for t, d, n in
              progspans.longest_gaps(dev, ps.spans, lo, hi, shift)),
          flush=True)
    steps = [s for s in ps.named("engine.step", lo, hi)
             if ps.inside(s, "engine.decode.dispatch")]
    if not steps:
        if runs:
            raise RuntimeError(f"{runs} decode runs in the trace, but no "
                               "engine.step span holds a decode dispatch")
        return None
    host = [s.dur - sum(c.dur for c in ps.inside(s, "engine.sync"))
            for s in steps]
    return sum(host) / len(host) * 1e3
