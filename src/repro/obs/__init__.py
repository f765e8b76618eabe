"""Serving observability: span tracing, a metrics registry, and the
sim-vs-measured drift monitor.

* ``obs.trace`` — :func:`span` marks each engine and executor span site
  with a ``jax.profiler`` annotation (on the device trace's clock inside a
  profiler capture) and, when a :class:`Tracer` is attached, records the
  same span and every request lifecycle phase as Chrome trace-event JSON
  (open a serve run in ``chrome://tracing`` or https://ui.perfetto.dev).
* ``obs.metrics`` — :class:`MetricsRegistry` of counters / gauges /
  histograms with run-vs-lifetime scopes, ``snapshot()`` and Prometheus
  text export; also the one shared percentile/TTFT/ITL helper family the
  benchmarks read.
* ``obs.drift`` — :class:`DriftMonitor` prices each executed serving step
  with the planner's own simulator and histograms the measured/simulated
  ratio, turning the one-off ``experiments/calibrate.py`` loop into a live
  costmodel-drift signal.

Everything here is opt-in on the serving hot path: an engine without a
tracer/drift monitor executes zero telemetry instructions per token, and a
bounded number of profiler annotations per step.
"""
from repro.obs.drift import DriftMonitor
from repro.obs.metrics import (
    Counter, Gauge, Histogram, MetricsRegistry,
    itl_seconds, percentile, percentile_summary,
    ttft_percentiles, ttft_seconds,
)
from repro.obs.trace import RequestTracks, Tracer, span

__all__ = [
    "Tracer", "RequestTracks", "span",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "percentile", "percentile_summary",
    "ttft_seconds", "itl_seconds", "ttft_percentiles",
    "DriftMonitor",
]
