"""Host seconds spent making programs, from JAX's own monitoring events."""
from __future__ import annotations

import time

import jax


class CompileMeter:
    """Tracing and lowering to MLIR, and the backend compile, which a
    persistent-cache hit replaces by a read.  ``traces`` counts every trace,
    so a window that made no program leaves it unchanged."""

    LOWER = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.lower = []  # (start, end) spans; nested traces overlap
        self.compile = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile += duration
            self.programs += 1

    def _span(self, event, start, end, **_):
        if event in self.LOWER:
            self.lower.append((start, end))
            self.traces += event == self.LOWER[0]

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (time.perf_counter(), len(self.lower), self.compile,
                self.programs, self.cache_hits, self.traces)

    def split(self, mark) -> dict:
        t0, l0, c0, p0, h0, n0 = mark
        wall = time.perf_counter() - t0
        lower, end = 0.0, float("-inf")
        for a, b in sorted(self.lower[l0:]):  # length of the spans' union
            lower += max(0.0, b - max(a, end))
            end = max(end, b)
        comp = self.compile - c0
        return {"wall": wall, "trace_lower": lower, "compile": comp,
                "programs": self.programs - p0,
                "cache_hits": self.cache_hits - h0,
                "traces": self.traces - n0,
                "rest": wall - lower - comp}

    def since(self, mark) -> str:
        s = self.split(mark)
        return (f"wall {s['wall']:.2f} s = trace+lower {s['trace_lower']:.2f} s"
                f" + compile {s['compile']:.2f} s ({s['programs']} programs, "
                f"{s['cache_hits']} persistent-cache hits, {s['traces']} traces)"
                f" + rest {s['rest']:.2f} s")
