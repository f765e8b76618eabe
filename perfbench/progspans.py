"""The program's own spans in a traced run, on the device trace's clock.

The engine and the Galaxy executor mark their host work with profiler
annotations (``repro.obs.trace.span``): ``engine.step`` and its children
(``engine.admit``, ``engine.prefill``, ``engine.decode.prepare``,
``engine.decode.dispatch``, ``engine.sample``, ``engine.sync``,
``engine.emit``...), and ``galaxy.prefill`` / ``galaxy.decode`` around the
executor's jitted calls.  They land on the host plane of the same
``.xplane.pb`` as the device's programs; ``xtrace.from_profile`` keeps only
the harness's ``perfbench.`` annotations, so this module reads these, with
their stats, and splits the first device's idle time by the innermost
program span the host was in.

The profile puts host and device events on one timeline, but not exactly on
one clock: on a TPU v5e the device's decode runs start up to 1.5 ms before
the host's ``galaxy.decode`` dispatching them has returned, which no run
can do.  ``device_lead`` reads that offset from the trace, and the split can
be taken with the device shifted back by it.

A program that predates these spans has none in its trace: ``of_run`` then
gives an empty ``Spans`` and the readers return nothing.  All times are
seconds, as in ``xtrace``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import xtrace

Interval = Tuple[float, float]

PREFIXES = ("engine.", "galaxy.")
WINDOW = xtrace.HOST_PREFIX + "window"
#: where ``perfbench/run.py`` writes a traced run's profile,
#: ``<config name>-<seed>/``
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), ".perfbench", "trace")
OUTSIDE = "outside"


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    stats: Dict[str, object]
    line: int                # host line (thread) the span ran on

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Spans:
    spans: List[Span]        # by start
    window: Optional[Interval]

    def __post_init__(self):
        self._starts = [s.start for s in self.spans]

    def named(self, name: str, lo: float, hi: float) -> List[Span]:
        """Spans called ``name`` that start in [lo, hi)."""
        return [s for s in self.spans if s.name == name and lo <= s.start < hi]

    def inside(self, parent: Span, name: str) -> List[Span]:
        """Spans called ``name`` nested in ``parent`` (same thread)."""
        i = bisect.bisect_left(self._starts, parent.start)
        j = bisect.bisect_right(self._starts, parent.end)
        return [s for s in self.spans[i:j] if s is not parent
                and s.name == name and s.line == parent.line
                and s.end <= parent.end]


def from_profile(pd) -> Spans:
    spans, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for n, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                if name == WINDOW:
                    window = (e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                elif name.startswith(PREFIXES):
                    spans.append(Span(e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      name, dict(e.stats), n))
    spans.sort(key=lambda s: (s.start, -s.end))
    return Spans(spans, window)


def load(pattern: str) -> Spans:
    """The spans of the newest ``.xplane.pb`` under the directories that
    ``pattern`` matches."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(pattern, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {pattern}")
    return from_profile(ProfileData.from_file(max(files, key=os.path.getmtime)))


def of_run(ctx) -> Spans:
    """The program spans of the traced run ``ctx`` describes; raises unless
    the file's ``perfbench.window`` is the window ``ctx`` was read over."""
    ps = load(os.path.join(TRACE_DIR, f"{ctx.conf['name']}-*"))
    if ps.window is None or abs(ps.window[0] - ctx.lo) > 1e-9 \
            or abs(ps.window[1] - ctx.hi) > 1e-9:
        raise RuntimeError(f"the newest trace's window {ps.window} is not "
                           f"the run's ({ctx.lo}, {ctx.hi})")
    return ps


# --- idle time by span ------------------------------------------------------------


def _innermost(spans: Sequence[Span], lo: float, hi: float
               ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut at every span boundary, each piece named by the
    innermost span covering it (the one begun last), else ``outside``."""
    pts = sorted({lo, hi} | {min(max(t, lo), hi) for s in spans
                             for t in (s.start, s.end)})
    order = sorted(spans, key=lambda s: s.start)
    i, active, out = 0, [], []
    for a, b in zip(pts, pts[1:]):
        while i < len(order) and order[i].start <= a:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.end > a]
        inner = max(active, key=lambda s: (s.start, -s.end), default=None)
        out.append((a, b, inner.name if inner else OUTSIDE))
    return out


def device_lead(device, spans: Sequence[Span], lo: float, hi: float
                ) -> Optional[float]:
    """How far the device's clock runs ahead of the host's: the median, over
    the decode runs that start in [lo, hi], of the end of the
    ``galaxy.decode`` span that dispatched the run less the run's start.
    On one clock it is at most about zero; None unless the window holds one
    span per run."""
    runs = [m for m in device.program_runs("decode") if lo <= m.start < hi]
    calls = [s for s in spans if s.name == "galaxy.decode"
             and lo <= s.start < hi]
    if not runs or len(runs) != len(calls):
        return None
    return statistics.median(c.end - r.start for c, r in zip(calls, runs))


def _split(device, spans: Sequence[Span], lo: float, hi: float,
           shift: float):
    """(idle interval, span name, seconds of it under that span), for every
    idle interval of ``device`` in [lo, hi], its ops moved by ``shift``."""
    ops = [(a + shift, b + shift)
           for a, b in xtrace.busy(device, lo - shift, hi - shift)]
    idle = xtrace.subtract([(lo, hi)], ops)
    segs = _innermost(spans, lo, hi)
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            ov = min(b, segs[k][1]) - max(a, segs[k][0])
            if ov > 0:
                yield (a, b), segs[k][2], ov
            k += 1


def idle_by_span(device, spans: Sequence[Span], lo: float, hi: float,
                 shift: float = 0.0) -> Dict[str, float]:
    """The device's idle time in [lo, hi], split by the innermost program
    span covering it; ``outside`` is idle time no span covers.  ``shift``
    moves the device's ops later by that many seconds first (a
    ``device_lead``)."""
    out: Dict[str, float] = {}
    for _, name, sec in _split(device, spans, lo, hi, shift):
        out[name] = out.get(name, 0.0) + sec
    return out


def longest_gaps(device, spans: Sequence[Span], lo: float, hi: float,
                 shift: float = 0.0, n: int = 5
                 ) -> List[Tuple[float, float, str]]:
    """The ``n`` longest idle intervals: (start - lo, length, the span that
    covers most of it), the device's ops moved by ``shift`` as above."""
    gaps: Dict[Interval, Dict[str, float]] = {}
    for gap, name, sec in _split(device, spans, lo, hi, shift):
        by = gaps.setdefault(gap, {})
        by[name] = by.get(name, 0.0) + sec
    top = sorted(gaps.items(), key=lambda kv: kv[0][0] - kv[0][1])[:n]
    return [(a - lo, b - a, max(by, key=by.get)) for (a, b), by in top]
