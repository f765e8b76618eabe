"""Span tracing: where did this request's milliseconds go?

Two sinks, one call per span site.  :func:`span` always enters a
``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` for an engine
step) under a stable name: nearly free when no profiler runs (about a
microsecond), and inside a ``jax.profiler`` capture an event on the host
plane of the same ``.xplane.pb`` as the device's programs and ops, on the
profiler's clock, with the span's keyword arguments as event stats.  When a
:class:`Tracer` is passed and enabled, the same call also records the span
in the Tracer's Chrome trace-event JSON (the ``traceEvents`` array format:
open a serve run in ``chrome://tracing`` or https://ui.perfetto.dev).
Tracing is on exactly when a profiler session is running or a Tracer is
attached; there is no other switch.

Span names are fixed, since readers key on them (``perfbench/progspans.py``):

* ``engine.step`` (``step_num``) — one continuous-scheduler iteration;
  inside it ``engine.admit`` (args ``admitted``, ``queue``),
  ``engine.prefix_lookup``, ``engine.prefill`` (one prompt chunk's host
  work, executor call included), ``engine.decode.prepare`` (tokens,
  positions, block table and their transfer), ``engine.decode.dispatch``
  (the executor call), ``engine.sample`` (RNG split and the sampling
  dispatch), ``engine.sync`` (the one place the host blocks on the device:
  the sampled tokens' readback), ``engine.emit`` (the per-slot emit/retire
  loop, one span per step), ``engine.spec_round`` / ``engine.spec_propose``
  / ``engine.spec_verify`` (speculative rounds);
* ``galaxy.prefill`` / ``galaxy.decode`` — the Galaxy executor's own host
  time around its jitted call (pytree flattening and dispatch).

The Tracer's JSON clock is a private ``perf_counter`` origin, so only the
profiler's events line up with the device trace.

Model of the JSON
-----------------
* One *process* (``pid``) per tracer; one *thread track* (``tid``) per
  label — the engine's spans go on the ``"engine"`` track, and one
  ``"req <uid>"`` track per request holds its lifecycle phases (queued →
  prefill → decode, :class:`RequestTracks`), which tile the request's
  submit→retire wall time contiguously.  Request phases overlap across
  requests and do not nest on the engine thread, so they live in the JSON
  only, never in the profiler.
* Spans follow strict stack discipline per track: :meth:`Tracer.begin`
  pushes, :meth:`Tracer.end` pops and emits one *complete* event
  (``ph="X"`` with ``ts``/``dur`` in microseconds).  Stack discipline makes
  un-nested or out-of-order spans unrepresentable, and durations are
  clamped at >= 0 against clock quirks.
* :meth:`Tracer.instant` marks zero-duration events (submit, rollback).

Overhead discipline: tracing never synchronizes the device (no
``block_until_ready``); span boundaries land on the host-side dispatch
points the engine already passes through.  The engine only calls the
Tracer when one was passed and is enabled, so a run without one executes
zero Tracer instructions, and a bounded number of annotations per engine
step, never one per token (both gated structurally in
``tests/test_obs.py``).

:class:`RequestTracks` is the small per-request phase bookkeeper the
engine drives (and the hypothesis property test in ``tests/test_obs.py``
hammers with random admit/retire/spec interleavings): phases are strictly
sequential per request, every transition closes the previous phase, and
``finish`` closes whatever is open — so a tracer owned by an engine ends
every run with zero open spans.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple, Union

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["Tracer", "RequestTracks", "span"]


class Tracer:
    """Structured span recorder with Chrome trace-event export."""

    def __init__(self, *, enabled: bool = True, pid: int = 1,
                 process_name: str = "repro-serving", clock=None):
        self.enabled = enabled
        self.pid = pid
        self._clock = clock or time.perf_counter
        self._t0 = self._clock()
        self._events: List[dict] = []
        self._tids: Dict[str, int] = {}
        # per-tid stack of open spans: (name, cat, ts_us, args)
        self._open: Dict[int, List[Tuple[str, str, float, dict]]] = {}
        self._meta: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": process_name},
        }]

    # --- clock / tracks -----------------------------------------------------
    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def tid(self, track: Union[str, int]) -> int:
        """Stable integer track id for a label (creates the track and its
        ``thread_name`` metadata on first use)."""
        if isinstance(track, int):
            return track
        t = self._tids.get(track)
        if t is None:
            t = self._tids[track] = len(self._tids) + 1
            self._meta.append({
                "ph": "M", "name": "thread_name", "pid": self.pid, "tid": t,
                "args": {"name": track},
            })
        return t

    # --- spans --------------------------------------------------------------
    def begin(self, track: Union[str, int], name: str, cat: str = "serve",
              **args) -> None:
        tid = self.tid(track)
        self._open.setdefault(tid, []).append(
            (name, cat, self._now_us(), dict(args)))

    def end(self, track: Union[str, int], **extra_args) -> None:
        tid = self.tid(track)
        stack = self._open.get(tid)
        if not stack:
            raise RuntimeError(f"end() on track {track!r} with no open span")
        name, cat, ts, args = stack.pop()
        if extra_args:
            args.update(extra_args)
        self._events.append({
            "name": name, "cat": cat, "ph": "X", "pid": self.pid, "tid": tid,
            "ts": ts, "dur": max(0.0, self._now_us() - ts), "args": args,
        })

    @contextmanager
    def span(self, track: Union[str, int], name: str, cat: str = "serve",
             **args):
        self.begin(track, name, cat, **args)
        try:
            yield self
        finally:
            self.end(track)

    def instant(self, track: Union[str, int], name: str, cat: str = "serve",
                **args) -> None:
        self._events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t", "pid": self.pid,
            "tid": self.tid(track), "ts": self._now_us(), "args": dict(args),
        })

    # --- export -------------------------------------------------------------
    def open_spans(self) -> List[Tuple[int, str]]:
        """(tid, name) of every span begun but not yet ended."""
        return [(tid, frame[0])
                for tid, stack in self._open.items() for frame in stack]

    @property
    def events(self) -> List[dict]:
        return list(self._events)

    def to_json(self, *, allow_open: bool = False) -> dict:
        """The ``chrome://tracing`` / Perfetto JSON object.

        Raises if spans are still open (an engine bug — every lifecycle
        path must close its spans) unless ``allow_open=True``.
        """
        if not allow_open and self.open_spans():
            raise RuntimeError(
                f"trace export with open spans: {self.open_spans()}"
            )
        return {
            "traceEvents": self._meta + sorted(
                self._events, key=lambda e: e["ts"]),
            "displayTimeUnit": "ms",
        }

    def write(self, path: str, *, allow_open: bool = False) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(allow_open=allow_open), f)


class RequestTracks:
    """Per-request lifecycle phases over a :class:`Tracer`.

    Drives one track per request uid through the strictly sequential phase
    chain ``queued -> prefill -> decode -> (closed)``; every transition
    closes the previous phase at the same timestamp it opens the next, so
    the phases tile submit→retire wall time with no gaps (the >=95 %
    span-coverage acceptance gate) and no request ever retires with an
    open span.
    """

    PHASES = ("queued", "prefill", "decode")

    def __init__(self, tracer: Tracer):
        self.tracer = tracer
        self._phase: Dict[int, Optional[str]] = {}

    def _track(self, uid: int) -> str:
        return f"req {uid}"

    def submit(self, uid: int) -> None:
        if uid in self._phase:
            raise ValueError(f"request {uid} already tracked")
        self.tracer.instant(self._track(uid), "submit")
        self.tracer.begin(self._track(uid), "queued", uid=uid)
        self._phase[uid] = "queued"

    def phase(self, uid: int, name: str, **args) -> None:
        """Advance to ``name``, closing the currently open phase.  Phases
        may be skipped but never revisited (monotone along ``PHASES``)."""
        cur = self._phase.get(uid)
        if cur is None:
            raise ValueError(f"request {uid} is not in an open phase")
        if self.PHASES.index(name) <= self.PHASES.index(cur):
            raise ValueError(
                f"request {uid}: phase {name!r} after {cur!r} is not monotone"
            )
        self.tracer.end(self._track(uid))
        self.tracer.begin(self._track(uid), name, uid=uid, **args)
        self._phase[uid] = name

    def event(self, uid: int, name: str, **args) -> None:
        """Zero-duration marker on the request's track (rollback, eviction)."""
        if self._phase.get(uid) is None:
            raise ValueError(f"request {uid} is not in an open phase")
        self.tracer.instant(self._track(uid), name, **args)

    def finish(self, uid: int, **args) -> None:
        """Close the open phase (retirement — from any phase)."""
        if self._phase.get(uid) is None:
            raise ValueError(f"request {uid} is not in an open phase")
        self.tracer.end(self._track(uid), **args)
        self._phase[uid] = None

    def is_open(self, uid: int) -> bool:
        return self._phase.get(uid) is not None

    def open_uids(self) -> List[int]:
        return [uid for uid, ph in self._phase.items() if ph is not None]


class _TracedSpan:
    """A span site with a Tracer attached: the profiler annotation and the
    Tracer's span open and close together."""

    __slots__ = ("_ann", "_tracer", "_name", "_args", "_end_args")

    def __init__(self, ann, tracer: Tracer, name: str, args: dict):
        self._ann = ann
        self._tracer = tracer
        self._name = name
        self._args = args
        self._end_args: dict = {}

    def __enter__(self):
        self._ann.__enter__()
        self._tracer.begin("engine", self._name, **self._args)
        return self

    def __exit__(self, *exc):
        self._tracer.end("engine", **self._end_args)
        return self._ann.__exit__(*exc)

    def set_metadata(self, **args) -> None:
        """Arguments known only at the span's end (both sinks)."""
        self._ann.set_metadata(**args)
        self._end_args.update(args)


def span(name: str, tracer: Optional[Tracer] = None, *,
         step: Optional[int] = None, **args):
    """Context manager for one span site.

    Always a ``jax.profiler`` annotation named ``name`` with ``args`` as its
    stats (``StepTraceAnnotation`` with ``step_num=step`` when ``step`` is
    given); with an enabled ``tracer``, also a span on its ``"engine"``
    track.  The object entered has ``set_metadata(**args)`` for arguments
    known only at the end."""
    ann = (TraceAnnotation(name, **args) if step is None
           else StepTraceAnnotation(name, step_num=step, **args))
    if tracer is None or not tracer.enabled:
        return ann
    if step is not None:
        args["step_num"] = step
    return _TracedSpan(ann, tracer, name, args)
