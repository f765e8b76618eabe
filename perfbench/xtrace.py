"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

``jax.profiler.ProfileData`` reads the file with JAX alone.  A device plane
(``/device:TPU:<n>``) has a line of module executions (``XLA Modules``: one
event per run of a compiled program, named after the jitted function, as
``jit_decode(17)``) and a line of the operations inside them (``XLA Ops``).
The host plane holds the harness's annotations (``jax.profiler.
TraceAnnotation``) on the same clock.  All times here are seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
#: an op is a collective by its own name or opcode, never by an operand's
#: name: TPU op events carry the whole HLO instruction,
#: ``%fusion.3 = f32[8,128]{...} fusion(%all-reduce.2, ...), kind=...``
_COLL = r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
COLLECTIVE = re.compile(rf"^%?{_COLL}|\s{_COLL}(-start|-done)?\(")
HOST_PREFIX = "perfbench."


def op_label(text: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0} fusion(...)`` -> ``fusion.3 f32[8,128]``:
    the instruction's name and its result type without layout."""
    name, _, rest = text.partition(" = ")
    rest = re.sub(r"\{[^}]*\}", "", rest)
    shape = rest[:rest.find(")") + 1] if rest.startswith("(") \
        else rest.split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape}".strip()


def program_name(module: str) -> str:
    """``jit_decode(17)`` -> ``decode``; ``jit_prefill.3`` -> ``prefill``."""
    name = re.sub(r"\(.*\)$", "", module.strip())
    name = re.sub(r"^jit_", "", name)
    return re.sub(r"\.\d+$", "", name)


@dataclasses.dataclass
class Event:
    start: float
    end: float
    name: str

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Event]
    ops: List[Event]

    def program_runs(self, program: str) -> List[Event]:
        return [m for m in self.modules if program_name(m.name) == program]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]        # the harness's annotations, prefix stripped

    def window(self) -> Interval:
        """The traced window: the ``window`` annotation, else the span of
        every event."""
        for e in self.host:
            if e.name == "window":
                return e.start, e.end
        evs = [e for d in self.devices for e in d.ops] + self.host
        return min(e.start for e in evs), max(e.end for e in evs)


def _events(line) -> List[Event]:
    return [Event(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def from_profile(pd) -> Trace:
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            modules, ops = [], []
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    modules += _events(line)
                elif line.name in OP_LINES:
                    ops += _events(line)
            devices.append(Device(plane.name, sorted(modules, key=lambda e: e.start),
                                  sorted(ops, key=lambda e: e.start)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in _events(line):
                    if e.name.startswith(HOST_PREFIX):
                        e.name = e.name[len(HOST_PREFIX):]
                        host.append(e)
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(2)))
    return Trace(devices, sorted(host, key=lambda e: e.start))


def load(directory: str) -> Trace:
    """The newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_profile(ProfileData.from_file(max(files, key=os.path.getmtime)))


# --- interval arithmetic ----------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the union ``a`` not covered by the union ``b``."""
    out, j = [], 0
    b = list(b)
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# --- per-device readings ----------------------------------------------------------


def busy(dev: Device, lo: float, hi: float) -> List[Interval]:
    """Union of the device's operation intervals inside [lo, hi]."""
    return clip(union((e.start, e.end) for e in dev.ops), lo, hi)


def program_seconds(trace: Trace, program: str, lo: float, hi: float
                    ) -> Tuple[float, int]:
    """Mean over devices of the device time of ``program``'s runs that start
    in [lo, hi], and the number of runs (on the first device)."""
    secs, runs = [], None
    for dev in trace.devices:
        rs = [m for m in dev.program_runs(program) if lo <= m.start < hi]
        secs.append(sum(m.dur for m in rs))
        runs = len(rs) if runs is None else runs
    return sum(secs) / len(secs), runs or 0


def exposed_collective(dev: Device, program: str, lo: float, hi: float
                       ) -> Optional[Tuple[float, float]]:
    """(collective seconds no compute op overlaps, program seconds) over the
    runs of ``program`` on one device; None when it has no run."""
    runs = [m for m in dev.program_runs(program) if lo <= m.start < hi]
    if not runs:
        return None
    spans = union((m.start, m.end) for m in runs)
    starts = [a for a, _ in spans]
    inside = []
    for e in dev.ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < spans[i][1]:
            inside.append(e)
    coll = union((e.start, e.end) for e in inside if COLLECTIVE.search(e.name))
    comp = union((e.start, e.end) for e in inside
                 if not COLLECTIVE.search(e.name))
    return length(subtract(coll, comp)), length(spans)


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The device operations that took most time, summed by program and
    name, mean over devices."""
    tot: Dict[str, float] = {}
    for dev in trace.devices:
        spans = [(m.start, m.end, program_name(m.name)) for m in dev.modules]
        j = 0
        for e in dev.ops:
            if not lo <= e.start < hi:
                continue
            while j < len(spans) and spans[j][1] <= e.start:
                j += 1
            prog = spans[j][2] if j < len(spans) and spans[j][0] <= e.start \
                else "?"
            key = f"{prog}/{op_label(e.name)}"
            tot[key] = tot.get(key, 0.0) + e.dur / len(trace.devices)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10
              ) -> List[Tuple[str, float]]:
    """The longest gaps in which the first device ran nothing, each named by
    the harness annotation that overlapped it most (``engine`` where none
    did: the rest of the engine loop)."""
    dev = trace.devices[0]
    gaps = subtract([(lo, hi)], busy(dev, lo, hi))
    out = []
    for a, b in gaps:
        best, name = 0.0, "engine"
        for e in trace.host:
            if e.name == "window":
                continue
            ov = min(b, e.end) - max(a, e.start)
            if ov > best:
                best, name = ov, e.name
        out.append((name, b - a))
    return sorted(out, key=lambda kv: -kv[1])[:n]
