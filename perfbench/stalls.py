"""Where a window's host time went missing: the longest gaps between token
emissions, and the time the Python collector spent in the window.

Both are read after the window from what the run keeps anyway (the token
stamps) and from ``gc.callbacks``, which costs a clock read per pass.
"""
from __future__ import annotations

import gc
import time
from typing import List, Tuple

#: an emission gap longer than this is a stall (a step takes about 0.05 s)
STALL_S = 0.25


class GcClock:
    """Every pass of the collector while installed: (start, seconds, gen)."""

    def __init__(self):
        self.passes: List[Tuple[float, float, int]] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.passes.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def report(self, ws: float, we: float) -> str:
        parts = []
        for gen in range(3):
            ds = [d for t, d, g in self.passes if g == gen and ws <= t < we]
            parts.append(f"gen{gen} {len(ds)} passes {sum(ds):.4f} s "
                         f"(longest {max(ds, default=0.0):.4f} s)")
        return "; ".join(parts)


def emission_gaps(requests, ws: float, we: float, top: int = 5) -> str:
    """The window's gaps between consecutive token emissions of any request:
    how many exceed ``STALL_S``, their sum, and the longest with their
    offset into the window."""
    ts = sorted({t for r in requests for t in r.token_times if ws <= t < we})
    gaps = sorted(((b - a, a - ws) for a, b in zip(ts, ts[1:])), reverse=True)
    long = [g for g, _ in gaps if g > STALL_S]
    head = ", ".join(f"{g:.3f} s at {at:.1f} s" for g, at in gaps[:top])
    return (f"{len(long)} emission gaps over {STALL_S} s, {sum(long):.3f} s "
            f"in all; longest: {head}")
