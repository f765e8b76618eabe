"""The stall report: emission gaps of a window and the collector's passes."""
import gc
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import stalls  # noqa: E402


def test_emission_gaps_count_only_the_window():
    # two requests stepping together every 0.05 s, one 1.5 s stall at 10.0,
    # and a 2 s gap that ends after the window
    ts = [10.0 + 0.05 * i for i in range(40)]
    ts = [t for t in ts if t < 11.0] + [t + 1.5 for t in ts if t >= 11.0]
    reqs = [SimpleNamespace(token_times=ts),
            SimpleNamespace(token_times=[t + 1e-4 for t in ts] + [20.0])]
    text = stalls.emission_gaps(reqs, 10.0, 13.0, top=1)
    assert text.startswith("1 emission gaps over 0.25 s, 1.550 s in all")
    assert "longest: 1.550 s at 1.0 s" in text


def test_gc_clock_times_passes_by_generation():
    clock = stalls.GcClock()
    try:
        gc.collect(2)
    finally:
        clock.close()
    assert clock not in gc.callbacks
    assert [g for _, _, g in clock.passes] == [2]
    t, d, _ = clock.passes[0]
    assert d >= 0
    assert "gen2 1 passes" in clock.report(t, t + 1)
    assert "gen2 0 passes" in clock.report(t + 1, t + 2)
