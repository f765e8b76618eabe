"""Serving observability (``repro.obs``): tracer, metrics registry, drift
monitor, and their engine integration.

The acceptance bars from the engine side:

* a traced serve run exports well-formed Chrome trace-event JSON with zero
  open spans, and the per-request phase spans cover >= 95 % of every
  request's submit→retire wall time — asserted on BOTH executors (the
  model-zoo path in-process, the 4-device uneven Galaxy plan in a
  subprocess);
* greedy tokens are bitwise identical with telemetry on or off;
* with telemetry disabled the engine executes ZERO tracer / histogram
  calls per token and a bounded number of profiler annotations per step,
  none per token (structural gate — call counting, not wall clock);
* the engine's profiler spans nest in their ``engine.step`` and arrive on
  a profiler capture's host plane with their stats;
* stats no longer silently persist across ``run()`` calls on a reused
  engine: ``reset_stats()`` zeroes the run scope, lifetime survives.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.obs import (
    DriftMonitor, MetricsRegistry, RequestTracks, Tracer,
    itl_seconds, percentile, percentile_summary, ttft_percentiles,
)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from helpers import smoke_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- metrics registry ---------------------------------------------------------

def test_counter_scopes_and_monotonicity():
    reg = MetricsRegistry()
    c = reg.counter("decode_steps")
    c.inc()
    c.inc(4)
    assert c.value == 5 and c.lifetime == 5
    c.set_run(9)  # the stats-facade write path (read + assign)
    assert c.value == 9 and c.lifetime == 9
    with pytest.raises(ValueError, match="may not decrease"):
        c.set_run(3)
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)
    reg.reset_run()
    assert c.value == 0 and c.lifetime == 9
    c.inc(2)
    assert c.value == 2 and c.lifetime == 11


def test_gauge_and_histogram_scopes():
    reg = MetricsRegistry()
    g = reg.gauge("queue_depth")
    g.set(3)
    g.set_max(1)  # peak tracking keeps the max
    assert g.value == 3
    g.set_max(7)
    assert g.value == 7

    h = reg.histogram("ttft_s")
    for v in (1.0, 2.0, 2.0, 10.0):
        h.observe(v)
    assert h.count == 4
    assert h.percentile(50) == 2.0
    assert h.value_counts() == {1.0: 1, 2.0: 2, 10.0: 1}
    reg.reset_run()
    assert h.count == 0 and g.value == 0
    assert h.percentile(50, scope="lifetime") == 2.0
    s = h.summary(scope="lifetime")
    assert s["n"] == 4 and s["min"] == 1.0 and s["max"] == 10.0


def test_registry_collision_snapshot_prometheus():
    reg = MetricsRegistry()
    reg.counter("requests").inc(3)
    reg.gauge("kv_pool_occupancy").set(0.5)
    reg.histogram("itl_s").observe(0.25)
    with pytest.raises(ValueError, match="different kind"):
        reg.gauge("requests")
    assert "requests" in reg and "nope" not in reg

    snap = reg.snapshot()
    assert snap["scope"] == "run"
    assert snap["counters"]["requests"] == 3
    assert snap["gauges"]["kv_pool_occupancy"] == 0.5
    assert snap["histograms"]["itl_s"]["n"] == 1
    with pytest.raises(ValueError):
        reg.snapshot(scope="bogus")

    text = reg.to_prometheus()
    assert "# TYPE repro_requests_total counter" in text
    assert "repro_requests_total 3" in text
    assert "# TYPE repro_kv_pool_occupancy gauge" in text
    assert "# TYPE repro_itl_s summary" in text
    assert 'repro_itl_s{quantile="0.5"} 0.25' in text
    assert "repro_itl_s_count 1" in text


def test_shared_latency_helpers_and_bench_wrapper():
    class R:
        def __init__(self, submit, times):
            self.submit_time = submit
            self.token_times = times

    reqs = [R(0.0, [1.0, 1.5, 2.5]), R(1.0, [1.2]), R(None, []), R(0.5, [])]
    assert percentile([], 50) != percentile([], 50)  # NaN on empty
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert percentile_summary([1.0, 2.0])["p50"] == 1.0  # nearest-rank
    assert itl_seconds(reqs) == [0.5, 1.0]
    out = ttft_percentiles(reqs)
    assert set(out) == {"p50", "p95", "n"} and out["n"] == 2
    assert out["p50"] == pytest.approx(0.2) and out["p95"] == 1.0

    # benchmarks/run.py keeps its historic entry point as a thin wrapper
    sys.path.insert(0, REPO)
    try:
        from benchmarks.run import ttft_percentiles as bench_ttft
        assert bench_ttft(reqs) == out
    finally:
        sys.path.remove(REPO)


# --- tracer -------------------------------------------------------------------

def _fake_clock(times):
    it = iter(times)
    return lambda: next(it)


def test_tracer_chrome_json_wellformed():
    # clock: t0, begin a, begin b, end b, instant, end a
    tr = Tracer(clock=_fake_clock([0.0, 1e-6, 2e-6, 5e-6, 6e-6, 9e-6]))
    tr.begin("engine", "outer", step=1)
    tr.begin("engine", "inner")
    tr.end("engine")
    tr.instant("engine", "mark")
    tr.end("engine", tokens=3)

    obj = tr.to_json()
    assert set(obj) == {"traceEvents", "displayTimeUnit"}
    meta = [e for e in obj["traceEvents"] if e["ph"] == "M"]
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["outer", "inner"]  # sorted by ts
    for e in spans:
        assert {"name", "cat", "ph", "pid", "tid", "ts", "dur", "args"} <= set(e)
        assert e["dur"] >= 0
    outer, inner = spans
    # strict nesting: inner lies within outer on the same track
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"step": 1, "tokens": 3}
    inst = [e for e in obj["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "mark"


def test_tracer_open_span_export_and_stack_errors():
    tr = Tracer(clock=_fake_clock([0.0, 1e-6, 2e-6, 3e-6]))
    with pytest.raises(RuntimeError, match="no open span"):
        tr.end("engine")
    tr.begin("engine", "loop")
    assert tr.open_spans() == [(tr.tid("engine"), "loop")]
    with pytest.raises(RuntimeError, match="open spans"):
        tr.to_json()
    assert tr.to_json(allow_open=True)["traceEvents"]
    tr.end("engine")
    assert tr.open_spans() == []


def test_tracer_negative_clock_clamped():
    tr = Tracer(clock=_fake_clock([0.0, 5e-6, 3e-6]))  # clock goes backwards
    tr.begin("t", "s")
    tr.end("t")
    [e] = [e for e in tr.to_json()["traceEvents"] if e["ph"] == "X"]
    assert e["dur"] == 0.0


def test_request_tracks_phase_discipline():
    tr = Tracer()
    tk = RequestTracks(tr)
    tk.submit(7)
    with pytest.raises(ValueError, match="already tracked"):
        tk.submit(7)
    tk.phase(7, "prefill", slot=0)
    with pytest.raises(ValueError, match="monotone"):
        tk.phase(7, "prefill")
    tk.event(7, "spec_rollback", rejected=2)
    tk.phase(7, "decode")
    assert tk.is_open(7) and tk.open_uids() == [7]
    tk.finish(7, tokens=4)
    assert not tk.is_open(7) and tk.open_uids() == []
    with pytest.raises(ValueError, match="not in an open phase"):
        tk.finish(7)
    names = [e["name"] for e in tr.to_json()["traceEvents"]
             if e["ph"] == "X"]
    assert names == ["queued", "prefill", "decode"]


def test_request_tracks_random_interleavings_property():
    """Random admit/retire/phase/spec-event interleavings over many
    requests never leave an open or out-of-order span."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.lists(st.tuples(st.integers(0, 7), st.integers(0, 99)),
                        max_size=200))
    @hyp.settings(deadline=None, max_examples=50)
    def run(ops):
        tr = Tracer()
        tk = RequestTracks(tr)
        state = {}  # uid -> phase index (None = retired)
        for uid, r in ops:
            ph = state.get(uid, -1)
            if ph == -1:
                tk.submit(uid)
                state[uid] = 0
            elif ph is None:
                continue  # retired uids never come back
            elif r % 4 == 0 or ph == 2:
                tk.finish(uid, tokens=r)  # retire from any phase
                state[uid] = None
            elif r % 4 == 1:
                tk.event(uid, "spec_rollback", rejected=r)
            else:
                nxt = min(2, ph + (2 if r % 8 == 7 else 1))  # may skip
                tk.phase(uid, RequestTracks.PHASES[nxt])
                state[uid] = nxt
        for uid in list(tk.open_uids()):
            tk.finish(uid)
        assert tr.open_spans() == []
        obj = tr.to_json()  # raises on any un-closed span
        by_tid = {}
        for e in obj["traceEvents"]:
            if e["ph"] == "X":
                by_tid.setdefault(e["tid"], []).append(e)
        for evs in by_tid.values():
            evs.sort(key=lambda e: e["ts"])
            for a, b in zip(evs, evs[1:]):
                assert a["dur"] >= 0
                # phases tile: each span ends where the next begins (or
                # earlier) — never out of order
                assert a["ts"] + a["dur"] <= b["ts"] + 1e-9

    run()


# --- drift monitor ------------------------------------------------------------

def test_drift_monitor_ratios_and_summary():
    reg = MetricsRegistry()
    mon = DriftMonitor(lambda kind, rows, context: 0.5 if kind != "nope"
                       else None, registry=reg)
    assert mon.observe("decode", 1.0, rows=1, context=8) == 2.0
    assert mon.observe("prefill_chunk", 0.25, rows=4, context=8,
                       synced=False) == 0.5
    assert mon.observe("nope", 1.0) is None  # unpriceable: skipped
    assert mon.observe("decode", -1.0) is None  # clock glitch: skipped
    assert len(mon.records) == 2

    s = mon.summary()
    assert s["decode"]["n"] == 1 and s["decode"]["p50"] == 2.0
    assert s["prefill_chunk_dispatch"]["p50"] == 0.5
    assert s["all"]["n"] == 1 and s["all_dispatch"]["n"] == 1
    snap = reg.snapshot()
    assert snap["histograms"]["sim_drift_ratio"]["n"] == 1
    assert snap["histograms"]["sim_drift_ratio_prefill_chunk_dispatch"]["n"] == 1


def test_make_step_pricer_matches_simulator():
    from repro.core import costmodel
    from repro.core.execplan import ExecPlan
    from repro.core.simulator import make_step_pricer, simulate_execplan

    cfg = smoke_cfg("qwen1.5-0.5b")
    ep = ExecPlan.even(2, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                       head_dim=cfg.head_dim, d_model=cfg.d_model)
    devices = [costmodel.jetson_nano("nano-l", 4.0) for _ in range(2)]
    link = costmodel.mbps(1000)
    price = make_step_pricer(ep, cfg, devices, link)

    t = price("decode", rows=1, context=32)
    assert t == simulate_execplan(ep, cfg, devices, link, 32,
                                  cached_prefix=31).latency
    assert price("spec_verify", rows=5, context=32) == simulate_execplan(
        ep, cfg, devices, link, 32, cached_prefix=27).latency
    assert price("decode", rows=1, context=32) == t  # memoized
    assert price("decode", rows=0, context=32) is None
    assert price("decode", rows=4, context=2) is None
    assert price("draft", rows=3, context=8) is None  # no draft_cfg bound

    with pytest.raises(ValueError, match="devices"):
        make_step_pricer(ep, cfg, devices[:1], link)


# --- engine integration (model-zoo executor, in-process) ----------------------

@pytest.fixture(scope="module")
def zoo():
    from repro.models import init_params
    from repro.serving import TransformerExecutor

    cfg = smoke_cfg("qwen1.5-0.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return TransformerExecutor(params, cfg)  # shared jit caches


def _requests():
    from repro.serving import Request
    return [Request(uid=i, prompt=[1 + (i * 7 + j) % 200 for j in range(6)],
                    max_new_tokens=8 if i % 2 == 0 else 3)
            for i in range(5)]


def _engine(zoo, **kw):
    from repro.serving import ServingEngine
    kw.setdefault("scheduler", "continuous")
    return ServingEngine(executor=zoo, max_batch=2, max_len=32, page_size=8,
                         **kw)


def _span_coverage(tracer, done):
    obj = tracer.to_json()
    spans = [e for e in obj["traceEvents"] if e.get("ph") == "X"]
    names = {e["tid"]: e["args"]["name"] for e in obj["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    cov = {}
    for r in done:
        tid = next(t for t, n in names.items() if n == f"req {r.uid}")
        track = [e for e in spans if e["tid"] == tid]
        lo = min(e["ts"] for e in track)
        hi = max(e["ts"] + e["dur"] for e in track)
        cov[r.uid] = (sum(e["dur"] for e in track) / (hi - lo)
                      if hi > lo else 1.0)
    return obj, spans, cov


def test_traced_serve_zoo_acceptance(zoo):
    """The tentpole acceptance on the zoo executor: faithful trace,
    populated snapshot, tokens bitwise-unchanged by telemetry."""
    tracer = Tracer()
    eng = _engine(zoo, tracer=tracer, record_times=True, prefix_cache=True,
                  prefill_chunk=4)
    for r in _requests():
        eng.submit(r)
    done = eng.run()
    assert len(done) == 5

    assert tracer.open_spans() == []
    obj, spans, cov = _span_coverage(tracer, done)
    assert min(cov.values()) >= 0.95  # phases tile submit->retire
    for e in spans:
        assert e["dur"] >= 0 and e["ts"] >= 0
    kinds = {e["name"] for e in spans}
    assert {"queued", "decode"} <= kinds
    assert "engine.prefill" in kinds

    snap = eng.metrics.snapshot()
    assert snap["histograms"]["ttft_s"]["n"] == 5
    assert snap["histograms"]["itl_s"]["n"] == sum(
        len(r.output) - 1 for r in done)
    assert snap["gauges"]["kv_pages_peak"] > 0
    assert snap["gauges"]["kv_pages_used"] == 0  # everything retired
    assert 0 <= snap["gauges"]["prefix_hit_rate"] <= 1
    assert snap["counters"]["decode_tokens"] > 0
    assert "spec_accepted_per_round" in snap["histograms"]

    # telemetry off: identical greedy tokens
    eng2 = _engine(zoo, prefix_cache=True, prefill_chunk=4)
    for r in _requests():
        eng2.submit(r)
    done2 = eng2.run()
    assert ({r.uid: tuple(r.output) for r in done}
            == {r.uid: tuple(r.output) for r in done2})


def test_traced_serve_wave_scheduler(zoo):
    from repro.serving import Request
    tracer = Tracer()
    eng = _engine(zoo, tracer=tracer, record_times=True, scheduler="wave")
    for r in _requests():
        eng.submit(r)
    # a zero-budget request must retire with a closed (rejected) span
    eng.submit(Request(uid=99, prompt=list(range(1, 33)), max_new_tokens=4))
    done = eng.run()
    assert tracer.open_spans() == []
    _, spans, cov = _span_coverage(tracer, [r for r in done if r.output])
    assert min(cov.values()) >= 0.95
    assert "engine.prefill" in {e["name"] for e in spans}
    rejected = [e for e in spans if e["args"].get("rejected")]
    assert len(rejected) == 1 and rejected[0]["name"] == "queued"


class _Annotations:
    """Stands in for the profiler annotations ``obs.trace.span`` enters:
    records each span with its arguments and the names of the spans open
    around it."""

    def __init__(self, monkeypatch):
        self.spans, self._open = [], []
        rec = self

        class Annotation:
            def __init__(self, name, **args):
                self.name, self.args = name, dict(args)

            def __enter__(self):
                self.parents = tuple(rec._open)
                rec._open.append(self.name)
                rec.spans.append(self)
                return self

            def __exit__(self, *exc):
                assert rec._open.pop() == self.name

            def set_metadata(self, **args):
                self.args.update(args)

        monkeypatch.setattr(obs_trace, "TraceAnnotation", Annotation)
        monkeypatch.setattr(obs_trace, "StepTraceAnnotation", Annotation)

    def named(self, name):
        return [a for a in self.spans if a.name == name]


class _Calls:
    """The executor, counting its model calls."""

    def __init__(self, inner):
        self.inner, self.n = inner, 0

    def __getattr__(self, name):
        attr = getattr(self.inner, name)
        if name in ("prefill_paged", "prefill_chunk", "decode_paged"):
            def call(*a, **k):
                self.n += 1
                return attr(*a, **k)
            return call
        return attr


def _step_children(ann):
    """Spans under each engine.step, by step, and the spans under none."""
    steps, outside = [], []
    for a in ann.spans:
        if a.name == "engine.step":
            steps.append([])
        elif "engine.step" in a.parents:
            steps[-1].append(a)
        else:
            outside.append(a)
    return steps, outside


def test_engine_spans_nest_in_steps_one_sync_per_model_call(zoo, monkeypatch):
    """One engine.step per loop iteration, every other span inside a step
    but the run's first admission, one engine.sync per model call, and a
    fixed number of spans per step whatever the number of live slots."""
    ann = _Annotations(monkeypatch)
    calls = _Calls(zoo)
    eng = _engine(calls)
    for r in _requests():
        eng.submit(r)
    done = eng.run()
    steps, outside = _step_children(ann)
    # no chunking: every iteration decodes the slots its admission filled
    assert len(steps) == eng.stats["decode_steps"] > 0
    assert [a.args["step_num"] for a in ann.named("engine.step")] == list(
        range(len(steps)))
    assert len(ann.named("engine.sync")) == calls.n
    assert len(ann.named("engine.sample")) == calls.n
    # the run's first admission precedes the loop
    assert outside[0].name == "engine.admit"
    assert all("engine.admit" in a.parents for a in outside[1:])
    for kids in steps:
        admitted = sum(a.args["admitted"] for a in kids
                       if a.name == "engine.admit")
        names = sorted(a.name for a in kids)
        # decode: prepare, dispatch, sample, sync, emit; one admission pass;
        # prefill, sample, sync per request admitted; none per token
        assert len(names) == 6 + 3 * admitted, names
        for name in ("engine.decode.prepare", "engine.decode.dispatch",
                     "engine.emit", "engine.admit"):
            assert names.count(name) == 1
        assert names.count("engine.prefill") == admitted
    assert sum(len(r.output) for r in done) > len(steps)  # tokens > steps

    # chunked prefill and the prefix cache: still nothing outside a step
    # but the first admission pass
    ann.spans.clear()
    eng = _engine(zoo, prefix_cache=True, prefill_chunk=4)
    for r in _requests():
        eng.submit(r)
    eng.run()
    steps, outside = _step_children(ann)
    assert outside[0].name == "engine.admit"
    assert all("engine.admit" in a.parents for a in outside[1:])
    assert ann.named("engine.prefix_lookup")
    assert sum(k.name == "engine.prefill" for kids in steps for k in kids) > 0
    # a mid-prompt chunk is not synced: one sync per prompt and decode step
    assert len(ann.named("engine.sync")) == 5 + eng.stats["decode_steps"]


def test_engine_spans_reach_a_profiler_capture(zoo, tmp_path):
    """A short CPU capture: the spans land on the host plane with their
    stats, as the benchmark's reader of them parses them."""
    sys.path.insert(0, REPO)
    from perfbench import progspans
    from repro.serving import Request

    eng = _engine(zoo)
    eng.submit(Request(uid=0, prompt=[3, 4, 5], max_new_tokens=3))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("perfbench.window"):
            eng.run()
    ps = progspans.load(str(tmp_path))
    lo, hi = ps.window
    steps = ps.named("engine.step", lo, hi)
    assert [s.stats["step_num"] for s in steps] == [0, 1]
    for s in steps:
        assert len(ps.inside(s, "engine.decode.dispatch")) == 1
        assert len(ps.inside(s, "engine.sync")) == 1
    admit = ps.named("engine.admit", lo, hi)[0]
    assert admit.stats["admitted"] == 1 and admit.stats["queue"] == 0
    assert ps.named("engine.decode.dispatch", lo, hi)[0].stats["live"] == 1


def test_disabled_telemetry_is_structurally_free(zoo, monkeypatch):
    """Tier-1 overhead gate: with no tracer and no record_times, serving a
    full mix executes ZERO tracer calls and ZERO histogram observations —
    counted at the class level, not timed — and a bounded number of
    profiler annotations per engine step."""
    calls = []
    ann = _Annotations(monkeypatch)

    def counting(cls, name):
        orig = getattr(cls, name)

        def wrapped(self, *a, **k):
            calls.append((cls.__name__, name))
            return orig(self, *a, **k)
        monkeypatch.setattr(cls, name, wrapped)

    for m in ("begin", "end", "instant", "tid"):
        counting(obs_trace.Tracer, m)
    counting(obs_metrics.Histogram, "observe")

    eng = _engine(zoo)
    for r in _requests():
        eng.submit(r)
    done = eng.run()
    assert sum(len(r.output) for r in done) > 0
    assert calls == []
    assert eng._trace is None and eng._tracks is None
    # per step at most 6 spans of its own and 3 per request it admits
    steps = len(ann.named("engine.step"))
    assert steps > 0
    assert len(ann.spans) <= 7 * steps + 3 * len(done) + 1

    # a *disabled* tracer is treated exactly like no tracer
    eng2 = _engine(zoo, tracer=Tracer(enabled=False))
    for r in _requests():
        eng2.submit(r)
    eng2.run()
    assert calls == []


def test_stats_facade_and_reset_regression(zoo):
    """Regression for the stats-accumulation bug: a reused engine's stats
    silently summed across run() calls; reset_stats() scopes them per run
    while the registry keeps lifetime totals."""
    eng = _engine(zoo)
    for r in _requests():
        eng.submit(r)
    done1 = eng.run()
    toks1 = sum(len(r.output) for r in done1)
    assert eng.stats["requests"] == 5
    # each request's first token comes from the prefill logits
    assert eng.stats["decode_tokens"] == toks1 - 5

    # without reset: the historic (buggy-looking) accumulation, now at
    # least explicit in the lifetime scope
    eng.reset_stats()
    assert eng.stats["requests"] == 0
    assert eng.stats["decode_tokens"] == 0
    assert eng.metrics.snapshot("lifetime")["counters"]["requests"] == 5

    for r in _requests():
        eng.submit(r)
    done2 = eng.run()
    assert eng.stats["requests"] == 5  # this run only
    assert eng.stats["decode_tokens"] == sum(len(r.output) for r in done2) - 5
    assert eng.metrics.snapshot("lifetime")["counters"]["requests"] == 10

    # facade contract: mapping behavior + derived keys are read-only
    assert set(dict(eng.stats)) == set(eng.stats.keys())
    assert eng.stats == dict(eng.stats)
    with pytest.raises(TypeError, match="derived"):
        eng.stats["spec_acceptance"] = 1.0
    with pytest.raises(TypeError):
        del eng.stats["requests"]
    with pytest.raises(KeyError):
        eng.stats["bogus"]


def test_drift_monitor_engine_integration(zoo):
    """A constant-price pricer sees every decode step and prefill chunk,
    and drift histograms land in the engine's own registry."""
    priced = []

    def pricer(kind, *, rows, context):
        priced.append((kind, rows, context))
        return 1e-3

    eng = _engine(zoo, drift=DriftMonitor(pricer), prefill_chunk=4)
    for r in _requests():
        eng.submit(r)
    done = eng.run()
    kinds = {k for k, _, _ in priced}
    assert kinds == {"decode", "prefill_chunk"}
    assert len(eng.drift.records) == len(priced)
    assert all(rec["ratio"] > 0 for rec in eng.drift.records)
    snap = eng.metrics.snapshot()
    assert snap["histograms"]["sim_drift_ratio"]["n"] == len(priced)
    assert snap["histograms"]["sim_drift_ratio_decode"]["n"] > 0

    # drift never perturbs tokens either
    eng2 = _engine(zoo, prefill_chunk=4)
    for r in _requests():
        eng2.submit(r)
    done2 = eng2.run()
    assert ({r.uid: tuple(r.output) for r in done}
            == {r.uid: tuple(r.output) for r in done2})


# --- galaxy executor (4-device uneven plan, subprocess) -----------------------

def test_traced_serve_galaxy_acceptance():
    """The same acceptance bar through the Galaxy HMP executor: an uneven
    3:2:2:1 plan on 4 forced CPU devices, traced end to end — >= 95 % span
    coverage, tokens bitwise-unchanged by telemetry."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"  # a CPU emulation never competes for a chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
    import jax
    from repro.core import hmp
    from repro.core.execplan import ExecPlan
    from repro.launch.mesh import make_mesh_compat
    from repro.obs import Tracer
    from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

    ep = ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8),
                  head_dim=2, d_model=32, seq_shares=(3.0, 2.0, 2.0, 1.0))
    mesh = make_mesh_compat((4,), ('model',))
    layers = hmp.init_stack_params(jax.random.PRNGKey(0), 2, 32, 16, 64)
    emb = jax.random.normal(jax.random.PRNGKey(7), (300, 32)) * 0.5
    executor = GalaxyHMPExecutor(layers, emb, ep, mesh)

    def requests():
        return [Request(uid=i,
                        prompt=[1 + (i * 5 + j) % 250 for j in range(6 + i)],
                        max_new_tokens=6 if i % 2 == 0 else 3)
                for i in range(4)]

    def run(tracer):
        eng = ServingEngine(executor=executor, max_batch=2, max_len=40,
                            scheduler='continuous', page_size=8,
                            tracer=tracer, record_times=tracer is not None)
        for r in requests():
            eng.submit(r)
        return eng, eng.run()

    tracer = Tracer()
    eng, done = run(tracer)
    assert tracer.open_spans() == []
    obj = tracer.to_json()
    spans = [e for e in obj['traceEvents'] if e.get('ph') == 'X']
    names = {e['tid']: e['args']['name'] for e in obj['traceEvents']
             if e.get('ph') == 'M' and e['name'] == 'thread_name'}
    for r in done:
        tid = next(t for t, n in names.items() if n == f'req {r.uid}')
        track = [e for e in spans if e['tid'] == tid]
        lo = min(e['ts'] for e in track)
        hi = max(e['ts'] + e['dur'] for e in track)
        assert hi == lo or sum(e['dur'] for e in track) / (hi - lo) >= 0.95

    snap = eng.metrics.snapshot()
    assert snap['histograms']['ttft_s']['n'] == 4
    assert snap['gauges']['kv_pages_peak'] > 0

    _, done_off = run(None)
    assert ({r.uid: tuple(r.output) for r in done}
            == {r.uid: tuple(r.output) for r in done_off})
    print('GALAXY-OBS-OK', len(spans))
    """)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    assert "GALAXY-OBS-OK" in proc.stdout
