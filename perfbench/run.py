#!/usr/bin/env python3
"""Chip benchmark of the serving system: one cell, one run.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) is one configuration
(``perfbench/configs/<config>.json``, served by the family module it names
under ``perfbench/families/``) under one traffic mix
(``perfbench/traffic/<traffic>.json``).  A metric is a reader of its own,
``perfbench/metrics/<name>.py``.  Adding any of the three is adding files
and ``BENCHMARK.json`` entries.

Set-up makes the weights on the device from the seed, builds the executor,
warms every prefill shape the mix's length bounds and the engine's padding
grain can produce and the decode step, and warms the engine's host path on a
few requests.  Closed-loop clients then drive ``ServingEngine.run()``
(``perfbench/clients.py``): a ramp, the window of ``--seconds``, a drain.
``--trace 0`` reports the end-to-end metrics and adds no profiler and no
host sync; ``--trace 1`` profiles the first seconds of the window and reports
the per-layer metrics.  After the window, a sample of the finished requests
drawn from the seed, the longest among them, is compared with a plain
float32 reference of the same weights: the logits rows the timed path
produced for the sampled requests, held on the device during the window by
the executor wrapper ``check.Tap``, and the tokens it served
(``perfbench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``, each number compared beside its limit; the
same numbers close standard error.  Without an accelerator, or with fewer
chips than the cell asks for, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import math
import os
import sys
from types import SimpleNamespace
from typing import List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# the TPU runtime writes no log files (by default it would, under /tmp)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np

#: the profiled part of a traced run's window, seconds
TRACE_SECONDS = 3.0
#: persistent compilation cache: a fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: traces of ``--trace 1`` runs, inside the checkout
TRACE_DIR = os.path.join(ROOT, ".perfbench", "trace")


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, unknown device, bad cell)."""


def load_module(path: str):
    name = "perfbench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_of(spec: dict, name: str):
    """(workload entry, configuration dict, mix dict) of cell ``name``."""
    from perfbench import traffic

    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    conf = load_json(os.path.join(ROOT, entry["file"]))
    return wl, conf, traffic.load_mix(wl["traffic"])


def metrics_of(spec: dict, kind: str, cell: str) -> List[dict]:
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def peaks_of(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table["devices"]:
        raise BenchError(f"device_kind {kind!r} is not in perfbench/peaks.json")
    return table["devices"][kind]


def accelerator(chips: int):
    """The devices of this run; raises without an accelerator or with fewer
    chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no accelerator: JAX finds {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs


def enable_cache() -> None:
    """Every program of the run goes to the checkout's compilation cache,
    however fast it compiled, so only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def log(*parts) -> None:
    print(*parts, flush=True)


# --- the executor seen by the engine ----------------------------------------------


class Probe:
    """Executor wrapper of a traced run: a host annotation around every
    prefill and decode call, and, while ``recording``, the call's analytic
    operations and bytes (read from the positions the engine passes)."""

    def __init__(self, inner, counts):
        self.inner = inner
        self.counts = counts
        self.recording = False
        self.calls: List[dict] = []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def prefill_paged(self, tokens, pool, block_row, length):
        import jax
        if self.recording:
            self.calls.append(self.counts.prefill(0, int(length)))
        with jax.profiler.TraceAnnotation("perfbench.prefill"):
            return self.inner.prefill_paged(tokens, pool, block_row, length)

    def prefill_chunk(self, tokens, pool, block_row, *, offset, length):
        import jax
        if self.recording:
            n = min(int(length), offset + tokens.shape[1]) - offset
            self.calls.append(self.counts.prefill(int(offset), n))
        with jax.profiler.TraceAnnotation("perfbench.prefill"):
            return self.inner.prefill_chunk(tokens, pool, block_row,
                                            offset=offset, length=length)

    def decode_paged(self, tokens, pool, block_table, positions):
        import jax
        if self.recording:
            live = np.asarray(block_table)[:, 0] != 0
            self.calls.append(self.counts.decode(np.asarray(positions)[live]))
        with jax.profiler.TraceAnnotation("perfbench.decode"):
            return self.inner.decode_paged(tokens, pool, block_table,
                                           positions)


class Counts:
    """The family's analytic counts for one configuration, per call."""

    def __init__(self, family, conf: dict):
        self.f, self.c = family, conf
        self.kvpp = family.kv_bytes_per_position(conf)
        self.lin = family.linear_flops_per_token(conf)

    def prefill(self, offset: int, n: int) -> dict:
        pos = np.arange(offset, offset + n)
        flops = (n * self.lin + sum(self.f.attention_flops(self.c, int(p))
                                    for p in pos) + self.f.unembed_flops(self.c))
        return {"kind": "prefill", "tokens": n, "flops": flops}

    def decode(self, positions) -> dict:
        flops = sum(self.lin + self.f.attention_flops(self.c, int(p))
                    + self.f.unembed_flops(self.c) for p in positions)
        byts = self.f.weight_bytes(self.c) + sum(
            (int(p) + 1) * self.kvpp for p in positions)
        return {"kind": "decode", "tokens": len(positions), "flops": flops,
                "bytes": byts}


# --- set-up -----------------------------------------------------------------------


def _roundup(x: int, m: int) -> int:
    return -(-x // m) * m


def warm(ex, mix: dict) -> int:
    """Run every prefill shape the mix's length bounds and the engine's
    padding grain can produce, and the decode step, once (through
    ``check.Tap``, which warms its slicing programs on them); returns the
    number of shapes."""
    import jax
    import jax.numpy as jnp
    from perfbench import traffic

    eng = mix["engine"]
    ps, max_len = eng["page_size"], eng["max_len"]
    grain = math.lcm(getattr(ex, "prompt_pad_multiple", 1), ps)
    width = _roundup(max_len, grain) // ps
    pages = 1 + eng["max_batch"] * width
    pool = ex.make_pool(pages, ps)
    row = jnp.asarray(np.arange(1, width + 1, dtype=np.int32))
    lo, hi = traffic.length_bounds(mix)
    shapes = 0
    for s in range(_roundup(lo, grain), _roundup(hi, grain) + 1, grain):
        _, pool = ex.prefill_paged(jnp.asarray(np.zeros((1, s), np.int32)),
                                   pool, row, length=s)
        shapes += 1
    if eng["prefix_cache"] and mix["kind"] == "sessions":
        # a hit leaves a grain-aligned suffix of any length the bounds allow
        for s in range(grain, _roundup(hi, grain), grain):
            logits, pool = ex.prefill_chunk(
                jnp.asarray(np.zeros((1, s), np.int32)), pool, row,
                offset=_roundup(hi, grain) - s, length=_roundup(hi, grain))
            logits[:, s - 1]  # the engine's slice of the last prompt row
            shapes += 1
    b = eng["max_batch"]
    table = np.arange(1, b * width + 1, dtype=np.int32).reshape(b, width)
    table = np.minimum(table, pages - 1)
    logits, pool = ex.decode_paged(jnp.asarray(np.zeros((b, 1), np.int32)),
                                   pool, jnp.asarray(table),
                                   jnp.asarray(np.zeros(b, np.int32)))
    jax.block_until_ready((logits, pool))
    return shapes + 1


def warm_engine(executor, mix: dict, vocab: int) -> None:
    """The engine's host path (sampling, key splits) on the first request
    of two clients of the mix, with the cell's engine options."""
    from perfbench import traffic
    from repro.serving import Request, ServingEngine

    e = ServingEngine(executor=executor, scheduler="continuous",
                      record_times=True, **mix["engine"])
    for uid, stream in enumerate(traffic.streams(mix, vocab, 0)[:2]):
        prompt, _ = stream.next(None)
        e.submit(Request(uid=uid, prompt=prompt, max_new_tokens=2))
    e.run()


# --- the run ----------------------------------------------------------------------


def window_samples(loop) -> SimpleNamespace:
    """End-to-end samples of the window: TTFT of every request due in it,
    every inter-token gap that ends in it, the tokens emitted in it."""
    ws, we = loop.window
    due = [r for r in loop.sent if ws <= loop.due[r.uid] < we]
    ttft = [r.token_times[0] - loop.due[r.uid] for r in due if r.token_times]
    itl, tokens = [], 0
    for r in loop.sent:
        ts = r.token_times
        tokens += sum(ws <= t < we for t in ts)
        itl += [b - a for a, b in zip(ts, ts[1:]) if ws <= b < we]
    return SimpleNamespace(requests=due, ttft=ttft, itl=itl, tokens=tokens,
                           seconds=we - ws,
                           failed=sum(not r.output for r in due))


def run_cell(conf: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
             devices, peaks: Optional[dict], e2e: List[dict],
             per_layer: List[dict], control: bool = False,
             wrap=None) -> dict:
    """One run of one cell on ``devices``; returns the result line's dict.
    ``wrap`` (tests) wraps the executor the engine drives."""
    import jax
    from perfbench import check, traffic, xtrace
    from perfbench.clients import ClosedLoop
    from perfbench.meter import CompileMeter
    from perfbench.stalls import GcClock, emission_gaps
    from perfbench.stat import pct
    from repro.serving import ServingEngine

    # the program runs at the matmul precision its configuration states
    prec = conf["matmul_precision"]
    jax.config.update("jax_default_matmul_precision",
                      None if prec == "default" else prec)
    meter = CompileMeter()
    start = meter.mark()
    family = load_module(os.path.join(HERE, "families", conf["family"] + ".py"))
    chips = conf["chips"]
    used = devices[:chips]

    m = meter.mark()
    weights = family.make_weights(conf, seed, used)
    jax.block_until_ready(weights)
    log(f"set-up: weights from seed {seed}: {meter.since(m)}")
    m = meter.mark()
    ex = family.make_system(conf, weights, used)
    probe = Probe(ex, Counts(family, conf)) if trace else None
    served = probe or ex
    if wrap is not None:
        served = wrap(served)
    tap = check.Tap(served, seed, mix["clients"])
    shapes = warm(tap, mix)
    log(f"set-up: executor and {shapes} warmed shapes: {meter.since(m)}")
    m = meter.mark()
    warm_engine(tap, mix, conf["vocab_size"])
    log(f"set-up: engine host path warmed: {meter.since(m)}")

    engine = ServingEngine(executor=tap, scheduler="continuous",
                           record_times=True, **mix["engine"])
    marks = {}
    snap = {}
    trace_dir = os.path.join(TRACE_DIR, f"{conf['name']}-{seed}")
    stat_keys = ("prefill_tokens", "cached_prefix_tokens", "decode_steps",
                 "decode_tokens", "prefix_hits")

    def on_window(what: str) -> None:
        if what == "window_start":
            marks["window"] = meter.mark()
        elif what == "trace_start":
            t = time.perf_counter()
            jax.profiler.start_trace(trace_dir)
            marks["profiler"] = [time.perf_counter() - t]
            marks["annotation"] = jax.profiler.TraceAnnotation(
                "perfbench.window")
            marks["annotation"].__enter__()
            snap["start"] = {k: engine.stats[k] for k in stat_keys}
            probe.recording = True
        elif what == "trace_stop":
            probe.recording = False
            snap["stop"] = {k: engine.stats[k] for k in stat_keys}
            marks["annotation"].__exit__(None, None, None)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            marks["profiler"].append(time.perf_counter() - t)
            log(f"trace: the profiler's start and stop held the engine "
                f"{marks['profiler'][0]:.3f} s and {marks['profiler'][1]:.3f} "
                "s inside the window")

    streams = traffic.streams(mix, conf["vocab_size"], seed)
    loop = ClosedLoop(engine, streams, seconds, on_window=on_window,
                      on_send=tap.choose,
                      trace_seconds=min(TRACE_SECONDS, seconds) if trace else 0)
    tap.engine = engine
    gcs = GcClock()
    reentries = loop.run()
    gcs.close()
    if loop.window is None:
        raise BenchError("the ramp never ended: a client finished nothing")
    in_window = meter.split(marks["window"])
    w = window_samples(loop)
    setup_s = loop.window[0] - T_START
    log(f"set-up: {setup_s:.3f} s from process start to window start; "
        f"of the process's making programs: {meter.since(start)}")
    log(f"window: {w.seconds:.3f} s; {len(w.requests)} requests due, "
        f"{len(w.ttft)} TTFT samples, {len(w.itl)} inter-token gaps, "
        f"{w.tokens} tokens emitted; run() re-entered {reentries} times")
    log(f"window: {in_window['traces']} traces and {in_window['programs']} "
        f"compiles from the window's start to the drain's end (should be 0); "
        f"{shapes} shapes warmed")
    log(f"window: {emission_gaps(loop.sent, *loop.window)}")
    log(f"window: collector {gcs.report(*loop.window)}")
    delays = sorted(loop.delays)
    log(f"window: injection delay p50 {pct(delays, 50) * 1e3:.4f} ms, p95 "
        f"{pct(delays, 95) * 1e3:.4f} ms over {len(delays)} submissions")
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in used) \
        if used[0].platform != "cpu" else 0
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}

    ctx = SimpleNamespace(conf=conf, family=family, chips=chips, peaks=peaks,
                          window=w, setup_s=setup_s, trace=None,
                          counters=None, calls=None, lo=None, hi=None)
    breakdown = None
    if trace:
        tr = xtrace.load(trace_dir)
        lo, hi = tr.window()
        ctx.trace, ctx.lo, ctx.hi = tr, lo, hi
        ctx.counters = {k: snap["stop"][k] - snap["start"][k]
                        for k in stat_keys}
        ctx.calls = probe.calls
        busy = [xtrace.length(xtrace.busy(d, lo, hi)) for d in tr.devices]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = hi - lo
        breakdown = {"device_ops": xtrace.top_ops(tr, lo, hi),
                     "idle_gaps": xtrace.idle_gaps(tr, lo, hi)}
        log(f"trace: {len(tr.devices)} device planes, window {hi - lo:.4f} s, "
            f"busy {device['busy_s']:.4f} s; {len(probe.calls)} calls "
            f"recorded; counters {ctx.counters}")
    metrics = {}
    for spec in (per_layer if trace else e2e):
        value = load_module(os.path.join(HERE, "metrics",
                                         spec["name"] + ".py")).read(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value),
                                     "unit": spec["unit"]}

    # free the program's state before the reference runs
    requests, rows = loop.sent, tap.rows
    del engine, loop, tap, served, probe, ex
    verdict = check.compare(family, conf, weights, requests, rows, seed,
                            max_len=mix["engine"]["max_len"], control=control)
    result = {"correct": verdict["correct"] and w.failed == 0,
              "attempted": len(w.requests), "failed": w.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    result["checks"]["failed_requests"] = {"value": w.failed, "limit": 0}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control's numbers")
    args = ap.parse_args(argv)
    try:
        spec = benchmark()
        wl, conf, mix = cell_of(spec, args.workload)
        devices = accelerator(wl["chips"])
        peaks = peaks_of(devices[0].device_kind)
        enable_cache()
        result = run_cell(conf, mix, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), devices=devices, peaks=peaks,
                          e2e=metrics_of(spec, "end_to_end", wl["name"]),
                          per_layer=metrics_of(spec, "per_layer", wl["name"]),
                          control=bool(args.control))
    except (BenchError, FileNotFoundError, KeyError) as e:
        print(f"perfbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
