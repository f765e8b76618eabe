"""The program's spans in a trace (``perfbench/progspans.py``) and the two
readers of them, on a small synthetic trace written as an ``.xplane.pb``."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import progspans, xtrace  # noqa: E402
from perfbench.run import load_module  # noqa: E402

US = 1_000_000  # picoseconds per microsecond

#: one device: decode runs at 10-60 and 100-150 us, ops cover them whole
DEVICE = [("XLA Modules", [("jit_decode(1)", 10, 50, {}),
                           ("jit_decode(1)", 100, 50, {})]),
          ("XLA Ops", [("fusion.1", 10, 50, {}), ("fusion.1", 100, 50, {})])]


def _step(at, n):
    """An engine step at ``at`` us: the decode's host work, the executor's
    call under the harness's annotation, the wait for the tokens (ending
    at ``at`` + 55), the emission and an admission pass."""
    return [("engine.step", at, 85, {"step_num": n}),
            ("engine.decode.prepare", at, 2, {}),
            ("engine.decode.dispatch", at + 2, 4, {"live": 2}),
            ("perfbench.decode", at + 3, 3, {}),
            ("galaxy.decode", at + 3, 2, {}),
            ("engine.sample", at + 6, 1, {}),
            ("engine.sync", at + 7, 48, {}),
            ("engine.emit", at + 55, 10, {}),
            ("engine.admit", at + 65, 20, {"admitted": 0, "queue": 0})]


def _plane(pid, name, lines):
    names = sorted({e[0] for _, evs in lines for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    keys = sorted({k for _, evs in lines for e in evs for k in e[3]})
    stat = {k: i + 1 for i, k in enumerate(keys)}
    body = []
    for lid, (ln, evs) in enumerate(lines, 1):
        body.append(f'lines {{ id: {lid} name: "{ln}" timestamp_ns: 0')
        for ev, start, dur, stats in evs:
            st = " ".join(f"stats {{ metadata_id: {stat[k]} int64_value: {v} }}"
                          for k, v in stats.items())
            body.append(f"  events {{ metadata_id: {meta[ev]} offset_ps: "
                        f"{start * US} duration_ps: {dur * US} {st} }}")
        body.append("}")
    body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
             for n, i in meta.items()]
    body += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
             for n, i in stat.items()]
    return f'planes {{ id: {pid} name: "{name}"\n' + "\n".join(body) + "\n}"


def _text(host):
    return "\n".join([_plane(1, "/device:TPU:0", DEVICE),
                      _plane(9, "/host:CPU", [("python", host)])])


def _host(window=(0, 195)):
    return ([("perfbench.window", window[0], window[1] - window[0], {})]
            + _step(5, 0) + _step(95, 1) + [("other", 2, 190, {})])


def _write(tmp_path, monkeypatch, host, seed=7):
    """The profile as ``run.py`` leaves it; returns the reader's context."""
    from jax.profiler import ProfileData

    monkeypatch.setattr(progspans, "TRACE_DIR", str(tmp_path))
    d = tmp_path / f"tiny-{seed}" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    text = _text(host)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    tr = xtrace.from_profile(ProfileData.from_text_proto(text))
    lo, hi = tr.window()
    return SimpleNamespace(conf={"name": "tiny"}, trace=tr, lo=lo, hi=hi)


def _reader(name):
    return load_module(os.path.join(ROOT, "perfbench", "metrics", name + ".py"))


def test_program_spans_nest_under_the_harness_annotation(tmp_path, monkeypatch):
    ctx = _write(tmp_path, monkeypatch, _host())
    ps = progspans.of_run(ctx)
    assert ps.window == pytest.approx((0.0, 195e-6))
    assert {s.name for s in ps.spans} == {
        "engine.step", "engine.decode.prepare", "engine.decode.dispatch",
        "galaxy.decode", "engine.sample", "engine.sync", "engine.emit",
        "engine.admit"}
    steps = ps.named("engine.step", ctx.lo, ctx.hi)
    assert [s.stats["step_num"] for s in steps] == [0, 1]
    # galaxy.decode sits under perfbench.decode, which the reader drops
    assert [len(ps.inside(s, "galaxy.decode")) for s in steps] == [1, 1]
    assert ps.named("engine.decode.dispatch", 0, 1)[0].stats["live"] == 2


def test_engine_host_time_leaves_out_the_sync(tmp_path, monkeypatch, capsys):
    ctx = _write(tmp_path, monkeypatch, _host())
    # 85 us steps, 48 us of each waiting on the device
    assert _reader("engine_host_ms_per_step").read(ctx) == pytest.approx(
        37e-3)
    assert _reader("decode_dispatch_ms").read(ctx) == pytest.approx(2e-3)
    out = capsys.readouterr().out
    assert "galaxy.decode" in out and "leads the host's by 0.0000 ms" in out


def test_idle_split_by_innermost_span(tmp_path, monkeypatch):
    ctx = _write(tmp_path, monkeypatch, _host())
    ps = progspans.of_run(ctx)
    dev = ctx.trace.devices[0]
    split = progspans.idle_by_span(dev, ps.spans, ctx.lo, ctx.hi)
    # idle 0-10, 60-100, 150-195 us; step 0 spans 5-90, step 1 95-180:
    # 0-5 no span; 5-7 prepare; 7-8 dispatch; 8-10 galaxy.decode (under
    # perfbench.decode); 60-70 emit; 70-90 admit; 90-95 no span; 95-97
    # prepare; 97-98 dispatch; 98-100 galaxy.decode; 150-160 emit; 160-180
    # admit; 180-195 no span.  The sync never idles the device.
    want = {"outside": 25, "engine.decode.prepare": 4,
            "engine.decode.dispatch": 2, "galaxy.decode": 4,
            "engine.emit": 20, "engine.admit": 40}
    assert split == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    assert sum(split.values()) == pytest.approx(
        (ctx.hi - ctx.lo) - xtrace.length(xtrace.busy(dev, ctx.lo, ctx.hi)))
    # the longest gap, 150-195 us, lies across the emission, the admission
    # and the end of the window; the next, 60-100 us, across step 0's
    # emission and admission, the gap between steps and step 1's dispatch
    gaps = progspans.longest_gaps(dev, ps.spans, ctx.lo, ctx.hi)
    assert gaps[0] == (pytest.approx(150e-6), pytest.approx(45e-6),
                       "engine.admit")
    assert gaps[1] == (pytest.approx(60e-6), pytest.approx(40e-6),
                       "engine.admit")
    assert progspans.device_lead(dev, ps.spans, ctx.lo, ctx.hi) == 0.0

    # the same run with the device's clock 3 us ahead of the host's: its
    # runs start before the galaxy.decode dispatching them ends, until the
    # device is shifted back by the lead read from the trace
    late = [e if e[0] == "perfbench.window" else (e[0], e[1] + 3, e[2], e[3])
            for e in _host()]
    ctx = _write(tmp_path / "late", monkeypatch, late)
    ps = progspans.of_run(ctx)
    lead = progspans.device_lead(dev, ps.spans, ctx.lo, ctx.hi)
    assert lead == pytest.approx(3e-6)
    assert "galaxy.decode" not in progspans.idle_by_span(
        dev, ps.spans, ctx.lo, ctx.hi)
    assert progspans.idle_by_span(dev, ps.spans, ctx.lo, ctx.hi, lead) == \
        pytest.approx({k: v * 1e-6 for k, v in want.items()})


def test_readers_refuse_a_foreign_window_or_a_missing_dispatch(
        tmp_path, monkeypatch):
    ctx = _write(tmp_path, monkeypatch, _host())
    ctx.hi += 1e-6
    with pytest.raises(RuntimeError, match="window"):
        _reader("decode_dispatch_ms").read(ctx)
    ctx.hi -= 1e-6
    # one galaxy.decode fewer than the decode runs on the device
    host = [e for e in _host() if not (e[0] == "galaxy.decode" and e[1] > 90)]
    ctx = _write(tmp_path / "b", monkeypatch, host)
    with pytest.raises(RuntimeError, match="galaxy.decode"):
        _reader("decode_dispatch_ms").read(ctx)
    # decode runs, and steps, but none holding a decode dispatch
    host = [e for e in _host() if e[0] != "engine.decode.dispatch"]
    ctx = _write(tmp_path / "c", monkeypatch, host)
    with pytest.raises(RuntimeError, match="engine.step"):
        _reader("engine_host_ms_per_step").read(ctx)


def test_a_program_without_spans_gives_nothing(tmp_path, monkeypatch):
    """The parent of the spans: only the harness's annotations."""
    host = [e for e in _host() if not e[0].startswith(("engine.", "galaxy."))]
    ctx = _write(tmp_path, monkeypatch, host)
    assert _reader("engine_host_ms_per_step").read(ctx) is None
    assert _reader("decode_dispatch_ms").read(ctx) is None
