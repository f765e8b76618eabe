"""The trace reduction and the per-layer readers on a small synthetic trace."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import xtrace  # noqa: E402
from perfbench.run import load_module  # noqa: E402

US = 1_000_000  # picoseconds per microsecond


def _line(lid, name, events, meta):
    out = [f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0']
    for ev_name, start_us, dur_us in events:
        out.append(f"  events {{ metadata_id: {meta[ev_name]} "
                   f"offset_ps: {start_us * US} duration_ps: {dur_us * US} }}")
    out.append("}")
    return "\n".join(out)


def _plane(pid, name, lines):
    names = sorted({e[0] for _, evs in lines for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    body = [_line(i + 1, ln, evs, meta) for i, (ln, evs) in enumerate(lines)]
    body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
             for n, i in meta.items()]
    return f'planes {{ id: {pid} name: "{name}"\n' + "\n".join(body) + "\n}"


def _device(pid, n, shift):
    """A prefill run 0-100 us with a collective at 40-60 us, half of it
    under a fusion; a decode run 150-170 us; an eager op at 190-195 us."""
    modules = [("jit_prefill(1)", 0, 100), ("jit_decode(2)", 150, 20),
               ("jit_add(3)", 190, 5)]
    ops = [("fusion.1", 0, 50), ("collective-permute-done.2", 40 + shift, 20),
           ("fusion.3", 65, 35), ("fusion.9", 150, 20), ("add.1", 190, 5)]
    return _plane(pid, f"/device:TPU:{n}",
                  [("XLA Modules", modules), ("XLA Ops", ops)])


def _trace():
    from jax.profiler import ProfileData

    host = _plane(9, "/host:CPU", [("python", [
        ("perfbench.window", 0, 200), ("perfbench.prefill", 0, 10),
        ("perfbench.decode", 140, 12), ("other", 100, 5)])])
    text = "\n".join([_device(1, 0, 0), _device(2, 1, 10), host])
    return xtrace.from_profile(ProfileData.from_text_proto(text))


def test_reduction_reads_devices_programs_and_annotations():
    tr = _trace()
    assert [d.name for d in tr.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert [e.name for e in tr.host] == ["window", "prefill", "decode"]
    lo, hi = tr.window()
    assert (lo, hi) == pytest.approx((0.0, 200e-6))
    secs, runs = xtrace.program_seconds(tr, "decode", lo, hi)
    assert runs == 1 and secs == pytest.approx(20e-6)
    # device 0: ops cover 0-60, 65-100, 150-170, 190-195 us
    assert xtrace.length(xtrace.busy(tr.devices[0], lo, hi)) == pytest.approx(
        120e-6)
    # collective 40-60 under fusion.1 until 50: 10 us exposed of 100 us
    exposed, total = xtrace.exposed_collective(tr.devices[0], "prefill", lo, hi)
    assert (exposed, total) == pytest.approx((10e-6, 100e-6))
    # device 1's collective (50-70) lies clear of fusion.1 and ends under
    # fusion.3 (65-): 15 us exposed
    exposed, _ = xtrace.exposed_collective(tr.devices[1], "prefill", lo, hi)
    assert exposed == pytest.approx(15e-6)
    assert xtrace.exposed_collective(tr.devices[0], "train", lo, hi) is None


def test_breakdown_names_ops_and_idle_gaps():
    tr = _trace()
    lo, hi = tr.window()
    ops = dict(xtrace.top_ops(tr, lo, hi))
    assert ops["prefill/fusion.1"] == pytest.approx(50e-6)
    assert ops["decode/fusion.9"] == pytest.approx(20e-6)
    gaps = xtrace.idle_gaps(tr, lo, hi)
    # device 0 idles 60-65, 100-150, 170-190, 195-200 us; the 100-150 gap
    # overlaps "decode" (140-152) more than anything else
    assert gaps[0] == ("decode", pytest.approx(50e-6))
    assert sorted(g for _, g in gaps) == pytest.approx(
        sorted([5e-6, 50e-6, 20e-6, 5e-6]))
    assert ("engine", pytest.approx(20e-6)) in gaps


def test_interval_arithmetic():
    assert xtrace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert xtrace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xtrace.clip([(0, 4), (6, 9)], 1, 7) == [(1, 4), (6, 7)]
    assert xtrace.program_name("jit_prefill.3(12)") == "prefill"


def _ctx(tr, **counters):
    lo, hi = tr.window()
    base = {"prefill_tokens": 0, "cached_prefix_tokens": 0, "decode_steps": 0}
    base.update(counters)
    return SimpleNamespace(trace=tr, lo=lo, hi=hi, chips=2, counters=base,
                           calls=[], peaks={"bf16_flops": 1e12,
                                            "hbm_bytes_per_s": 1e9})


def _reader(name):
    return load_module(os.path.join(ROOT, "perfbench", "metrics", name + ".py"))


def test_readers_on_the_trace():
    tr = _trace()
    ctx = _ctx(tr, prefill_tokens=50, decode_steps=1)
    assert _reader("prefill_ms_per_ktok").read(ctx) == pytest.approx(
        100e-6 / 50 * 1e6)
    assert _reader("decode_step_ms").read(ctx) == pytest.approx(0.02)
    # device 1's collective (50-70) fills its 60-65 gap: 125 us busy
    assert _reader("device_idle_share").read(ctx) == pytest.approx(
        100 * ((1 - 120 / 200) + (1 - 125 / 200)) / 2)
    # one decode call of 1e-9 s least time against 20 us on the device
    ctx.calls = [{"kind": "decode", "flops": 2e3, "bytes": 1.0,
                  "tokens": 1}]
    assert _reader("decode_roofline").read(ctx) == pytest.approx(
        100 * 1e-9 / 20e-6)
    ctx.calls.append(dict(ctx.calls[0]))
    with pytest.raises(RuntimeError, match="decode"):
        _reader("decode_roofline").read(ctx)


def test_readers_refuse_a_missing_program_and_stay_silent_without_work():
    tr = _trace()
    for dev in tr.devices:
        dev.modules = [m for m in dev.modules if "prefill" not in m.name]
    with pytest.raises(RuntimeError, match="prefill"):
        _reader("prefill_ms_per_ktok").read(_ctx(tr, prefill_tokens=5))
    assert _reader("prefill_ms_per_ktok").read(_ctx(tr)) is None
