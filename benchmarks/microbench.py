"""Real timed microbenchmarks on this host (CPU): HMP schedules vs
baselines on a multi-device subprocess, kernel fusion wins, and the
Galaxy profiler's measured block latencies.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp

Row = Tuple[str, float, str]


def _cpu_env(devices: int) -> dict:
    """Environment of a child process that emulates ``devices`` devices on
    the host CPU.  ``JAX_PLATFORMS=cpu`` keeps it off any accelerator: a
    chip belongs to one process, and this parent imports JAX."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return env


def _time(fn, *args, iters=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def measure_execplan_layers(eplan, seq: int, *, devices: int = 4,
                            iters: int = 10) -> dict:
    """Measured per-layer wall time (seconds) of hmp / hmp_ring executing an
    ExecPlan on forced CPU devices.

    The one measurement harness shared by the execplan benches below and
    ``experiments/calibrate.py`` (the measured side of the calibration
    loop), so all three time the identical program: a fresh subprocess with
    ``--xla_force_host_platform_device_count``, the plan's padded params,
    the (possibly ragged) sequence layout, warm-up, then ``iters`` timed
    jitted calls.  Raises on subprocess failure.
    """
    code = rf"""
import jax, jax.numpy as jnp, time
from repro.core import hmp
from repro.core.execplan import ExecPlan
from repro.launch.mesh import make_mesh_compat
mesh = make_mesh_compat(({devices},), ('model',))
eplan = ExecPlan(heads={tuple(eplan.heads)}, columns={tuple(eplan.columns)},
                 head_dim={eplan.head_dim}, d_model={eplan.d_model},
                 seq_shares={tuple(eplan.seq_shares)},
                 compute_backend={eplan.compute_backend!r},
                 transport={eplan.transport!r},
                 double_buffer={eplan.double_buffer})
p = hmp.init_layer_params(jax.random.PRNGKey(0), eplan.d_model,
                          eplan.num_heads, eplan.d_ff)
pp = eplan.pad_layer_params(p)
x = jax.random.normal(jax.random.PRNGKey(1), (1, {seq}, eplan.d_model))
xp = eplan.seq_layout({seq}).scatter(x)  # identity for dense layouts
for name, overlap in [('hmp', False), ('hmp_ring', True)]:
    f = jax.jit(lambda p, x, o=overlap: hmp.hmp_layer(p, x, mesh, overlap=o,
                                                      plan=eplan, seq={seq}))
    out = f(pp, xp); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range({iters}):
        out = f(pp, xp)
    jax.block_until_ready(out)
    print(f"{{name}},{{(time.perf_counter()-t0)/{iters}:.9f}}")
"""
    env = _cpu_env(devices)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"execplan measurement subprocess failed:\n{proc.stderr[-2000:]}"
        )
    return {
        name: float(sec)
        for name, sec in (ln.split(",") for ln in proc.stdout.strip().splitlines())
    }


def kernel_fusion() -> Iterator[Row]:
    """fused_connective (1 HBM pass) vs unfused dropout+residual+LN."""
    from repro.kernels.ops import fused_connective
    from repro.kernels.ref import fused_connective_ref

    s, d = 2048, 1024
    x = jax.random.normal(jax.random.PRNGKey(0), (s, d))
    res = jax.random.normal(jax.random.PRNGKey(1), (s, d))
    mask = jnp.ones((s, d))
    scale, bias = jnp.ones((d,)), jnp.zeros((d,))
    unfused = jax.jit(lambda *a: fused_connective_ref(*a, rate=0.0))
    t_ref = _time(unfused, x, res, mask, scale, bias)
    t_fused = _time(lambda *a: fused_connective(*a, rate=0.0), x, res, mask, scale, bias)
    yield ("micro/connective_unfused", t_ref, "jnp 3-pass")
    yield ("micro/connective_fused", t_fused, f"pallas 1-pass,{t_ref/t_fused:.2f}x")


def flash_vs_naive() -> Iterator[Row]:
    from repro.kernels.ops import flash_attention
    from repro.kernels.ref import flash_attention_ref

    b, h, s, hd = 1, 8, 1024, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, s, hd))
    t_naive = _time(jax.jit(lambda q, k, v: flash_attention_ref(q, k, v)), q, k, v, iters=3)
    t_flash = _time(lambda q, k, v: flash_attention(q, k, v), q, k, v, iters=3)
    yield ("micro/attention_naive", t_naive, "materialized scores")
    yield ("micro/attention_flash", t_flash,
           "pallas blocked (interpret on CPU; wins are on-TPU)")


def profiler_blocks() -> Iterator[Row]:
    """Galaxy Profiler measuring real block latencies (paper step 1)."""
    from repro.configs import get_config
    from repro.core.profiler import HostProfiler

    prof = HostProfiler(get_config("distilbert"), seq=128, iters=3)
    t = prof.measure_blocks(heads=12, columns=3072)
    yield ("micro/profiler_mha_full", t["mha"] * 1e6, "L(MHA,full,host)")
    yield ("micro/profiler_mlp_full", t["mlp"] * 1e6, "L(MLP,full,host)")
    yield ("micro/profiler_con_full", t["con"] * 1e6, "L(CON,full,host)")
    half = prof.measure_blocks(heads=6, columns=1536)
    yield ("micro/profiler_mha_half", half["mha"] * 1e6,
           f"half-partition,{t['mha']/half['mha']:.2f}x")


def hmp_schedules_multidevice() -> Iterator[Row]:
    """Per-layer wall time of hmp / hmp_ring / megatron / sp on 4 CPU
    devices (subprocess) — the real executable of the paper's comparison.
    CPU ppermute/collectives are emulation-grade; relative numbers only."""
    code = r"""
import jax, jax.numpy as jnp, time
from repro.core import hmp
from repro.launch.mesh import make_mesh_compat
mesh = make_mesh_compat((4,), ('model',))
p = hmp.init_layer_params(jax.random.PRNGKey(0), 256, 8, 1024)
x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 256))
for name, fn in hmp.SCHEDULES.items():
    f = jax.jit(lambda p, x, fn=fn: fn(p, x, mesh))
    out = f(p, x); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(10):
        out = f(p, x)
    jax.block_until_ready(out)
    print(f"{name},{(time.perf_counter()-t0)/10*1e6:.1f}")
"""
    env = _cpu_env(4)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        yield ("micro/hmp_schedules", float("nan"), "subprocess failed")
        return
    rows = dict(line.split(",") for line in proc.stdout.strip().splitlines())
    base = float(rows.get("megatron", "nan"))
    for name, us in rows.items():
        yield (f"micro/layer_{name}", float(us),
               f"vs megatron={base/float(us):.2f}x" if base == base else "")


def execplan_uneven() -> Iterator[Row]:
    """Measured vs simulated latency of the *same* uneven ExecPlan.

    The planner partitions a DistilBert layer over a 3:2:2:1 heterogeneous
    cluster; the resulting ExecPlan is (a) scored by the simulator (assigned
    workload and padded SPMD workload) and (b) executed for real through
    hmp / hmp_ring on 4 forced CPU devices.  Absolute scales differ (host
    CPU vs simulated Jetsons) — the point is one plan flowing through both.
    """
    import dataclasses

    from repro.configs import get_config
    from repro.core import costmodel, planner
    from repro.core.execplan import ExecPlan
    from repro.core.profiler import AnalyticProfiler
    from repro.core.simulator import simulate_execplan

    seq = 128
    cfg = dataclasses.replace(get_config("distilbert"), num_layers=1)
    caps = [3.0, 2.0, 2.0, 1.0]
    devices = [
        costmodel.DeviceSpec(f"edge{i}", flops=c * 7.1e9, mem_bw=4.0e9,
                             memory_budget=1.5e9)
        for i, c in enumerate(caps)
    ]
    link = costmodel.mbps(1000)
    prof = AnalyticProfiler(cfg, seq)
    pl = planner.plan(prof.model_profile(), prof.device_profiles(devices))
    if not pl.feasible:
        yield ("micro/execplan", float("nan"), f"plan infeasible:{pl.reason}")
        return
    eplan = ExecPlan.from_plan(pl, head_dim=cfg.head_dim, d_model=cfg.d_model)

    for name, padded, overlap in [
        ("sim/execplan_galaxy", False, False),
        ("sim/execplan_galaxy_overlap", False, True),
        ("sim/execplan_galaxy_overlap_padded", True, True),
    ]:
        r = simulate_execplan(eplan, cfg, devices, link, seq,
                              overlap=overlap, padded=padded)
        yield (name, r.latency * 1e6,
               f"simulated,{eplan.describe()}" if not padded else
               "simulated,every device runs max(units)")

    # measurement failures propagate: the bench runner exits nonzero on
    # exceptions, same as execplan_raggedsp below
    measured = measure_execplan_layers(eplan, seq)
    for name, sec in measured.items():
        yield (f"micro/execplan_{name}", sec * 1e6,
               f"measured,heads={list(eplan.heads)},cols={list(eplan.columns)}")


def execplan_raggedsp() -> Iterator[Row]:
    """Ragged sequence parallelism: equal vs bandwidth-aware seq split.

    A 3:2:2:1 DistilBert cluster with one slow link (100 Mbps against
    1 Gbps elsewhere): the planner solves uneven sequence tiles from
    capacity + link bandwidth (planner.sequence_partition), and the
    simulator scores both splits over the ragged ring
    (costmodel.t_ring_exchange).  The bandwidth-aware split keeps large
    tiles off the slow hop, so it must come out faster; the padded row
    shows what the SPMD pad-and-mask emulation of the same plan costs.
    The ragged plan is then executed for real through hmp / hmp_ring on 4
    forced CPU devices (measured, exactness asserted in tests).
    """
    import dataclasses

    from repro.configs import get_config
    from repro.core import costmodel
    from repro.core.execplan import ExecPlan
    from repro.core.profiler import AnalyticProfiler
    from repro.core.simulator import simulate_execplan

    seq = 128
    cfg = dataclasses.replace(get_config("distilbert"), num_layers=1)
    caps = [3.0, 2.0, 2.0, 1.0]
    devices = [
        costmodel.DeviceSpec(f"edge{i}", flops=c * 7.1e9, mem_bw=4.0e9,
                             memory_budget=1.5e9)
        for i, c in enumerate(caps)
    ]
    links = [costmodel.mbps(1000), costmodel.mbps(1000),
             costmodel.mbps(100), costmodel.mbps(1000)]
    prof = AnalyticProfiler(cfg, seq)
    ep_equal = ExecPlan.from_plan(prof.plan(devices), head_dim=cfg.head_dim,
                                  d_model=cfg.d_model)
    ep_aware = ExecPlan.from_plan(prof.plan(devices, links=links),
                                  head_dim=cfg.head_dim, d_model=cfg.d_model)

    r_eq = simulate_execplan(ep_equal, cfg, devices, links, seq, overlap=True)
    r_bw = simulate_execplan(ep_aware, cfg, devices, links, seq, overlap=True)
    r_pad = simulate_execplan(ep_aware, cfg, devices, links, seq,
                              overlap=True, padded=True)
    yield ("sim/raggedsp_equal_seq", r_eq.latency * 1e6,
           "simulated,slow link carries full tiles")
    yield ("sim/raggedsp_bandwidth_aware", r_bw.latency * 1e6,
           f"simulated,tiles={list(ep_aware.seq_tiles(seq))},"
           f"speedup={r_eq.latency / r_bw.latency:.2f}x")
    yield ("sim/raggedsp_bandwidth_aware_padded", r_pad.latency * 1e6,
           f"simulated,SPMD ships max tile,sp_waste="
           f"{ep_aware.seq_padding_waste():.1%}")

    measured = measure_execplan_layers(ep_aware, seq)
    for name, sec in measured.items():
        yield (f"micro/raggedsp_{name}", sec * 1e6,
               f"measured,tiles={list(ep_aware.seq_tiles(seq))},"
               f"padded rows per device={ep_aware.seq_tile(seq)}")


def execplan_overlap() -> Iterator[Row]:
    """Tile-granular overlap transports on an emulated slow-link cluster:
    padded vs bucketed vs bucketed + double-buffered ring exchanges.

    Same 3:2:2:1 DistilBert cluster as ``execplan_raggedsp`` with one
    100 Mbps link: the bandwidth-aware ragged plan runs ``hmp_ring`` for
    real on 4 forced CPU devices under all three transports, and the
    subprocess asserts the transports are *bitwise*-identical to each
    other and allclose to the unoverlapped sync schedule.  Forced host
    devices share one memory bus, so the wire cannot be throttled
    in-process; each variant's end-to-end latency is therefore *emulated*
    as measured compute wall + the cost model's wire time for the rows
    that transport actually ships (4 ring rotations per layer through
    ``costmodel.t_ring_exchange`` over the skewed links).  Double
    buffering issues the exchange before the GEMM that hides it, so its
    wire contributes only the overhang ``max(0, wire - wall)``.

    Gates (raise, not assert — they must also gate under -O):

    1. The bucketed schedule ships strictly fewer rows per rotation than
       padded transport on this plan (``RingSchedule.total_wire_rows``).
    2. Emulated bucketed+db latency lands closer to the simulator's
       ``sim/raggedsp_bandwidth_aware`` target than emulated padded
       transport does — the overlap transport closes the gap between the
       padded SPMD emulation and the plan the simulator priced.
    """
    import dataclasses

    from repro.configs import get_config
    from repro.core import costmodel
    from repro.core.execplan import ExecPlan
    from repro.core.profiler import AnalyticProfiler
    from repro.core.simulator import simulate_execplan

    seq = 128
    cfg = dataclasses.replace(get_config("distilbert"), num_layers=1)
    caps = [3.0, 2.0, 2.0, 1.0]
    devices = [
        costmodel.DeviceSpec(f"edge{i}", flops=c * 7.1e9, mem_bw=4.0e9,
                             memory_budget=1.5e9)
        for i, c in enumerate(caps)
    ]
    links = [costmodel.mbps(1000), costmodel.mbps(1000),
             costmodel.mbps(100), costmodel.mbps(1000)]
    prof = AnalyticProfiler(cfg, seq)
    ep = ExecPlan.from_plan(prof.plan(devices, links=links),
                            head_dim=cfg.head_dim, d_model=cfg.d_model)
    variants = {
        "padded": ep,
        "bucketed": ep.with_transport("bucketed"),
        "bucketed_db": ep.with_transport("bucketed", double_buffer=True),
    }

    # measured compute walls; outputs checked inside the subprocess
    code = rf"""
import jax, jax.numpy as jnp, numpy as np, time
from repro.core import hmp
from repro.core.execplan import ExecPlan
from repro.launch.mesh import make_mesh_compat
mesh = make_mesh_compat((4,), ('model',))
base = ExecPlan(heads={tuple(ep.heads)}, columns={tuple(ep.columns)},
                head_dim={ep.head_dim}, d_model={ep.d_model},
                seq_shares={tuple(ep.seq_shares)})
seq = {seq}
p = hmp.init_layer_params(jax.random.PRNGKey(0), base.d_model,
                          base.num_heads, base.d_ff)
pp = base.pad_layer_params(p)
x = jax.random.normal(jax.random.PRNGKey(1), (1, seq, base.d_model))
xp = base.seq_layout(seq).scatter(x)
outs = {{}}
sync = hmp.hmp_layer(pp, xp, mesh, overlap=False, plan=base, seq=seq)
for name, transport, db in [('padded', 'padded', False),
                            ('bucketed', 'bucketed', False),
                            ('bucketed_db', 'bucketed', True)]:
    ep = base.with_transport(transport, double_buffer=db)
    f = jax.jit(lambda p, x, e=ep: hmp.hmp_layer(p, x, mesh, overlap=True,
                                                 plan=e, seq=seq))
    y = f(pp, xp); jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(10):
        y = f(pp, xp)
    jax.block_until_ready(y)
    outs[name] = np.asarray(y)
    print(f"wall_{{name}},{{(time.perf_counter()-t0)/10:.9f}}")
err = np.abs(outs['padded'] - np.asarray(sync)).max()
if err >= 1e-4:
    raise RuntimeError(f"ring vs sync max err {{err:.3e}}")
for name in ('bucketed', 'bucketed_db'):
    if not np.array_equal(outs[name], outs['padded']):
        raise RuntimeError(f"{{name}} transport is not bitwise-equal to padded")
print(f"err_sync,{{err:.3e}}")
"""
    env = _cpu_env(4)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"overlap subprocess failed:\n{proc.stderr[-2000:]}")
    rows = dict(ln.split(",") for ln in proc.stdout.strip().splitlines())

    # modeled wire time of what each transport actually ships: 4 ring
    # rotations per layer (qkv/w1 allgather + wo/w2 reduce-scatter)
    row_bytes = cfg.d_model * costmodel.BYTES_ACT
    wire = {}
    for name, plan in variants.items():
        sched = plan.ring_schedule(seq)
        wire[name] = 4 * costmodel.t_ring_exchange(
            [int(b) * row_bytes for b in sched.buckets], links)
    sched_b = variants["bucketed"].ring_schedule(seq)
    if not sched_b.total_wire_rows() < sched_b.padded_wire_rows():
        raise RuntimeError(
            f"bucketed transport sheds nothing: ships "
            f"{sched_b.total_wire_rows()} of {sched_b.padded_wire_rows()} rows"
        )

    target = simulate_execplan(ep, cfg, devices, links, seq,
                               overlap=True).latency
    emulated = {}
    for name in variants:
        wall = float(rows[f"wall_{name}"])
        hidden = wall if name == "bucketed_db" else 0.0
        emulated[name] = wall + max(0.0, wire[name] - hidden)
    if not (abs(emulated["bucketed_db"] - target)
            < abs(emulated["padded"] - target)):
        raise RuntimeError(
            f"overlap transport does not close the gap to the simulator: "
            f"db={emulated['bucketed_db'] * 1e6:.0f}us "
            f"padded={emulated['padded'] * 1e6:.0f}us "
            f"target={target * 1e6:.0f}us"
        )

    yield ("sim/overlap_target", target * 1e6,
           "simulated,sim/raggedsp_bandwidth_aware (exact-bytes wire)")
    for name, plan in variants.items():
        sched = plan.ring_schedule(seq)
        yield (f"micro/overlap_{name}", emulated[name] * 1e6,
               f"emulated=wall+wire,wall={float(rows[f'wall_{name}']) * 1e6:.0f}us,"
               f"wire={wire[name] * 1e6:.0f}us,"
               f"wire_rows={sched.total_wire_rows()}/{sched.padded_wire_rows()},"
               f"bitwise-equal to padded")
    yield ("micro/overlap_err_sync", float(rows["err_sync"]),
           "ring vs unoverlapped sync schedule (atol 1e-4 gate)")


def execplan_padshed() -> Iterator[Row]:
    """Pad shedding: the pallas valid-length backend vs the padded-XLA
    oracle on the 3:2:2:1 uneven DistilBert plan.

    Two parts:

    1. Analytic per-device live-block counts of the four per-shard GEMMs
       at the executor's block sizes (``tiled_gemm.dense_block_count``,
       the blocks the kernel's grid issues a dot for), and the share of
       the padded blocks they skip next to ``ExecPlan.padding_waste()``.
       Kernel time itself comes from a device trace, not from this host.
    2. Backend outputs agree with the padded-XLA oracle (atol 1e-4) on the
       layer, prefill, and paged-decode paths (4 forced CPU devices), with
       wall times reported for both (interpret-mode pallas on a CPU host,
       which says nothing about the MXU).
    """
    import dataclasses

    from repro.configs import get_config
    from repro.core import costmodel, planner
    from repro.core.execplan import ExecPlan
    from repro.core.profiler import AnalyticProfiler
    from repro.kernels.tiled_gemm import dense_block_count

    seq = 128
    cfg = dataclasses.replace(get_config("distilbert"), num_layers=1)
    caps = [3.0, 2.0, 2.0, 1.0]
    devices = [
        costmodel.DeviceSpec(f"edge{i}", flops=c * 7.1e9, mem_bw=4.0e9,
                             memory_budget=1.5e9)
        for i, c in enumerate(caps)
    ]
    prof = AnalyticProfiler(cfg, seq)
    pl = planner.plan(prof.model_profile(), prof.device_profiles(devices))
    if not pl.feasible:
        yield ("padshed/plan", float("nan"), f"infeasible:{pl.reason}")
        return
    eplan = ExecPlan.from_plan(pl, head_dim=cfg.head_dim, d_model=cfg.d_model,
                               compute_backend="pallas")

    d, hd = cfg.d_model, cfg.head_dim
    ph, pc = eplan.pad_heads, eplan.pad_columns

    def blocks(heads, cols):
        # the four per-shard GEMMs the executor traces on one sequence tile
        tile = seq // eplan.num_devices
        return (dense_block_count(tile, 3 * ph * hd, d, valid_n=heads * hd,
                                  seg_n=ph * hd)
                + dense_block_count(tile, d, ph * hd, valid_k=heads * hd)
                + dense_block_count(tile, pc, d, valid_n=cols)
                + dense_block_count(tile, d, pc, valid_k=cols))

    padded = blocks(ph, pc)
    live = []
    for dev, (heads, cols) in enumerate(zip(eplan.heads, eplan.columns)):
        live.append(blocks(heads, cols))
        yield (f"padshed/blocks_dev{dev}", float(live[-1]),
               f"analytic,heads={heads}/{ph},cols={cols}/{pc},"
               f"padded_blocks={padded}")
    shed = 1.0 - sum(live) / (padded * eplan.num_devices)
    yield ("padshed/blocks_shed", shed * 100,
           f"percent,analytic,vs ExecPlan.padding_waste="
           f"{eplan.padding_waste():.1%},flops_shed={eplan.flops_shed():.1%}")

    # backend outputs vs the padded-XLA oracle on 4 forced CPU devices
    code = rf"""
import jax, jax.numpy as jnp, numpy as np, time
from repro.core import hmp
from repro.core.execplan import ExecPlan
from repro.launch.mesh import make_mesh_compat
mesh = make_mesh_compat((4,), ('model',))
ep = ExecPlan(heads={tuple(eplan.heads)}, columns={tuple(eplan.columns)},
              head_dim={cfg.head_dim}, d_model={cfg.d_model})
layers = [hmp.init_layer_params(jax.random.PRNGKey(0), ep.d_model,
                                ep.num_heads, ep.d_ff)]
seq, page = {seq}, 32
x = jax.random.normal(jax.random.PRNGKey(1), (1, seq, ep.d_model)) * 0.5
x_new = jax.random.normal(jax.random.PRNGKey(2), (1, 1, ep.d_model)) * 0.5
outs = {{}}
for name in ('xla', 'pallas'):
    b = ep.with_backend(name)
    pp = b.pad_layer_params(layers[0])
    f = jax.jit(lambda p, x, b=b: hmp.hmp_layer(p, x, mesh, overlap=True,
                                                plan=b, seq=seq))
    y = f(pp, x); jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(3):
        y = f(pp, x)
    jax.block_until_ready(y)
    wall = (time.perf_counter() - t0) / 3
    cache = hmp.make_kv_cache(1, seq + 4, 1, mesh, b)
    y_pre, cache = hmp.hmp_prefill(layers, x, mesh, cache, plan=b,
                                   overlap=True, seq=seq)
    pages = hmp.make_paged_kv_cache(6, page, 1, mesh, b)
    row = jnp.arange(1, 6, dtype=jnp.int32)
    y_pp, pages = hmp.hmp_prefill(layers, x, mesh, pages, plan=b,
                                  overlap=True, seq=seq, block_row=row)
    y_dec, pages = hmp.hmp_decode(layers, x_new, mesh, pages,
                                  jnp.asarray([seq]), plan=b,
                                  block_table=row[None])
    outs[name] = (np.asarray(y), np.asarray(y_pre), np.asarray(y_dec))
    print(f"wall_{{name}},{{wall:.9f}}")
for i, path in enumerate(('layer', 'prefill', 'decode_paged')):
    err = np.abs(outs['pallas'][i] - outs['xla'][i]).max()
    if err >= 1e-4:
        raise RuntimeError(f"{{path}}: pallas vs xla max err {{err:.3e}}")
    print(f"err_{{path}},{{err:.3e}}")
"""
    env = _cpu_env(4)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"padshed subprocess failed:\n{proc.stderr[-2000:]}")
    rows = dict(ln.split(",") for ln in proc.stdout.strip().splitlines())
    for name in ("xla", "pallas"):
        yield (f"micro/padshed_layer_{name}", float(rows[f"wall_{name}"]) * 1e6,
               "measured,interpret-mode pallas on CPU host" if name == "pallas"
               else "measured,padded dense oracle")
    for path in ("layer", "prefill", "decode_paged"):
        yield (f"padshed/allclose_{path}", float(rows[f"err_{path}"]),
               "max |pallas - xla| (atol 1e-4 gate)")


def continuous_vs_wave() -> Iterator[Row]:
    """Continuous batching vs wave scheduling on a skewed request mix.

    16 requests, equal 8-token prompts, output lengths skewed 32/4/4/4 — the
    wave scheduler's worst case: every wave drains at the pace of its longest
    request while the short requests' slots sit idle.  Continuous batching
    refills a slot the moment its request retires, so the decode batch stays
    full.  Reports tokens/sec and p50/p95 per-token latency per scheduler;
    greedy tokens are asserted identical (the engine-level contract).
    """
    import statistics

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.obs import itl_seconds, ttft_percentiles
    from repro.serving import Request, ServingEngine

    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    from repro.serving import TransformerExecutor

    executor = TransformerExecutor(params, cfg)  # shared jit caches

    def requests():
        return [
            Request(uid=i, prompt=[1 + (i * 7 + j) % 200 for j in range(8)],
                    max_new_tokens=32 if i % 4 == 0 else 4)
            for i in range(16)
        ]

    def run_once(scheduler: str, timed: bool):
        eng = ServingEngine(executor=executor, max_batch=4, max_len=48,
                            scheduler=scheduler, page_size=8,
                            record_times=timed)
        for r in requests():
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        return done, wall, eng.stats

    results = {}
    outputs = {}
    done_by = {}
    for scheduler in ("wave", "continuous"):
        run_once(scheduler, timed=False)  # warm the jit caches
        done, wall, stats = run_once(scheduler, timed=True)
        toks = sum(len(r.output) for r in done)
        gaps = itl_seconds(done)  # the one shared ITL definition (repro.obs)
        results[scheduler] = (wall, toks, stats["decode_steps"], gaps)
        outputs[scheduler] = {r.uid: tuple(r.output) for r in done}
        done_by[scheduler] = done
    assert outputs["wave"] == outputs["continuous"], \
        "greedy tokens diverged between schedulers"

    wave_wall, wave_toks, wave_steps, wave_gaps = results["wave"]
    cont_wall, cont_toks, cont_steps, cont_gaps = results["continuous"]
    q = lambda xs, p: statistics.quantiles(xs, n=100)[p - 1] * 1e3  # ms

    yield ("serve/wave_us_per_token", wave_wall / wave_toks * 1e6,
           f"tokens/s={wave_toks / wave_wall:.1f},steps={wave_steps},"
           f"p50={q(wave_gaps, 50):.1f}ms,p95={q(wave_gaps, 95):.1f}ms")
    yield ("serve/continuous_us_per_token", cont_wall / cont_toks * 1e6,
           f"tokens/s={cont_toks / cont_wall:.1f},steps={cont_steps},"
           f"p50={q(cont_gaps, 50):.1f}ms,p95={q(cont_gaps, 95):.1f}ms,"
           f"speedup={wave_wall / cont_wall:.2f}x")
    for scheduler in ("wave", "continuous"):
        ttft = ttft_percentiles(done_by[scheduler])
        yield (f"serve/{scheduler}_ttft_p95", ttft["p95"] * 1e6,
               f"first-token latency,p50={ttft['p50'] * 1e3:.1f}ms,"
               f"n={ttft['n']}")


def prefix_sharing() -> Iterator[Row]:
    """Shared-prefix KV cache on a skewed request mix with a common
    256-token system prompt: tokens/sec + TTFT p50/p95, prefix cache on vs
    off (``serving/prefix_cache.py`` radix tree over refcounted pool pages).

    Acceptance gates (raise, not assert — they must also gate under -O):

    1. Suffix-only prefill: with the cache on, the engine's measured
       prefill token count equals ``sum(prompt_len - cached_prefix_len)``
       over all requests (prefix-hit tokens are *not* recomputed).
    2. Sharing is real: >= 1 physical page is referenced by >= 2 concurrent
       slots at some admission, with the pool's refcount algebra verified
       by ``PagedKVPool.check()`` on every sharing admission.
    3. Greedy tokens are identical cache on vs off (the engine contract).
    """
    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.obs import ttft_percentiles
    from repro.serving import Request, ServingEngine, TransformerExecutor

    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    executor = TransformerExecutor(params, cfg)  # shared jit caches

    prefix_len, tail_len = 256, 16
    system_prompt = [11 + (i * 13) % 150 for i in range(prefix_len)]

    def requests():
        return [
            Request(uid=i,
                    prompt=system_prompt
                    + [200 + (i * 7 + j) % 50 for j in range(tail_len)],
                    max_new_tokens=24 if i % 4 == 0 else 6)
            for i in range(12)
        ]

    def run_once(prefix_cache: bool, timed: bool):
        eng = ServingEngine(executor=executor, max_batch=4,
                            max_len=prefix_len + tail_len + 32,
                            scheduler="continuous", page_size=16,
                            prefix_cache=prefix_cache, record_times=timed)
        for r in requests():
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        return done, wall, eng.stats, eng.prefix_stats

    runs = {}
    for on in (False, True):
        run_once(on, timed=False)  # warm the jit caches
        runs[on] = run_once(on, timed=True)

    done_off, wall_off, stats_off, _ = runs[False]
    done_on, wall_on, stats_on, pstats = runs[True]
    if ({r.uid: tuple(r.output) for r in done_off}
            != {r.uid: tuple(r.output) for r in done_on}):
        raise RuntimeError("greedy tokens diverged between prefix cache on/off")
    total_prompt = sum(len(r.prompt) for r in done_on)
    cached = stats_on["cached_prefix_tokens"]
    if cached <= 0:
        raise RuntimeError("prefix cache never hit on a shared system prompt")
    if stats_on["prefill_tokens"] + cached != total_prompt:
        raise RuntimeError(
            f"suffix-only prefill broken: computed {stats_on['prefill_tokens']}"
            f" + cached {cached} != prompt tokens {total_prompt}"
        )
    if stats_on["peak_shared_pages"] < 1:
        raise RuntimeError("no physical page was shared across >=2 live slots")

    for on, label in ((False, "prefix_off"), (True, "prefix_on")):
        done, wall, stats, _ = runs[on]
        toks = sum(len(r.output) for r in done)
        ttft = ttft_percentiles(done)
        extra = ""
        if on:
            extra = (f",hit_rate={pstats['hit_rate']:.0%},"
                     f"cached_tokens={cached},"
                     f"shared_pages={stats['peak_shared_pages']},"
                     f"prefill={stats['prefill_tokens']}/{total_prompt},"
                     f"speedup={wall_off / wall_on:.2f}x")
        yield (f"serve/{label}_us_per_token", wall / toks * 1e6,
               f"tokens/s={toks / wall:.1f},"
               f"ttft_p50={ttft['p50'] * 1e3:.1f}ms,"
               f"ttft_p95={ttft['p95'] * 1e3:.1f}ms{extra}")


def continuous_vs_wave_galaxy() -> Iterator[Row]:
    """Continuous vs wave through the Galaxy HMP executor under an uneven
    3:2:2:1 ExecPlan on 4 forced CPU devices (subprocess) — the same skewed
    mix, decoded through the paper-exact schedule against the head-sharded
    page pool."""
    code = r"""
import jax, jax.numpy as jnp, time
from repro.core import hmp, planner
from repro.core.execplan import ExecPlan
from repro.core.planner import DeviceProfile, ModelProfile
from repro.launch.mesh import make_mesh_compat
from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

caps = [3.0, 2.0, 2.0, 1.0]
model = ModelProfile('bench', 2, 16, 256, 1e6, 2e6)
devs = [DeviceProfile(f'd{i}', c, 1e12) for i, c in enumerate(caps)]
ep = ExecPlan.from_plan(planner.plan(model, devs), head_dim=8, d_model=128)
mesh = make_mesh_compat((4,), ('model',))
layers = hmp.init_stack_params(jax.random.PRNGKey(0), 2, 128, 16, 256)
emb = jax.random.normal(jax.random.PRNGKey(7), (300, 128)) * 0.5
exe = GalaxyHMPExecutor(layers, emb, ep, mesh, overlap=True)

def run(scheduler):
    eng = ServingEngine(executor=exe, max_batch=4, max_len=48,
                        scheduler=scheduler, page_size=8)
    for i in range(8):
        eng.submit(Request(uid=i, prompt=[1 + (i + j) % 250 for j in range(12)],
                           max_new_tokens=24 if i % 4 == 0 else 4))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    return wall, sum(len(r.output) for r in done), {r.uid: tuple(r.output) for r in done}

outs = {}
for scheduler in ('wave', 'continuous'):
    run(scheduler)  # warm
    wall, toks, out = run(scheduler)
    outs[scheduler] = out
    print(f"{scheduler},{wall / toks * 1e6:.1f},{toks / wall:.1f}")
assert outs['wave'] == outs['continuous'], 'greedy tokens diverged'
print(f"page_bytes,{ep.kv_page_bytes(8)},{ep.describe()}")
"""
    env = _cpu_env(4)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"galaxy continuous bench failed:\n{proc.stderr[-2000:]}")
    rows = {}
    for line in proc.stdout.strip().splitlines():
        name, us, derived = line.split(",", 2)
        rows[name] = (float(us), derived)
    speed = rows["wave"][0] / rows["continuous"][0]
    yield ("serve/galaxy_wave_us_per_token", rows["wave"][0],
           f"tokens/s={rows['wave'][1]}")
    yield ("serve/galaxy_continuous_us_per_token", rows["continuous"][0],
           f"tokens/s={rows['continuous'][1]},speedup={speed:.2f}x")
    yield ("serve/galaxy_kv_page_bytes", rows["page_bytes"][0],
           rows["page_bytes"][1])


def spec_decode() -> Iterator[Row]:
    """Speculative decoding (``serving/spec.py``) in the batch-1 latency
    regime: a 2-layer draft proposes k=4 tokens, a 12-layer target verifies
    all of them in one 5-row chunk prefill over the paged cache.

    The model pair is constructed so the draft genuinely approximates the
    target: the target's first two layer groups *are* the draft's (same
    embedding, tied unembedding), and its remaining ten layers are random
    weights scaled by eps=0.2 — a small residual on top of the shared
    trunk, yielding a high-but-imperfect acceptance rate (rejections and
    all-accept rounds both occur).

    Acceptance gates (raise, not assert — they must also gate under -O):

    1. Greedy tokens are bitwise identical spec on vs off (the engine
       contract: verification pins the sequential argmax path).
    2. Speculation actually accepted drafts (acceptance rate > 0) and at
       least one round rejected a draft (the rollback path ran).
    3. Tokens/sec improves with speculation on.
    """
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving import Request, ServingEngine, TransformerExecutor

    draft_cfg = reduced(get_config("qwen1.5-0.5b"))
    target_cfg = dataclasses.replace(
        reduced(get_config("codeqwen1.5-7b")),
        num_layers=12, tie_embeddings=True,
    )
    draft_params = init_params(draft_cfg, jax.random.PRNGKey(0))
    target_params = init_params(target_cfg, jax.random.PRNGKey(1))
    eps = 0.2
    target_params = {
        "embed": draft_params["embed"],
        "final_norm": draft_params["final_norm"],
        "tail": target_params["tail"],
        "groups": jax.tree.map(
            lambda d, t: jnp.concatenate(
                [d, t[draft_cfg.num_layers:] * eps], axis=0)
            if jnp.issubdtype(t.dtype, jnp.floating) else t,
            draft_params["groups"], target_params["groups"],
        ),
    }
    target_exec = TransformerExecutor(target_params, target_cfg)
    draft_exec = TransformerExecutor(draft_params, draft_cfg)

    def requests():  # skewed prompt lengths, batch-1 latency mix
        return [
            Request(uid=i,
                    prompt=[1 + (i * 7 + j) % 200
                            for j in range(24 if i % 3 == 0 else 8)],
                    max_new_tokens=32 if i % 2 == 0 else 12)
            for i in range(6)
        ]

    def run_once(spec: bool):
        eng = ServingEngine(executor=target_exec, max_batch=1, max_len=64,
                            scheduler="continuous", page_size=8,
                            draft_executor=draft_exec if spec else None,
                            spec_k=4 if spec else None)
        for r in requests():
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        return done, wall, eng.stats

    runs = {}
    for spec in (False, True):
        run_once(spec)  # warm the jit caches
        runs[spec] = run_once(spec)

    done_off, wall_off, stats_off = runs[False]
    done_on, wall_on, stats_on = runs[True]
    if ({r.uid: tuple(r.output) for r in done_off}
            != {r.uid: tuple(r.output) for r in done_on}):
        raise RuntimeError("greedy tokens diverged between spec on/off")
    if stats_on["spec_accepted"] <= 0:
        raise RuntimeError("speculation never accepted a draft token")
    if stats_on["spec_accepted"] >= stats_on["spec_proposed"]:
        raise RuntimeError("no draft was ever rejected: rollback never ran")
    if wall_on >= wall_off:
        raise RuntimeError(
            f"speculation did not improve tokens/sec "
            f"({wall_off:.3f}s off vs {wall_on:.3f}s on)"
        )

    toks_off = sum(len(r.output) for r in done_off)
    toks_on = sum(len(r.output) for r in done_on)
    yield ("serve/spec_off_us_per_token", wall_off / toks_off * 1e6,
           f"tokens/s={toks_off / wall_off:.1f},"
           f"decode_steps={stats_off['decode_steps']}")
    counts = ",".join(
        f"{k}:{v}" for k, v in sorted(stats_on["spec_accept_counts"].items()))
    yield ("serve/spec_on_us_per_token", wall_on / toks_on * 1e6,
           f"tokens/s={toks_on / wall_on:.1f},"
           f"speedup={wall_off / wall_on:.2f}x,"
           f"acceptance={stats_on['spec_acceptance']:.0%},"
           f"rounds={stats_on['spec_steps']},"
           f"accept_counts={counts}")


def serving_telemetry() -> Iterator[Row]:
    """Serving observability (``repro.obs``): what telemetry costs and
    whether the exported trace is faithful.

    Acceptance gates (raise, not assert — they must also gate under -O):

    1. Structural zero overhead when disabled: a serve run with no tracer
       and ``record_times=False`` executes **zero** ``Tracer`` calls and
       zero ``Histogram.observe`` calls (counted by patching the classes)
       — the disabled path is a per-token no-op by construction, not
       merely "fast enough on this host".
    2. Greedy tokens are bitwise identical telemetry on vs off (the
       engine contract — tracing must not perturb the RNG path).
    3. Trace fidelity: the Chrome trace exports with no open spans and the
       per-request phase spans cover >= 95 % of every request's
       submit→retire wall time.

    With ``TELEMETRY_ARTIFACT_DIR`` set (the CI bench-smoke job), writes
    ``telemetry-trace.json`` + ``telemetry-metrics.json`` there for
    artifact upload.
    """
    import json as _json

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.obs import Tracer
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    from repro.serving import Request, ServingEngine, TransformerExecutor

    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    executor = TransformerExecutor(params, cfg)  # shared jit caches

    def requests():
        return [
            Request(uid=i, prompt=[1 + (i * 7 + j) % 200 for j in range(8)],
                    max_new_tokens=24 if i % 4 == 0 else 6)
            for i in range(8)
        ]

    def run_once(tracer=None, record_times=False):
        eng = ServingEngine(executor=executor, max_batch=4, max_len=48,
                            scheduler="continuous", page_size=8,
                            record_times=record_times, tracer=tracer)
        for r in requests():
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        return eng, done, wall

    run_once()  # warm the jit caches

    # gate 1: count every tracer / histogram-observe invocation while the
    # disabled engine serves the full mix
    calls = {"n": 0}

    def counting(fn):
        def wrapped(*a, **k):
            calls["n"] += 1
            return fn(*a, **k)
        return wrapped

    patched = [(obs_trace.Tracer, m) for m in ("begin", "end", "instant")]
    patched.append((obs_metrics.Histogram, "observe"))
    originals = [(cls, name, getattr(cls, name)) for cls, name in patched]
    for cls, name, orig in originals:
        setattr(cls, name, counting(orig))
    try:
        _, done_off, wall_off = run_once()
    finally:
        for cls, name, orig in originals:
            setattr(cls, name, orig)
    if calls["n"] != 0:
        raise RuntimeError(
            f"disabled telemetry executed {calls['n']} tracer/histogram "
            f"calls — the off path must be a structural no-op"
        )

    tracer = Tracer()
    eng_on, done_on, wall_on = run_once(tracer=tracer, record_times=True)
    if ({r.uid: tuple(r.output) for r in done_off}
            != {r.uid: tuple(r.output) for r in done_on}):
        raise RuntimeError("greedy tokens diverged telemetry on vs off")

    obj = tracer.to_json()  # raises if any span is still open
    spans = [e for e in obj["traceEvents"] if e.get("ph") == "X"]
    names = {e["tid"]: e["args"]["name"] for e in obj["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    coverage = []
    for r in done_on:
        tid = next(t for t, n in names.items() if n == f"req {r.uid}")
        track = [e for e in spans if e["tid"] == tid]
        lo = min(e["ts"] for e in track)
        hi = max(e["ts"] + e["dur"] for e in track)
        coverage.append(sum(e["dur"] for e in track) / (hi - lo) if hi > lo
                        else 1.0)
    min_cov = min(coverage)
    if min_cov < 0.95:
        raise RuntimeError(
            f"request phase spans cover only {min_cov:.1%} of submit->retire"
        )

    out_dir = os.environ.get("TELEMETRY_ARTIFACT_DIR")
    if out_dir:
        with open(os.path.join(out_dir, "telemetry-trace.json"), "w") as f:
            _json.dump(obj, f)
        with open(os.path.join(out_dir, "telemetry-metrics.json"), "w") as f:
            _json.dump(eng_on.metrics.snapshot(), f, indent=2, default=float)

    toks_off = sum(len(r.output) for r in done_off)
    toks_on = sum(len(r.output) for r in done_on)
    snap = eng_on.metrics.snapshot()
    yield ("serve/telemetry_off_us_per_token", wall_off / toks_off * 1e6,
           "no tracer: 0 telemetry calls per token (structurally gated)")
    yield ("serve/telemetry_on_us_per_token", wall_on / toks_on * 1e6,
           f"overhead={wall_on / wall_off - 1:+.1%},"
           f"trace_events={len(spans)},"
           f"min_span_coverage={min_cov:.1%},"
           f"ttft_p50={snap['histograms']['ttft_s']['p50'] * 1e3:.1f}ms")


ALL = [kernel_fusion, flash_vs_naive, profiler_blocks,
       hmp_schedules_multidevice, execplan_uneven, execplan_raggedsp,
       execplan_overlap, execplan_padshed, continuous_vs_wave,
       continuous_vs_wave_galaxy, prefix_sharing, spec_decode,
       serving_telemetry]
