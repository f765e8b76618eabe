"""ExecPlan: uneven planner output executed end-to-end.

Pure-python tests cover the pad-and-mask algebra (exactness needs no mesh:
zero-padded params compute the identical layer function even on one
device).  Multi-device tests run in subprocesses with
``--xla_force_host_platform_device_count`` (pattern per
test_hmp_distributed.py): an uneven plan from ``planner.plan`` must match
``reference_layer`` through hmp / hmp_ring, and the ServingEngine must
drive prefill + decode through the Galaxy schedule.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import hmp, planner
from repro.core.execplan import ExecPlan
from repro.core.planner import DeviceProfile, ModelProfile

REPO = os.path.join(os.path.dirname(__file__), "..")


def run_multidevice(body: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"  # a CPU emulation never competes for a chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def _uneven_plan(caps=(3.0, 2.0, 2.0, 1.0), heads=16, columns=64):
    model = ModelProfile("tiny", num_layers=2, num_heads=heads,
                         mlp_columns=columns, m_att=1e6, m_mlp=2e6)
    devs = [DeviceProfile(f"d{i}", c, 1e12) for i, c in enumerate(caps)]
    return planner.plan(model, devs)


# --- pure-python: geometry + padding algebra ---------------------------------

def test_from_plan_geometry():
    pl = _uneven_plan()
    assert pl.feasible
    ep = ExecPlan.from_plan(pl, head_dim=2, d_model=32)
    assert ep.heads == (6, 4, 4, 2) and ep.columns == (24, 16, 16, 8)
    assert ep.num_heads == 16 and ep.d_ff == 64
    assert ep.pad_heads == 6 and ep.pad_columns == 24
    assert ep.padded_heads == 24 and ep.padded_ff == 96
    assert not ep.is_even
    assert ep.head_mask().sum() == 16 and ep.column_mask().sum() == 64
    assert 0.3 < ep.padding_waste() < 0.45
    # planner output keeps the SP axis equal unless links are given
    assert not ep.uneven_seq and ep.seq_padding_waste() == 0.0
    assert ep.seq_tile(32) == 8
    # non-dividing lengths get a ragged layout instead of an error
    assert ep.seq_tiles(30) == (8, 8, 7, 7)
    assert ep.seq_tile(30) == 8 and ep.padded_seq(30) == 32
    assert ep.seq_grain == 4


def test_even_plan_is_identity_layout():
    ep = ExecPlan.even(4, num_heads=8, d_ff=64, head_dim=4, d_model=32)
    assert ep.is_even and ep.padded_heads == 8 and ep.padded_ff == 64
    assert ep.padding_waste() == 0.0
    with pytest.raises(ValueError):
        ExecPlan.even(3, num_heads=8, d_ff=64, head_dim=4, d_model=32)


def test_infeasible_plan_rejected():
    pl = planner.Plan(np.array([8, 8]), np.array([32, 32]),
                      np.array([0.5, 0.5]), feasible=False, reason="OOM")
    with pytest.raises(ValueError, match="infeasible"):
        ExecPlan.from_plan(pl, head_dim=2, d_model=32)


def test_pad_layer_params_is_exact():
    """Zero-padding heads/columns leaves the layer *function* unchanged:
    the single-device reference over padded params equals the original."""
    import jax
    import jax.numpy as jnp

    ep = ExecPlan.from_plan(_uneven_plan(), head_dim=2, d_model=32)
    p = hmp.init_layer_params(jax.random.PRNGKey(0), 32, 16, 64)
    pp = ep.pad_layer_params(p)
    assert pp["wq"].shape == (32, 24, 2) and pp["w1"].shape == (32, 96)
    assert pp["wo"].shape == (24, 2, 32) and pp["w2"].shape == (96, 32)
    # pad slots are zero, real slots are the original slices
    hm, cm = ep.head_mask(), ep.column_mask()
    assert not np.any(np.asarray(pp["wq"])[:, ~hm, :])
    assert not np.any(np.asarray(pp["w2"])[~cm, :])
    np.testing.assert_array_equal(
        np.asarray(pp["wq"])[:, hm, :], np.asarray(p["wq"]))
    np.testing.assert_array_equal(
        np.asarray(pp["w1"])[:, cm], np.asarray(p["w1"]))

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32)) * 0.5
    ref = hmp.reference_layer(p, x)
    out = hmp.reference_layer(pp, x)
    assert float(jnp.abs(out - ref).max()) < 1e-6
    # idempotent: already-padded params pass through
    assert ep.ensure_padded(pp) is pp


def test_param_mismatch_rejected():
    import jax

    ep = ExecPlan.from_plan(_uneven_plan(), head_dim=2, d_model=32)
    p = hmp.init_layer_params(jax.random.PRNGKey(0), 32, 8, 64)  # 8 != 16 heads
    with pytest.raises(ValueError, match="heads"):
        ep.pad_layer_params(p)


def test_to_planner_plan_fractions():
    ep = ExecPlan.from_plan(_uneven_plan(), head_dim=2, d_model=32)
    a, b = ep.compute_fractions()
    assert np.isclose(a.sum(), 1.0) and np.isclose(b.sum(), 1.0)
    ap, bp = ep.compute_fractions(padded=True)
    # padded execution: every device runs the straggler's share
    assert np.allclose(ap, 6 / 16) and np.allclose(bp, 24 / 64)
    assert ep.to_planner_plan().mha.sum() == 16
    assert np.all(ep.to_planner_plan(padded=True).mha == 6)


def test_prefill_gemm_flops_prices_suffix_only():
    """A prefix-cache hit shrinks per-shard prefill GEMM FLOPs to the
    uncached suffix rows (GEMM cost is row-linear; the attention-core
    context term is the simulator's job)."""
    ep = ExecPlan.from_plan(_uneven_plan(), head_dim=2, d_model=32)
    full = ep.prefill_gemm_flops(128)
    half = ep.prefill_gemm_flops(128, cached_prefix=64)
    np.testing.assert_allclose(half, full / 2)
    np.testing.assert_array_equal(half, ep.device_gemm_flops(64))
    # padded view scales the same way (every device at max(units))
    np.testing.assert_allclose(
        ep.prefill_gemm_flops(128, cached_prefix=64, padded=True),
        ep.device_gemm_flops(128, padded=True) / 2)
    for bad in (-1, 128, 200):
        with pytest.raises(ValueError, match="cached_prefix"):
            ep.prefill_gemm_flops(128, cached_prefix=bad)


def _ragged_plan():
    """3:2:2:1 cluster with uneven heads, columns AND sequence tiles."""
    return ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8), head_dim=2,
                    d_model=32, seq_shares=(3.0, 2.0, 2.0, 1.0))


def test_seq_layout_geometry():
    ep = _ragged_plan()
    assert ep.uneven_seq
    assert ep.seq_tiles(128) == (48, 32, 32, 16)  # the acceptance split
    lay = ep.seq_layout(13)
    assert lay.tiles == (5, 3, 3, 2) and lay.seq == 13
    assert lay.pad_tile == 5 and lay.padded_len == 20 and not lay.is_dense
    # rows/positions are inverse maps; pad rows carry -1
    assert lay.rows.shape == (13,) and lay.positions.shape == (20,)
    np.testing.assert_array_equal(lay.positions[lay.rows], np.arange(13))
    assert (lay.positions[~lay.valid] == -1).all()
    assert lay.valid.sum() == 13
    np.testing.assert_array_equal(lay.offsets, [0, 5, 8, 11])
    assert 0 < lay.padding_waste() < 1
    # padded plan view ships the straggler's fraction on every device
    padded = ep.to_planner_plan(padded=True)
    assert np.allclose(padded.seq, 3.0 / 8.0)
    assert "seq=" in ep.describe() and "sp_waste" in ep.describe()


def test_seq_layout_scatter_gather_roundtrip():
    import jax

    ep = _ragged_plan()
    lay = ep.seq_layout(13)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 13, 4))
    xp = lay.scatter(x)
    assert xp.shape == (2, 20, 4)
    np.testing.assert_allclose(np.asarray(lay.gather(xp)), np.asarray(x))
    # pad rows are zero after scatter
    assert not np.any(np.asarray(xp)[:, ~lay.valid])
    # dense layouts are identities (keeps the pre-ragged XLA graph)
    dense = ep.seq_layout(16)  # 3:2:2:1 of 16 -> (6,4,4,2), sums to pad? no
    # 16 * [0.375, .25, .25, .125] = (6,4,4,2): pad_tile 6, padded 24 — ragged
    assert not dense.is_dense
    even = ExecPlan.even(4, num_heads=8, d_ff=64, head_dim=4, d_model=32)
    lay_even = even.seq_layout(16)
    assert lay_even.is_dense and lay_even.scatter(x) is x


def test_seq_layout_attention_mask():
    lay = _ragged_plan().seq_layout(7)  # tiles (3,2,1,1), pad 3
    m = lay.attention_mask()
    pos, valid = lay.positions, lay.valid
    for i in range(lay.padded_len):
        for j in range(lay.padded_len):
            if valid[i]:
                assert m[i, j] == (valid[j] and pos[j] <= pos[i])
            else:
                assert m[i, j]  # pad queries attend everywhere (finite softmax)


def test_sequence_partition_bandwidth_aware():
    """planner.sequence_partition: capacity-proportional without links,
    shifted off the slow hop with them, loud on a degenerate byte weight."""
    from repro.core import costmodel
    from repro.core.planner import sequence_partition

    out = planner.sequence_partition(128, [3.0, 2.0, 2.0, 1.0])
    assert out.tolist() == [48, 32, 32, 16]

    caps = [1.0, 1.0, 1.0, 1.0]
    links = [costmodel.mbps(1000), costmodel.mbps(1000),
             costmodel.mbps(100), costmodel.mbps(1000)]
    aware = sequence_partition(128, caps, links)  # default unit_bytes works
    assert aware.sum() == 128 and (aware >= 0).all()
    # the slow hop 2->3 carries every tile except device 3's: the search
    # must shift rows onto device 3 to shrink the slow link's traffic
    assert aware[3] == aware.max() and aware[3] > 32, aware.tolist()
    # a zero byte weight would silently disable the bandwidth term
    with pytest.raises(ValueError, match="unit_bytes"):
        sequence_partition(128, caps, links, unit_bytes=0.0)
    # uniform links + uniform caps: stays the equal split
    assert sequence_partition(
        128, caps, costmodel.mbps(1000)).tolist() == [32, 32, 32, 32]


def test_plan_with_links_carries_uneven_seq():
    from repro.core import costmodel

    links = [costmodel.mbps(1000), costmodel.mbps(1000),
             costmodel.mbps(100), costmodel.mbps(1000)]
    model = ModelProfile("tiny", 2, 16, 64, 1e6, 2e6)
    devs = [DeviceProfile(f"d{i}", 1.0, 1e12) for i in range(4)]
    pl = planner.plan(model, devs, links, seq_units=128)
    assert pl.feasible
    assert np.isclose(pl.seq.sum(), 1.0)
    assert pl.seq.max() > 0.26  # no longer the equal split
    # heads/columns are untouched by the SP solve
    assert pl.mha.sum() == 16 and pl.mlp.sum() == 64
    ep = ExecPlan.from_plan(pl, head_dim=2, d_model=32)
    assert ep.uneven_seq


def test_seq_shares_validation():
    with pytest.raises(ValueError, match="seq_shares"):
        ExecPlan(heads=(4, 4), columns=(8, 8), head_dim=2, d_model=16,
                 seq_shares=(1.0,))
    with pytest.raises(ValueError, match="non-negative"):
        ExecPlan(heads=(4, 4), columns=(8, 8), head_dim=2, d_model=16,
                 seq_shares=(-1.0, 2.0))


def test_compute_backend_knob():
    """Backend validation, the shed-aware padded planner view, and the
    effective-vs-padded FLOPs accounting behind ``describe()``."""
    ep = ExecPlan.from_plan(_uneven_plan(), head_dim=2, d_model=32)
    assert ep.compute_backend == "xla"
    with pytest.raises(ValueError, match="compute_backend"):
        ep.with_backend("cuda")
    pal = ep.with_backend("pallas")
    assert pal.compute_backend == "pallas" and pal.heads == ep.heads

    # xla padded view executes max(units); pallas sheds back to assigned
    assert np.all(ep.to_planner_plan(padded=True).mha == ep.pad_heads)
    shed = pal.to_planner_plan(padded=True)
    assert np.all(shed.mha == np.asarray(ep.heads))
    assert np.all(shed.mlp == np.asarray(ep.columns))
    # ...but the transport side still ships the padded sequence tile
    assert np.allclose(shed.seq, ep.to_planner_plan(padded=True).seq)

    eff = ep.device_gemm_flops()
    pad = ep.device_gemm_flops(padded=True)
    assert np.all(eff <= pad) and len(set(pad)) == 1
    assert 0 < ep.flops_shed() < ep.padding_waste() + 0.1
    # describe prints per-device effective-vs-padded FLOPs + the backend
    assert "eff/pad flops=[" in ep.describe()
    assert "backend=pallas" in pal.describe()


def test_simulator_scores_shed_backend():
    """simulate_execplan(padded=True) on a pallas plan prices effective
    compute: between the unpadded view and the fully padded xla view."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import costmodel
    from repro.core.profiler import AnalyticProfiler
    from repro.core.simulator import simulate_execplan

    cfg = dataclasses.replace(get_config("distilbert"), num_layers=1)
    devices = [
        costmodel.DeviceSpec(f"e{i}", flops=c * 7.1e9, mem_bw=4.0e9,
                             memory_budget=1.5e9)
        for i, c in enumerate([3.0, 2.0, 2.0, 1.0])
    ]
    link = costmodel.mbps(1000)
    prof = AnalyticProfiler(cfg, 128)
    pl = planner.plan(prof.model_profile(), prof.device_profiles(devices))
    ep = ExecPlan.from_plan(pl, head_dim=cfg.head_dim, d_model=cfg.d_model)

    plain = simulate_execplan(ep, cfg, devices, link, 128, overlap=True)
    padded = simulate_execplan(ep, cfg, devices, link, 128, overlap=True,
                               padded=True)
    shed = simulate_execplan(ep.with_backend("pallas"), cfg, devices, link,
                             128, overlap=True, padded=True)
    assert plain.latency - 1e-12 <= shed.latency <= padded.latency + 1e-12
    # the equal seq split makes transport identical: shedding recovers the
    # whole compute-side padding premium here
    assert shed.latency < padded.latency


# --- multi-device: uneven plans through the real executor --------------------

def test_uneven_plan_matches_reference():
    """Acceptance: capacities [3,2,2,1], heads=16, columns=64 planned by
    planner.plan, executed through hmp/hmp_ring/megatron on meshes carved
    from an 8-device host platform — allclose vs reference_layer."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp, planner
        from repro.core.execplan import ExecPlan
        from repro.core.planner import DeviceProfile, ModelProfile
        from repro.launch.mesh import make_mesh_compat

        def plan_for(caps, heads=16, columns=64):
            model = ModelProfile('tiny', 2, heads, columns, 1e6, 2e6)
            devs = [DeviceProfile(f'd{i}', c, 1e12) for i, c in enumerate(caps)]
            pl = planner.plan(model, devs)
            assert pl.feasible, pl.reason
            return ExecPlan.from_plan(pl, head_dim=2, d_model=32)

        cases = [
            (plan_for([3.0, 2.0, 2.0, 1.0]),
             make_mesh_compat((4,), ('model',), devices=jax.devices()[:4])),
            (plan_for([3.0, 2.0, 2.0, 1.0, 4.0, 1.0, 2.0, 3.0]),
             make_mesh_compat((8,), ('model',))),
        ]
        p = hmp.init_layer_params(jax.random.PRNGKey(0), 32, 16, 64)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32)) * 0.5
        ref = hmp.reference_layer(p, x)
        for ep, mesh in cases:
            assert not ep.is_even, ep.describe()
            for name in ('hmp', 'hmp_ring', 'megatron'):
                out = hmp.SCHEDULES[name](p, x, mesh, plan=ep)
                err = float(jnp.abs(out - ref).max())
                assert err < 1e-5, (name, ep.describe(), err)
                print(ep.num_devices, name, 'ok', err)
    """)


def test_uneven_stack_prefill_decode_matches_reference():
    """hmp_prefill + hmp_decode under an uneven plan == full-context
    reference recompute, including a non-dividing prompt length."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp, planner
        from repro.core.execplan import ExecPlan
        from repro.core.planner import DeviceProfile, ModelProfile
        from repro.launch.mesh import make_mesh_compat

        caps = [3.0, 2.0, 2.0, 1.0, 4.0, 1.0, 2.0, 3.0]
        model = ModelProfile('tiny', 2, 16, 64, 1e6, 2e6)
        devs = [DeviceProfile(f'd{i}', c, 1e12) for i, c in enumerate(caps)]
        ep = ExecPlan.from_plan(planner.plan(model, devs), head_dim=2, d_model=32)
        mesh = make_mesh_compat((8,), ('model',))

        layers = hmp.init_stack_params(jax.random.PRNGKey(0), 2, 32, 16, 64)
        s, s_pad, extra = 11, ep.padded_seq(11), 3
        x_full = jax.random.normal(jax.random.PRNGKey(1), (2, s + extra, 32)) * 0.5

        # prefill over the padded prompt
        x_pad = jnp.zeros((2, s_pad, 32)).at[:, :s].set(x_full[:, :s])
        cache = hmp.make_kv_cache(2, 32, 2, mesh, ep)
        y, cache = hmp.hmp_prefill(layers, x_pad, mesh, cache, plan=ep,
                                   overlap=True)
        ref = hmp.reference_stack(layers, x_full)
        err = float(jnp.abs(y[:, :s] - ref[:, :s]).max())
        assert err < 2e-5, ('prefill', err)
        print('prefill ok', err)

        # decode steps s, s+1, ... against the cache
        for t in range(extra):
            y, cache = hmp.hmp_decode(layers, x_full[:, s + t:s + t + 1],
                                      mesh, cache, jnp.int32(s + t), plan=ep)
            err = float(jnp.abs(y[:, 0] - ref[:, s + t]).max())
            assert err < 2e-5, ('decode', t, err)
            print('decode', t, 'ok', err)
    """)


def test_serving_engine_galaxy_executor():
    """Acceptance: ServingEngine drives prefill + decode through the Galaxy
    schedule under an uneven 8-device plan; greedy tokens equal a
    full-context reference recompute."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp, planner
        from repro.core.execplan import ExecPlan
        from repro.core.planner import DeviceProfile, ModelProfile
        from repro.launch.mesh import make_mesh_compat
        from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

        caps = [3.0, 2.0, 2.0, 1.0, 4.0, 1.0, 2.0, 3.0]
        model = ModelProfile('tiny', 3, 16, 64, 1e6, 2e6)
        devs = [DeviceProfile(f'd{i}', c, 1e12) for i, c in enumerate(caps)]
        ep = ExecPlan.from_plan(planner.plan(model, devs), head_dim=2, d_model=32)
        mesh = make_mesh_compat((8,), ('model',))

        vocab, n_layers = 50, 3
        layers = hmp.init_stack_params(jax.random.PRNGKey(0), n_layers, 32, 16, 64)
        emb = jax.random.normal(jax.random.PRNGKey(7), (vocab, 32)) * 0.5

        exe = GalaxyHMPExecutor(layers, emb, ep, mesh, overlap=True)
        eng = ServingEngine(executor=exe, max_batch=4, max_len=24)
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11],
                   [4, 7, 1, 9, 2, 8, 3, 6, 5, 10, 12]]
        for i, pr in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=pr, max_new_tokens=4))
        done = {r.uid: r for r in eng.run()}
        assert eng.stats['decode_steps'] >= 3

        # reference: greedy full-context recompute per request
        for uid, pr in enumerate(prompts):
            toks = list(pr)
            for _ in range(4):
                x = emb[jnp.asarray([toks])]
                y = hmp.reference_stack(layers, x)
                logits = y[:, -1] @ emb.T
                toks.append(int(jnp.argmax(logits[0])))
            assert done[uid].output == toks[len(pr):], (
                uid, done[uid].output, toks[len(pr):])
            print('request', uid, 'tokens ok', done[uid].output)

        # direct numeric check of the executor's prefill/decode logits
        toks = jnp.asarray([prompts[0]], jnp.int32)
        cache = exe.make_cache(1, 24)
        logits, cache = exe.prefill(toks, cache)
        x = emb[toks]
        ref_logits = (hmp.reference_stack(layers, x)[:, -1] @ emb.T)
        err = float(jnp.abs(logits - ref_logits).max())
        assert err < 1e-4, ('prefill logits', err)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        logits2, cache = exe.decode(nxt, cache, jnp.int32(toks.shape[1]))
        x2 = jnp.concatenate([toks, nxt], axis=1)
        ref2 = (hmp.reference_stack(layers, emb[x2])[:, -1] @ emb.T)
        err2 = float(jnp.abs(logits2 - ref2).max())
        assert err2 < 1e-4, ('decode logits', err2)
        print('executor logits ok', err, err2)
    """)


def test_serving_engine_galaxy_continuous_batching():
    """Acceptance: continuous batching over the paged head-sharded KV pool
    under an uneven 8-device plan — greedy tokens equal both the wave path
    and a full-context reference recompute, and mixed-length waves (prompts
    sharing a padded bucket) stay exact."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp, planner
        from repro.core.execplan import ExecPlan
        from repro.core.planner import DeviceProfile, ModelProfile
        from repro.launch.mesh import make_mesh_compat
        from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

        caps = [3.0, 2.0, 2.0, 1.0, 4.0, 1.0, 2.0, 3.0]
        model = ModelProfile('tiny', 3, 16, 64, 1e6, 2e6)
        devs = [DeviceProfile(f'd{i}', c, 1e12) for i, c in enumerate(caps)]
        ep = ExecPlan.from_plan(planner.plan(model, devs), head_dim=2, d_model=32)
        mesh = make_mesh_compat((8,), ('model',))
        assert not ep.is_even, ep.describe()

        vocab, n_layers = 50, 3
        layers = hmp.init_stack_params(jax.random.PRNGKey(0), n_layers, 32, 16, 64)
        emb = jax.random.normal(jax.random.PRNGKey(7), (vocab, 32)) * 0.5
        exe = GalaxyHMPExecutor(layers, emb, ep, mesh, overlap=True)
        assert exe.prompt_pad_multiple == 8 and exe.supports_paged

        # mixed prompt lengths (11, 11, 8, 4): lengths 8 and 4 share the
        # padded-8 wave bucket, so the wave path also runs mixed-depth decode
        prompts = [[1,2,3,4,5,6,7,8,9,10,11], [4,7,1,9,2,8,3,6,5,10,12],
                   [3,1,4,1,5,9,2,6], [2,7,1,8]]

        def run(scheduler):
            eng = ServingEngine(executor=exe, max_batch=3, max_len=24,
                                scheduler=scheduler, page_size=8)
            for i, pr in enumerate(prompts):
                eng.submit(Request(uid=i, prompt=list(pr), max_new_tokens=3 + i))
            return {r.uid: r.output for r in eng.run()}, eng.stats

        wave, wave_stats = run('wave')
        cont, cont_stats = run('continuous')
        assert cont == wave, (cont, wave)
        assert cont_stats['decode_steps'] <= wave_stats['decode_steps']

        for uid, pr in enumerate(prompts):
            toks = list(pr)
            for _ in range(3 + uid):
                x = emb[jnp.asarray([toks])]
                y = hmp.reference_stack(layers, x)
                toks.append(int(jnp.argmax(y[:, -1] @ emb.T, -1)[0]))
            assert cont[uid] == toks[len(pr):], (uid, cont[uid], toks[len(pr):])
            print('request', uid, 'tokens ok', cont[uid])
        print('continuous == wave == reference;',
              cont_stats['decode_steps'], 'vs', wave_stats['decode_steps'], 'steps')
    """)


def test_uneven_seq_plan_matches_reference():
    """Acceptance (mirrors the uneven-head case): ragged sequence tiles on
    4- and 8-device meshes — hmp / hmp_ring under uneven seq_shares match
    reference_layer for dividing and non-dividing lengths."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp
        from repro.core.execplan import ExecPlan
        from repro.launch.mesh import make_mesh_compat

        cases = [
            (ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8), head_dim=2,
                      d_model=32, seq_shares=(3.0, 2.0, 2.0, 1.0)),
             make_mesh_compat((4,), ('model',), devices=jax.devices()[:4])),
            (ExecPlan(heads=(3, 2, 2, 1, 4, 1, 2, 1),
                      columns=(12, 8, 8, 4, 16, 4, 8, 4), head_dim=2,
                      d_model=32,
                      seq_shares=(3.0, 2.0, 2.0, 1.0, 4.0, 0.0, 2.0, 3.0)),
             make_mesh_compat((8,), ('model',))),
        ]
        p = hmp.init_layer_params(jax.random.PRNGKey(0), 32, 16, 64)
        for ep, mesh in cases:
            assert ep.uneven_seq, ep.describe()
            for s in (16, 13):
                lay = ep.seq_layout(s)
                x = jax.random.normal(jax.random.PRNGKey(1), (2, s, 32)) * 0.5
                ref = hmp.reference_layer(p, x)
                xp = lay.scatter(x)
                for overlap in (False, True):
                    y = hmp.hmp_layer(p, xp, mesh, overlap=overlap, plan=ep,
                                      seq=s)
                    err = float(jnp.abs(lay.gather(y) - ref).max())
                    assert err < 2e-5, (ep.num_devices, s, overlap, err)
                    print(ep.num_devices, 'devs seq', s, 'overlap', overlap,
                          'ok', err)
    """)


def test_uneven_seq_serving_acceptance():
    """ISSUE acceptance: tiles [48, 32, 32, 16] on a 3:2:2:1 cluster with
    one slow link — prefill + decode through GalaxyHMPExecutor produce
    greedy tokens exactly matching the full-context reference, and the
    simulator scores the bandwidth-aware split below the equal split."""
    run_multidevice("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp
        from repro.core.execplan import ExecPlan
        from repro.launch.mesh import make_mesh_compat
        from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

        ep = ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8), head_dim=2,
                      d_model=32, seq_shares=(3.0, 2.0, 2.0, 1.0))
        assert ep.seq_tiles(128) == (48, 32, 32, 16), ep.seq_tiles(128)
        mesh = make_mesh_compat((4,), ('model',))

        vocab, n_layers = 50, 3
        layers = hmp.init_stack_params(jax.random.PRNGKey(0), n_layers, 32, 16, 64)
        emb = jax.random.normal(jax.random.PRNGKey(7), (vocab, 32)) * 0.5
        exe = GalaxyHMPExecutor(layers, emb, ep, mesh, overlap=True)
        prompts = [[1,2,3,4,5,6,7,8,9,10,11], [4,7,1,9,2,8,3,6,5,10,12],
                   [3,1,4,1,5,9,2,6], [2,7,1,8]]

        def run(scheduler):
            eng = ServingEngine(executor=exe, max_batch=3, max_len=24,
                                scheduler=scheduler, page_size=8)
            for i, pr in enumerate(prompts):
                eng.submit(Request(uid=i, prompt=list(pr), max_new_tokens=3 + i))
            return {r.uid: r.output for r in eng.run()}

        wave, cont = run('wave'), run('continuous')
        assert wave == cont, (wave, cont)
        for uid, pr in enumerate(prompts):
            toks = list(pr)
            for _ in range(3 + uid):
                y = hmp.reference_stack(layers, emb[jnp.asarray([toks])])
                toks.append(int(jnp.argmax(y[:, -1] @ emb.T, -1)[0]))
            assert cont[uid] == toks[len(pr):], (uid, cont[uid], toks[len(pr):])
            print('request', uid, 'tokens ok', cont[uid])

        # simulator half of the acceptance: bandwidth-aware < equal
        from repro.configs import get_config
        from repro.core import costmodel
        from repro.core.profiler import AnalyticProfiler
        from repro.core.simulator import simulate_execplan
        cfg = dataclasses.replace(get_config('distilbert'), num_layers=1)
        caps = [3.0, 2.0, 2.0, 1.0]
        devices = [costmodel.DeviceSpec(f'e{i}', flops=c * 7.1e9, mem_bw=4.0e9,
                                        memory_budget=1.5e9)
                   for i, c in enumerate(caps)]
        links = [costmodel.mbps(1000), costmodel.mbps(1000),
                 costmodel.mbps(100), costmodel.mbps(1000)]
        prof = AnalyticProfiler(cfg, 128)
        ep_eq = ExecPlan.from_plan(prof.plan(devices), head_dim=cfg.head_dim,
                                   d_model=cfg.d_model)
        ep_bw = ExecPlan.from_plan(prof.plan(devices, links=links),
                                   head_dim=cfg.head_dim, d_model=cfg.d_model)
        assert ep_bw.uneven_seq and not ep_eq.uneven_seq
        r_eq = simulate_execplan(ep_eq, cfg, devices, links, 128, overlap=True)
        r_bw = simulate_execplan(ep_bw, cfg, devices, links, 128, overlap=True)
        assert r_bw.latency < r_eq.latency, (r_bw.latency, r_eq.latency)
        print(f'sim: aware {r_bw.latency*1e3:.1f}ms < equal '
              f'{r_eq.latency*1e3:.1f}ms')
    """, devices=4)


def test_overlap_transport_serving_acceptance():
    """ISSUE acceptance: bucketed ragged transport + double-buffered tile
    overlap through the full serving stack — a 4-device uneven plan serves
    with ``transport='bucketed', double_buffer=True`` on both schedulers,
    greedy tokens pinned equal to the padded-transport executor and a
    full-context reference recompute, and the executor's plan confirms the
    transport actually sheds wire rows."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp
        from repro.core.execplan import ExecPlan
        from repro.launch.mesh import make_mesh_compat
        from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

        # uneven on every axis: heads, columns, and sequence tiles
        ep = ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8), head_dim=2,
                      d_model=32, seq_shares=(3.0, 2.0, 2.0, 1.0))
        mesh = make_mesh_compat((4,), ('model',))
        vocab, n_layers = 50, 3
        layers = hmp.init_stack_params(jax.random.PRNGKey(0), n_layers, 32, 16, 64)
        emb = jax.random.normal(jax.random.PRNGKey(7), (vocab, 32)) * 0.5
        prompts = [[1,2,3,4,5,6,7,8,9,10,11], [4,7,1,9,2,8,3,6,5,10,12],
                   [3,1,4,1,5,9,2,6], [2,7,1,8]]

        def serve(exe, scheduler):
            eng = ServingEngine(executor=exe, max_batch=3, max_len=24,
                                scheduler=scheduler, page_size=8)
            for i, pr in enumerate(prompts):
                eng.submit(Request(uid=i, prompt=list(pr), max_new_tokens=3 + i))
            return {r.uid: r.output for r in eng.run()}

        exe_pad = GalaxyHMPExecutor(layers, emb, ep, mesh, overlap=True)
        exe_db = GalaxyHMPExecutor(layers, emb, ep, mesh, overlap=True,
                                   transport='bucketed', double_buffer=True)
        assert exe_db.plan.transport == 'bucketed' and exe_db.plan.double_buffer
        sched = exe_db.plan.ring_schedule(128)
        assert sched.total_wire_rows() < sched.padded_wire_rows(), \\
            'bucketed transport sheds no wire on this plan'

        runs = {(label, scheduler): serve(exe, scheduler)
                for label, exe in (('padded', exe_pad), ('bucketed_db', exe_db))
                for scheduler in ('wave', 'continuous')}
        first = runs[('padded', 'wave')]
        for key, out in runs.items():
            assert out == first, (key, out, first)

        # and the shared answer is the full-context greedy reference
        for uid, pr in enumerate(prompts):
            toks = list(pr)
            for _ in range(3 + uid):
                y = hmp.reference_stack(layers, emb[jnp.asarray([toks])])
                toks.append(int(jnp.argmax(y[:, -1] @ emb.T, -1)[0]))
            assert first[uid] == toks[len(pr):], (uid, first[uid], toks[len(pr):])
            print('request', uid, 'tokens ok', first[uid])
        print('wire rows', sched.total_wire_rows(), '/',
              sched.padded_wire_rows())
    """, devices=4)


def test_prefix_cache_serving_acceptance():
    """ISSUE acceptance on the Galaxy executor: greedy tokens with the
    shared-prefix KV cache on == cache off == chunked prefill ==
    full-context reference, on both schedulers, under an uneven
    (heads, columns, sequence) 3:2:2:1 4-device plan — with suffix-only
    prefill measured (computed == prompt - cached), >= 1 physical page
    shared across >= 2 concurrent slots, and the pool's refcount algebra
    verified by ``check()``."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp
        from repro.core.execplan import ExecPlan
        from repro.launch.mesh import make_mesh_compat
        from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

        ep = ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8), head_dim=2,
                      d_model=32, seq_shares=(3.0, 2.0, 2.0, 1.0))
        mesh = make_mesh_compat((4,), ('model',))
        vocab, n_layers = 50, 2
        layers = hmp.init_stack_params(jax.random.PRNGKey(0), n_layers,
                                       32, 16, 64)
        emb = jax.random.normal(jax.random.PRNGKey(7), (vocab, 32)) * 0.5
        exe = GalaxyHMPExecutor(layers, emb, ep, mesh, overlap=True)

        sysp = list(range(1, 17))  # 16-token shared system prompt (2 pages)
        prompts = [sysp + [20 + i, 21, 22 + i, 23] for i in range(4)]

        def run(**kw):
            eng = ServingEngine(executor=exe, max_batch=3, max_len=40,
                                page_size=8, **kw)
            for i, pr in enumerate(prompts):
                eng.submit(Request(uid=i, prompt=list(pr),
                                   max_new_tokens=3 + i))
            return {r.uid: r.output for r in eng.run()}, eng

        base, eng0 = run(scheduler='continuous')
        wave, _ = run(scheduler='wave')
        on, eng1 = run(scheduler='continuous', prefix_cache=True)
        chunked, eng2 = run(scheduler='continuous', prefill_chunk=8)
        both, eng3 = run(scheduler='continuous', prefix_cache=True,
                         prefill_chunk=8)
        assert wave == base and on == base, (wave, on, base)
        assert chunked == base and both == base, (chunked, both, base)

        s1 = eng1.stats
        total_prompt = sum(len(p) for p in prompts)
        assert s1['cached_prefix_tokens'] > 0
        assert s1['prefill_tokens'] + s1['cached_prefix_tokens'] == total_prompt
        assert s1['peak_shared_pages'] >= 1, s1
        assert eng2.stats['prefill_chunks'] >= len(prompts)
        eng1.pool.check()
        print('suffix-only prefill:', s1['prefill_tokens'], 'of',
              total_prompt, '| shared pages:', s1['peak_shared_pages'],
              '| hits:', s1['prefix_hits'])

        # full-context reference: plain stacked layers, no paging/sharing
        for uid, pr in enumerate(prompts):
            toks = list(pr)
            for _ in range(3 + uid):
                y = hmp.reference_stack(layers, emb[jnp.asarray([toks])])
                toks.append(int(jnp.argmax(y[:, -1] @ emb.T, -1)[0]))
            assert on[uid] == toks[len(pr):], (uid, on[uid], toks[len(pr):])
        print('prefix cache on == off == chunked == wave == reference')
    """, devices=4)


def test_pallas_backend_serving_acceptance():
    """ISSUE acceptance: the pad-shedding pallas backend on an uneven
    (heads, columns, sequence) 3:2:2:1 plan — greedy serving tokens through
    ``compute_backend="pallas"`` equal the padded-XLA oracle equal the
    full-context reference, on both schedulers; layer outputs agree across
    backends for dividing and non-dividing lengths."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import hmp
        from repro.core.execplan import ExecPlan
        from repro.launch.mesh import make_mesh_compat
        from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

        ep = ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8), head_dim=2,
                      d_model=32, seq_shares=(3.0, 2.0, 2.0, 1.0))
        mesh = make_mesh_compat((4,), ('model',))

        # layer: pallas == xla == reference on ragged + dense lengths
        p = hmp.init_layer_params(jax.random.PRNGKey(0), 32, 16, 64)
        for s in (16, 13):
            lay = ep.seq_layout(s)
            x = jax.random.normal(jax.random.PRNGKey(1), (2, s, 32)) * 0.5
            ref = hmp.reference_layer(p, x)
            xp = lay.scatter(x)
            for overlap in (False, True):
                y_x = hmp.hmp_layer(p, xp, mesh, overlap=overlap, plan=ep,
                                    seq=s)
                y_p = hmp.hmp_layer(p, xp, mesh, overlap=overlap,
                                    plan=ep.with_backend('pallas'), seq=s)
                e_ref = float(jnp.abs(lay.gather(y_p) - ref).max())
                e_xla = float(jnp.abs(y_p - y_x).max())
                assert e_ref < 2e-5 and e_xla < 1e-4, (s, overlap, e_ref, e_xla)
                print('layer seq', s, 'overlap', overlap, 'ok', e_ref, e_xla)

        # serving: greedy tokens pallas == xla == full-context reference
        vocab, n_layers = 50, 3
        layers = hmp.init_stack_params(jax.random.PRNGKey(0), n_layers, 32, 16, 64)
        emb = jax.random.normal(jax.random.PRNGKey(7), (vocab, 32)) * 0.5
        prompts = [[1,2,3,4,5,6,7,8,9,10,11], [4,7,1,9,2,8,3,6,5,10,12],
                   [3,1,4,1,5,9,2,6], [2,7,1,8]]

        def run(backend, scheduler):
            exe = GalaxyHMPExecutor(layers, emb, ep, mesh, overlap=True,
                                    compute_backend=backend)
            assert exe.plan.compute_backend == backend
            eng = ServingEngine(executor=exe, max_batch=3, max_len=24,
                                scheduler=scheduler, page_size=8)
            for i, pr in enumerate(prompts):
                eng.submit(Request(uid=i, prompt=list(pr), max_new_tokens=3 + i))
            return {r.uid: r.output for r in eng.run()}

        out = {(b, s): run(b, s) for b in ('xla', 'pallas')
               for s in ('wave', 'continuous')}
        assert out['pallas', 'wave'] == out['xla', 'wave']
        assert out['pallas', 'continuous'] == out['xla', 'continuous']
        assert out['pallas', 'continuous'] == out['pallas', 'wave']

        for uid, pr in enumerate(prompts):
            toks = list(pr)
            for _ in range(3 + uid):
                y = hmp.reference_stack(layers, emb[jnp.asarray([toks])])
                toks.append(int(jnp.argmax(y[:, -1] @ emb.T, -1)[0]))
            assert out['pallas', 'continuous'][uid] == toks[len(pr):], (
                uid, out['pallas', 'continuous'][uid], toks[len(pr):])
            print('request', uid, 'pallas tokens ok',
                  out['pallas', 'continuous'][uid])
        print('pallas == xla == reference on both schedulers')
    """, devices=4)


def test_ring_tile_size_validation():
    """Non-dividing sequences raise ValueError at trace time (not a bare
    assert), for both ring and sync reduce-scatter paths."""
    run_multidevice("""
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.core import hmp, ring
        from repro.core.execplan import ExecPlan
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((4,), ('model',))

        h = jax.random.normal(jax.random.PRNGKey(0), (1, 30, 16))  # 30 % 4 != 0
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        for fn in (ring.matmul_ring_reducescatter, ring.sync_matmul_reducescatter):
            try:
                jax.shard_map(lambda hl, wl, f=fn: f(hl, wl, 'model'), mesh=mesh,
                          in_specs=(P(None, None, 'model'), P('model', None)),
                          out_specs=P(None, 'model', None))(h, w)
            except ValueError as e:
                print('ok:', type(e).__name__)
            else:
                raise SystemExit('expected ValueError for non-dividing seq')

        # a schedule whose pad_tile disagrees with the shapes is rejected
        h2 = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 16))
        bad4 = ring.RingSchedule.dense(4, 4)
        try:
            jax.shard_map(lambda hl, wl: ring.matmul_ring_reducescatter(
                          hl, wl, 'model', schedule=bad4), mesh=mesh,
                      in_specs=(P(None, None, 'model'), P('model', None)),
                      out_specs=P(None, 'model', None))(h2, w)
        except ValueError as e:
            print('ok:', type(e).__name__)
        else:
            raise SystemExit('expected ValueError for wrong tile size')

        # hmp_layer under a plan rejects a non-dividing sequence up front
        ep = ExecPlan.even(4, num_heads=8, d_ff=32, head_dim=4, d_model=32)
        p = hmp.init_layer_params(jax.random.PRNGKey(0), 32, 8, 32)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 30, 32))
        try:
            hmp.hmp_layer(p, x, mesh, plan=ep)
        except ValueError as e:
            print('ok:', type(e).__name__)
        else:
            raise SystemExit('expected ValueError from hmp_layer')
    """, devices=4)


def test_simulator_scores_the_executed_plan():
    """simulate_execplan consumes the same ExecPlan the executor runs and
    exposes the padding premium of SPMD execution."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import costmodel
    from repro.core.simulator import simulate_execplan

    cfg = dataclasses.replace(get_config("distilbert"), num_layers=1)
    caps = [3.0, 2.0, 2.0, 1.0]
    devices = [
        costmodel.DeviceSpec(f"e{i}", flops=c * 7.1e9, mem_bw=4.0e9,
                             memory_budget=1.5e9)
        for i, c in enumerate(caps)
    ]
    link = costmodel.mbps(1000)
    from repro.core.profiler import AnalyticProfiler

    prof = AnalyticProfiler(cfg, 128)
    pl = planner.plan(prof.model_profile(), prof.device_profiles(devices))
    assert pl.feasible
    ep = ExecPlan.from_plan(pl, head_dim=cfg.head_dim, d_model=cfg.d_model)
    assert not ep.is_even

    sync = simulate_execplan(ep, cfg, devices, link, 128, overlap=False)
    ring_ = simulate_execplan(ep, cfg, devices, link, 128, overlap=True)
    padded = simulate_execplan(ep, cfg, devices, link, 128, overlap=True,
                               padded=True)
    assert 0 < ring_.latency <= sync.latency
    # padding makes every device run the straggler's share: never faster
    assert padded.latency >= ring_.latency - 1e-12
    with pytest.raises(ValueError, match="devices"):
        simulate_execplan(ep, cfg, devices[:2], link, 128)
