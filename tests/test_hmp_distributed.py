"""Multi-device HMP equivalence tests.

These need >1 XLA device, so each test runs a SUBPROCESS with
--xla_force_host_platform_device_count set (the main pytest process must
keep seeing 1 device).  The subprocess asserts allclose and exits nonzero
on failure.
"""
import os
import subprocess
import sys
import textwrap


REPO = os.path.join(os.path.dirname(__file__), "..")


def run_multidevice(body: str, devices: int = 4) -> str:
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


def test_all_schedules_match_reference():
    """hmp / hmp_ring / megatron / sp all reproduce the single-device layer
    (paper Fig. 5 consistency requirement)."""
    run_multidevice("""
        import jax, jax.numpy as jnp
        from repro.core import hmp
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((4,), ('model',))
        p = hmp.init_layer_params(jax.random.PRNGKey(0), 64, 8, 128)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64)) * 0.5
        ref = hmp.reference_layer(p, x)
        for name, fn in hmp.SCHEDULES.items():
            out = fn(p, x, mesh)
            err = float(jnp.abs(out - ref).max())
            assert err < 1e-5, (name, err)
            print(name, 'ok', err)
    """)


def test_ring_primitives_match_sync():
    """ring AllGather⊗GEMM and GEMM⊗ReduceScatter == unoverlapped versions
    (paper §III-D: 'without yielding results inconsistent')."""
    run_multidevice("""
        import functools, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import ring
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((4,), ('model',))
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
        w1 = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
        h = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
        w2 = jax.random.normal(jax.random.PRNGKey(3), (64, 16))

        def ag(fn):
            return jax.shard_map(lambda xl, wl: fn(xl, wl, 'model'), mesh=mesh,
                             in_specs=(P(None,'model',None), P(None,'model')),
                             out_specs=P(None,None,'model'))
        out_r = ag(ring.ring_allgather_matmul)(x, w1)
        out_s = ag(ring.sync_allgather_matmul)(x, w1)
        assert float(jnp.abs(out_r - out_s).max()) < 1e-5
        expected = jnp.einsum('bsd,df->bsf', x, w1)
        assert float(jnp.abs(out_r - expected).max()) < 1e-5

        def rs(fn):
            return jax.shard_map(lambda hl, wl: fn(hl, wl, 'model'), mesh=mesh,
                             in_specs=(P(None,None,'model'), P('model',None)),
                             out_specs=P(None,'model',None))
        out_r = rs(ring.matmul_ring_reducescatter)(h, w2)
        out_s = rs(ring.sync_matmul_reducescatter)(h, w2)
        assert float(jnp.abs(out_r - out_s).max()) < 1e-5
        expected = jnp.einsum('bsf,fd->bsd', h, w2)
        assert float(jnp.abs(out_r - expected).max()) < 1e-4
        print('ring primitives ok')
    """)


def test_gspmd_model_matches_single_device():
    """The production GSPMD path (sharding constraints) is numerically the
    single-device model: run the reduced qwen forward on a 1x4 mesh."""
    run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.models import apply_model, init_params
        from repro.models.sharding import axis_rules, make_rules
        cfg = reduced(get_config('qwen1.5-0.5b'))
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        ref, _, _ = apply_model(params, cfg, mode='train', tokens=toks)
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((1, 4), ('data', 'model'))
        rules = make_rules(mesh, 'train', batch_size=2)
        with mesh:
            def fwd(p, t):
                with axis_rules(rules):
                    return apply_model(p, cfg, mode='train', tokens=t)[0]
            out = jax.jit(fwd)(params, toks)
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-4, err
        print('gspmd ok', err)
    """)


def test_gspmd_moe_matches_single_device():
    run_multidevice("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, reduced
        from repro.models import apply_model, init_params
        from repro.models.sharding import axis_rules, make_rules
        cfg = reduced(get_config('olmoe-1b-7b'))
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        ref, _, _ = apply_model(params, cfg, mode='train', tokens=toks)
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((2, 2), ('data', 'model'))
        rules = make_rules(mesh, 'train', batch_size=2)
        with mesh:
            def fwd(p, t):
                with axis_rules(rules):
                    return apply_model(p, cfg, mode='train', tokens=t)[0]
            out = jax.jit(fwd)(params, toks)
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-4, err
        print('gspmd moe ok', err)
    """)


def test_hmp_stack_of_layers():
    """Multiple stacked HMP layers (ring mode) remain consistent — catches
    cross-layer sharding drift."""
    run_multidevice("""
        import jax, jax.numpy as jnp
        from repro.core import hmp
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((4,), ('model',))
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        layers = [hmp.init_layer_params(k, 32, 4, 64) for k in keys]
        x = jax.random.normal(jax.random.PRNGKey(9), (2, 8, 32)) * 0.5
        ref = x
        for p in layers:
            ref = hmp.reference_layer(p, ref)
        out = x
        for p in layers:
            out = hmp.hmp_layer(p, out, mesh, overlap=True)
        err = float(jnp.abs(out - ref).max())
        assert err < 2e-5, err
        print('stack ok', err)
    """)
