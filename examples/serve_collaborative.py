"""Collaborative serving example: batched requests through the serving
engine + the multi-device HMP layer schedules (paper's core loop),
executed for real on forced CPU devices.

    PYTHONPATH=src python examples/serve_collaborative.py

Serving
-------
The engine (``repro.serving.ServingEngine``) runs **continuous batching**
over a paged KV pool whenever the executor implements the paged protocol
(both bundled executors do):

1. ``PagedKVPool`` (``serving/kvpool.py``) owns fixed-size KV pages and a
   block table mapping (slot, logical page) -> physical page; page storage
   lives with the executor — head-sharded exactly like the dense HMP cache
   for ``GalaxyHMPExecutor``, the model-zoo cache pytree for
   ``TransformerExecutor``.
2. A request is admitted the moment a decode slot is free *and* the pool
   can reserve its worst-case page count (deadlock-free admission); its
   prompt prefills straight into its pages (``hmp_prefill(block_row=)``
   scatters prompt KV inside the shard_map on the Galaxy path).
3. Every decode step advances all live slots at their own depths in one
   batched call: the block table gathers each slot's pages, the new KV
   entry scatters back into its page (``hmp_decode(block_table=)``).
4. A request retires on EOS or max-len; its pages return to the free list
   and the freed slot refills from the queue on the same step — no slot
   idles while work is queued, which is where the tokens/sec win over
   wave scheduling comes from (see ``benchmarks/microbench.py:
   continuous_vs_wave``).

``scheduler="wave"`` keeps the legacy lockstep path (same greedy tokens —
the engine-level contract tests pin both executors against it); executors
without the paged protocol fall back to it automatically.  Prompt padding
policy belongs to the executor (``prompt_pad_multiple``): 1 for the
single-device zoo, the mesh size for the SP-sharded Galaxy prefill.

Prompt-heavy traffic adds two continuous-scheduler features (see
``prefix_sharing_demo`` and the ``--prefix-cache on|off`` /
``--prefill-chunk N`` flags here and on ``launch/serve.py``): the
shared-prefix KV cache admission flow — radix-tree lookup of the prompt ->
refcount bump on the hit's shared pages -> suffix-only chunked prefill ->
insert the new full pages for later requests — and chunked prefill, which
interleaves page-sized prefill chunks with decode steps so long prompts
stop stalling live slots.
"""
import os
import subprocess
import sys

REPO = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(REPO, "src")
sys.path.insert(0, SRC)


def serve_demo():
    import jax
    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving import Request, SamplerConfig, ServingEngine

    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(params, cfg, max_batch=4, max_len=64,
                           sampler=SamplerConfig(temperature=0.8, top_k=20))
    import numpy as np

    rng = np.random.default_rng(0)
    for i in range(10):
        engine.submit(Request(uid=i, prompt=rng.integers(0, 500, 16).tolist(),
                              max_new_tokens=12))
    done = engine.run()
    print(f"served {len(done)} requests; stats={engine.stats}")
    print(f"sample output: {done[0].output}")


def hmp_demo():
    """Run the paper's four schedules on 4 devices (subprocess)."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.core import hmp\n"
        "from repro.launch.mesh import make_mesh_compat\n"
        "mesh = make_mesh_compat((4,), ('model',))\n"
        "p = hmp.init_layer_params(jax.random.PRNGKey(0), 128, 8, 512)\n"
        "x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 128))\n"
        "ref = hmp.reference_layer(p, x)\n"
        "for name, fn in hmp.SCHEDULES.items():\n"
        "    err = float(jnp.abs(fn(p, x, mesh) - ref).max())\n"
        "    print(f'  {name:10s} matches reference: max_err={err:.2e}')\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    print("HMP schedules on a 4-device ring (paper Fig. 5-7):")
    subprocess.run([sys.executable, "-c", code], env=env, check=True)


def continuous_batching_demo():
    """Continuous batching vs waves on a skewed request mix (single device)."""
    import time

    import jax
    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving import Request, ServingEngine, TransformerExecutor

    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    executor = TransformerExecutor(params, cfg)
    print("Continuous batching vs waves (skewed output lengths):")
    for scheduler in ("wave", "continuous"):
        for _ in range(2):  # first pass warms the jit caches
            eng = ServingEngine(executor=executor, max_batch=4, max_len=48,
                                scheduler=scheduler, page_size=8)
            for i in range(12):
                eng.submit(Request(uid=i, prompt=[1 + i] * 8,
                                   max_new_tokens=24 if i % 4 == 0 else 4))
            t0 = time.perf_counter()
            done = eng.run()
            wall = time.perf_counter() - t0
        toks = sum(len(r.output) for r in done)
        print(f"  {scheduler:10s} {toks} tokens in {wall*1e3:6.1f}ms "
              f"({toks/wall:6.1f} tok/s, {eng.stats['decode_steps']} steps)")


def raggedsp_serving_demo():
    """Bandwidth-heterogeneous cluster: the planner solves uneven *sequence*
    tiles from capacity + per-link bandwidth (one slow hop in the ring), and
    the executor runs them as a padded ragged layout — any prompt length,
    no mesh divisibility."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.core import costmodel, hmp\n"
        "from repro.core.execplan import ExecPlan\n"
        "from repro.core.profiler import AnalyticProfiler\n"
        "from repro.core.simulator import simulate_execplan\n"
        "from repro.configs import get_config\n"
        "import dataclasses\n"
        "from repro.launch.mesh import make_mesh_compat\n"
        "from repro.serving import GalaxyHMPExecutor, Request, ServingEngine\n"
        "cfg = dataclasses.replace(get_config('distilbert'), num_layers=1)\n"
        "caps = [3.0, 2.0, 2.0, 1.0]\n"
        "devs = [costmodel.DeviceSpec(f'edge{i}', flops=c*7.1e9, mem_bw=4e9,\n"
        "                             memory_budget=1.5e9)\n"
        "        for i, c in enumerate(caps)]\n"
        "links = [costmodel.mbps(1000), costmodel.mbps(1000),\n"
        "         costmodel.mbps(100), costmodel.mbps(1000)]  # one slow hop\n"
        "prof = AnalyticProfiler(cfg, 128)\n"
        "pl = prof.plan(devs, links=links)\n"
        "ep = ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8),\n"
        "              head_dim=8, d_model=128,\n"
        "              seq_shares=tuple(pl.seq))  # tiny demo model, real split\n"
        "print('  plan:', ep.describe())\n"
        "eq = simulate_execplan(ExecPlan.from_plan(prof.plan(devs),\n"
        "      head_dim=cfg.head_dim, d_model=cfg.d_model), cfg, devs, links, 128)\n"
        "bw = simulate_execplan(ExecPlan.from_plan(pl, head_dim=cfg.head_dim,\n"
        "      d_model=cfg.d_model), cfg, devs, links, 128)\n"
        "print(f'  simulated/layer: equal {eq.latency*1e3:.1f}ms vs '\n"
        "      f'bandwidth-aware {bw.latency*1e3:.1f}ms '\n"
        "      f'({eq.latency/bw.latency:.2f}x)')\n"
        "mesh = make_mesh_compat((4,), ('model',))\n"
        "layers = hmp.init_stack_params(jax.random.PRNGKey(0), 2, 128, 16, 48)\n"
        "ep = dataclasses.replace(ep, columns=(18, 12, 12, 6))\n"
        "emb = jax.random.normal(jax.random.PRNGKey(7), (500, 128)) * 0.5\n"
        "exe = GalaxyHMPExecutor(layers, emb, ep, mesh)\n"
        "eng = ServingEngine(executor=exe, max_batch=4, max_len=48,\n"
        "                    scheduler='continuous', page_size=8)\n"
        "for i in range(6):\n"
        "    eng.submit(Request(uid=i, prompt=list(range(1 + i, 14 + 2 * i)),\n"
        "                       max_new_tokens=10 if i % 3 == 0 else 4))\n"
        "done = eng.run()\n"
        "print(f'  served {len(done)} requests over ragged sequence tiles; '\n"
        "      f'stats={eng.stats}')\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    print("Ragged SP on a bandwidth-heterogeneous cluster (one 100 Mbps hop):")
    subprocess.run([sys.executable, "-c", code], env=env, check=True)


def overlap_transport_demo():
    """The ring transport knobs (``ExecPlan.with_transport`` /
    ``GalaxyHMPExecutor(transport=..., double_buffer=...)``): "padded"
    ships the straggler's whole sequence tile on every ring hop, while
    "bucketed" ships each tile's bucket-rounded valid rows and
    ``double_buffer=True`` issues the next hop before the GEMM that hides
    it (``core/ring.py`` RingSchedule).  Greedy tokens are bitwise
    identical by construction; the wire savings show up in
    ``ExecPlan.describe()`` and ``RingSchedule.total_wire_rows``."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.core import hmp\n"
        "from repro.core.execplan import ExecPlan\n"
        "from repro.launch.mesh import make_mesh_compat\n"
        "from repro.serving import GalaxyHMPExecutor, Request, ServingEngine\n"
        "ep = ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8),\n"
        "              head_dim=8, d_model=128,\n"
        "              seq_shares=(3.0, 2.0, 2.0, 1.0))  # uneven seq tiles\n"
        "mesh = make_mesh_compat((4,), ('model',))\n"
        "layers = hmp.init_stack_params(jax.random.PRNGKey(0), 2, 128, 16, 64)\n"
        "emb = jax.random.normal(jax.random.PRNGKey(7), (500, 128)) * 0.5\n"
        "outs = {}\n"
        "for label, kw in [('padded', {}),\n"
        "                  ('bucketed+db', dict(transport='bucketed',\n"
        "                                       double_buffer=True))]:\n"
        "    exe = GalaxyHMPExecutor(layers, emb, ep, mesh, **kw)\n"
        "    print('  plan:', exe.plan.describe())\n"
        "    eng = ServingEngine(executor=exe, max_batch=4, max_len=40,\n"
        "                        scheduler='continuous', page_size=8)\n"
        "    for i in range(6):\n"
        "        eng.submit(Request(uid=i, prompt=list(range(1 + i, 12 + i)),\n"
        "                           max_new_tokens=8 if i % 3 == 0 else 4))\n"
        "    outs[label] = {r.uid: tuple(r.output) for r in eng.run()}\n"
        "assert outs['padded'] == outs['bucketed+db'], 'transports diverged'\n"
        "sched = exe.plan.ring_schedule(128)\n"
        "print('  greedy tokens identical across transports; one rotation'\n"
        "      f' ships {sched.total_wire_rows()} rows vs'\n"
        "      f' {sched.padded_wire_rows()} padded'\n"
        "      f' ({sched.wire_fraction():.0%} of the padded wire)')\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    print("Overlap ring transport (padded vs bucketed + double-buffered):")
    subprocess.run([sys.executable, "-c", code], env=env, check=True)


def padshed_backend_demo():
    """The ``compute_backend`` knob (``ExecPlan.compute_backend`` /
    ``GalaxyHMPExecutor(compute_backend=...)`` / ``launch/serve.py
    --compute-backend``): "xla" runs the padded dense oracle — every device
    executes max(units) work, zeros included — while "pallas" routes every
    per-shard matmul and the prefill attention through the valid-length
    kernels (``kernels/ops.py``), whose grids skip pad blocks so each
    device's MXU work tracks its *assigned* units.  Greedy tokens are
    identical by construction; ``ExecPlan.describe()`` shows the per-device
    effective-vs-padded FLOPs the shedding recovers."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.core import hmp, planner\n"
        "from repro.core.execplan import ExecPlan\n"
        "from repro.core.planner import DeviceProfile, ModelProfile\n"
        "from repro.launch.mesh import make_mesh_compat\n"
        "from repro.serving import GalaxyHMPExecutor, Request, ServingEngine\n"
        "caps = [3.0, 2.0, 2.0, 1.0]\n"
        "model = ModelProfile('demo', 2, 16, 256, 1e6, 2e6)\n"
        "devs = [DeviceProfile(f'd{i}', c, 1e12) for i, c in enumerate(caps)]\n"
        "ep = ExecPlan.from_plan(planner.plan(model, devs), head_dim=8,\n"
        "                        d_model=128)\n"
        "print('  plan:', ep.describe())\n"
        "mesh = make_mesh_compat((4,), ('model',))\n"
        "layers = hmp.init_stack_params(jax.random.PRNGKey(0), 2, 128, 16, 256)\n"
        "emb = jax.random.normal(jax.random.PRNGKey(7), (500, 128)) * 0.5\n"
        "outs = {}\n"
        "for backend in ('xla', 'pallas'):\n"
        "    exe = GalaxyHMPExecutor(layers, emb, ep, mesh,\n"
        "                            compute_backend=backend)\n"
        "    eng = ServingEngine(executor=exe, max_batch=4, max_len=32,\n"
        "                        scheduler='continuous', page_size=8)\n"
        "    for i in range(4):\n"
        "        eng.submit(Request(uid=i, prompt=list(range(1 + i, 11 + i)),\n"
        "                           max_new_tokens=6))\n"
        "    outs[backend] = {r.uid: tuple(r.output) for r in eng.run()}\n"
        "assert outs['xla'] == outs['pallas'], 'backends diverged'\n"
        "print('  greedy tokens identical across xla/pallas backends;'\n"
        "      ' pallas sheds', f'{ep.padding_waste():.0%}', 'pad units')\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    print("Pad-shedding compute backend (xla oracle vs pallas valid-length):")
    subprocess.run([sys.executable, "-c", code], env=env, check=True)


def prefix_sharing_demo(prefix_cache: str = "on", prefill_chunk=16):
    """Shared-prefix KV cache + chunked prefill (the admission flow:
    radix-tree lookup -> shared-page refcount bump -> suffix-only chunked
    prefill).  Requests carrying a common system prompt map its pages to
    the *same* refcounted pool pages (``serving/prefix_cache.py``), so only
    each request's own tail is prefetched — and with ``prefill_chunk`` the
    engine interleaves prefill chunks with decode steps instead of stalling
    live slots.  Prints hit-rate stats from ``PrefixCache.stats()``."""
    import time

    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving import Request, ServingEngine, TransformerExecutor

    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    executor = TransformerExecutor(params, cfg)
    rng = np.random.default_rng(0)
    system_prompt = rng.integers(1, 400, 48).tolist()

    print(f"Shared-prefix KV cache (--prefix-cache {prefix_cache}, "
          f"--prefill-chunk {prefill_chunk}):")
    for on in ([False, True] if prefix_cache == "on" else [False]):
        for _ in range(2):  # first pass warms the jit caches
            eng = ServingEngine(executor=executor, max_batch=4, max_len=96,
                                scheduler="continuous", page_size=8,
                                prefix_cache=on, prefill_chunk=prefill_chunk)
            for i in range(10):
                tail = rng.integers(1, 400, 8).tolist()
                eng.submit(Request(uid=i, prompt=system_prompt + tail,
                                   max_new_tokens=8))
            t0 = time.perf_counter()
            done = eng.run()
            wall = time.perf_counter() - t0
        toks = sum(len(r.output) for r in done)
        label = "prefix cache on " if on else "prefix cache off"
        print(f"  {label} {toks} tokens in {wall*1e3:6.1f}ms "
              f"({toks/wall:6.1f} tok/s, prefilled "
              f"{eng.stats['prefill_tokens']} prompt tokens, "
              f"{eng.stats['peak_shared_pages']} pages shared)")
        if on:
            print(f"  PrefixCache.stats(): {eng.prefix_stats}")


def speculative_decoding_demo():
    """Speculative decoding (``serving/spec.py``): a small draft model on
    the fastest device proposes k tokens per round; the serving executor
    verifies all of them in ONE chunked paged prefill (k+1 rows of
    per-position logits) instead of k sequential decode steps.  Accepted
    drafts emit immediately; the first mismatch rolls the slot back via
    block-table truncation (``PagedKVPool.truncate``).  Greedy tokens are
    bitwise identical to plain decoding — the verifier re-derives the exact
    sequential argmax path, speculation only changes how many mesh steps it
    takes to walk it."""
    import time

    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving import Request, ServingEngine, TransformerExecutor

    cfg = reduced(get_config("qwen1.5-0.5b"))
    target = TransformerExecutor(init_params(cfg, jax.random.PRNGKey(0)), cfg)
    # the demo draft reuses the target weights (acceptance ~100%); a real
    # deployment drafts with a much smaller zoo arch (launch/serve.py
    # --draft-model) so each draft step is cheap
    draft = TransformerExecutor(init_params(cfg, jax.random.PRNGKey(0)), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 400, 12).tolist() for _ in range(4)]

    print("Speculative decoding (draft k=4, verify in one chunk prefill):")
    outs = {}
    for spec_k in (None, 4):
        for _ in range(2):  # first pass warms the jit caches
            eng = ServingEngine(
                executor=target, max_batch=1, max_len=48,
                scheduler="continuous", page_size=8,
                draft_executor=draft if spec_k else None, spec_k=spec_k)
            for i in range(4):
                eng.submit(Request(uid=i, prompt=prompts[i],
                                   max_new_tokens=16))
            t0 = time.perf_counter()
            done = eng.run()
            wall = time.perf_counter() - t0
        outs[spec_k] = {r.uid: tuple(r.output) for r in done}
        toks = sum(len(r.output) for r in done)
        if spec_k is None:
            print(f"  plain decode  {toks} tokens in {wall*1e3:6.1f}ms "
                  f"({eng.stats['decode_steps']} mesh steps)")
        else:
            s = eng.stats
            print(f"  speculative   {toks} tokens in {wall*1e3:6.1f}ms "
                  f"({s['spec_steps']} rounds, "
                  f"acceptance {s['spec_acceptance']:.0%}, "
                  f"accept_counts={dict(sorted(s['spec_accept_counts'].items()))})")
    assert outs[None] == outs[4], "speculation changed greedy tokens"
    print("  greedy tokens bitwise identical spec on/off")


def telemetry_demo(trace_path=None):
    """Serving telemetry (``repro.obs``): the same engine run traced end to
    end.  A ``Tracer`` records one span track per request (queued ->
    prefill -> decode, tiling submit->retire) plus an engine track
    (prefill chunks, decode steps, prefix lookups) and exports Chrome
    trace-event JSON — load it in chrome://tracing or ui.perfetto.dev.
    ``engine.metrics`` is the registry behind ``engine.stats``: TTFT/ITL
    histograms, KV-pool occupancy gauges, prefix hit rate — with run vs
    lifetime scopes (``engine.reset_stats()`` zeroes the run scope) and a
    Prometheus text rendering.  A ``DriftMonitor`` prices every executed
    step with the planner's simulator and histograms measured/simulated
    ratios.  All opt-in: a disabled tracer costs zero calls on the hot
    path, and greedy tokens are bitwise identical telemetry on/off
    (``launch/serve.py --trace/--metrics/--drift`` is the CLI spelling)."""
    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.core import costmodel
    from repro.core.execplan import ExecPlan
    from repro.core.simulator import make_step_pricer
    from repro.models import init_params
    from repro.obs import DriftMonitor, Tracer
    from repro.serving import Request, ServingEngine, TransformerExecutor

    cfg = reduced(get_config("qwen1.5-0.5b"))
    executor = TransformerExecutor(init_params(cfg, jax.random.PRNGKey(0)), cfg)
    rng = np.random.default_rng(0)
    system_prompt = rng.integers(1, 400, 16).tolist()

    tracer = Tracer()
    eplan = ExecPlan.even(1, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                          head_dim=cfg.head_dim, d_model=cfg.d_model)
    drift = DriftMonitor(make_step_pricer(
        eplan, cfg, [costmodel.jetson_nano("nano-l", 4.0)],
        costmodel.mbps(1000)))
    eng = ServingEngine(executor=executor, max_batch=4, max_len=64,
                        scheduler="continuous", page_size=8,
                        prefix_cache=True, prefill_chunk=8,
                        record_times=True, tracer=tracer, drift=drift)
    for i in range(8):
        tail = rng.integers(1, 400, 6).tolist()
        eng.submit(Request(uid=i, prompt=system_prompt + tail,
                           max_new_tokens=10 if i % 3 == 0 else 4))
    done = eng.run()

    print("Serving telemetry (tracer + metrics registry + drift monitor):")
    snap = eng.metrics.snapshot()
    ttft, itl = snap["histograms"]["ttft_s"], snap["histograms"]["itl_s"]
    print(f"  served {len(done)} requests; "
          f"ttft p50={ttft['p50']*1e3:.1f}ms itl p50={itl['p50']*1e3:.1f}ms "
          f"prefix_hit_rate={snap['gauges']['prefix_hit_rate']:.0%} "
          f"kv_pages_peak={snap['gauges']['kv_pages_peak']:.0f}")
    spans = [e for e in tracer.to_json()["traceEvents"] if e["ph"] == "X"]
    print(f"  trace: {len(tracer.events)} events, {len(spans)} spans, "
          f"0 left open (open_spans={tracer.open_spans()})")
    if trace_path:
        tracer.write(trace_path)
        print(f"  wrote {trace_path} — open in ui.perfetto.dev")
    d = drift.summary()["all"]
    print(f"  drift (measured/simulated, nominal nano-l specs): "
          f"n={d['n']} p50={d['p50']:.2f} p95={d['p95']:.2f}")
    # the registry scopes runs: reset_stats() zeroes the run scope while
    # lifetime totals survive (the old flat dict silently accumulated)
    eng.reset_stats()
    print(f"  after reset_stats(): run requests="
          f"{eng.stats['requests']}, lifetime="
          f"{eng.metrics.snapshot('lifetime')['counters']['requests']}")


def galaxy_serving_demo():
    """Uneven planner output served end-to-end: plan -> ExecPlan ->
    GalaxyHMPExecutor -> continuous batching over the paged head-sharded
    pool, on a 4-device 3:2:2:1 cluster."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.core import hmp, planner\n"
        "from repro.core.execplan import ExecPlan\n"
        "from repro.core.planner import DeviceProfile, ModelProfile\n"
        "from repro.launch.mesh import make_mesh_compat\n"
        "from repro.serving import GalaxyHMPExecutor, Request, ServingEngine\n"
        "caps = [3.0, 2.0, 2.0, 1.0]\n"
        "model = ModelProfile('demo', 2, 16, 256, 1e6, 2e6)\n"
        "devs = [DeviceProfile(f'd{i}', c, 1e12) for i, c in enumerate(caps)]\n"
        "pl = planner.plan(model, devs)\n"
        "ep = ExecPlan.from_plan(pl, head_dim=8, d_model=128)\n"
        "print('  plan:', ep.describe())\n"
        "mesh = make_mesh_compat((4,), ('model',))\n"
        "layers = hmp.init_stack_params(jax.random.PRNGKey(0), 2, 128, 16, 256)\n"
        "emb = jax.random.normal(jax.random.PRNGKey(7), (500, 128)) * 0.5\n"
        "exe = GalaxyHMPExecutor(layers, emb, ep, mesh)\n"
        "eng = ServingEngine(executor=exe, max_batch=4, max_len=48,\n"
        "                    scheduler='continuous', page_size=8)\n"
        "for i in range(6):\n"
        "    eng.submit(Request(uid=i, prompt=list(range(1 + i, 15 + i)),\n"
        "                       max_new_tokens=12 if i % 3 == 0 else 4))\n"
        "done = eng.run()\n"
        "print(f'  served {len(done)} requests through the uneven plan; '\n"
        "      f'stats={eng.stats}')\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    print("Galaxy serving on an uneven 3:2:2:1 plan (planner -> ExecPlan -> engine):")
    subprocess.run([sys.executable, "-c", code], env=env, check=True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--prefix-cache", choices=("on", "off"), default="on",
                    help="shared-prefix KV cache in prefix_sharing_demo "
                         "(off runs the baseline only)")
    ap.add_argument("--prefill-chunk", type=int, default=16, metavar="N",
                    help="prefill chunk size (tokens) for prefix_sharing_demo")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write telemetry_demo's Chrome trace-event JSON "
                         "here (open in ui.perfetto.dev)")
    args = ap.parse_args()

    serve_demo()
    hmp_demo()
    continuous_batching_demo()
    speculative_decoding_demo()
    galaxy_serving_demo()
    raggedsp_serving_demo()
    overlap_transport_demo()
    padshed_backend_demo()
    prefix_sharing_demo(args.prefix_cache, args.prefill_chunk)
    telemetry_demo(args.trace)
