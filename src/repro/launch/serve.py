"""End-to-end serving driver: batched requests through the serving engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduce \
      --requests 16 --prompt-len 32 --max-new 32

``--executor galaxy`` serves through the paper-exact Galaxy HMP schedule on
all local devices (an even ExecPlan over the device mesh) instead of the
GSPMD model zoo; there ``--compute-backend pallas`` switches the per-shard
compute path to the valid-length Pallas kernels (``ExecPlan.compute_backend``
— pad-block work is shed per device; "xla" keeps the padded dense oracle).
It builds the arch's own block: the paper's Fig. 2 layer for the paper's
models (``--arch gpt2-l``...), the DeepSeek-V3 block on one device for
``--arch moonlight-16b-a3b``; any other arch is refused.

``--prefix-cache on`` shares prompt-prefix KV across requests through the
radix-tree cache (``serving/prefix_cache.py``; requests get a common system
prompt so hits occur) and ``--prefill-chunk N`` interleaves N-token prefill
chunks with decode steps — both continuous-scheduler features, on either
executor.

``--draft-model <zoo-arch> --spec-k N`` turns on speculative decoding
(``serving/spec.py``): the draft arch proposes N tokens per round on the
fastest device and the serving executor verifies them in one chunked paged
prefill — greedy-only, continuous scheduler only, output bitwise-identical
to plain decoding.

Telemetry (``repro.obs``): ``--trace out.json`` records request/engine
spans and writes Chrome trace-event JSON (open in ``chrome://tracing`` or
https://ui.perfetto.dev), ``--metrics`` prints the metrics-registry
snapshot plus its Prometheus text rendering, and ``--drift`` prices every
executed step with the planner's simulator and reports measured/simulated
drift ratios.  All three are opt-in; none changes the emitted tokens.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import (
    ASSIGNED_ARCHS, GALAXY_ARCHS, MLAMoEConfig, get_config, reduced,
    reduced_mla_moe,
)
from repro.configs.paper_models import PAPER_MODELS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import (
    Request, SamplerConfig, ServingEngine, TransformerExecutor,
)


def _galaxy_executor(cfg, compute_backend: str):
    """A Galaxy HMP executor for the arch's own block, or SystemExit.

    The Galaxy path implements two blocks: the paper's Fig. 2 layer, which
    the paper's evaluation models are stacks of (an even plan over every
    local device), and the DeepSeek-V3 block of an ``MLAMoEConfig`` (one
    device; every routed expert held).  Any other arch is refused rather
    than served as the paper layer at its widths."""
    from repro.core import hmp, mla_moe
    from repro.core.execplan import ExecPlan
    from repro.launch.mesh import make_mesh_compat
    from repro.serving import GalaxyHMPExecutor, GalaxyMLAExecutor

    if isinstance(cfg, MLAMoEConfig):
        if compute_backend != "xla":
            raise SystemExit(f"{cfg.name}: the MLA/MoE block has no "
                             "'pallas' compute backend")
        plan = ExecPlan.even(1, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                             head_dim=cfg.v_head_dim, d_model=cfg.d_model)
        mesh = make_mesh_compat((1,), ("model",), devices=jax.devices()[:1])
        layers, vocab = jax.jit(lambda k: mla_moe.init_params(cfg, k))(
            jax.random.PRNGKey(0))
        return GalaxyMLAExecutor(layers, vocab, plan, mesh)
    if cfg.name.removesuffix("-smoke") not in PAPER_MODELS:
        raise SystemExit(
            f"{cfg.name}: the Galaxy executor implements the paper's Fig. 2 "
            f"layer ({', '.join(PAPER_MODELS)}) and the DeepSeek-V3 block "
            f"({', '.join(GALAXY_ARCHS)}), not this arch's block; serve it "
            "with --executor zoo")
    n = jax.device_count()
    if cfg.num_heads % n or cfg.d_ff % n:
        raise SystemExit(
            f"{cfg.name}: {cfg.num_heads} heads / {cfg.d_ff} columns do not "
            f"split over {n} local devices — pick a dividing arch/--reduce"
        )
    plan = ExecPlan.even(n, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                         head_dim=cfg.head_dim, d_model=cfg.d_model)
    mesh = make_mesh_compat((n,), ("model",))
    layers = hmp.init_stack_params(
        jax.random.PRNGKey(0), cfg.num_layers, cfg.d_model, cfg.num_heads,
        cfg.d_ff)
    embed = jax.random.normal(
        jax.random.PRNGKey(1), (cfg.vocab_size, cfg.d_model)) * 0.02
    return GalaxyHMPExecutor(layers, embed, plan, mesh,
                             compute_backend=compute_backend)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=ASSIGNED_ARCHS + GALAXY_ARCHS + tuple(PAPER_MODELS),
                    help="a zoo arch; the Galaxy-only blocks "
                         f"({', '.join(GALAXY_ARCHS)}) and the paper's "
                         "models serve through --executor galaxy")
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--scheduler", choices=("auto", "continuous", "wave"),
                    default="auto",
                    help="auto = continuous batching when the executor "
                         "implements the paged protocol, else waves")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV pool page size (continuous batching)")
    ap.add_argument("--prefix-cache", choices=("on", "off"), default="off",
                    help="shared-prefix KV cache (serving/prefix_cache.py): "
                         "requests with a common page-aligned prompt prefix "
                         "map it to the same refcounted pool pages and "
                         "prefill only the uncached suffix (continuous "
                         "scheduler only)")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="N",
                    help="chunked prefill: interleave N-token prefill chunks "
                         "with decode steps instead of stalling live slots "
                         "for a whole long-prompt prefill (continuous "
                         "scheduler only)")
    ap.add_argument("--draft-model", default=None, metavar="ARCH",
                    help="speculative decoding (serving/spec.py): a small "
                         "zoo arch drafts --spec-k tokens per round on the "
                         "fastest device and the serving executor verifies "
                         "them in one chunked paged prefill (greedy only, "
                         "continuous scheduler only)")
    ap.add_argument("--spec-k", type=int, default=None, metavar="N",
                    help="draft tokens proposed per speculative round "
                         "(requires --draft-model)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record request/engine spans and write Chrome "
                         "trace-event JSON (chrome://tracing, "
                         "ui.perfetto.dev)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the metrics registry snapshot (TTFT/ITL "
                         "percentiles, pool occupancy, hit/acceptance "
                         "rates) and its Prometheus text rendering")
    ap.add_argument("--drift", action="store_true",
                    help="price every executed step with the planner's "
                         "simulator (core/simulator.make_step_pricer) and "
                         "report measured/simulated drift ratios "
                         "(diagnostics: syncs once per prefill chunk)")
    ap.add_argument("--executor", choices=("zoo", "galaxy"), default="zoo",
                    help="zoo = GSPMD model zoo; galaxy = paper-exact HMP "
                         "schedule over all local devices")
    ap.add_argument("--compute-backend", choices=("xla", "pallas"),
                    default="xla",
                    help="Galaxy per-shard compute path "
                         "(ExecPlan.compute_backend): 'pallas' sheds "
                         "pad-block work via the valid-length kernels; "
                         "'xla' is the padded dense oracle.  Galaxy "
                         "executor only — the zoo path is GSPMD-sharded "
                         "and has no padded shards to shed")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    galaxy_only = isinstance(cfg, MLAMoEConfig)
    if args.reduce:
        cfg = reduced_mla_moe(cfg) if galaxy_only else reduced(cfg)
    if galaxy_only and args.executor != "galaxy":
        raise SystemExit(f"{cfg.name}: its block serves through "
                         "--executor galaxy only")
    if not galaxy_only and cfg.input_mode != "token":
        raise SystemExit(f"{cfg.name} is a stub-frontend arch; serve the token archs")

    draft_executor = None
    if (args.draft_model is None) != (args.spec_k is None):
        raise SystemExit("--draft-model and --spec-k go together")
    if args.draft_model is not None:
        if args.scheduler == "wave":
            raise SystemExit(
                "--draft-model requires the continuous scheduler: the wave "
                "path has no paged chunk-prefill to verify drafts with "
                "(drop --scheduler wave)")
        if args.temperature != 0.0:
            raise SystemExit(
                "--draft-model is greedy-only: verification pins tokens to "
                "the sequential argmax path (drop --temperature)")
        from repro.core.costmodel import DeviceSpec
        from repro.serving import place_draft

        draft_cfg = get_config(args.draft_model)
        if args.reduce:
            draft_cfg = reduced(draft_cfg)
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise SystemExit(
                f"draft {draft_cfg.name} vocab {draft_cfg.vocab_size} != "
                f"target vocab {cfg.vocab_size}")
        draft_params = init_params(draft_cfg, jax.random.PRNGKey(2))
        # the draft runs alone on one device; place_draft picks the
        # highest-FLOPS spec (local devices report uniform capacity, so
        # this degenerates to index 0 — on a real heterogeneous edge mesh
        # the DeviceSpecs come from the profiler)
        specs = [DeviceSpec(str(d), 1.0, 1.0, 1.0) for d in jax.local_devices()]
        dev = jax.local_devices()[place_draft(specs)]
        draft_params = jax.device_put(draft_params, dev)
        draft_executor = TransformerExecutor(draft_params, draft_cfg)

    if args.executor == "galaxy":
        executor = _galaxy_executor(cfg, args.compute_backend)
    else:
        if args.compute_backend != "xla":
            raise SystemExit(
                "--compute-backend applies to --executor galaxy (the zoo "
                "executor has no padded ExecPlan shards to shed)")
        params = init_params(cfg, jax.random.PRNGKey(0))
        executor = TransformerExecutor(params, cfg)

    tracer = None
    if args.trace is not None:
        from repro.obs import Tracer
        tracer = Tracer()
    drift = None
    if args.drift and galaxy_only:
        raise SystemExit("--drift prices the zoo and paper archs only")
    if args.drift:
        from repro.core import costmodel
        from repro.core.execplan import ExecPlan
        from repro.core.simulator import make_step_pricer
        from repro.obs import DriftMonitor

        # the galaxy executor exposes the exact plan it runs; the zoo path
        # is priced as a single-device even plan.  Nominal device/link
        # specs — run experiments/calibrate.py for fitted ones; the drift
        # *trend* (ratio p50 moving over time) is meaningful either way
        eplan = (executor.plan if args.executor == "galaxy" else
                 ExecPlan.even(1, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                               head_dim=cfg.head_dim, d_model=cfg.d_model))
        devices = [costmodel.jetson_nano("nano-l", 4.0)
                   for _ in range(eplan.num_devices)]
        drift = DriftMonitor(make_step_pricer(
            eplan, cfg, devices, costmodel.mbps(1000)))

    engine = ServingEngine(
        executor=executor,
        max_batch=args.max_batch,
        max_len=args.prompt_len + args.max_new,
        sampler=SamplerConfig(temperature=args.temperature),
        scheduler=args.scheduler,
        page_size=args.page_size,
        prefix_cache=args.prefix_cache == "on",
        prefill_chunk=args.prefill_chunk,
        draft_executor=draft_executor,
        spec_k=args.spec_k,
        # TTFT/ITL histograms fill from the record_times stamps
        record_times=bool(args.metrics or args.trace or args.drift),
        tracer=tracer,
        drift=drift,
    )

    rng = np.random.default_rng(0)
    # with the prefix cache on, model the traffic it targets: a shared
    # system prompt (half the prompt) ahead of each request's own tail
    shared = (rng.integers(0, cfg.vocab_size, size=args.prompt_len // 2).tolist()
              if args.prefix_cache == "on" else [])
    for i in range(args.requests):
        tail = rng.integers(
            0, cfg.vocab_size, size=args.prompt_len - len(shared)).tolist()
        engine.submit(Request(uid=i, prompt=shared + tail,
                              max_new_tokens=args.max_new))

    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    new_tokens = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests in {dt:.2f}s "
          f"({new_tokens} new tokens, {new_tokens/dt:,.1f} tok/s)")
    print(f"stats: {engine.stats}")
    if args.spec_k is not None:
        s = engine.stats
        print(f"speculative: k={args.spec_k} rounds={s['spec_steps']} "
              f"proposed={s['spec_proposed']} accepted={s['spec_accepted']} "
              f"acceptance={s['spec_acceptance']:.1%} "
              f"accept_counts={dict(sorted(s['spec_accept_counts'].items()))}")
    if engine.prefix_stats is not None:
        print(f"prefix cache: {engine.prefix_stats}")
    if tracer is not None:
        tracer.write(args.trace)
        print(f"trace: {len(tracer.events)} events -> {args.trace}")
    if args.metrics:
        print("metrics snapshot:")
        print(json.dumps(engine.metrics.snapshot(), indent=2, default=float))
        print(engine.metrics.to_prometheus(), end="")
    if drift is not None:
        print("sim-vs-measured drift (measured/simulated ratio):")
        for kind, s in drift.summary().items():
            print(f"  {kind}: n={s['n']} p50={s['p50']:.2f} "
                  f"p95={s['p95']:.2f}")


if __name__ == "__main__":
    main()
