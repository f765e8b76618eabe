#!/usr/bin/env python3
"""Chip smoke test: drive the serving system once on a TPU at published widths.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # the Galaxy path across a 2x2 host

One chip (the default) runs two phases through the entry points a user
calls (``ServingEngine`` over an executor):

* zoo: qwen1.5-0.5b at its published config (24 layers, bf16) through
  ``TransformerExecutor`` and the continuous scheduler with the paged pool
  and the shared-prefix cache;
* galaxy: the paper's gpt2-l layer (widths as published, depth cut, f32)
  through ``GalaxyHMPExecutor`` on a 1-device mesh, once per compute
  backend ("xla", then "pallas").

``--four-chips`` runs only the Galaxy path across four devices: the
hmp / hmp_ring / megatron layers and Galaxy serving under an even plan and
an uneven 3:2:2:1 plan, each against the single-device reference on one
chip.

Every served logit checked is compared with a plain reference forward of
the same seeded weights; errors are measured on the logit scale (max |ref|).
Any failed check or exception exits nonzero.  Without a TPU the script
exits nonzero before any phase.  The last line of stdout is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``; everything else
(device, versions, per-phase compile and run seconds, request counts,
errors beside their limits) goes on earlier lines.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax
import jax.numpy as jnp
import numpy as np

#: bf16 zoo forward: served vs no-cache logits, over the logit scale
ZOO_LOGIT_RTOL = 5e-2
#: f32 Galaxy stack (on the TPU some dots, Mosaic's among them, round f32
#: operands to bf16): served vs reference_stack logits, over the logit scale
GALAXY_LOGIT_RTOL = 2e-2
#: a served token's reference logit may sit this far below the best one
#: (over the logit scale): near-ties may break either way
ARGMAX_RTOL = 2e-2
#: single-layer schedule outputs vs reference_layer, max abs (outputs are
#: layer-normed, so unit scale)
LAYER_ATOL = 5e-2

GALAXY_LAYERS = 4       # gpt2-l depth on one chip (published: 36)
GALAXY_LAYERS_4CHIP = 2


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


# --- compile accounting ---------------------------------------------------------

class CompileMeter:
    """Host seconds spent making programs, from JAX's own monitoring events:
    tracing and lowering to MLIR (Pallas kernels lower to Mosaic here), and
    the backend compile, which a persistent-cache hit replaces by a read."""

    LOWER = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.lower = []  # (start, end) spans; nested traces overlap
        self.compile = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile += duration
            self.programs += 1

    def _span(self, event, start, end, **_):
        if event in self.LOWER:
            self.lower.append((start, end))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (time.perf_counter(), len(self.lower), self.compile,
                self.programs, self.cache_hits)

    def since(self, mark) -> str:
        t0, l0, c0, p0, h0 = mark
        wall = time.perf_counter() - t0
        lower, end = 0.0, float("-inf")
        for a, b in sorted(self.lower[l0:]):  # length of the spans' union
            lower += max(0.0, b - max(a, end))
            end = max(end, b)
        comp = self.compile - c0
        return (f"wall {wall:.1f} s = trace+lower {lower:.1f} s + compile "
                f"{comp:.1f} s ({self.programs - p0} programs, "
                f"{self.cache_hits - h0} persistent-cache hits) + rest "
                f"{wall - lower - comp:.1f} s")


# --- executor recorder ----------------------------------------------------------

class Recorder:
    """Executor wrapper that keeps what the engine's programs returned.

    Every prefill records the logits row of the last real prompt token (the
    first generated token's logits).  With ``track_decode`` each live
    decode row records (request prompt, position, fed token, logits); rows
    are tied to requests by their first KV page, which is unique per live
    slot when no prefix pages are shared.  ``abstract`` keeps the argument
    shapes each jitted program was first called with, so its compiled text
    can be inspected afterwards."""

    def __init__(self, inner, *, track_decode: bool = False):
        self.inner = inner
        self.track_decode = track_decode
        self.prefills = []   # (offset, length, chunk tokens, logits row)
        self.decodes = []    # (prompt tuple, position, fed token, logits)
        self.abstract = {}   # program key -> abstract args
        self.pool_devices = set()
        self._owner = {}     # first page id -> prompt tuple

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _note(self, key, *args):
        # uncommitted arrays (host inputs jit may place anywhere) keep no
        # sharding, as in the served call
        if key not in self.abstract:
            self.abstract[key] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=a.sharding if a.committed else None),
                (self.inner.layers, self.inner.embed, *args)
                if hasattr(self.inner, "layers") else args)

    def _pool(self, pool):
        for leaf in jax.tree.leaves(pool):
            self.pool_devices |= {s.device for s in leaf.addressable_shards}

    def prefill_paged(self, tokens, pool, block_row, length):
        self._pool(pool)
        self._note(("paged", tokens.shape[1]), tokens, pool, block_row,
                   jnp.asarray(length, jnp.int32))
        logits, pool = self.inner.prefill_paged(tokens, pool, block_row,
                                                length)
        self._prefill(0, length, tokens, logits[0], block_row)
        return logits, pool

    def prefill_chunk(self, tokens, pool, block_row, *, offset, length):
        self._pool(pool)
        self._note(("chunk", tokens.shape[1]), tokens, pool, block_row,
                   jnp.asarray(offset, jnp.int32),
                   jnp.asarray(length, jnp.int32))
        logits, pool = self.inner.prefill_chunk(
            tokens, pool, block_row, offset=offset, length=length)
        row = max(0, min(length - 1 - offset, tokens.shape[1] - 1))
        self._prefill(offset, length, tokens, logits[0, row], block_row)
        return logits, pool

    def _prefill(self, offset, length, tokens, row, block_row):
        chunk = np.asarray(tokens)[0]
        self.prefills.append((offset, length, chunk,
                              np.asarray(row, np.float32)))
        if self.track_decode:
            if offset:
                fail("decode tracking needs whole-prompt prefills")
            self._owner[int(block_row[0])] = tuple(chunk[:length].tolist())

    def decode_paged(self, tokens, pool, block_table, positions):
        self._pool(pool)
        self._note("decode", tokens, pool, block_table, positions)
        logits, pool = self.inner.decode_paged(tokens, pool, block_table,
                                               positions)
        if self.track_decode:
            bt = np.asarray(block_table)
            lg = np.asarray(logits, np.float32)
            pos = np.asarray(positions)
            fed = np.asarray(tokens)[:, 0]
            for i in np.flatnonzero(bt[:, 0]):  # idle rows hold the null page
                self.decodes.append((self._owner[int(bt[i, 0])], int(pos[i]),
                                     int(fed[i]), lg[i]))
        return logits, pool

    def program_text(self, key) -> str:
        """Compiled text of one of the inner executor's jitted programs
        (reuses the compilation of the served call)."""
        fn = (self.inner._decode_paged_fn if key == "decode"
              else self.inner._prefill_fns[key])
        return fn.lower(*self.abstract[key]).compile().as_text()


def request_of(requests, offset, length, chunk):
    """The served request a recorded prefill belongs to."""
    for r in requests:
        if (len(r.prompt) == length
                and list(chunk[:length - offset]) == r.prompt[offset:]):
            return r
    fail(f"no request matches a prefill of length {length} at {offset}")


def scale_err(got, ref) -> float:
    """max |got - ref| over the logit scale max |ref|."""
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def argmax_gap(token: int, ref) -> float:
    """How far the served token's reference logit sits below the best
    reference logit, over the logit scale."""
    return float((ref.max() - ref[token]) / np.max(np.abs(ref)))


def split(total: int, shares) -> tuple:
    """``total`` units in proportion to ``shares``, largest remainders first
    (gpt2-l's 20 heads over 3:2:2:1 -> 8, 5, 5, 2)."""
    exact = np.asarray(shares) * total / sum(shares)
    units = np.floor(exact).astype(int)
    for i in np.argsort(units - exact, kind="stable")[:total - units.sum()]:
        units[i] += 1
    return tuple(int(u) for u in units)


def prompts_of(rng, vocab, lengths, shared=()):
    return [list(shared) + rng.integers(0, vocab, n - len(shared)).tolist()
            for n in lengths]


# --- zoo: qwen1.5-0.5b through TransformerExecutor --------------------------------

def phase_zoo(seed: int, meter: CompileMeter):
    from repro.configs import get_config
    from repro.models import init_params
    from repro.models.transformer import apply_model
    from repro.serving import Request, ServingEngine, TransformerExecutor

    cfg = get_config("qwen1.5-0.5b")
    print(f"zoo: {cfg.name} as published: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.param_dtype}; random weights, seed {seed}")
    mark = meter.mark()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    page = 64
    system = rng.integers(0, cfg.vocab_size, 2 * page).tolist()
    # four requests open with one page-aligned system prompt (prefix hits),
    # four stand alone; 64..512 prompt tokens
    prompts = (prompts_of(rng, cfg.vocab_size, (256, 256, 512, 192), system)
               + prompts_of(rng, cfg.vocab_size, (64, 500, 200, 129)))
    new = 16
    ex = Recorder(TransformerExecutor(params, cfg))
    eng = ServingEngine(executor=ex, max_batch=len(prompts),
                        max_len=max(map(len, prompts)) + new,
                        scheduler="continuous", page_size=page,
                        prefix_cache=True)
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=new))
    done = eng.run()
    print(f"zoo: served {len(done)} requests, "
          f"{sum(len(r.output) for r in done)} new tokens; "
          f"{meter.since(mark)}")
    check(len(done) == len(prompts), f"{len(done)} of {len(prompts)} retired")
    for r in done:
        check(len(r.output) == new,
              f"request {r.uid} retired with {len(r.output)} of {new} tokens")
    eng.pool.check()
    hits = eng.stats["prefix_hits"]
    check(hits >= 1, "the prefix cache recorded no hit")
    print(f"zoo: pool check passed; prefix hits {hits}, cached prompt tokens "
          f"{eng.stats['cached_prefix_tokens']}, prefill tokens computed "
          f"{eng.stats['prefill_tokens']}")

    # first-token logits of a whole-prompt prefill and of a prefix-hit
    # suffix prefill against a no-cache forward of the same prompt
    picked = [next(rec for rec in ex.prefills if rec[0] == 0),
              next(rec for rec in ex.prefills if rec[0] > 0)]
    reqs = [request_of(done, *rec[:3]) for rec in picked]
    width = max(len(r.prompt) for r in reqs)
    toks = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        toks[i, :len(r.prompt)] = r.prompt  # right pad: causal rows unchanged

    @jax.jit
    def reference(params, tokens, rows):
        logits, _, _ = apply_model(params, cfg, tokens=tokens)
        return logits[jnp.arange(tokens.shape[0]), rows].astype(jnp.float32)

    mark = meter.mark()
    ref = np.asarray(reference(params, jnp.asarray(toks),
                               jnp.asarray([len(r.prompt) - 1 for r in reqs])))
    for i, (rec, r) in enumerate(zip(picked, reqs)):
        err = scale_err(rec[3], ref[i])
        gap = argmax_gap(r.output[0], ref[i])
        kind = "prefix-hit suffix" if rec[0] else "whole-prompt"
        print(f"zoo: request {r.uid} ({kind} prefill, {len(r.prompt)} "
              f"tokens, {rec[0]} cached): first-token logit error {err:.4g} "
              f"(limit {ZOO_LOGIT_RTOL}), first token's gap below the "
              f"reference argmax {gap:.4g} (limit {ARGMAX_RTOL})")
        check(err <= ZOO_LOGIT_RTOL, f"zoo request {r.uid} logit error {err}")
        check(gap <= ARGMAX_RTOL, f"zoo request {r.uid} argmax gap {gap}")
    print(f"zoo: reference forward {meter.since(mark)}")
    print("zoo: PASS")


# --- galaxy: gpt2-l through GalaxyHMPExecutor -------------------------------------

def _gpt2l(seed: int, layers: int):
    from repro.configs import get_config
    from repro.core import hmp

    cfg = get_config("gpt2-l")
    print(f"galaxy: {cfg.name} widths as published (d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); "
          f"depth cut from {cfg.num_layers} to {layers} layers to bound "
          f"compile time; f32, random weights, seed {seed}")
    key = jax.random.PRNGKey(seed)
    stack = hmp.init_stack_params(key, layers, cfg.d_model, cfg.num_heads,
                                  cfg.d_ff)
    embed = jax.random.normal(jax.random.fold_in(key, 1),
                              (cfg.vocab_size, cfg.d_model)) * 0.02
    return cfg, stack, embed


def _galaxy_reference_rows(stack, embed, seqs, rows):
    """reference_stack logits at (sequence i, row r) pairs, on the device
    the weights live on (one chip)."""
    from repro.core import hmp

    width = max(map(len, seqs))
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s  # right pad: causal rows unchanged
    b_idx = np.asarray([b for b, _ in rows], np.int32)
    r_idx = np.asarray([r for _, r in rows], np.int32)

    @jax.jit
    def reference(stack, embed, toks, b_idx, r_idx):
        y = hmp.reference_stack(stack, embed[toks])
        return y[b_idx, r_idx] @ embed.T

    return np.asarray(reference(stack, embed, jnp.asarray(toks),
                                jnp.asarray(b_idx), jnp.asarray(r_idx)))


def serve_galaxy(tag, stack, embed, plan, mesh, backend, prompts, new,
                 meter: CompileMeter):
    """Serve ``prompts`` through GalaxyHMPExecutor and check every
    prefill and decode logit against reference_stack on one chip."""
    from repro.serving import GalaxyHMPExecutor, Request, ServingEngine

    mark = meter.mark()
    ex = Recorder(GalaxyHMPExecutor(stack, embed, plan, mesh,
                                    compute_backend=backend),
                  track_decode=True)
    eng = ServingEngine(executor=ex, max_batch=len(prompts),
                        max_len=max(map(len, prompts)) + new,
                        scheduler="continuous", page_size=64)
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=new))
    done = sorted(eng.run(), key=lambda r: r.uid)
    print(f"{tag}: served {len(done)} requests, "
          f"{sum(len(r.output) for r in done)} new tokens; "
          f"{meter.since(mark)}")
    check(len(done) == len(prompts), f"{tag}: {len(done)} retired")
    for r in done:
        check(len(r.output) == new, f"{tag}: request {r.uid} short")
    eng.pool.check()

    # teacher-forced reference over prompt + served tokens: row
    # len(prompt) - 1 + j predicts served token j
    seqs = [r.prompt + r.output[:-1] for r in done]
    index = {tuple(r.prompt): i for i, r in enumerate(done)}
    checks = []  # (what, request index, row, served logits or None, token)
    for off, length, chunk, row in ex.prefills:
        r = request_of(done, off, length, chunk)
        checks.append(("prefill", r.uid, length - 1, row, r.output[0]))
    for prompt, pos, fed, row in ex.decodes:
        i = index[prompt]
        check(seqs[i][pos] == fed, f"{tag}: decode fed {fed} at {pos}")
        checks.append(("decode", i, pos, row, done[i].output[pos - len(prompt) + 1]))
    ref = _galaxy_reference_rows([jax.device_put(p, jax.devices()[0])
                                  for p in stack],
                                 jax.device_put(embed, jax.devices()[0]),
                                 seqs, [(i, r) for _, i, r, _, _ in checks])
    worst = {"prefill": (0.0, 0.0, 0), "decode": (0.0, 0.0, 0)}
    for (what, _, _, row, tok), rr in zip(checks, ref):
        err, gap, n = worst[what]
        worst[what] = (max(err, scale_err(row, rr)),
                       max(gap, argmax_gap(tok, rr)), n + 1)
    for what, (err, gap, n) in worst.items():
        print(f"{tag}: {n} {what} logit rows vs reference_stack: max error "
              f"{err:.4g} (limit {GALAXY_LOGIT_RTOL}), max served-token gap "
              f"below the reference argmax {gap:.4g} (limit {ARGMAX_RTOL})")
        check(n > 0, f"{tag}: no {what} rows recorded")
        check(err <= GALAXY_LOGIT_RTOL, f"{tag}: {what} logit error {err}")
        check(gap <= ARGMAX_RTOL, f"{tag}: {what} argmax gap {gap}")
    if backend == "pallas":
        for key in ex.abstract:
            check("tpu_custom_call" in ex.program_text(key),
                  f"{tag}: compiled {key} program has no Mosaic kernel")
        print(f"{tag}: compiled programs {sorted(map(str, ex.abstract))} "
              "all contain tpu_custom_call (Mosaic, not the interpreter)")
    return ex


def phase_galaxy_one_chip(seed: int, meter: CompileMeter):
    from repro.core.execplan import ExecPlan
    from repro.launch.mesh import make_mesh_compat

    cfg, stack, embed = _gpt2l(seed, GALAXY_LAYERS)
    plan = ExecPlan.even(1, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                         head_dim=cfg.head_dim, d_model=cfg.d_model)
    mesh = make_mesh_compat((1,), ("model",))
    prompts = prompts_of(np.random.default_rng(seed), cfg.vocab_size,
                         (100, 128, 200, 256))
    for backend in ("xla", "pallas"):
        serve_galaxy(f"galaxy[{backend}]", stack, embed, plan, mesh, backend,
                     prompts, 8, meter)
    print("galaxy: PASS")


# --- four chips: the Galaxy path across a mesh ------------------------------------

def phase_four_chips(seed: int, meter: CompileMeter):
    from repro.core import hmp
    from repro.core.execplan import ExecPlan
    from repro.launch.mesh import make_mesh_compat

    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, "
          f"JAX sees {len(jax.devices())}")
    cfg, stack, embed = _gpt2l(seed, GALAXY_LAYERS_4CHIP)
    mesh = make_mesh_compat((4,), ("model",), devices=jax.devices()[:4])
    hd = cfg.head_dim
    shares = (3.0, 2.0, 2.0, 1.0)
    plans = {
        "even": ExecPlan.even(4, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                              head_dim=hd, d_model=cfg.d_model),
        # 3:2:2:1 capacities over heads, MLP columns and sequence tiles
        "3:2:2:1": ExecPlan(heads=split(cfg.num_heads, shares),
                            columns=split(cfg.d_ff, shares), head_dim=hd,
                            d_model=cfg.d_model, seq_shares=shares),
    }
    one = jax.devices()[0]
    seq = 256
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, seq, cfg.d_model))
    layer = stack[0]
    ref = jax.jit(hmp.reference_layer)(jax.device_put(layer, one),
                                       jax.device_put(x, one))
    for name, plan in plans.items():
        print(f"four-chip plan {name}: heads {plan.heads}, columns "
              f"{plan.columns}, sequence tiles {plan.seq_tiles(seq)} of {seq}")
        layout = plan.seq_layout(seq)
        for backend in ("xla", "pallas"):
            bplan = plan.with_backend(backend)
            mark = meter.mark()
            errs = {}
            for sched in ("hmp", "hmp_ring", "megatron"):
                fn = hmp.SCHEDULES[sched]
                if sched == "megatron":
                    y = jax.jit(lambda p, x: fn(p, x, mesh, plan=bplan))(
                        layer, x)
                else:
                    y = layout.gather(jax.jit(
                        lambda p, x: fn(p, x, mesh, plan=bplan, seq=seq))(
                            layer, layout.scatter(x)))
                errs[sched] = float(jnp.max(jnp.abs(
                    jax.device_put(y, one) - ref)))
            print(f"four-chip plan {name} [{backend}]: layer max abs error vs "
                  f"reference_layer on one chip "
                  + ", ".join(f"{k} {v:.3g}" for k, v in errs.items())
                  + f" (limit {LAYER_ATOL}); {meter.since(mark)}")
            for sched, err in errs.items():
                check(err <= LAYER_ATOL, f"{name} {backend} {sched}: {err}")

    prompts = prompts_of(np.random.default_rng(seed), cfg.vocab_size,
                         (200, 230, 256))
    for name, plan in plans.items():
        for backend in ("xla", "pallas"):
            ex = serve_galaxy(f"four-chip plan {name} galaxy[{backend}]",
                              stack, embed, plan, mesh, backend, prompts, 6,
                              meter)
            weights = {s.device for s in ex.layers[0]["wq"].addressable_shards}
            print(f"four-chip plan {name} galaxy[{backend}]: layer weight "
                  f"shards on {len(weights)} devices, KV pool shards on "
                  f"{len(ex.pool_devices)} devices")
            check(len(weights) == 4 and len(ex.pool_devices) == 4,
                  "weights or KV pool not spread over 4 devices")
    print("four-chip: PASS")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the Galaxy path across four devices")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and traffic")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: jax.devices()[0] is a {dev.platform} device "
             "(this script has no CPU fallback)")
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}; jax {jax.__version__}; compile cache {cache}")
    meter = CompileMeter()
    mark = meter.mark()
    if args.four_chips:
        phase_four_chips(args.seed, meter)
    else:
        phase_zoo(args.seed, meter)
        phase_galaxy_one_chip(args.seed, meter)
    print(f"total: {meter.since(mark)}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
